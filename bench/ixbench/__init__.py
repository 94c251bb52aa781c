"""The chip benchmark's own code: harness, graph generation, references'
shared listing, the comparison that decides ``correct``, and the trace
reduction. Nothing here is imported by the program under test."""
