"""intersect_roofline (Pallas kernels): the share of the memory roofline
that the intersection kernels reach, in percent.

Numerator: the least time the chips need to read, at their HBM bandwidth
(``bench/peaks.json``), the operand streams of every intersection in the
canonical plan of the query (``bench/work/<query>.py``), computed from the
graph and the query alone. Denominator: the Mosaic kernels' device time per
query, per chip. The bound is memory because a sorted-set intersection does
one compare per element read and no multiply: the chip publishes no
integer-compare peak for its vector unit, and its bf16 and int8 peaks are
matrix-unit peaks that this work never uses. None without kernel time."""


def read(r):
    if r.trace is None or r.trace.kernel_s <= 0:
        return None
    least_s = r.work_bytes / (r.peaks["hbm_bytes_per_s"] * r.chips)
    return 100.0 * least_s / (r.trace.kernel_s / r.queries)
