"""glue_ms_per_query (XLA glue: core/batch.py, graph/csr.py padded_rows,
compaction): device time of every operation in the traced window that is
neither a Mosaic kernel nor a collective, per chip and per query."""


def read(r):
    if r.trace is None:
        return None
    return r.trace.glue_s * 1e3 / r.queries
