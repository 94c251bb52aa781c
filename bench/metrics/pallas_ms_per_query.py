"""pallas_ms_per_query (Pallas kernels, kernels/intersect.py and
svinter.py): device time of the Mosaic kernel operations in the traced
window, per chip and per query. None when the trace holds no kernel."""


def read(r):
    if r.trace is None or r.trace.kernel_s <= 0:
        return None
    return r.trace.kernel_s * 1e3 / r.queries
