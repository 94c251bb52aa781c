"""host_syncs_per_query (wave interpreter): the registry counter
``host_syncs`` over the window, per query: the times the host waits for a
device result (count partials, expand meta). Each one drains the device
queue, so fewer move ``query_s``."""


def read(r):
    return r.counters["host_syncs"] / r.queries
