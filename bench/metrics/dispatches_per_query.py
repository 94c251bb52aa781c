"""dispatches_per_query (wave interpreter, mining/engine.py and shard.py):
the registry counter ``level_kernel_dispatches`` over the window, per
query. Fewer dispatches move ``query_s`` where each one costs a fixed
host and launch overhead."""


def read(r):
    return r.counters["level_kernel_dispatches"] / r.queries
