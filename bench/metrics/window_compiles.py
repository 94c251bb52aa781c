"""window_compiles (session layer, mining/session.py): executables the
session built during the measured window, from ``Miner.exec_cache.misses``.
The warm-up builds every shape, so this reads 0; anything else means a
compile inside the window, which ``query_s`` would carry."""


def read(r):
    return r.window_compiles
