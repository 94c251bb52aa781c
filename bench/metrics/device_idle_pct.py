"""device_idle_pct (device): the share of the traced window in which a
chip runs no operation, averaged over the chips, in percent. Idle time is
where the host holds the chip back."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
