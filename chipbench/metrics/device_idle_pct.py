"""Share, in percent, of the traced window in which no operation ran on the
device, averaged over the chips the cell uses."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.mean_busy_s() / r.trace.window_s)
