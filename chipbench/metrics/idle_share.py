"""idle_share: percent of the traced window in which no operation ran on
the device (the union of its op intervals), the mean over the chips."""


def read(run):
    t = run.trace
    if t is None or not t.busy_s or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
