"""Share of the traced window in which the device ran no operation: one
minus the union of its op intervals over the window, mean over the cell's
devices."""


def read(run):
    t = run.window.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s_mean / t.window_s)
