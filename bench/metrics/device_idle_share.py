"""Share of the traced window in which no operation ran on the device:
``1 - busy / window``, busy the union of the device ops' intervals,
averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or not t.chips or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
