"""Device time of the fused GWT-Adam kernel per training step: the sum of
its events' durations in the traced window over the window's steps."""

from bench.trace import is_gwt_kernel


def read(run):
    t = run.trace
    if t is None or not t.chips:
        return None
    s = t.op_seconds(is_gwt_kernel)
    if s <= 0:
        return None
    return 1e3 * s / run.steps
