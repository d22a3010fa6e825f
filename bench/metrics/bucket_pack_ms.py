"""Device time per training step under the program's named scope
``optim.pack``: the optimizer engine's bucket layout (stacking a bucket's
leaves, the reshapes and transposes into the kernel's layout, the per-leaf
slices back).  The union of the intervals of the ops whose ``op_name``
carries the scope, read from the window superstep's HLO text
(``bench/scopes.py``)."""


def read(run):
    if run.scopes is None or not run.trace.chips:
        return None
    return run.scopes["optim.pack"] or None
