"""Device time per training step under the program's named scope ``moe``:
the expert FFN sublayers — routing, dispatch, the grouped matmuls and the
combine — in the forward, the rematerialised forward and the backward.
The union of the intervals of the ops whose ``op_name`` carries the
scope, read from the window superstep's HLO text (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    if run.hlo is None or run.trace is None or not run.trace.chips:
        return None
    s = scopes.scope_seconds(run.trace, scopes.scope_map(run.hlo), "moe")
    return 1e3 * s / run.steps or None
