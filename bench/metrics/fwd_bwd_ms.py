"""Device time per training step under the program's named scope
``train.fwd_bwd``: the model's forward and backward (the microbatch split,
the forward, the backward, the gradient's accumulation and cast).  The
union of the intervals of the ops whose ``op_name`` carries the scope, read
from the window superstep's HLO text (``bench/scopes.py``)."""


def read(run):
    if run.scopes is None or not run.trace.chips:
        return None
    return run.scopes["train.fwd_bwd"] or None
