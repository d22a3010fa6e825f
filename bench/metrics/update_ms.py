"""Device time per training step under the program's named scope
``train.update``: the optimizer's update (the GWT buckets, their fused
kernel and packing, and the plain-Adam leaves).  The union of the
intervals of the ops whose ``op_name`` carries the scope, read from the
window superstep's HLO text (``bench/scopes.py``)."""


def read(run):
    if run.scopes is None or not run.trace.chips:
        return None
    return run.scopes["train.update"] or None
