"""Share of the elements the fused GWT-Adam kernel updates with the
limiter on whose norm pass (phase 0) reads only the gradient and the
moments and writes nothing: the program's trace-time counter
``gwt.kernel.norm_pass``, summed over the window superstep's buckets.  A
program without the counter reads nothing.

The program sets the counter from which kernel it launches (the
f32-moment kernel pins its other windows through phase 0, the int8 one
writes its state through), so this is a check of the update's
structure, not a reading of the run.  What the norm pass costs is read
by ``gwt_kernel_ms``."""


def read(run):
    c = (run.counters or {}).get("gwt.kernel.norm_pass")
    if not c:
        return None
    norm_only, full = c["elements_norm_only"], c["elements_full"]
    if norm_only + full <= 0:
        return None
    return 100.0 * norm_only / (norm_only + full)
