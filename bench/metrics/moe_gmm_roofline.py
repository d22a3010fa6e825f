"""The grouped matmuls' share of their roofline: the least time the chip
could take for one step's expert products — the larger of their minimum
bytes over the HBM bandwidth and their FLOPs over the bf16 peak, from the
architecture's ``gmm_work`` (rows routed to held experts, four passes) —
over the device time per step of the ops under the program's named scope
``moe.experts``."""

from bench import scopes


def read(run):
    work = getattr(run.arch, "gmm_work", None)
    if work is None or run.peaks is None or run.hlo is None \
            or run.trace is None or not run.trace.chips:
        return None
    s = scopes.scope_seconds(run.trace, scopes.scope_map(run.hlo),
                             "moe.experts") / run.steps
    if s <= 0:
        return None
    flops, nbytes = work(run.spec, run.traffic)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops"])
    return 100.0 * least / s
