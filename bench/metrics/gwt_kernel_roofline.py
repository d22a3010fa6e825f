"""The fused GWT-Adam kernel's share of its roofline: the least time the
chip could take for one step's wavelet updates — the larger of the
algorithm's minimum bytes over the HBM bandwidth and its arithmetic over
the bf16 peak (``bench.counts.gwt_kernel_work`` over the architecture's
layout) — over the kernel's measured time per step."""

from bench.counts import gwt_kernel_work
from bench.trace import is_gwt_kernel


def read(run):
    t = run.trace
    if t is None or not t.chips or run.peaks is None:
        return None
    s = t.op_seconds(is_gwt_kernel) / run.steps
    if s <= 0:
        return None
    flops, nbytes = gwt_kernel_work(run.arch, run.spec,
                                    run.traffic["optimizer"]["level"])
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops"])
    return 100.0 * least / s
