"""Model FLOP/s utilization of the whole training step: the architecture's
model FLOPs per token (its ``model_flops_per_token``: PaLM's for the dense
decoder) times the tokens per second of the traced window, over the chips'
bf16 peak.  Recomputation is not counted, so the share cannot pass
100%."""


def read(run):
    if run.peaks is None or not run.tokens_per_s:
        return None
    flops = run.arch.model_flops_per_token(run.spec, run.traffic["seq"])
    return 100.0 * flops * run.tokens_per_s / (run.chips
                                               * run.peaks["bf16_flops"])
