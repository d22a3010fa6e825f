"""Device time per training step of the expert FFN outside its grouped
matmuls: the ops under the program's named scope ``moe`` but not under
``moe.experts`` (routing, top-k and aux; the sort, group sizes and
gather; the combine), forward and backward.  The union of their
intervals, per chip, averaged over the chips.  A ``conditional`` (the
choice of dispatch buffer) spans the ops of the branch it runs, the
grouped matmuls among them: its own interval is left out, its ops count
as themselves."""

import math

from bench import scopes, trace


def read(run):
    if run.hlo is None or run.trace is None or not run.trace.chips:
        return None
    smap = scopes.scope_map(run.hlo)
    memo = {}

    def outside_experts(text):
        name = trace.op_name(text)
        if name not in memo:
            comps = scopes.components(smap.get(name, ""))
            memo[name] = ("moe" in comps and "moe.experts" not in comps
                          and " conditional(" not in text)
        return memo[name]

    total = 0.0
    for chip in run.trace.chips:
        ivs = [(s, e) for text, s, e in chip.ops if outside_experts(text)]
        total += trace.busy(ivs, -math.inf, math.inf)
    s = total / len(run.trace.chips)
    return 1e3 * s / run.steps or None
