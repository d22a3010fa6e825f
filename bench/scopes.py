"""The step split by the program's own names, read from the profiler trace.

Two kinds of name reach the trace.  On the device, the program's
``jax.named_scope`` names (``train.fwd_bwd``, ``train.update``,
``optim.pack``, ``gwt.kernel``) travel in each compiled instruction's
``op_name`` metadata, which the op events do not carry: ``scope_map``
reads them from the compiled step's HLO text, keyed by instruction name.
On the host, the train loop's spans (``train.input_wait``,
``train.dispatch``, ...) are ``TraceAnnotation`` events on the same clock
as the device's ops.

``summarize`` extends ``bench.trace.summarize`` with the two things its
summary does not keep: every host event clipped to the window, and the
idle intervals whose lengths ``idle_gaps`` lists.  The functions below are
pure over those, so they can be checked on a hand-made trace.
:func:`step_split` is the one reduction of a traced window to ms per step
by scope and by group of host spans: the training driver fills its
``RunInfo`` with it, and ``bench/tools/layers.py`` prints it.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace

# an HLO instruction line: ``[ROOT] %name = <shape> op(...), ...,
# metadata={op_name="a/b/c" ...}``
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)
# a transformation's wrapper around a name-stack component: ``jvp(``,
# ``transpose(``, ``jit(``
_WRAPPER = re.compile(r"[\w\-]+\(")

# the step's named scopes (device time of each, ms per step)
SCOPES = ("train.fwd_bwd", "train.update", "optim.pack", "gwt.kernel",
          "train.dp_reduce")
# host spans under which an idle gap counts as waiting for input, or for
# the loop's own synchronisation (fetches, log lines, dispatch)
IDLE_UNDER = {
    "input": ("train.input_wait", "train.place", "train.close"),
    "sync": ("train.block", "train.log", "train.dispatch",
             "train.dispatch_first"),
}


@dataclasses.dataclass
class Summary(trace.Summary):
    host_spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)                    # name, start, end (s)
    gap_spans: List[trace.Interval] = dataclasses.field(
        default_factory=list)                    # idle intervals, all chips


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name to its ``op_name``, under both the name the HLO
    text prints (``%fusion.12``) and the bare one (``fusion.12``)."""
    out = {}
    for name, op_name in _INSTR.findall(hlo_text):
        out[name] = out["%" + name] = op_name
    return out


def components(op_name: str) -> List[str]:
    """The name stack's components with the transformations' wrappers
    taken off: ``jit(step)/train.fwd_bwd/transpose(jvp(while))/body``
    gives ``step``, ``train.fwd_bwd``, ``while``, ``body``."""
    return [c for c in _WRAPPER.sub("", op_name).replace(")", "").split("/")
            if c]


def scope_seconds(summary: trace.Summary, scopes: Dict[str, str],
                  component: str) -> float:
    """Device seconds in which an op whose ``op_name`` has ``component``
    ran: per chip the union of those ops' intervals (a ``while`` and the
    ops of its body count once), averaged over the chips."""
    memo: Dict[str, bool] = {}
    total = 0.0
    for chip in summary.chips:
        ivs = []
        for text, s, e in chip.ops:
            name = trace.op_name(text)
            hit = memo.get(name)
            if hit is None:
                hit = memo[name] = component in components(
                    scopes.get(name, ""))
            if hit:
                ivs.append((s, e))
        total += trace.busy(ivs, -math.inf, math.inf)
    return total / max(len(summary.chips), 1)


def idle_under(summary: Summary, names: Iterable[str]) -> Optional[float]:
    """Idle device seconds, averaged over the chips, in the gaps whose
    middle lies inside a host span named in ``names``; ``None`` when no
    such span is in the window (a program that does not name them)."""
    names = set(names)
    spans = [(s, e) for n, s, e in summary.host_spans if n in names]
    if not spans:
        return None
    idle = 0.0
    for lo, hi in summary.gap_spans:
        mid = 0.5 * (lo + hi)
        if any(s <= mid <= e for s, e in spans):
            idle += hi - lo
    return idle / max(len(summary.chips), 1)


def step_split(summary: Summary, hlo_text: str, steps: int) -> tuple:
    """``(scopes, idle)`` of a traced window of ``steps`` steps and the
    HLO text of its program, in ms per step: the device time of each of
    :data:`SCOPES`, and the idle time under each group of
    :data:`IDLE_UNDER` (``None`` where no span of the group is in the
    window)."""
    smap = scope_map(hlo_text)
    per_step = 1e3 / steps
    scoped = {sc: per_step * scope_seconds(summary, smap, sc)
              for sc in SCOPES}
    idle = {}
    for group, names in IDLE_UNDER.items():
        sec = idle_under(summary, names)
        idle[group] = None if sec is None else per_step * sec
    return scoped, idle


def summarize(xplane_path: str) -> Summary:
    """``bench.trace.summarize`` plus the host spans and idle intervals."""
    from jax.profiler import ProfileData
    base = trace.summarize(xplane_path)
    host = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                     for line in plane.lines for ev in line.events]
    lo, hi = next((s, e) for n, s, e in host if n == trace.WINDOW)
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in host
             if e > lo and s < hi]
    idle = [g for chip in base.chips
            for g in trace.gaps([(s, e) for _, s, e in chip.ops], lo, hi)]
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(trace.Summary)}
    return Summary(**fields, host_spans=spans, gap_spans=idle)
