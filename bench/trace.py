"""Reduction of a profiler trace to what the per-layer metrics read.

The window is the span of the benchmark's own ``TraceAnnotation``
(``bench.window``) on the host; a chip's busy time is the union of the
intervals of its operations (the ``XLA Ops`` line of each
``/device:TPU:n`` plane) inside that window; the idle gaps are what the
union leaves out, each labelled by the innermost host span that covers its
middle.  The pure functions below work on plain ``(start, end)`` pairs so
that they can be checked on a hand-made trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    out: List[list] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """Name of the shortest host span covering the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Tuple[float, str]] = None
    for name, s, e in host:
        if s <= mid <= e and name != WINDOW:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else WINDOW


@dataclasses.dataclass
class Chip:
    name: str
    ops: List[Tuple[str, float, float]]   # HLO instruction, start, end (s)
    busy_s: float


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: List[Chip]
    idle_gaps: List[Tuple[str, float]]          # label, seconds (longest first)

    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / max(len(self.chips), 1)

    def op_seconds(self, match) -> float:
        """Device seconds of the ops ``match(name)`` selects, averaged over
        the chips."""
        tot = sum(e - s for c in self.chips for n, s, e in c.ops
                  if match(n))
        return tot / max(len(self.chips), 1)

    def top_ops(self, k: int = 10, width: int = 160
                ) -> List[Tuple[str, float]]:
        """The ``k`` ops that took most device time (an op inside a loop
        counts apart from the loop), names cut to ``width`` characters."""
        acc: Dict[str, float] = {}
        for c in self.chips:
            for n, s, e in c.ops:
                acc[n] = acc.get(n, 0.0) + (e - s) / len(self.chips)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(n[:width], t) for n, t in top]


def op_name(text: str) -> str:
    """The instruction's own name (``%fusion.12``) from an op event's
    name, which on the TPU is the whole HLO instruction."""
    return text.split(" = ", 1)[0]


def is_gwt_kernel(name: str) -> bool:
    """An event of the fused GWT-Adam Pallas kernel: a Mosaic custom call
    whose instruction is named after the kernel's entry point
    (``kernels/gwt_adam/ops.py``: ``_fused_write_update``, ``_q8`` for
    int8 moments)."""
    return ('custom_call_target="tpu_custom_call"' in name
            and "fused_write_update" in op_name(name))


def summarize(xplane_path: str) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    host: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                host.append((ev.name, s, e))
                if ev.name == WINDOW and window is None:
                    window = (s, e)
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} span in {xplane_path}")
    lo, hi = window
    chips, all_gaps = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                if e > lo and s < hi:
                    ops.append((ev.name, max(s, lo), min(e, hi)))
        ivs = [(s, e) for _, s, e in ops]
        chips.append(Chip(plane.name, ops, busy(ivs, lo, hi)))
        all_gaps += [(label(g, host), g[1] - g[0]) for g in gaps(ivs, lo, hi)]
    all_gaps.sort(key=lambda x: -x[1])
    return Summary(window_s=hi - lo, chips=chips, idle_gaps=all_gaps)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
