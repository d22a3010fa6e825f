"""Run one cell traced and print its step split by the program's own names:
device time per named scope of the step, idle device time per group of the
train loop's host spans, both read from the same profiler trace.

    python3 bench/tools/layers.py --workload qwen2.5-3b-l9.s256 --seed 7 \
        --seconds 10 --out layers.jsonl

The run is the benchmark's own ``--trace 1`` run of the cell: the driver
(``bench/drivers/train.py``) reduces the trace with ``bench/scopes.py``
(``step_split``) into the ``RunInfo`` the per-layer metrics read, and this
tool prints that same split with what it takes to read it.  One line:

* ``ms_per_step``: device busy and idle time, the union of the ops under
  each scope (``scopes.SCOPES``), the GWT kernel found by its instruction
  name (as ``gwt_kernel_ms`` finds it), the busy time under neither
  ``train.fwd_bwd`` nor ``train.update``, and the idle time whose gap lies
  under the input spans and under the sync spans (``scopes.IDLE_UNDER``);
* ``unscoped_ops``: the ops under neither scope, by device time;
* ``gaps``: the longest idle gaps, each with the benchmark's label, the
  innermost program span (``train.*``) over its middle, and the program
  spans it overlaps, in ms from the gap's start;
* ``spans``: count, total and longest duration of each program span;
* ``instructions``: how many of the HLO's instructions carry each scope;
* ``tokens_per_s`` of the traced window, the cell's ``correct``, and the
  result line's per-layer metrics.
"""

import argparse
import collections
import json
import math
import pathlib
import sys
import time

CLOCK0 = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, scopes, trace  # noqa: E402


def _innermost(mid: float, spans) -> str:
    best = None
    for n, s, e in spans:
        if n.startswith("train.") and s <= mid <= e:
            if best is None or e - s < best[0]:
                best = (e - s, n)
    return best[1] if best else ""


def report(run) -> dict:
    """The split of a traced run (the driver's ``RunInfo``), in ms per
    step, with the ops, gaps and spans behind it."""
    t, steps = run.trace, run.steps
    smap = scopes.scope_map(run.hlo)
    per_step = 1e3 / steps
    own = {n: set(scopes.components(smap.get(trace.op_name(n), "")))
           for c in t.chips for n, _, _ in c.ops}
    main = {"train.fwd_bwd", "train.update"}
    scoped, rest = 0.0, collections.Counter()
    for c in t.chips:
        ivs = [(s, e) for n, s, e in c.ops if own[n] & main]
        scoped += trace.busy(ivs, -math.inf, math.inf)
        for n, s, e in c.ops:
            if not own[n] & main:
                rest[trace.op_name(n)] += (e - s) / len(t.chips)
    ms = {"busy": per_step * t.busy_s,
          "idle": per_step * (t.window_s - t.busy_s),
          "gwt_kernel_by_name": per_step * t.op_seconds(trace.is_gwt_kernel),
          **run.scopes,
          "unscoped": per_step * (t.busy_s
                                  - scoped / max(len(t.chips), 1)),
          "input_wait": run.idle_under["input"],
          "sync_wait": run.idle_under["sync"]}
    unscoped = [{"op": n, "ms_per_step": per_step * v,
                 "op_name": smap.get(n, "")}
                for n, v in rest.most_common(12)]
    gaps = sorted(t.gap_spans, key=lambda g: g[0] - g[1])[:12]
    host = [(n, s, e) for n, s, e in t.host_spans if n != trace.WINDOW]
    gap_rows = [{"ms": 1e3 * (e - s), "label": trace.label((s, e), host),
                 "program_span": _innermost(0.5 * (s + e), host),
                 "spans": [(n, 1e3 * (a - s), 1e3 * (b - s))
                           for n, a, b in host
                           if n.startswith("train.") and a < e and b > s]}
                for s, e in gaps]
    spans = collections.defaultdict(list)
    for n, s, e in t.host_spans:
        if n.startswith("train."):
            spans[n].append(e - s)
    span_rows = {n: {"count": len(d), "total_ms": 1e3 * sum(d),
                     "max_ms": 1e3 * max(d)} for n, d in spans.items()}
    named = {sc: sum(sc in scopes.components(v) for k, v in smap.items()
                     if not k.startswith("%")) for sc in scopes.SCOPES}
    return {"ms_per_step": ms, "unscoped_ops": unscoped,
            "gaps": gap_rows, "spans": span_rows, "instructions": named}


def layers(root, name: str, seed: int, seconds: float,
           require_tpu: bool = True, **run_kw) -> dict:
    """Run cell ``name`` of the benchmark at ``root`` traced; its split."""
    cell = harness.Cell(root, name)
    out = cell.driver().run(cell, seed=seed, seconds=seconds, trace=True,
                            clock0=CLOCK0, require_tpu=require_tpu, **run_kw)
    line = harness.result_line(cell, out, True)
    return {"workload": name, "seed": seed, "correct": line["correct"],
            "steps": out.run.steps,
            "tokens_per_s": out.end_to_end["tokens_per_s"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            **report(out.run)}


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/tools/layers.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="", help="append the line to this file")
    args = ap.parse_args(argv)
    row = layers(ROOT, args.workload, args.seed, args.seconds)
    text = json.dumps(row)
    print(text, flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
