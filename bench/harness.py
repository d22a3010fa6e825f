"""The benchmark's entry: find a cell's files by name, run its driver, read
its per-layer metrics, print the result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the configuration (the ``file`` of its
  ``configs`` entry); its ``bench_arch`` names ``bench/arch/<arch>.py``,
  the architecture module (``bench/model.py`` says what it provides);
* ``bench/traffic/<traffic>.json`` — the traffic mix or job; its
  ``driver`` names ``bench/drivers/<driver>.py``, the code that runs it;
* ``bench/limits/<workload>.json`` — the limits of the correctness check;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric, a
  ``read(run) -> float | None`` over what the driver measured.

A new cell, configuration, architecture, traffic mix or metric is a new
file and a new ``BENCHMARK.json`` entry; no existing file changes.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each compared number with its limit
(the same numbers are the last lines on standard error).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Cell:
    """One ``workloads`` entry with its files resolved under ``root``."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = pathlib.Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        try:
            self.entry = next(w for w in self.bench["workloads"]
                              if w["name"] == name)
        except StopIteration:
            raise SystemExit(f"bench: no workload {name!r} in "
                             f"{self.root / 'BENCHMARK.json'}") from None
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.entry["config"])
        self.config_path = self.root / cfg["file"]
        here = self.root / "bench"
        arch = json.loads(self.config_path.read_text())["bench_arch"]
        self.arch_path = here / "arch" / f"{arch}.py"
        self.traffic_path = here / "traffic" / f"{self.entry['traffic']}.json"
        self.traffic = json.loads(self.traffic_path.read_text())
        self.limits = json.loads(
            (here / "limits" / f"{name}.json").read_text())
        self.driver_path = here / "drivers" / f"{self.traffic['driver']}.py"

    def applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.applies(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.applies(m)]

    def reader(self, metric_name: str):
        return load_module(self.root / "bench" / "metrics"
                           / f"{metric_name}.py")

    def driver(self):
        return load_module(self.driver_path)

    def arch(self):
        return load_module(self.arch_path)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(cell: Cell, run) -> dict:
    """``{name: {"value", "unit"}}`` of every per-layer metric whose reader
    finds something to read in ``run``."""
    out = {}
    for m in cell.per_layer():
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome, trace: bool) -> dict:
    """Assemble the result from a driver's outcome (see
    ``bench/drivers/train.py``)."""
    if trace:
        metrics = read_per_layer(cell, outcome.run)
    else:
        metrics = {}
        for m in cell.end_to_end():
            if m["name"] not in outcome.end_to_end:
                raise RuntimeError(f"driver reported no {m['name']!r}")
            metrics[m["name"]] = {"value": float(outcome.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": bool(outcome.correct), "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics,
            "device": outcome.device}
    if trace and outcome.breakdown is not None:
        line["breakdown"] = outcome.breakdown
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in outcome.check.items()}
    return line


def run_cell(root, name: str, seed: int, seconds: float, trace: bool,
             clock0: float, require_tpu: bool = True, **driver_kw) -> dict:
    cell = Cell(root, name)
    outcome = cell.driver().run(cell, seed=seed, seconds=seconds,
                                trace=trace, clock0=clock0,
                                require_tpu=require_tpu, **driver_kw)
    return result_line(cell, outcome, trace)


def print_result(line: dict) -> None:
    for k, c in line["check"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv, clock0: float, root: Optional[pathlib.Path] = None) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1, keep the profiler's trace in this "
                         "directory (for reading by hand)")
    args = ap.parse_args(argv)
    root = pathlib.Path(root or ROOT)
    if not (root / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {root}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    try:
        line = run_cell(root, args.workload, args.seed, args.seconds,
                        bool(args.trace), clock0,
                        keep_trace=args.keep_trace)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print_result(line)
    return 0
