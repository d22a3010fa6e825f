"""Readings that the limits of a training cell's check are set from, at the
cell's own size, on the chip, in one process:

* the program's numbers (``bench/check.py``) on each seed — the lower
  readings;
* the fp8 control (the architecture's ``Reference(precision="fp8")`` in
  the program's place) and the half-batch fault
  (``Reference(fault="half_batch")``) on the first ``--faults`` seeds —
  the upper readings;
* with ``--bf16``, on every seed, the reference with its products' operands
  rounded to bfloat16 (``Reference(precision="bf16")``), the program's own
  operand type: how far that rounding alone, with no code of the program,
  moves each number;
* with ``--no-limiter``, all of the above with the norm-growth limiter
  left out of the program and of every reference: its share of a gap.

    python3 bench/tools/calibrate.py --workload mistral-7b-l4.s256 \
        --seeds 11 12 13 --faults 3 --bf16 --out calib.jsonl

Each seed prints one JSON line.  For every reading it names the slice each
norm gap is worst at, and for the worst slice of the parameters' change:
how much of the reference's change its largest 0.1% of elements hold, the
gap with those elements left out of both sides, and its first gradient's
norm over the median slice's.
"""

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check, data, harness  # noqa: E402
from bench import weights as wlib  # noqa: E402

TOP = 1e-3       # the share of a slice's elements that counts as its top


def worst_slice(prog: dict, ref: dict, keep=None) -> tuple:
    """``(path, layer)`` of the largest norm gap."""
    keys = sorted(ref)
    floor = np.median(np.concatenate([np.ravel(ref[k]) for k in keys]))
    best, where = -1.0, None
    for k in keys:
        p, r = np.ravel(prog[k]), np.ravel(ref[k])
        g = np.abs(p - r) / np.maximum(r, floor)
        if keep is not None:
            g = np.where(np.ravel(keep[k]), g, -1.0)
        i = int(np.argmax(g))
        if g[i] > best:
            best, where = float(g[i]), (k, i)
    return where


def leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    """The largest change gap of each stored leaf, over its slices."""
    keys = sorted(ref)
    floor = np.median(np.concatenate([np.ravel(ref[k]) for k in keys]))
    out = {}
    for k in keys:
        p, r = np.ravel(prog[k]), np.ravel(ref[k])
        g = np.where(np.ravel(keep[k]), np.abs(p - r) / np.maximum(r, floor),
                     0.0)
        out[k] = float(np.max(g))
    return out


def _slice(x, path: str, i: int) -> np.ndarray:
    x = np.asarray(x)
    return (x[i] if path.startswith("layers/") else x).astype(np.float32)


def tail(path: str, i: int, weights, other_params, ref_params,
         ref_change_floor: float) -> dict:
    """The worst slice's change on each side, element by element: the
    share of the reference's squared change in its top ``TOP`` elements,
    and the norm gap with those elements left out of both sides."""
    w = _slice(weights[path], path, i)
    d_o = _slice(other_params[path], path, i) - w
    d_r = _slice(ref_params[path], path, i) - w
    a = np.abs(d_r).ravel()
    n_top = max(1, int(TOP * a.size))
    top = np.argpartition(a, -n_top)[-n_top:]
    rest = np.ones(a.size, bool)
    rest[top] = False
    o2, r2 = d_o.ravel() ** 2, d_r.ravel() ** 2
    norm = lambda v: float(np.sqrt(np.sum(v, dtype=np.float64)))
    r_rest = norm(r2[rest])
    return {"elements": int(a.size), "top": n_top,
            "ref_top_share": float(np.sum(r2[top]) / np.sum(r2)),
            "other_top_share": float(np.sum(o2[top]) / np.sum(o2)),
            "gap_without_top": abs(norm(o2[rest]) - r_rest)
            / max(r_rest, ref_change_floor)}


def numbers(other: dict, ref: dict, weights, other_params, ref_params) -> dict:
    out = check.numbers(other, ref)
    out["grad_worst"] = "{}[{}]".format(*worst_slice(other["grad_band"],
                                                     ref["grad_band"]))
    keep = check.moving_slices(ref["grad_full"])
    path, i = worst_slice(other["change"], ref["change"], keep)
    out["update_worst"] = f"{path}[{i}]"
    grad_med = np.median(np.concatenate(
        [np.ravel(v) for v in ref["grad_full"].values()]))
    change_med = float(np.median(np.concatenate(
        [np.ravel(v) for v in ref["change"].values()])))
    out["update_worst_detail"] = {
        "other": float(np.ravel(other["change"][path])[i]),
        "reference": float(np.ravel(ref["change"][path])[i]),
        "grad_over_median": float(np.ravel(ref["grad_full"][path])[i]
                                  / grad_med),
        **tail(path, i, weights, other_params, ref_params, change_med)}
    out["leaf_gaps"] = leaf_gaps(other["change"], ref["change"], keep)
    return out


def host_params(flat: dict) -> dict:
    import jax
    return {k: np.asarray(v) for k, v in jax.device_get(flat).items()}


def calibrate(prog, seed: int, steps: int, refs: dict) -> dict:
    """One seed, ``steps`` steps: the program, then each reference in
    ``refs`` (the first is the one the others are compared with)."""
    spec, tr, opt = prog.spec, prog.traffic, prog.opt
    params, opt_state, readings = prog.first_steps(seed)
    prog_params = host_params(wlib.flatten(params))
    del params, opt_state
    gc.collect()
    weights = wlib.make_weights(prog.arch, spec, seed, opt["level"])
    batches = [data.make_source(seed, spec.vocab, tr).batch(j)
               for j in range(steps)]
    (_, reference), *others = refs.items()
    ref = reference.run(weights, batches, keep_params=True)
    ref_params = host_params(ref.pop("params"))
    w = host_params(weights)
    rec = {"seed": seed,
           "losses": {"program": readings["losses"],
                      "reference": ref["losses"]},
           "program": numbers(readings, ref, w, prog_params, ref_params)}
    del prog_params
    for name, other_ref in others:
        other = other_ref.run(weights, batches, keep_params=True)
        other_params = host_params(other.pop("params"))
        rec[name] = numbers(other, ref, w, other_params, ref_params)
        del other, other_params
    del weights, ref
    gc.collect()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) that also run the control "
                         "and the half-batch fault")
    ap.add_argument("--bf16", action="store_true",
                    help="also run the bf16-operand reference on every seed")
    ap.add_argument("--no-limiter", action="store_true",
                    help="leave the norm-growth limiter out of the program "
                         "and of every reference")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.cache import enable_compile_cache
    cell = harness.Cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = cell.driver()
    lim = not args.no_limiter
    prog = driver.Program(cell, require_tpu=True, limiter=lim)
    spec, tr, opt = prog.spec, prog.traffic, prog.opt
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec["workload"] = args.workload
        rec["limiter"] = lim
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    ref = lambda **kw: prog.arch.Reference(spec, opt, tr["seq"], limiter=lim,
                                           **kw)
    reference = ref()
    faults = {"control_fp8": ref(precision="fp8"),
              "fault_half_batch": ref(fault="half_batch")}
    bf16 = {"reference_bf16": ref(precision="bf16")} if args.bf16 else {}
    for i, seed in enumerate(args.seeds):
        t0 = time.monotonic()
        refs = {"reference": reference, **bf16,
                **(faults if i < args.faults else {})}
        rec = calibrate(prog, seed, driver.CHECK_STEPS, refs)
        rec["seconds"] = time.monotonic() - t0
        emit(rec)


if __name__ == "__main__":
    main()
