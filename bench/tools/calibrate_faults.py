"""``calibrate.py``'s readings plus the architecture's own planted faults,
for setting a training cell's limits on the chip in one process.

On each seed: the program's numbers (``bench/check.py``) against the
reference; on the first ``--faults`` seeds also the fp8 control, the
half-batch fault and every fault the architecture's ``Reference`` lists in
``FAULTS`` (``bench/arch/moe.py``: ``drop``, capacity 1.0 in token order),
each in the program's place.  Where the reference reports ``held_share``
(the share of routed pairs whose expert is held here, per layer, for each
step), the line carries the first step's.

    python3 bench/tools/calibrate_faults.py \
        --workload qwen3-moe-30b-a3b-l6.s1024 --seeds 11 12 13 --faults 2 \
        --out calib.jsonl
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

calibrate = harness.load_module(ROOT / "bench" / "tools" / "calibrate.py")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bench/tools/calibrate_faults.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=2,
                    help="seeds (the first ones) that also run the control "
                         "and the faults")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate_faults: no TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell(ROOT, args.workload)
    driver = cell.driver()
    prog = driver.Program(cell, require_tpu=True)
    spec, tr, opt = prog.spec, prog.traffic, prog.opt
    ref = lambda **kw: prog.arch.Reference(spec, opt, tr["seq"], **kw)
    reference = ref()
    faults = {"control_fp8": ref(precision="fp8"),
              "fault_half_batch": ref(fault="half_batch")}
    for name in getattr(prog.arch.Reference, "FAULTS", ()):
        faults[f"fault_{name}"] = ref(fault=name)
    out = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(args.seeds):
            t0 = time.monotonic()
            refs = {"reference": reference,
                    **(faults if i < args.faults else {})}
            rec = calibrate.calibrate(prog, seed, driver.CHECK_STEPS, refs)
            shares = getattr(reference, "held_share", None)
            if shares:
                rec["held_share"] = shares[0]
            rec.update(workload=args.workload, seconds=time.monotonic() - t0)
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
