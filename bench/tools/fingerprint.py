"""Fingerprint of a cell's timed program: the SHA-256 of the window
superstep as lowered for this machine's devices (StableHLO text without
debug information, so neither source locations nor the name stack count),
built by the benchmark found under ``--root``.  Two trees that print the
same fingerprint for a cell time the same program.  On the TPU the
Pallas kernels' payloads carry their source files' paths, so compare two
trees unpacked in turn at one path.

    python3 bench/tools/fingerprint.py --workload mistral-7b-l4.s256 \
        qwen2.5-3b-l9.s256 --root <a checkout>

Nothing runs and nothing is compiled: the parameters and the optimizer's
state are shapes (``jax.eval_shape``).  It imports the ``bench`` package
of ``--root`` (this checkout by default), so one copy of the tool reads
an older tree as well.
"""

import argparse
import hashlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def fingerprint(root: pathlib.Path, name: str) -> dict:
    import jax
    import numpy as np
    from bench import harness
    from repro.models import lm
    cell = harness.Cell(root, name)
    prog = cell.driver().Program(
        cell, require_tpu=jax.devices()[0].platform == "tpu")
    with prog.ctx.activate():
        params = jax.eval_shape(lambda: lm.init(prog.cfg, jax.random.key(0)))
        opt_state = jax.eval_shape(prog.optimizer.init, params)
    tr = prog.traffic
    start = 3 * tr["log_every"]            # the window's first superstep
    sds = jax.ShapeDtypeStruct(
        (prog.chunk_at(start), tr["batch"], tr["seq"]), np.int32)
    if prog.loop._superstep is None:
        prog.loop._superstep = prog.loop._build_superstep()
    with prog.ctx.activate():
        text = prog.loop._superstep.lower(
            params, opt_state, {"tokens": sds, "labels": sds}).as_text()
    return {"workload": name, "root": str(root),
            "platform": jax.devices()[0].platform,
            "superstep_steps": prog.chunk_at(start),
            "sha256": hashlib.sha256(text.encode()).hexdigest()}


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/tools/fingerprint.py")
    ap.add_argument("--workload", required=True, nargs="+")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args(argv)
    root = pathlib.Path(args.root).resolve()
    sys.path[:0] = [str(root), str(root / "src")]
    for name in args.workload:
        print(json.dumps(fingerprint(root, name)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
