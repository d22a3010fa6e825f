import os
import sys


def _jax_backend_uninitialized() -> bool:
    """XLA reads XLA_FLAGS at first *backend init*, not at jax import —
    so the fake-device request below is effective (and worth setting) any
    time before that, and pure pollution after (it would only leak into
    child-process environments, e.g. the test suite's subprocesses)."""
    if "jax" not in sys.modules:
        return True
    try:
        from jax._src import xla_bridge
        return not xla_bridge._backends
    except Exception:
        return False


if _jax_backend_uninitialized():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture × input shape × mesh): build abstract params +
optimizer state + inputs (ShapeDtypeStruct — zero allocation), lower the
step function with explicit in/out shardings, ``.compile()``, and record
``memory_analysis()`` / ``cost_analysis()`` / parsed-HLO roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch jamba-v0.1-52b \
        --shape train_4k [--multipod] [--out out.json] [--level 3]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.gwt import gwt as gwt_optimizer
from repro.distributed import sharding as shr
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, lm
from repro.runtime.context import MeshContext


def _decode_fill(shape):
    """Cache depth for decode cells: 'one new token with a KV cache of
    seq_len' — the new token lands in the last slot."""
    return shape.seq_len


def build_cell(cfg, shape, mesh, *, gwt_level: int = 2, optimizer=None,
               rules_override=None, ctx: MeshContext = None):
    """Returns (fn, args, in_shardings, out_shardings) ready to lower."""
    if ctx is None:
        ctx = MeshContext.create(mesh=mesh)
    is_encdec = cfg.arch_class == "encdec"
    mod = encdec if is_encdec else lm
    params_abs = mod.abstract_params(cfg)
    params_axes = mod.param_axes(cfg)
    batch_abs = configs.input_specs(cfg, shape)
    batch_sh = shr.batch_shardings(batch_abs, mesh)

    if shape.kind == "train":
        rules = rules_override or shr.train_rules(mesh)
        params_sh = shr.tree_shardings(params_abs, params_axes, mesh, rules)
        opt = optimizer or gwt_optimizer(
            lr=1e-2, level=gwt_level, alpha=0.25, state_dtype=jnp.bfloat16)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_sh = shr.gwt_state_shardings(params_abs, params_axes, mesh, rules,
                                         gwt_level)
        dp = mesh.shape["data"] * mesh.shape.get("pod", 1)
        accum = max(1, min(shape.accum_steps, shape.global_batch // dp))
        fn = mod.make_train_step(cfg, opt, accum_steps=accum, ctx=ctx)
        args = (params_abs, opt_abs, batch_abs)
        in_sh = (params_sh, opt_sh, batch_sh)
        out_sh = (params_sh, opt_sh, None)
        return fn, args, in_sh, out_sh, {"accum_steps": accum}

    rules = rules_override or shr.decode_rules(mesh)
    params_sh = shr.tree_shardings(params_abs, params_axes, mesh, rules)
    if shape.kind == "prefill":
        fn = mod.make_prefill_step(cfg, ctx=ctx)
        return fn, (params_abs, batch_abs), (params_sh, batch_sh), None, {}

    # decode
    fill = _decode_fill(shape)
    if is_encdec:
        cache_abs = mod.abstract_cache(cfg, shape.global_batch, fill,
                                       enc_len=shape.seq_len // 4)
        cache_ax = mod.cache_axes(cfg)
    else:
        cache_abs = mod.abstract_cache(cfg, shape.global_batch, fill)
        cache_ax = mod.cache_axes(cfg)
    cache_sh = shr.tree_shardings(cache_abs, cache_ax, mesh, rules)
    fn = mod.make_decode_step(cfg, ctx=ctx)
    args = (params_abs, cache_abs, batch_abs)
    in_sh = (params_sh, cache_sh, batch_sh)
    out_sh = (None, cache_sh)
    return fn, args, in_sh, out_sh, {}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             gwt_level: int = 2, save_hlo: str = "", verbose: bool = True):
    cfg = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    skip = configs.skip_reason(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skip", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = MeshContext.create(mesh=mesh)
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, meta = build_cell(cfg, shape, mesh,
                                                   gwt_level=gwt_level,
                                                   ctx=ctx)
        # donation: params+opt_state (train) / cache (decode) alias in place
        donate = (0, 1) if shape.kind == "train" \
            else ((1,) if shape.kind == "decode" else ())
        with ctx.activate():
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
    except Exception as e:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:]}

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
    from benchmarks import hlo_analysis
    n_chips = mesh.devices.size
    io_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes)
    roof = hlo_analysis.analyze(hlo, n_chips=n_chips, cost_analysis=cost,
                                io_bytes=max(io_bytes, 0))
    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "n_chips": n_chips, **meta,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "total_bytes_per_device": (mem.argument_size_in_bytes
                                       + mem.output_size_in_bytes
                                       + mem.temp_size_in_bytes
                                       - mem.alias_size_in_bytes),
        },
        "hbm_budget_bytes": 16 * 1024 ** 3,
        "roofline": roof,
    }
    result["fits_hbm"] = result["memory"]["total_bytes_per_device"] \
        < result["hbm_budget_bytes"]
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
        result["hlo_path"] = save_hlo
    if verbose:
        m = result["memory"]["total_bytes_per_device"] / 2 ** 30
        r = roof
        print(f"[{arch} × {shape_name} × {'2pod' if multi_pod else '1pod'}] "
              f"OK mem={m:.2f}GiB/dev fits={result['fits_hbm']} "
              f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms -> {r['bottleneck']}",
              flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch × shape) cells on BOTH meshes")
    ap.add_argument("--level", type=int, default=2, help="GWT level")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    results = []

    def flush():
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    if args.all:
        for arch in configs.ARCH_IDS:
            for shape in configs.SHAPES:
                for mp in (False, True):
                    r = run_cell(arch, shape, multi_pod=mp,
                                 gwt_level=args.level)
                    if r["status"] != "ok":
                        print(f"[{arch} × {shape} × "
                              f"{'2pod' if mp else '1pod'}] "
                              f"{r['status'].upper()}: "
                              f"{r.get('reason') or r.get('error')}",
                              flush=True)
                    results.append(r)
                    flush()  # incremental: survive a mid-run crash
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        results.append(run_cell(args.arch, args.shape,
                                multi_pod=args.multipod,
                                gwt_level=args.level))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_bad = sum(r["status"] == "error" for r in results)
    print(f"{len(results)} cells: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skip' for r in results)} skip, {n_bad} error")
    sys.exit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
