"""Benchmark harness — one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows.  Model-quality proxies use
tiny configs + the synthetic pipeline (offline container); memory numbers
are exact accounting; op microbenchmarks are wall-clock on CPU.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

ROWS = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _time(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------
# Table I — memory & complexity of optimizer states
# ---------------------------------------------------------------------------

def table1_memory(quick: bool):
    from repro import configs
    from repro.core.gwt import state_memory_bytes
    from repro.models import lm
    cfg = configs.LLAMA["llama-60m"]
    params = lm.abstract_params(cfg)
    mn = sum(p.size for p in jax.tree.leaves(params))
    for name, level, expect in [("full_adam", 0, "2mn"),
                                ("gwt2", 2, "mn/2"), ("gwt3", 3, "mn/4")]:
        mem = state_memory_bytes(params, level)
        emit(f"table1/{name}_state_MiB", 0.0,
             f"{mem['total_bytes']/2**20:.1f}MiB expect~{expect}")
    emit("table1/params_M", 0.0, f"{mn/1e6:.1f}M")


# ---------------------------------------------------------------------------
# Table II — pre-training quality proxy (final loss, tiny LLaMA)
# ---------------------------------------------------------------------------

def table2_pretrain(quick: bool):
    from repro import configs, optim
    from repro.data.pipeline import SyntheticLM
    from repro.models import lm
    from repro.optim.schedules import warmup_cosine
    steps = 30 if quick else 80
    cfg = configs.LLAMA["llama-60m"].with_(
        n_layers=3, d_model=192, n_heads=4, n_kv_heads=4, head_dim=48,
        d_ff=512, vocab=1024)
    methods = [("adam", "adam", dict(lr=warmup_cosine(0.0025, steps))),
               ("galore_1_4", "galore", dict(lr=warmup_cosine(0.01, steps),
                                             rank_frac=0.25, update_gap=25)),
               ("apollo_1_4", "apollo", dict(lr=warmup_cosine(0.01, steps),
                                             rank_frac=0.25, update_gap=25)),
               ("fira_1_4", "fira", dict(lr=warmup_cosine(0.01, steps),
                                         rank_frac=0.25, update_gap=25)),
               ("muon", "muon", dict(lr=warmup_cosine(0.01, steps))),
               ("gwt2", "gwt", dict(lr=warmup_cosine(0.01, steps), level=2)),
               ("gwt3", "gwt", dict(lr=warmup_cosine(0.01, steps), level=3))]
    for tag, name, kw in methods:
        opt = optim.make(name, **kw)
        params = lm.init(cfg, jax.random.key(0))
        st = opt.init(params)
        data = SyntheticLM(cfg.vocab, 64, 16, seed=0)
        step = jax.jit(lm.make_train_step(cfg, opt))
        t0 = time.perf_counter()
        loss = None
        for i in range(steps):
            b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            params, st, m = step(params, st, b)
            loss = float(m["loss"])
        dt = (time.perf_counter() - t0) / steps * 1e6
        emit(f"table2/{tag}_final_loss", dt, f"{loss:.4f}")


# ---------------------------------------------------------------------------
# Table III — update-op throughput (the optimizer step itself)
# ---------------------------------------------------------------------------

def table3_throughput(quick: bool):
    from repro import optim
    m, n = (1024, 4096) if not quick else (256, 1024)
    params = {"mlp": {"w": jax.random.normal(jax.random.key(0), (m, n),
                                             jnp.float32)}}
    grads = {"mlp": {"w": jax.random.normal(jax.random.key(1), (m, n),
                                            jnp.float32) * 0.01}}
    for tag, name, kw in [("adam", "adam", {}),
                          ("galore_1_4", "galore", {"rank_frac": 0.25,
                                                    "update_gap": 200}),
                          ("apollo_1_4", "apollo", {"rank_frac": 0.25,
                                                    "update_gap": 200}),
                          ("gwt2", "gwt", {"level": 2}),
                          ("gwt3", "gwt", {"level": 3})]:
        opt = optim.make(name, lr=1e-3, **kw)
        st = opt.init(params)
        upd = jax.jit(opt.update)
        p2, s2 = upd(grads, st, params)  # includes any step-0 SVD
        us = _time(lambda g, s, p: upd(g, s, p)[0], grads, s2, p2, n=20)
        emit(f"table3/{tag}_update", us, f"{m}x{n}")
    # GaLore's SVD refresh step (the O(mn^2) cost the paper avoids):
    opt = optim.make("galore", lr=1e-3, rank_frac=0.25, update_gap=1)
    st = opt.init(params)
    upd = jax.jit(opt.update)
    p2, s2 = upd(grads, st, params)
    us = _time(lambda g, s, p: upd(g, s, p)[0], grads, s2, p2, n=5)
    emit("table3/galore_refresh_step", us, "SVD every step")


# ---------------------------------------------------------------------------
# Table IV — sequence-length robustness proxy
# ---------------------------------------------------------------------------

def table4_seqlen(quick: bool):
    from repro import configs, optim
    from repro.data.pipeline import SyntheticLM
    from repro.models import lm
    from repro.optim.schedules import warmup_cosine
    steps = 20 if quick else 50
    cfg = configs.LLAMA["llama-60m"].with_(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab=512)
    for seq in ((64, 128) if quick else (64, 128, 256)):
        for tag, name, kw in [("gwt2", "gwt", {"level": 2}),
                              ("galore", "galore",
                               {"rank_frac": 0.25, "update_gap": 25})]:
            opt = optim.make(name, lr=warmup_cosine(0.01, steps), **kw)
            params = lm.init(cfg, jax.random.key(0))
            st = opt.init(params)
            data = SyntheticLM(cfg.vocab, seq, 8, seed=0)
            step = jax.jit(lm.make_train_step(cfg, opt))
            loss = None
            for i in range(steps):
                b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
                params, st, m = step(params, st, b)
                loss = float(m["loss"])
            emit(f"table4/{tag}_seq{seq}_final_loss", 0.0, f"{loss:.4f}")


# ---------------------------------------------------------------------------
# Table XI — per-model memory estimates (weights + optimizer states, bf16)
# ---------------------------------------------------------------------------

def table11_memory_estimate(quick: bool):
    from repro import configs
    from repro.core.gwt import state_memory_bytes
    from repro.models import lm
    models = ["llama-60m", "llama-130m"] if quick else \
        ["llama-60m", "llama-130m", "llama-350m", "llama-1b"]
    for name in models:
        cfg = configs.LLAMA[name]
        params = lm.abstract_params(cfg)
        w = sum(p.size for p in jax.tree.leaves(params)) * 2 / 2**30
        for tag, level in [("adam", 0), ("gwt2", 2), ("gwt3", 3)]:
            st = state_memory_bytes(params, level)["total_bytes"] / 2**30
            emit(f"table11/{name}_{tag}", 0.0,
                 f"weights={w:.2f}G states={st:.2f}G")


# ---------------------------------------------------------------------------
# Table XII — GWT level sweep: state memory + fused-update throughput
# ---------------------------------------------------------------------------

def table12_levels(quick: bool):
    from repro import configs
    from repro.core.gwt import state_memory_bytes
    from repro.kernels.gwt_adam import ops as gops
    from repro.models import lm
    cfg = configs.LLAMA["llama-60m"]
    params = lm.abstract_params(cfg)
    m, n = (512, 4096) if not quick else (128, 1024)
    g = jax.random.normal(jax.random.key(0), (m, n))
    for level in (1, 2, 3, 4, 5):
        st = {"m": jnp.zeros((m, n >> level)), "v": jnp.zeros((m, n >> level))}
        us = _time(lambda gg, ss: gops.fused_update(
            gg, ss, jnp.int32(1), level=level, impl="jnp")[0], g, st, n=20)
        mem = state_memory_bytes(params, level)["total_bytes"] / 2**20
        emit(f"table12/gwt{level}", us, f"state={mem:.1f}MiB")


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (fused vs unfused + HBM-traffic model)
# ---------------------------------------------------------------------------

def kernels_bench(quick: bool):
    from repro.core import haar
    from repro.kernels.gwt_adam import ref as gref
    from repro.optim import hosts
    m, n, level = (512, 4096, 2) if not quick else (128, 1024, 2)
    g = jax.random.normal(jax.random.key(0), (m, n))
    ms = jnp.zeros((m, n >> level))
    vs = jnp.zeros((m, n >> level))

    fused = jax.jit(lambda g, m_, v_: gref.gwt_adam_tile(g, m_, v_,
                                                         level=level))
    us_f = _time(lambda *a: fused(*a)[0], g, ms, vs, n=20)
    emit("kernel/gwt_adam_fused_ref", us_f, f"{m}x{n} l{level}")

    host = hosts.adam()

    def unfused(g, m_, v_):
        a, ds = haar.haar_forward(g, level)
        pre, dsc, lrm, st = host.update(a, {"m": m_, "v": v_}, jnp.int32(0))
        tilde = [d * haar.detail_scale_upsample(dsc, level, level - i)
                 for i, d in enumerate(ds)]
        return haar.haar_inverse(pre, tilde)

    us_u = _time(jax.jit(unfused), g, ms, vs, n=20)
    emit("kernel/gwt_adam_unfused", us_u, f"fused_speedup={us_u/us_f:.2f}x")

    # backend sweep through the portability layer: the same fused_update
    # entry point the optimizer uses, per available impl on this platform
    # ('pallas' only where supported — REPRO_KERNEL_IMPL / MeshContext
    # route the same knob at launch time).
    from repro.kernels.gwt_adam import ops as gops
    impls = ["jnp", "interpret"]
    if jax.default_backend() == "tpu":   # platform support, not the
        impls.append("pallas")           # REPRO_KERNEL_IMPL override
    st = {"m": ms, "v": vs}
    for impl in impls:
        us_i = _time(lambda gg, ss: gops.fused_update(
            gg, ss, jnp.int32(1), level=level, impl=impl)[0], g, st,
            n=5 if impl == "interpret" else 20)
        emit(f"kernel/gwt_adam_impl_{impl}", us_i, f"{m}x{n} l{level}")

    # fusion HBM-traffic model (what matters on TPU): elements per grad el.
    l = level
    fused_traffic = 2 + 4 / 2 ** l
    unfused_traffic = 6 + 10 / 2 ** l
    emit("kernel/gwt_adam_traffic_model", 0.0,
         f"fused={fused_traffic:.2f} unfused={unfused_traffic:.2f} "
         f"el/el -> {unfused_traffic/fused_traffic:.2f}x bw win")


# ---------------------------------------------------------------------------
# Trace-size / compile-time: per-leaf loop vs bucketed engine.
#
# The payoff of the leaf-plan engine: the jitted update trace holds one scan
# body per (rule, shape) bucket instead of one unrolled update graph per
# leaf, so jaxpr equation count stays ~flat as layers are added while the
# per-leaf loop grows linearly.  Writes BENCH_trace_cpu.json next to this
# file (the ROADMAP multi-backend-sweep baseline).
# ---------------------------------------------------------------------------

def _layered_params(n_layers: int, d: int = 64, f: int = 128, vocab: int = 256):
    k = jax.random.key(0)
    p = {"embed": jax.random.normal(jax.random.fold_in(k, 999),
                                    (vocab, d)) * 0.02,
         "norm": jnp.ones((d,))}
    for i in range(n_layers):
        kk = jax.random.fold_in(k, i)
        p[f"layer_{i:02d}"] = {
            "attn": {"wq": jax.random.normal(jax.random.fold_in(kk, 0),
                                             (d, d)) * 0.05,
                     "wo": jax.random.normal(jax.random.fold_in(kk, 1),
                                             (d, d)) * 0.05},
            "mlp": {"w1": jax.random.normal(jax.random.fold_in(kk, 2),
                                            (d, f)) * 0.05,
                    "w2": jax.random.normal(jax.random.fold_in(kk, 3),
                                            (f, d)) * 0.05}}
    return p


def _trace_cell(opt_name, kw, n_layers, impl=None):
    """(jaxpr_eqns, lower+compile seconds) for one optimizer update step."""
    from repro import optim
    okw = dict(kw)
    if impl is not None:
        okw["impl"] = impl
    opt = optim.make(opt_name, lr=1e-3, **okw)
    params = _layered_params(n_layers)
    grads = jax.tree.map(lambda p: p * 0.01, params)
    st = opt.init(params)
    eqns = len(jax.make_jaxpr(opt.update)(grads, st, params).eqns)
    t0 = time.perf_counter()
    jax.jit(opt.update).lower(grads, st, params).compile()
    return eqns, time.perf_counter() - t0


def trace_bench(quick: bool):
    import json
    import os
    layer_counts = (2, 8) if quick else (2, 4, 8, 16)
    out = {"layer_counts": list(layer_counts), "cells": {}}
    for tag, name, kw, impls in [
            ("gwt2", "gwt", {"level": 2}, ["jnp"] if quick
             else ["jnp", "interpret"]),
            ("adam", "adam", {}, [None])]:
        for impl in impls:
            itag = f"{tag}_{impl}" if impl else tag
            for bucketed, btag in ((False, "perleaf"), (True, "bucketed")):
                eqns_row, secs_row = [], []
                for nl in layer_counts:
                    eqns, secs = _trace_cell(name, dict(kw, bucketed=bucketed),
                                             nl, impl)
                    eqns_row.append(eqns)
                    secs_row.append(round(secs, 3))
                out["cells"][f"{itag}_{btag}"] = {"jaxpr_eqns": eqns_row,
                                                 "compile_s": secs_row}
                emit(f"trace/{itag}_{btag}_compile_us_L{layer_counts[-1]}",
                     secs_row[-1] * 1e6,
                     f"eqns={eqns_row} compile_s={secs_row}")
    # growth check: bucketed eqn count must grow sublinearly in layer count
    lo, hi = layer_counts[0], layer_counts[-1]
    for cell, data in out["cells"].items():
        if cell.endswith("bucketed"):
            e = data["jaxpr_eqns"]
            ratio = e[-1] / max(e[0], 1)
            linear = hi / lo
            emit(f"trace/{cell}_growth", 0.0,
                 f"{ratio:.2f}x over {lo}->{hi} layers "
                 f"(per-leaf would be ~{linear:.0f}x)")
    # quick (CI smoke) runs don't overwrite the committed full baseline
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_trace_cpu_quick.json" if quick
                        else "BENCH_trace_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("trace/json", 0.0, path)


# ---------------------------------------------------------------------------
# Train-step runtime: steps/sec + tokens/sec of the pipelined donated
# TrainLoop vs the pre-PR eager loop, and peak-live-bytes of the donated
# vs undonated train step (XLA buffer assignment).  Writes
# BENCH_step_cpu.json; --quick additionally gates against the committed
# baseline (>20% steps/sec regression on the headline cell fails CI).
# ---------------------------------------------------------------------------

STEP_HEADLINE = "gwt_jnp"


def _loop_steps_per_sec(loop, params, st, steps, repeats=3):
    """Best-of-N steps/sec for one warmed loop (compile excluded by a
    prior untimed run; params/state copied per run — the pipelined loop
    donates its inputs)."""
    import jax
    best = 0.0
    for _ in range(repeats):
        p, s = jax.tree.map(lambda a: a.copy(), (params, st))
        t0 = time.perf_counter()
        p, s, _ = loop.run(p, s, num_steps=steps)
        jax.block_until_ready(p)
        best = max(best, steps / (time.perf_counter() - t0))
    return best


def _fused_write_live_bytes():
    """Peak live bytes of the fused-write (megakernel) dataflow vs the
    staged pipeline, from XLA buffer assignment on a representative
    stacked ``(L, m, n)`` bucket.

    Fused: ONE program takes ``(g, p, m, v, prev_norm)`` and emits
    ``(new_p, new_norm, new_m, new_v)`` with ``p``/state donated — g̃
    lives only as an in-program temp.  Staged (the pre-megakernel
    dataflow): stage A runs the DWT+Adam core and EMITS g̃ as a program
    output; stage B applies limiter+step+write.  The staged peak charges
    stage A with ``p`` and ``prev_norm`` held live across the launch
    boundary — exactly the buffers fusion lets the scheduler drop.  Both
    sides are measured on the tiled jnp oracle (``impl='jnp'``), which
    mirrors the kernel's dataflow 1:1 (tested bitwise); the interpret
    backend's Pallas *emulation* allocates per-grid-point scratch that a
    real lowering doesn't, so it would measure emulator overhead, not the
    algorithm."""
    from repro.core import limiter
    from repro.kernels.gwt_adam import ops as gops
    from repro.optim.engine import live_update_bytes

    L, m, n, level = 4, 256, 2048, 2
    g = jnp.zeros((L, m, n), jnp.float32)
    p = jnp.zeros((L, m, n), jnp.float32)
    st = {"m": jnp.zeros((L, m, n >> level), jnp.float32),
          "v": jnp.zeros((L, m, n >> level), jnp.float32)}
    pn = jnp.zeros((L,), jnp.float32)
    kw = dict(lr_t=jnp.float32(1e-3), alpha=0.25, weight_decay=0.0,
              gamma=1.01, use_limiter=True, level=level)

    fused = jax.jit(
        lambda gs, ps, st, pn: gops.fused_write_update(
            gs, ps, st, jnp.int32(2), pn, impl="jnp", **kw),
        donate_argnums=(1, 2, 3)).lower(list(g), list(p), st, pn).compile()

    stage_a = jax.jit(
        lambda g, st: gops.fused_update(g, st, jnp.int32(2), level=level,
                                        impl="jnp"),
        donate_argnums=(1,)).lower(g, st).compile()

    def _stage_b(gt, p, pn, lr_mult):
        def one(gtl, pl, pnl):
            gl, nl = limiter.limit(gtl, pnl, gamma=1.01)
            step = jnp.float32(1e-3) * lr_mult * 0.25
            new_p = pl.astype(jnp.float32) - step * gl.astype(jnp.float32)
            return new_p.astype(pl.dtype), nl
        return jax.vmap(one)(gt, p, pn)

    # donate p only: g̃ has no same-shaped output left to alias (new_p
    # pairs with p), so donating it would just trip the unusable-donation
    # warning without changing the accounting.
    stage_b = jax.jit(_stage_b, donate_argnums=(1,)).lower(
        g, p, pn, jnp.float32(1.0)).compile()

    fused_live = live_update_bytes(fused)
    live_a = live_update_bytes(stage_a)
    live_b = live_update_bytes(stage_b)
    if None in (fused_live, live_a, live_b):
        return None
    held = p.size * p.dtype.itemsize + pn.size * pn.dtype.itemsize
    staged_live = max(live_a + held, live_b)
    return {"bucket": [L, m, n], "level": level,
            "fused_live_bytes": fused_live,
            "staged_live_bytes": staged_live,
            "staged_stage_a_bytes": live_a,
            "staged_stage_b_bytes": live_b,
            "staged_held_across_boundary_bytes": held,
            "ratio": round(fused_live / staged_live, 4)}


def step_bench(quick: bool):
    import json
    import os

    from repro import configs, optim
    from repro.data.pipeline import SyntheticLM
    from repro.models import lm
    from repro.optim.engine import live_update_bytes, state_bytes
    from repro.runtime.fault_tolerance import TrainLoop

    cfg = configs.get_smoke("llama-60m")
    B, S = 1, 64
    chunk = 20                      # superstep length = log cadence
    silent = lambda s: None  # noqa: E731
    out = {"config": {"arch": cfg.name, "batch": B, "seq": S,
                      "chunk": chunk},
           "cells": {}}
    cells = [("gwt", "jnp", "f32"), ("gwt", "interpret", "f32"),
             ("gwt", "jnp", "int8"),
             ("adam", None, "f32"), ("galore", None, "f32")]
    for name, impl, cdc in cells:
        tag = f"{name}_{impl}" if impl else name
        if cdc != "f32":
            tag += f"_{cdc}"
        interp = impl == "interpret"
        steps = (chunk if quick else 2 * chunk) if interp \
            else (2 * chunk if quick else 3 * chunk)
        kw = {"level": 2, "impl": impl} if name == "gwt" else \
            ({"rank_frac": 0.25, "update_gap": 2 * steps}
             if name == "galore" else {})
        opt = optim.make(name, lr=1e-3, state_codec=cdc, **kw)
        params = lm.init(cfg, jax.random.key(0))
        st = opt.init(params)
        data = SyntheticLM(cfg.vocab, S, B, seed=0)
        b0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

        # peak live bytes: XLA buffer assignment of the jitted train step,
        # donated vs not — donation must alias params+opt_state through.
        plain = jax.jit(lm.make_train_step(cfg, opt)) \
            .lower(params, st, b0).compile()
        donated = lm.make_train_step(cfg, opt, donate=True) \
            .lower(params, st, b0).compile()
        live_plain, live_don = (live_update_bytes(plain),
                                live_update_bytes(donated))
        sb = state_bytes(opt, params)

        # pre-PR loop: per-step dispatch + float(loss) sync, sync fetch,
        # no donation.
        eager_loop = TrainLoop(jax.jit(lm.make_train_step(cfg, opt)), None,
                               data, log_every=10, log=silent,
                               pipelined=False)
        eager_loop.run(*jax.tree.map(lambda a: a.copy(), (params, st)),
                       num_steps=2)  # warm the jit cache
        eager = _loop_steps_per_sec(eager_loop, params, st, steps,
                                    repeats=1 if interp else 3)

        # pipelined loop: donated scan-over-chunk supersteps, prefetched
        # batches, loss fetched once per chunk.
        pipe_loop = TrainLoop(lm.make_train_step(cfg, opt), None, data,
                              log_every=chunk, max_chunk=chunk, log=silent)
        pipe_loop.run(*jax.tree.map(lambda a: a.copy(), (params, st)),
                      num_steps=chunk)  # compile the superstep
        pipe = _loop_steps_per_sec(pipe_loop, params, st, steps,
                                   repeats=1 if interp else 3)

        cell = {"steps_per_sec_eager": round(eager, 2),
                "steps_per_sec_pipelined": round(pipe, 2),
                "tokens_per_sec_pipelined": round(pipe * B * S, 1),
                "speedup": round(pipe / eager, 3),
                "opt_state_bytes": sb,
                "peak_live_bytes_plain": live_plain,
                "peak_live_bytes_donated": live_don}
        out["cells"][tag] = cell
        emit(f"step/{tag}", 1e6 / pipe,
             f"pipelined={pipe:.1f}steps/s eager={eager:.1f} "
             f"speedup={pipe/eager:.2f}x "
             f"live={live_don}B vs {live_plain}B undonated")
        if live_plain is not None and live_don is not None \
                and live_don >= live_plain:
            emit(f"step/{tag}_donation_ERROR", 0.0,
                 f"donated peak live {live_don} >= undonated {live_plain}")

    # compound substrate win: GWT moment subspaces x blocked-int8 codec vs
    # the full-Adam f32 reference (both measured on this config's real
    # init — the gate trips if either side's accounting drifts)
    full_adam = out["cells"]["adam"]["opt_state_bytes"]
    q8 = out["cells"]["gwt_jnp_int8"]["opt_state_bytes"]
    ratio = full_adam / q8
    out["compression"] = {"full_adam_f32_bytes": full_adam,
                          "gwt_int8_bytes": q8,
                          "ratio": round(ratio, 2)}
    if ratio < 10.0:
        emit("step/compression_ERROR", 0.0,
             f"gwt+int8 opt state {q8}B only {ratio:.1f}x under full-Adam "
             f"f32 {full_adam}B (< 10x)")
    else:
        emit("step/compression_gate", 0.0,
             f"gwt+int8 {q8}B = {ratio:.1f}x under full-Adam f32 "
             f"{full_adam}B (ok)")

    # fused-write megakernel gate: the one-launch grad→wavelet→limit→write
    # program must peak strictly below the staged two-launch pipeline
    # (where g̃ crosses the launch boundary and p waits out stage A).
    fw = _fused_write_live_bytes()
    out["fused_write"] = fw
    if fw is None:
        emit("step/fusedwrite_ERROR", 0.0,
             "memory_analysis unavailable; fused-write live bytes unmeasured")
    elif fw["fused_live_bytes"] >= fw["staged_live_bytes"]:
        emit("step/fusedwrite_ERROR", 0.0,
             f"fused-write peak live {fw['fused_live_bytes']}B >= staged "
             f"{fw['staged_live_bytes']}B")
    else:
        emit("step/fusedwrite_gate", 0.0,
             f"fused-write peak live {fw['fused_live_bytes']}B = "
             f"{fw['ratio']:.2f}x of staged {fw['staged_live_bytes']}B (ok)")

    hl = out["cells"][STEP_HEADLINE]
    out["headline"] = {"cell": STEP_HEADLINE, "speedup": hl["speedup"]}
    here = os.path.dirname(os.path.abspath(__file__))
    committed = os.path.join(here, "BENCH_step_cpu.json")
    if quick and os.path.exists(committed):
        with open(committed) as f:
            base = json.load(f)["cells"].get(STEP_HEADLINE)
        if base:
            ref = base["steps_per_sec_pipelined"]
            now = hl["steps_per_sec_pipelined"]
            if now < 0.8 * ref:
                emit("step/regression_ERROR", 0.0,
                     f"pipelined {now:.1f} steps/s < 80% of committed "
                     f"{ref:.1f} (gwt_jnp cell)")
            else:
                emit("step/regression_gate", 0.0,
                     f"{now:.1f} steps/s vs committed {ref:.1f} (ok)")
    path = os.path.join(here, "BENCH_step_cpu_quick.json" if quick
                        else "BENCH_step_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("step/json", 0.0, path)


# ---------------------------------------------------------------------------
# Optimizer-state accounting: the full family x codec matrix on the real
# llama-60m (abstract params, eval_shape only — no allocation), writing
# BENCH_state_cpu.json.  Gates (always): int8 strictly shrinks every
# moment-bearing family, and eval_shape bytes are self-consistent across
# codecs (q + scales never exceed ~27% of the f32 moment slots).
# ---------------------------------------------------------------------------

def state_bench(quick: bool):
    import json
    import os

    from repro import configs, optim
    from repro.models import lm
    from repro.optim.engine import state_bytes

    cfg = configs.get_smoke("llama-60m") if quick \
        else configs.get_config("llama-60m")
    params = lm.abstract_params(cfg)
    p_bytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                  for l in jax.tree_util.tree_leaves(params))
    families = [("adam", {}), ("adam_mini", {}), ("muon", {}), ("sgd", {}),
                ("galore", {"rank_frac": 0.25}),
                ("apollo", {"rank_frac": 0.25}),
                ("fira", {"rank_frac": 0.25}),
                ("gwt", {"level": 2})]
    out = {"config": {"arch": cfg.name, "params_bytes": p_bytes},
           "cells": {}}
    for name, kw in families:
        row = {}
        for cdc in ("f32", "int8"):
            opt = optim.make(name, lr=1e-3, state_codec=cdc, **kw)
            row[cdc] = state_bytes(opt, params)
        row["int8_saving"] = round(row["f32"] / row["int8"], 3)
        out["cells"][name] = row
        emit(f"state/{name}", 0.0,
             f"f32={row['f32']}B int8={row['int8']}B "
             f"({row['int8_saving']}x)")
        if row["int8"] >= row["f32"]:
            emit(f"state/{name}_codec_ERROR", 0.0,
                 f"int8 {row['int8']}B does not shrink f32 {row['f32']}B")
    full_adam = out["cells"]["adam"]["f32"]
    q8 = out["cells"]["gwt"]["int8"]
    out["compound"] = {"full_adam_f32_bytes": full_adam,
                       "gwt_int8_bytes": q8,
                       "ratio": round(full_adam / q8, 2)}
    emit("state/compound", 0.0,
         f"gwt+int8 {q8}B = {full_adam / q8:.1f}x under full-Adam f32 "
         f"{full_adam}B (params {p_bytes}B)")
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_state_cpu_quick.json" if quick
                        else "BENCH_state_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("state/json", 0.0, path)


# ---------------------------------------------------------------------------
# Sharded train path (DESIGN.md §3): DP all-reduce wire bytes (exact f32 vs
# wavelet-compressed) and steps/sec of the mesh-aware step on a simulated
# 8-device mesh.  The measurement runs in a SUBPROCESS with its own
# --xla_force_host_platform_device_count=8 (this process keeps its real
# single device); writes BENCH_shard_cpu.json.  Gates (always): the f8
# level-2 wire format must move ≥2× fewer bytes than exact f32 on the real
# llama-60m gradient tree; --quick additionally fails on a >20% steps/sec
# regression vs the committed baseline.
# ---------------------------------------------------------------------------

SHARD_WIRE_GATE = 2.0


def _shard_worker(quick: bool):
    """Runs inside the 8-device subprocess; prints one JSON line."""
    import json

    from repro import compat, configs, optim
    from repro.data.pipeline import SyntheticLM
    from repro.distributed import sharding as shr
    from repro.distributed.compression import DPReduceSpec, tree_wire_bytes
    from repro.models import lm
    from repro.runtime.context import MeshContext
    from repro.runtime.fault_tolerance import TrainLoop

    # -- wire accounting on the REAL llama-60m gradient tree (abstract) ----
    grads_abs = lm.abstract_params(configs.LLAMA["llama-60m"])
    full = tree_wire_bytes(grads_abs, None)
    wire = {"exact_f32": {"bytes_per_step": full, "ratio": 1.0}}
    for tag, level, dt in [("bf16_l2", 2, jnp.bfloat16),
                           ("bf16_l3", 3, jnp.bfloat16),
                           ("f8_l2", 2, jnp.float8_e4m3fn),
                           ("f8_l4", 4, jnp.float8_e4m3fn)]:
        b = tree_wire_bytes(grads_abs, DPReduceSpec(level=level,
                                                    detail_dtype=dt))
        wire[tag] = {"bytes_per_step": b, "ratio": round(full / b, 3)}

    # -- steps/sec through the pipelined loop, 8-device sim ----------------
    cfg = configs.get_smoke("llama-60m")
    B, S, chunk = 16, 32, 8
    steps = chunk * (2 if quick else 4)
    silent = lambda s: None  # noqa: E731
    cells = {}
    for tag, mesh_shape, dp in [
            ("nomesh_1dev", None, None),
            ("mesh8_exact", (8,), DPReduceSpec(level=2, detail_dtype=None)),
            ("mesh8_compressed", (8,), DPReduceSpec(level=2))]:
        ctx = MeshContext.create(
            mesh=None if mesh_shape is None
            else compat.make_mesh(mesh_shape, ("data",)))
        opt = optim.make("gwt", lr=1e-3, level=2)
        params = lm.init(cfg, jax.random.key(0))
        st = opt.init(params)
        data = SyntheticLM(cfg.vocab, S, B, seed=0)
        shardings = None
        if mesh_shape is not None:
            b0 = data.batch(0)
            batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for k, v in b0.items()}
            shardings = shr.train_step_shardings(cfg, lm, batch_abs,
                                                 ctx.mesh,
                                                 shard_params=False)
            params = jax.device_put(params, shardings.params)
            st = jax.device_put(st, shr.replicated_like(st, ctx.mesh))
        step = lm.make_train_step(cfg, opt, ctx=ctx, dp_reduce=dp,
                                  shardings=shardings)
        loop = TrainLoop(step, None, data, log_every=chunk, max_chunk=chunk,
                         log=silent,
                         batch_shardings=None if shardings is None
                         else shardings.batch)
        with ctx.activate():
            loop.run(*jax.tree.map(lambda a: a.copy(), (params, st)),
                     num_steps=chunk)            # pay the compile
            sps = _loop_steps_per_sec(loop, params, st, steps,
                                      repeats=1 if quick else 2)
        cells[tag] = {"steps_per_sec": round(sps, 2),
                      "tokens_per_sec": round(sps * B * S, 1)}

    print(json.dumps({
        "config": {"arch": cfg.name, "batch": B, "seq": S, "chunk": chunk,
                   "devices": jax.device_count(),
                   "wire_model": "llama-60m full (abstract grads)"},
        "wire": wire, "cells": cells}))


def shard_bench(quick: bool):
    import json
    import os
    import subprocess

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    cmd = [sys.executable, "-m", "benchmarks.run", "--shard-worker"]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1200)
    if r.returncode != 0:
        emit("shard/worker_ERROR", 0.0, (r.stdout + r.stderr)[-500:])
        return
    out = json.loads(r.stdout.strip().splitlines()[-1])

    for tag, w in out["wire"].items():
        emit(f"shard/wire_{tag}", 0.0,
             f"{w['bytes_per_step']/2**20:.1f}MiB/step {w['ratio']}x")
    for tag, c in out["cells"].items():
        emit(f"shard/{tag}", 1e6 / max(c["steps_per_sec"], 1e-9),
             f"{c['steps_per_sec']:.1f}steps/s "
             f"{c['tokens_per_sec']:.0f}tok/s")

    # acceptance gate: the committed artifact must show a ≥2× wire win at
    # level ≥ 2 (the f8 wire format; bf16 tops out at 2× asymptotically)
    ratio = out["wire"]["f8_l2"]["ratio"]
    if ratio < SHARD_WIRE_GATE:
        emit("shard/wire_gate_ERROR", 0.0,
             f"f8_l2 ratio {ratio} < {SHARD_WIRE_GATE}")
    else:
        emit("shard/wire_gate", 0.0,
             f"f8_l2 moves {ratio}x fewer bytes (gate >= "
             f"{SHARD_WIRE_GATE}x)")

    # steps/sec on the simulated mesh is telemetry, not a gate: 8 fake
    # devices are 8 threads contending for the same cores, and run-to-run
    # variance exceeds any sane regression band (observed ±40% on an
    # otherwise-idle container).  A throughput gate belongs with real
    # multi-chip numbers (ROADMAP); the wire gate above is deterministic.
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_shard_cpu_quick.json" if quick
                        else "BENCH_shard_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("shard/json", 0.0, path)


# ---------------------------------------------------------------------------
# Data subsystem: corpus-build CLI smoke + loader throughput (thread
# Prefetcher vs shared-memory process workers).  The tokenization-heavy
# source (on-the-fly BPE) is GIL-bound, so the thread path serializes with
# the consumer while process workers scale — GATED: process workers must
# not be slower than the thread path on that source.  The mmap corpus row
# is telemetry (pre-tokenized reads are too cheap for workers to matter).
# ---------------------------------------------------------------------------

FIXTURE_GLOB = "tests/fixtures/corpus/*.txt"
DATA_WORKER_GATE = 0.9   # process/thread tokens/sec floor (noise margin)


_FIXTURE_DIR = None


def _fixture_corpus() -> str:
    """Build the committed fixture corpus once per benchmark process
    (deterministic content: same text + tokenizer config -> same shards
    + hash).  A fresh ``mkdtemp`` per process — a fixed world-readable
    /tmp path would race concurrent benchmark runs and collide across
    users.  eval_fraction 0.1 keeps ~9 held-out seq-64 windows, enough
    for one full unique eval batch."""
    global _FIXTURE_DIR
    if _FIXTURE_DIR is None:
        import tempfile
        from repro.data.build_corpus import build
        _FIXTURE_DIR = tempfile.mkdtemp(prefix="repro_bench_corpus_")
        build(FIXTURE_GLOB, _FIXTURE_DIR, tokenizer_kind="bpe",
              vocab_size=512, eval_fraction=0.1)
    return _FIXTURE_DIR


def _drain_tokens_per_sec(pf, n_batches: int, warmup: int, seq: int,
                          batch: int, segments: int = 3) -> float:
    """Steady-state production rate, best of ``segments`` back-to-back
    timed drains.  The warmup must EXCEED the queue depth (otherwise the
    timed drain partly reads batches buffered during construction and
    flatters the slow path); best-of-segments because a 2-core CI box
    under frequency/background drift swings single-shot readings ~2×,
    and the gate below compares two such readings."""
    for _ in range(warmup):
        next(pf)
    best = 0.0
    for _ in range(segments):
        t0 = time.perf_counter()
        for _ in range(n_batches):
            next(pf)
        best = max(best, n_batches * batch * seq
                   / (time.perf_counter() - t0))
    return best


def data_bench(quick: bool):
    import json
    import os
    import subprocess
    import tempfile

    from repro.data.build_corpus import DOC_SEP, read_documents
    from repro.data.pipeline import (CorpusLM, Prefetcher, TokenizingTextLM)
    from repro.data.store import TokenStore
    from repro.data.workers import ProcessPrefetcher

    # corpus-build CLI smoke: the exact command the README quickstart gives
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as td:
        r = subprocess.run(
            [sys.executable, "-m", "repro.data.build_corpus",
             "--input", FIXTURE_GLOB, "--out", os.path.join(td, "c"),
             "--tokenizer", "bpe", "--vocab", "512", "--verify"],
            capture_output=True, text=True, cwd=repo,
            env=dict(os.environ, PYTHONPATH="src"), timeout=300)
    if r.returncode != 0 or "roundtrip=ok" not in r.stdout:
        emit("data/build_cli_ERROR", 0.0, (r.stdout + r.stderr)[-300:])
        return
    emit("data/build_cli", 0.0, r.stdout.strip().splitlines()[0][:80])

    corpus = _fixture_corpus()
    store = TokenStore(corpus)
    # B=32 keeps each BPE batch ~15-30ms of pure-python encode: heavy
    # enough that the per-batch IPC+copy overhead of the worker path is
    # noise next to the encode the workers parallelize
    S, B = 64, 32
    n = 10 if quick else 14
    depth = 4
    out = {"config": {"seq": S, "batch": B, "batches_timed": n,
                      "corpus_hash": store.corpus_hash[:12]}, "cells": {}}

    # mmap fast path (telemetry): pre-tokenized windows are nearly free
    mm = CorpusLM(corpus, S, B, seed=0)
    with Prefetcher(mm, depth=depth) as pf:
        mmap_tps = _drain_tokens_per_sec(pf, n, depth + 2, S, B)
    out["cells"]["corpus_mmap_thread"] = {"tokens_per_sec": round(mmap_tps)}
    emit("data/corpus_mmap_thread", 1e6 * B * S / mmap_tps,
         f"{mmap_tps:,.0f} tok/s (pre-tokenized mmap)")

    # tokenization-heavy source: on-the-fly BPE (GIL-bound pure python).
    # Thread and process paths are timed in INTERLEAVED segments (both
    # pipelines alive, best segment each): on a shared CI host,
    # sequential measurements live in different background-noise epochs
    # and the ratio gate flaps; interleaving samples both paths across
    # the same minutes.  The idle pipeline is quiescent meanwhile — its
    # bounded queue/slot ring fills and its producers block.
    text = DOC_SEP.join(read_documents(os.path.join(repo, FIXTURE_GLOB)))
    heavy = TokenizingTextLM(text, store.tokenizer, S, B, seed=0)
    # workers sized to the host: oversubscribing a small box (4 workers
    # on 2 cores) just context-switches away the win
    workers = 2 if quick else max(2, min(4, os.cpu_count() or 2))
    thread_tps = proc_tps = 0.0
    with Prefetcher(heavy, depth=depth) as pf, \
            ProcessPrefetcher(heavy, depth=2 * workers,
                              num_workers=workers) as pp:
        for _ in range(3 if quick else 4):
            # per-segment warmup >= the pipeline's buffer capacity: the
            # idle path refills its queue/slots during the other path's
            # segment, and timing those pre-buffered batches flatters a
            # path by buffer/n (measured: a phantom 1.5x thread "win")
            thread_tps = max(thread_tps,
                             _drain_tokens_per_sec(pf, n, depth + 1, S, B,
                                                   segments=1))
            proc_tps = max(proc_tps,
                           _drain_tokens_per_sec(pp, n, 2 * workers + 3,
                                                 S, B, segments=1))
    ratio = proc_tps / thread_tps
    out["cells"]["bpe_thread"] = {"tokens_per_sec": round(thread_tps)}
    out["cells"][f"bpe_process_{workers}w"] = {
        "tokens_per_sec": round(proc_tps), "vs_thread": round(ratio, 3)}
    emit("data/bpe_thread", 1e6 * B * S / thread_tps,
         f"{thread_tps:,.0f} tok/s (GIL-bound)")
    emit(f"data/bpe_process_{workers}w", 1e6 * B * S / proc_tps,
         f"{proc_tps:,.0f} tok/s ({ratio:.2f}x thread)")
    if ratio < DATA_WORKER_GATE:
        emit("data/worker_gate_ERROR", 0.0,
             f"process workers {proc_tps:,.0f} tok/s < "
             f"{DATA_WORKER_GATE}x thread {thread_tps:,.0f}")
    else:
        emit("data/worker_gate", 0.0,
             f"process {ratio:.2f}x thread (gate >= {DATA_WORKER_GATE}x)")

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_data_cpu_quick.json" if quick
                        else "BENCH_data_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("data/json", 0.0, path)


# ---------------------------------------------------------------------------
# Loss-curve harness (the paper's actual yardstick): train {gwt, adam,
# galore} smoke configs on the committed fixture corpus through the real
# pipelined TrainLoop with streaming held-out eval, record final/AUC train
# loss + eval perplexity curve to BENCH_curve_cpu.json.  Gate: every
# optimizer must LEARN (final loss well under its initial loss) — a
# numerics regression in any engine family trips it.
# ---------------------------------------------------------------------------

CURVE_LEARN_GATE = 0.9   # final loss must be < gate * initial loss
# (galore-1/4 on the 24-step --quick budget only reaches ~0.79× its
# initial loss — the gate is a did-it-learn-at-all tripwire, not a
# quality bar; quality lives in the committed per-cell numbers)

CURVE_TRACK_GATE = 1.25  # gwt2_int8 final loss must stay under this
# multiple of the gwt2 f32 final loss.  Measured on the fixture corpus
# the two runs land within run-to-run noise of each other (±~10% of
# final loss at the 24-step --quick budget, tighter at 72); a broken
# rounding stream stalls near the ~126-nat initial loss, far past any
# plausible noise band.

LORA_TRACK_GATE = 1.25   # gwt2-LoRA final loss vs adam-LoRA final loss.
# The fine-tune cells start from the adam cell's trained base, so the
# learn gate (a from-scratch tripwire) does not apply; what matters is
# that compressing the ADAPTER moments into wavelet subspaces tracks the
# uncompressed adapter run — same tolerance philosophy as int8 tracking.

LORA_RANK, LORA_ALPHA = 8, 16.0


def curve_bench(quick: bool):
    import json
    import os

    from repro import configs, optim
    from repro.data.eval import make_lm_evaluator
    from repro.data.pipeline import CorpusLM
    from repro.models import lm
    from repro.optim.schedules import warmup_cosine
    from repro.runtime.fault_tolerance import TrainLoop

    corpus = _fixture_corpus()
    steps = 24 if quick else 72
    S, B = 64, 8
    cfg = configs.LLAMA["llama-60m"].with_(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab=512)
    eval_every = max(steps // 3, 1)
    silent = lambda s: None  # noqa: E731
    train_src = CorpusLM(corpus, S, B, seed=0)
    out = {"config": {"arch": cfg.name, "seq": S, "batch": B,
                      "steps": steps, "eval_every": eval_every,
                      "corpus_hash": train_src.store.corpus_hash[:12]},
           "cells": {}}
    methods = [("gwt2", "gwt", dict(level=2)),
               ("gwt2_int8", "gwt", dict(level=2, state_codec="int8")),
               ("adam", "adam", {}),
               ("galore_1_4", "galore", dict(rank_frac=0.25,
                                             update_gap=steps))]
    base_params = None  # the adam cell's trained weights seed the LoRA cells
    for tag, name, kw in methods:
        opt = optim.make(name, lr=warmup_cosine(0.01, steps), **kw)
        params = lm.init(cfg, jax.random.key(0))
        st = opt.init(params)
        ev = make_lm_evaluator(cfg, lm,
                               CorpusLM(corpus, S, B, seed=0, split="eval"),
                               n_batches=4)
        loop = TrainLoop(lm.make_train_step(cfg, opt), None, train_src,
                         log_every=eval_every, max_chunk=8, log=silent,
                         evaluator=ev, eval_every=eval_every)
        t0 = time.perf_counter()
        trained, _, losses = loop.run(params, st, num_steps=steps)
        dt = time.perf_counter() - t0
        if tag == "adam":
            base_params = trained
        k = max(steps // 10, 1)
        cell = {"initial_loss": round(losses[0], 4),
                "final_loss": round(sum(losses[-k:]) / k, 4),
                "auc_loss": round(sum(losses) / len(losses), 4),
                "eval_curve": [(s, round(v, 4)) for s, v in ev.history],
                "final_eval_loss": round(ev.history[-1][1], 4),
                "steps_per_sec": round(steps / dt, 2)}
        out["cells"][tag] = cell
        emit(f"curve/{tag}", dt / steps * 1e6,
             f"final={cell['final_loss']} auc={cell['auc_loss']} "
             f"eval={cell['final_eval_loss']}")
        if cell["final_loss"] > CURVE_LEARN_GATE * cell["initial_loss"]:
            emit(f"curve/{tag}_learn_gate_ERROR", 0.0,
                 f"final {cell['final_loss']} > {CURVE_LEARN_GATE} * "
                 f"initial {cell['initial_loss']}")

    # quantized tracking gate: the int8 substrate must follow the f32 GWT
    # curve, not merely "learn" — stochastic rounding is unbiased, so the
    # two runs should land within noise of each other.
    f32_final = out["cells"]["gwt2"]["final_loss"]
    q8_final = out["cells"]["gwt2_int8"]["final_loss"]
    out["int8_tracking"] = {"final_loss_ratio": round(q8_final / f32_final,
                                                      4),
                            "bound": CURVE_TRACK_GATE}
    if q8_final > CURVE_TRACK_GATE * f32_final:
        emit("curve/int8_tracking_ERROR", 0.0,
             f"gwt2_int8 final loss {q8_final} > {CURVE_TRACK_GATE} * "
             f"gwt2 f32 final {f32_final}")
    else:
        emit("curve/int8_tracking_gate", 0.0,
             f"gwt2_int8 final {q8_final} vs f32 {f32_final} "
             f"(ratio {q8_final / f32_final:.3f} <= {CURVE_TRACK_GATE}, ok)")

    # ---- fine-tune cells: LoRA on the adam cell's trained base ----------
    # The paper claims GWT works for fine-tuning too: here the FROZEN base
    # carries zero optimizer state and only the adapters' Adam moments go
    # through the engine — "gwt2_lora" compresses those into wavelet
    # subspaces, "adam_lora" keeps them raw.  Same steps budget, fresh
    # data-order seed (a stand-in for a downstream corpus).
    from repro.models import lora
    ft_src = CorpusLM(corpus, S, B, seed=1)
    for tag, name, kw in [("gwt2_lora", "gwt", dict(level=2)),
                          ("adam_lora", "adam", {})]:
        inner = optim.make(name, lr=warmup_cosine(0.01, steps), **kw)
        opt = lora.wrap_optimizer(inner)
        # fresh buffers per cell: TrainLoop donates its input tree, which
        # would delete the shared base arrays for the next cell
        tree = lora.inject(jax.tree.map(jnp.copy, base_params), LORA_RANK,
                           jax.random.fold_in(jax.random.key(0), 777))
        st = opt.init(tree)
        ev = make_lm_evaluator(cfg, lora.loss_module(lm, LORA_ALPHA,
                                                     LORA_RANK),
                               CorpusLM(corpus, S, B, seed=0, split="eval"),
                               n_batches=4)
        loop = TrainLoop(
            lora.make_train_step(lm, cfg, opt, rank=LORA_RANK,
                                 alpha=LORA_ALPHA),
            None, ft_src, log_every=eval_every, max_chunk=8, log=silent,
            evaluator=ev, eval_every=eval_every)
        t0 = time.perf_counter()
        _, _, losses = loop.run(tree, st, num_steps=steps)
        dt = time.perf_counter() - t0
        k = max(steps // 10, 1)
        cell = {"initial_loss": round(losses[0], 4),
                "final_loss": round(sum(losses[-k:]) / k, 4),
                "auc_loss": round(sum(losses) / len(losses), 4),
                "eval_curve": [(s, round(v, 4)) for s, v in ev.history],
                "final_eval_loss": round(ev.history[-1][1], 4),
                "steps_per_sec": round(steps / dt, 2),
                "lora_rank": LORA_RANK, "lora_alpha": LORA_ALPHA}
        out["cells"][tag] = cell
        emit(f"curve/{tag}", dt / steps * 1e6,
             f"final={cell['final_loss']} auc={cell['auc_loss']} "
             f"eval={cell['final_eval_loss']}")
    lf32, lgwt = (out["cells"]["adam_lora"]["final_loss"],
                  out["cells"]["gwt2_lora"]["final_loss"])
    out["lora_tracking"] = {"final_loss_ratio": round(lgwt / lf32, 4),
                            "bound": LORA_TRACK_GATE}
    if lgwt > LORA_TRACK_GATE * lf32:
        emit("curve/lora_tracking_ERROR", 0.0,
             f"gwt2_lora final loss {lgwt} > {LORA_TRACK_GATE} * "
             f"adam_lora final {lf32}")
    else:
        emit("curve/lora_tracking_gate", 0.0,
             f"gwt2_lora final {lgwt} vs adam_lora {lf32} "
             f"(ratio {lgwt / lf32:.3f} <= {LORA_TRACK_GATE}, ok)")

    # ---- substrate cells: the non-llama architectures through the same
    # TrainLoop + gwt2 path, no per-arch call-site patches (the encdec
    # frame stub is a pipeline adapter, exactly as in the launcher).
    # Gate: the losses must stay finite — a routing/leaf-plan regression
    # on any substrate shows up as NaN/divergence within a few steps.
    import math as _math
    from repro.data.pipeline import WithEncoderFrames
    from repro.models import encdec as encdec_mod
    sub_steps = 6 if quick else 12
    for tag, arch in [("moe", "qwen2-moe-a2.7b"), ("ssm", "jamba-v0.1-52b"),
                      ("xlstm", "xlstm-350m"),
                      ("encdec", "seamless-m4t-large-v2")]:
        scfg = configs.get_smoke(arch)
        mod = encdec_mod if scfg.arch_class == "encdec" else lm
        src = CorpusLM(corpus, S, 4, seed=0)
        if scfg.arch_class == "encdec":
            src = WithEncoderFrames(src, S // 4, scfg.d_model)
        opt = optim.make("gwt", lr=warmup_cosine(0.01, sub_steps), level=2)
        sparams = mod.init(scfg, jax.random.key(0))
        sst = opt.init(sparams)
        loop = TrainLoop(mod.make_train_step(scfg, opt), None, src,
                         log_every=sub_steps, max_chunk=4, log=silent)
        t0 = time.perf_counter()
        _, _, losses = loop.run(sparams, sst, num_steps=sub_steps)
        dt = time.perf_counter() - t0
        cell = {"arch": scfg.name,
                "initial_loss": round(losses[0], 4),
                "final_loss": round(losses[-1], 4),
                "steps_per_sec": round(sub_steps / dt, 2)}
        out["cells"][f"substrate_{tag}"] = cell
        emit(f"curve/substrate_{tag}", dt / sub_steps * 1e6,
             f"initial={cell['initial_loss']} final={cell['final_loss']}")
        if not all(_math.isfinite(l) for l in losses):
            emit(f"curve/substrate_{tag}_ERROR", 0.0,
                 f"non-finite loss in {losses}")

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_curve_cpu_quick.json" if quick
                        else "BENCH_curve_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("curve/json", 0.0, path)


# ---------------------------------------------------------------------------
# Serving runtime (DESIGN.md §9): continuous batching vs static waves on a
# fixture-corpus-trained tiny llama, open-loop Poisson latency, and int8 KV
# fidelity.  Writes BENCH_serve_cpu.json.  Gates (always): continuous must
# clear SERVE_RATIO_GATE x static tokens/sec on the mixed-length backlog,
# and the int8 KV engine must match the f32 engine's greedy outputs on
# >= SERVE_INT8_MATCH_GATE of generated tokens.  Like the shard bench,
# absolute steps/sec regression vs the committed JSON is NOT gated — on a
# shared 1-core CPU box run-to-run wall-clock variance exceeds any sane
# band; the scheduling RATIO divides that noise out, which is exactly why
# it is the headline.
# ---------------------------------------------------------------------------

SERVE_RATIO_GATE = 1.3
SERVE_INT8_MATCH_GATE = 0.95


def _serve_workload(prompts, n, max_gen, rate, seed):
    """Requests over real corpus prompt windows with the bimodal
    short/long generation mix of ``launch.serve.build_workload``."""
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.RandomState(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        row = prompts[i % len(prompts)]
        plen = int(rng.randint(max(1, len(row) // 4), len(row) + 1))
        if rng.rand() < 0.25:
            glen = int(rng.randint(max(2, 3 * max_gen // 4), max_gen + 1))
        else:
            glen = int(rng.randint(max(1, max_gen // 16),
                                   max(2, max_gen // 8) + 1))
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        reqs.append(Request(rid=i, prompt=row[:plen].tolist(), max_gen=glen,
                            arrival=t if rate > 0 else 0.0))
    return reqs


def serve_bench(quick: bool):
    import json
    import os
    import tempfile

    import numpy as np

    from repro import configs, optim
    from repro.checkpoint.manager import CheckpointManager
    from repro.data.pipeline import CorpusLM
    from repro.models import lm
    from repro.optim.schedules import warmup_cosine
    from repro.runtime.fault_tolerance import TrainLoop
    from repro.serve.engine import Engine, EngineConfig

    # -- train the serving model on the fixture corpus and checkpoint it --
    corpus = _fixture_corpus()
    steps = 30 if quick else 72
    S, B = 64, 8
    cfg = configs.LLAMA["llama-60m"].with_(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab=512)
    opt = optim.make("gwt", lr=warmup_cosine(0.01, steps), level=2)
    params = lm.init(cfg, jax.random.key(0))
    train_src = CorpusLM(corpus, S, B, seed=0)
    loop = TrainLoop(lm.make_train_step(cfg, opt), None, train_src,
                     log_every=steps, max_chunk=8, log=lambda s: None)
    params, ostate, losses = loop.run(params, opt.init(params),
                                      num_steps=steps)
    ckpt = tempfile.mkdtemp(prefix="repro_serve_ckpt_")
    CheckpointManager(ckpt).save(steps, {"opt": ostate, "params": params},
                                 blocking=True)
    emit("serve/train", 0.0,
         f"{steps} steps, loss {losses[0]:.2f}->{losses[-1]:.2f}")

    # prefill_chunk=32 keeps multi-chunk prefill on the hot path (prompts
    # run 12-48 tokens) while amortizing per-dispatch overhead; gen up to
    # 48 keeps the workload decode-dominated, which is where continuous
    # slot reuse pays.
    max_prompt, max_gen = 48, 48
    n_req = 32 if quick else 96
    ecfg = EngineConfig(num_slots=8, page_size=16,
                        max_ctx=max_prompt + max_gen, prefill_chunk=32)
    eng = Engine.from_checkpoint(cfg, ckpt, ecfg)
    prompts = np.asarray(CorpusLM(corpus, max_prompt, 16,
                                  seed=1).batch(0)["tokens"])
    eng.warmup()
    out = {"config": {"arch": cfg.name, "train_steps": steps,
                      "num_slots": ecfg.num_slots,
                      "page_size": ecfg.page_size,
                      "prefill_chunk": ecfg.prefill_chunk,
                      "max_ctx": ecfg.max_ctx, "requests": n_req,
                      "workload": "bimodal gen 3-6 (75%) / 36-48 (25%), "
                                  "corpus prompts 12-48"},
           "cells": {}}

    # -- headline: backlogged continuous vs static waves (best of 3: the
    # ratio is scheduling, the repeats squeeze out host-noise outliers) --
    keep = ("tokens_per_sec", "requests_per_sec", "makespan_s",
            "generated_tokens")
    for mode, static in (("continuous", False), ("static", True)):
        best = None
        for rep in range(3):
            reqs = _serve_workload(prompts, n_req, max_gen, 0.0, seed=7)
            eng.reset()
            s = eng.run(reqs, static=static)
            if best is None or s["tokens_per_sec"] > best["tokens_per_sec"]:
                best = s
        out["cells"][mode] = {k: round(best[k], 3) for k in keep}
        emit(f"serve/{mode}", 0.0,
             f"{best['tokens_per_sec']:.0f}tok/s "
             f"{best['requests_per_sec']:.1f}req/s "
             f"makespan={best['makespan_s']:.2f}s")
    ratio = (out["cells"]["continuous"]["tokens_per_sec"]
             / out["cells"]["static"]["tokens_per_sec"])
    out["headline"] = {"continuous_over_static": round(ratio, 3),
                       "gate": SERVE_RATIO_GATE}
    if ratio < SERVE_RATIO_GATE:
        emit("serve/ratio_gate_ERROR", 0.0,
             f"continuous only {ratio:.2f}x static tokens/sec "
             f"(gate >= {SERVE_RATIO_GATE}x)")
    else:
        emit("serve/ratio_gate", 0.0,
             f"continuous {ratio:.2f}x static tokens/sec "
             f"(gate >= {SERVE_RATIO_GATE}x)")

    # -- open-loop Poisson arrivals at ~60% of measured capacity:
    # completion latency under load (telemetry — latency percentiles on a
    # 1-core shared box are reported, not gated) --
    rate = 0.6 * out["cells"]["continuous"]["requests_per_sec"]
    reqs = _serve_workload(prompts, max(16, n_req // 2), max_gen, rate,
                           seed=11)
    eng.reset()
    s = eng.run(reqs)
    out["open_loop"] = {"arrival_rps": round(rate, 2),
                        "requests": len(reqs),
                        "p50_s": round(s["p50_s"], 4),
                        "p99_s": round(s["p99_s"], 4),
                        "tokens_per_sec": round(s["tokens_per_sec"], 1)}
    emit("serve/open_loop", 0.0,
         f"poisson {rate:.1f}req/s p50={s['p50_s']*1e3:.0f}ms "
         f"p99={s['p99_s']*1e3:.0f}ms")

    # -- int8 KV fidelity: same checkpoint, quantized pages --------------
    eng8 = Engine.from_checkpoint(cfg, ckpt, EngineConfig(
        num_slots=ecfg.num_slots, page_size=ecfg.page_size,
        max_ctx=ecfg.max_ctx, prefill_chunk=ecfg.prefill_chunk,
        kv_quant="int8"))
    eng8.warmup()
    n8 = 16 if quick else 32
    outs = {}
    for tag, e in (("f32", eng), ("int8", eng8)):
        reqs = _serve_workload(prompts, n8, max_gen, 0.0, seed=13)
        e.reset()
        e.run(reqs)
        outs[tag] = [r.generated for r in reqs]
    total = match = 0
    for a, b in zip(outs["f32"], outs["int8"]):
        total += len(a)
        match += sum(int(x == y) for x, y in zip(a, b))
    rate8 = match / total
    out["int8_kv"] = {"match_rate": round(rate8, 4), "tokens": total,
                      "gate": SERVE_INT8_MATCH_GATE,
                      "arena_bytes_f32": eng.kv_bytes(),
                      "arena_bytes_int8": eng8.kv_bytes()}
    shrink = eng8.kv_bytes() / eng.kv_bytes()
    if rate8 < SERVE_INT8_MATCH_GATE:
        emit("serve/int8_gate_ERROR", 0.0,
             f"int8 KV greedy match {rate8:.3f} < {SERVE_INT8_MATCH_GATE} "
             f"({match}/{total})")
    else:
        emit("serve/int8_gate", 0.0,
             f"int8 KV matches f32 greedy on {rate8:.1%} of {total} tokens "
             f"(arena {shrink:.2f}x f32 bytes)")

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_serve_cpu_quick.json" if quick
                        else "BENCH_serve_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("serve/json", 0.0, path)


# ---------------------------------------------------------------------------
# Observability overhead + artifact validity (DESIGN.md §12).  Two gates:
#
#   1. steps/sec with taps fused into the superstep (and telemetry
#      writing JSONL) must stay >= OBS_OVERHEAD_GATE of the taps-off
#      loop — the taps ride the existing norm pass and log_every fetch,
#      so the budget is tight (<=2%).  Estimator: adjacent off/on
#      segment PAIRS, gate on the median of per-pair ratios — on a
#      shared 1-core box single-segment wall clock swings +-10%, far
#      wider than the band, and only pairing + a median divides that
#      host noise out (same reasoning as the serve scheduling-ratio
#      gate).  Up to 3 rounds, passing if any round's median clears.
#   2. the emitted artifacts are real: every metrics.jsonl line parses,
#      train_step records carry tap scalars, serve_request records carry
#      latency fields, and trace.json passes the Chrome trace_event
#      schema check with spans from BOTH the train loop and the serve
#      engine.
# ---------------------------------------------------------------------------

OBS_OVERHEAD_GATE = 0.98


def obs_bench(quick: bool):
    import json
    import os
    import tempfile

    from repro import configs, obs, optim
    from repro.data.pipeline import SyntheticLM
    from repro.launch.serve import build_workload
    from repro.models import lm
    from repro.obs import trace as obs_trace
    from repro.runtime.fault_tolerance import TrainLoop
    from repro.serve.engine import Engine, EngineConfig

    cfg = configs.get_smoke("llama-60m")
    B, S = 1, 64
    chunk = 20                      # superstep length = log cadence,
    seg = 4 * chunk                 # matching step_bench's chunk
    pairs = 3 if quick else 5       # off/on segment pairs per round
    silent = lambda s: None  # noqa: E731

    opt = optim.make("gwt", lr=1e-3, level=2)
    params = lm.init(cfg, jax.random.key(0))
    st = opt.init(params)
    data = SyntheticLM(cfg.vocab, S, B, seed=0)
    loop_off = TrainLoop(lm.make_train_step(cfg, opt), None, data,
                         log_every=chunk, max_chunk=chunk, log=silent)
    loop_on = TrainLoop(lm.make_train_step(cfg, opt), None, data,
                        log_every=chunk, max_chunk=chunk, log=silent,
                        tap_step=lm.make_train_step(cfg, opt, taps=True))

    # warm both superstep jits before timing anything
    obs.configure()                 # null telemetry
    for lp in (loop_off, loop_on):
        lp.run(*jax.tree.map(lambda a: a.copy(), (params, st)),
               num_steps=chunk)

    # -- paired segments: taps-off under the null telemetry (the
    # metrics-dir-unset path), taps-on with the JSONL sink + tracer live
    # so each pair covers the full observability cost back-to-back --
    import statistics
    meas = tempfile.mkdtemp(prefix="repro_obs_meas_")
    round_medians = []
    off = on = ratio = 0.0
    for _ in range(3):
        offs, ons = [], []
        for _ in range(pairs):
            obs.configure()
            offs.append(_loop_steps_per_sec(loop_off, params, st, seg,
                                            repeats=1))
            obs.configure(meas, run={"cmd": "bench-obs"})
            ons.append(_loop_steps_per_sec(loop_on, params, st, seg,
                                           repeats=1))
        med = statistics.median(n / o for n, o in zip(ons, offs))
        round_medians.append(round(med, 4))
        if med > ratio:
            ratio = med
            off = statistics.median(offs)
            on = statistics.median(ons)
        if ratio >= OBS_OVERHEAD_GATE:
            break
    obs.shutdown()
    out = {"config": {"arch": cfg.name, "batch": B, "seq": S,
                      "chunk": chunk, "segment_steps": seg,
                      "pairs_per_round": pairs},
           "cells": {"taps_off_steps_per_sec": round(off, 2),
                     "taps_on_steps_per_sec": round(on, 2),
                     "on_over_off": round(ratio, 4),
                     "round_medians": round_medians,
                     "gate": OBS_OVERHEAD_GATE}}
    if ratio < OBS_OVERHEAD_GATE:
        emit("obs/overhead_gate_ERROR", 0.0,
             f"taps-on {on:.1f} steps/s is {ratio:.3f}x taps-off "
             f"{off:.1f} (gate >= {OBS_OVERHEAD_GATE}x)")
    else:
        emit("obs/overhead_gate", 0.0,
             f"taps-on {on:.1f} steps/s = {ratio:.3f}x taps-off "
             f"{off:.1f} (gate >= {OBS_OVERHEAD_GATE}x)")

    # -- artifact phase: one fresh telemetry session covering a train
    # chunk AND a small serve run, then validate what it wrote --
    art = tempfile.mkdtemp(prefix="repro_obs_art_")
    tel = obs.configure(art, run={"cmd": "bench-obs", "arch": cfg.name})
    loop_on.run(*jax.tree.map(lambda a: a.copy(), (params, st)),
                num_steps=chunk)
    eng = Engine(cfg, params, EngineConfig(
        num_slots=2, page_size=8, max_ctx=24, prefill_chunk=16))
    eng.warmup()
    reqs = build_workload(4, cfg.vocab, 16, 8, 0.0, seed=3)
    eng.run(reqs)
    assert tel is obs.get()
    obs.shutdown()                  # writes <art>/trace.json

    with open(os.path.join(art, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    kinds = {}
    for r in records:
        kinds.setdefault(r.get("kind"), []).append(r)
    train_recs = kinds.get("train_step", [])
    tapped = [r for r in train_recs
              if any("/" in k for k in r if k not in ("kind",))]
    serve_recs = kinds.get("serve_request", [])
    probs = []
    if not records or records[0].get("kind") != "run" \
            or "run" not in records[0]:
        probs.append("missing run-provenance header")
    if not tapped:
        probs.append("no train_step records with tap scalars")
    if len(serve_recs) != len(reqs):
        probs.append(f"{len(serve_recs)} serve_request records for "
                     f"{len(reqs)} requests")
    if any("ttft_s" not in r or "latency_s" not in r for r in serve_recs):
        probs.append("serve_request records missing latency fields")

    with open(os.path.join(art, "trace.json")) as f:
        doc = json.load(f)
    try:
        obs_trace.validate(doc)
    except Exception as e:  # noqa: BLE001 - surfaced as a gate row
        probs.append(f"trace schema: {type(e).__name__}: {e}")
    evs = doc.get("traceEvents", [])
    cats = {e.get("cat") for e in evs}
    names = {e.get("name") for e in evs}
    if not ({"train.input_wait", "train.place", "train.block"} <= names
            and names & {"train.dispatch", "train.dispatch_first"}):
        probs.append(f"train spans missing from trace (names={names})")
    if "serve" not in cats:
        probs.append("no serve-category events in trace")

    out["artifacts"] = {
        "metrics_records": len(records),
        "train_step_records": len(train_recs),
        "tap_keys": sorted(k for k in (tapped[0] if tapped else {})
                           if "/" in k)[:8],
        "serve_request_records": len(serve_recs),
        "trace_events": len(evs),
        "trace_cats": sorted(c for c in cats if c)}
    if probs:
        emit("obs/artifact_ERROR", 0.0, "; ".join(probs))
    else:
        emit("obs/artifact", 0.0,
             f"{len(records)} jsonl records ({len(train_recs)} train_step, "
             f"{len(tapped)} tapped, {len(serve_recs)} serve_request), "
             f"{len(evs)} trace events across cats "
             f"{sorted(c for c in cats if c)}")

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_obs_cpu_quick.json" if quick
                        else "BENCH_obs_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    emit("obs/json", 0.0, path)


TABLES = {
    "table1": table1_memory,
    "table2": table2_pretrain,
    "table3": table3_throughput,
    "table4": table4_seqlen,
    "table11": table11_memory_estimate,
    "table12": table12_levels,
    "kernels": kernels_bench,
    "trace": trace_bench,
    "step": step_bench,
    "state": state_bench,
    "shard": shard_bench,
    "data": data_bench,
    "curve": curve_bench,
    "serve": serve_bench,
    "obs": obs_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--shard-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: 8-device subprocess
    args = ap.parse_args()
    if args.shard_worker:
        _shard_worker(args.quick)
        return
    if args.only and args.only not in TABLES:
        # a typo'd --only would otherwise run nothing and exit 0 — a CI
        # gate that silently stops gating.
        ap.error(f"unknown bench {args.only!r}; choose from "
                 f"{', '.join(TABLES)}")
    print("name,us_per_call,derived")
    for name, fn in TABLES.items():
        if args.only and args.only != name:
            continue
        try:
            fn(args.quick)
        except Exception as e:  # keep the harness robust
            emit(f"{name}/ERROR", 0.0, f"{type(e).__name__}: {e}")
    bad = [r for r in ROWS if "ERROR" in r[0]]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
