"""Smoke run of the main path on a TPU: GWT pre-training of llama-1b.

    python chip_smoke.py             # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4   # four chips: the DP-reduction phase only

One chip:

(a) the device JAX found (the script fails when it is not a TPU);
(b) kernel parity: the fused GWT-Adam kernels (f32 and int8 moments) and
    the compressed DP wire's DWT, ``impl="pallas"`` against the jnp oracle
    at llama-1b bucket widths; and the fused kernel fed bf16 gradients
    (its one-pass schedule) bitwise against the same kernel run through
    the f32 schedule's three-pass shuffles, and one launch per leaf (the
    optimizer's path) bitwise against one launch over the stacked bucket,
    at the benchmark cells' bucket widths (``python chip_smoke.py
    --parity`` runs these two checks alone);
(c) llama-1b GWT level-2 training through ``repro.launch.train.main``:
    f32 moments, then the int8 state codec.  Before each run the train
    step is compiled once more on its own to check that every GWT leaf
    runs the Pallas kernel (``tpu_custom_call`` in the HLO, one per
    leaf) and to print the step's ``memory_analysis``;
(d) the last line: ``{"ok": true, "device": {...}}``.

Four chips: llama-350m trained on a four-chip data-parallel mesh with the
wavelet-compressed reduction (bf16 detail bands) and FSDP-sharded state,
against the exact f32 reduction on the same mesh and against one chip
accumulating the same four shards (``--accum 4``).

Everything runs in this one process — a chip belongs to one process, so
the script starts no child that touches JAX (data loading stays in
process, ``--workers 0``).  It sets its own import path, so it runs from
the checkout's root with no environment.  Times it prints are smoke output
of one run, compilation included; they are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "llama-1b"
SEQ = 256          # the paper's pre-training sequence length
BATCH = 2          # the largest that fits 16 GB in f32 (memory_analysis)
LEVEL = 2
# The four-chip phase's one-chip reference accumulates four shards in an
# f32 gradient buffer, which llama-1b cannot hold next to its state on one
# chip, so that phase trains llama-350m.
FOUR_CHIP_ARCH = "llama-350m"
FOUR_CHIP_BATCH = 4  # per chip
FOUR_CHIP_LR = 1e-3

# Parity tolerances (kept from the hardware parity tests this phase
# replaces): Mosaic and XLA:TPU may round the same chain differently by an
# ulp (FMA contraction, divide and sqrt sequences), far below the garbage
# an aliased-window clobber would leave.
NORM_TOL = dict(rtol=1e-5, atol=1e-6)
M_TOL = dict(rtol=1e-5, atol=1e-6)
V_TOL = dict(rtol=1e-5, atol=1e-7)
SCALE_TOL = dict(rtol=1e-6, atol=0)
WRITE_SLACK = 8    # spacings of the write chain's largest operand

# Four-chip phase, largest relative loss difference from the exact
# reduction over the run.  The exact reduction and the one-chip
# accumulation sum the same shards in other orders; bf16 detail bands
# perturb every step's gradient.  At lr 1e-3 (llama-350m, seq 64, on the
# CPU) the two measured 0.03% and 0.09% over 8 steps; the bounds are ten
# to twenty times those.  At lr 1e-2 the tied-embedding init (loss near
# 1000) makes the first steps chaotic: on the chip exact and one-chip
# runs parted by 10% by step 6, which says nothing about the reduction.
COMPRESSED_REL_TOL = 0.01
EXACT_REL_TOL = 0.005


class SmokeFailure(Exception):
    pass


def say(*parts):
    print("smoke:", *parts, flush=True)


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", json.dumps(info))
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0].platform is "
                           f"{info['platform']!r}")
    check(info["count"] == chips,
          f"--chips {chips} needs {chips} TPU devices, found {info['count']}")
    return info


# ---------------------------------------------------------------------------
# (b) kernel parity on the chip
# ---------------------------------------------------------------------------

def _write_parity(a, b, p_in, tag):
    """``new_p`` from two lowerings of ``p - step·(g̃·coef)``: within
    ``WRITE_SLACK`` spacings of the largest of |a|, |b|, |p_in|, plus
    ``WRITE_SLACK`` f32 epsilons of the row's largest update.  The second
    term is the inverse butterfly's: where its operands cancel, an ulp of
    difference in them (a divide or sqrt rounded apart, a contraction)
    stays as an absolute error of g̃, and the operands are at most about
    twice the row's largest |g̃| (tests/test_kernels.py measures the same
    effect between two CPU lowerings)."""
    import numpy as np
    a, b, p_in = (np.asarray(x, np.float32) for x in (a, b, p_in))
    mag = np.maximum(np.maximum(np.abs(a), np.abs(b)), np.abs(p_in))
    diff = np.abs(a - b)
    chain = WRITE_SLACK * np.spacing(mag)
    row_upd = np.max(np.abs(p_in - b), axis=-1, keepdims=True)
    bad = diff > chain + WRITE_SLACK * np.finfo(np.float32).eps * row_upd
    say(f"parity {tag}: new_p max |diff| {float(diff.max())!r}, "
        f"max spacings {float((diff / np.spacing(mag)).max())!r}, past "
        f"{WRITE_SLACK} spacings: {int((diff > chain).sum())}, past the "
        f"bound: {int(bad.sum())} of {bad.size}")
    check(not bad.any(), f"parity {tag}: {int(bad.sum())} new_p elements "
                         f"past the bound")


def _allclose(a, b, tag, tol):
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    bad = np.abs(a - b) > tol["atol"] + tol["rtol"] * np.abs(b)
    say(f"parity {tag}: max |diff| {float(np.abs(a - b).max())!r}, "
        f"outside {tol}: {int(bad.sum())} of {bad.size}")
    check(not bad.any(), f"parity {tag}: {int(bad.sum())} elements outside "
                         f"{tol}")


def _bucket_inputs(L, m, n, seed):
    import jax
    import jax.numpy as jnp
    k = jax.random.key(seed)
    g = jax.random.normal(k, (L, m, n), jnp.float32)
    p = jax.random.normal(jax.random.fold_in(k, 1), (L, m, n), jnp.float32)
    na = n >> LEVEL
    st = {"m": jnp.abs(jax.random.normal(jax.random.fold_in(k, 2),
                                         (L, m, na))) * 0.1,
          "v": jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                         (L, m, na))) * 0.01}
    pn = jnp.arange(L, dtype=jnp.float32) * 0.3
    return g, p, st, pn


def _write_kw(use_limiter):
    import jax.numpy as jnp
    return dict(lr_t=jnp.float32(0.01), alpha=0.25, weight_decay=0.0,
                gamma=1.01, use_limiter=use_limiter, level=LEVEL)


def kernel_parity():
    """The fused kernels at llama-1b bucket widths — the attention bucket
    (four 2048×2048 leaves) and the MLP bucket (two leaves of 5461 rows
    after the transform swap) — with the layer stack cut to 4 of 24
    (4·5461 rows: many row tiles, a partial last one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.gwt_adam import ops as gops
    from repro.kernels.haar_dwt import ops as dops
    from repro.optim import codec

    depth = 4
    attn = (4, depth * 2048, 2048)
    mlp = (2, depth * 5461, 2048)
    for shape, use_limiter in ((attn, True), (mlp, True), (mlp, False)):
        tag = f"f32 {shape} limiter={use_limiter}"
        g, p, st, pn = _bucket_inputs(*shape, seed=6)
        kw = _write_kw(use_limiter)
        out_k = gops.fused_write_update(list(g), list(p), st, jnp.int32(2),
                                        pn, impl="pallas", **kw)
        out_j = gops.fused_write_update(list(g), list(p), st, jnp.int32(2),
                                        pn, impl="jnp", **kw)
        (pk, nk, sk), (pj, nj, sj) = jax.device_get((out_k, out_j))
        pk, pj = np.stack(pk), np.stack(pj)
        _allclose(nk, nj, tag + " norm", NORM_TOL)
        _allclose(sk["m"], sj["m"], tag + " m", M_TOL)
        _allclose(sk["v"], sj["v"], tag + " v", V_TOL)
        _write_parity(pk, pj, p, tag)
        del g, p, st, out_k, out_j

    L, m, n = mlp
    tag = f"int8 {mlp} limiter=True"
    g, p, _, pn = _bucket_inputs(L, m, n, seed=7)
    key = codec.make_key(0)
    leaf_ids = jnp.arange(L, dtype=jnp.uint32)
    k = jax.random.key(9)
    enc = {}
    for slot, name, scale in ((0, "m", 0.1), (1, "v", 0.01)):
        src = jnp.abs(jax.random.normal(jax.random.fold_in(k, 4 + slot),
                                        (L, m, n >> LEVEL))) * scale
        qs = [codec.blocked_quant(src[l], codec.slot_salt(
            key, jnp.uint32(0), slot, leaf_ids[l])) for l in range(L)]
        enc[name] = {"q": jnp.stack([q for q, _ in qs]),
                     "scale": jnp.stack([s for _, s in qs])}
    kw = _write_kw(True)
    out_k = gops.fused_write_update_q8(list(g), list(p), enc, jnp.int32(1),
                                       key, leaf_ids, pn, impl="pallas",
                                       **kw)
    out_j = gops.fused_write_update_q8(list(g), list(p), enc, jnp.int32(1),
                                       key, leaf_ids, pn, impl="jnp", **kw)
    (pk, nk, sk), (pj, nj, sj) = jax.device_get((out_k, out_j))
    pk, pj = np.stack(pk), np.stack(pj)
    _allclose(nk, nj, tag + " norm", NORM_TOL)
    for name in ("m", "v"):
        # an ulp of drift before the quantizer can flip one stochastic
        # rounding decision: a budget of one int8 code
        dq = np.abs(sk[name]["q"].astype(np.int32)
                    - sj[name]["q"].astype(np.int32))
        say(f"parity {tag} {name}.q: max code diff {int(dq.max())}, "
            f"codes that differ: {int((dq > 0).sum())} of {dq.size}")
        check(dq.max() <= 1, f"parity {tag} {name}.q: code diff {dq.max()}")
        _allclose(sk[name]["scale"], sj[name]["scale"],
                  f"{tag} {name}.scale", SCALE_TOL)
    _write_parity(pk, pj, p, tag)
    del g, p, enc, out_k, out_j

    # the compressed DP wire: f32 approximation band, bf16 detail bands
    g = jax.random.normal(jax.random.key(12), (depth * 5461, 2048),
                          jnp.float32)
    bk = jax.device_get(dops.dwt_wire(g, LEVEL, jnp.bfloat16, impl="pallas"))
    bj = jax.device_get(dops.dwt_wire(g, LEVEL, jnp.bfloat16, impl="jnp"))
    check(bk[0].dtype == np.float32, "dwt_wire: approximation band not f32")
    _allclose(bk[0], bj[0], "dwt_wire A (f32)", dict(rtol=1e-6, atol=1e-6))
    for i, (dk, dj) in enumerate(zip(bk[1:], bj[1:])):
        # an ulp of f32 drift may move a bf16 rounding: one bf16 ulp
        _allclose(dk, dj, f"dwt_wire D{i} (bf16)",
                  dict(rtol=2.0 ** -7, atol=1e-6))


# The benchmark cells' GWT buckets (L, rows, n) at level 2: Qwen2.5-3B's
# gate/up, down, q/o and k/v stacks of 9 layers, Mistral-7B's of 4.  The
# schedule parity cuts each to four of its row tiles.
CELL_BUCKETS = [(2, 18432, 11008), (1, 99072, 2048), (2, 18432, 2048),
                (2, 18432, 256), (2, 16384, 14336), (1, 57344, 4096),
                (2, 16384, 4096), (2, 16384, 1024)]


@contextlib.contextmanager
def three_pass_schedule():
    """Inside, the fused kernel runs every gradient tile through the f32
    schedule (three-pass shuffles, rounded to the gradient's dtype at the
    end): the kernel as it was before the bf16 schedule.  Takes effect
    for kernels traced inside."""
    import jax.numpy as jnp
    from repro.kernels.gwt_adam import kernel as kg
    core = kg._dht_adam_core

    def shuffled(x, m_st, v_st, level, b1, b2, eps, xla=False):
        out, m, v = kg._core_shuffled(x.astype(jnp.float32), m_st, v_st,
                                      level, b1, b2, eps, xla)
        return out.astype(x.dtype), m, v

    kg._dht_adam_core = shuffled
    try:
        yield
    finally:
        kg._dht_adam_core = core


def schedule_parity():
    """The fused f32-moment kernel fed bf16 ``g``/``p`` (the cells'
    dtypes) returns ``new_p, m, v, new_norm`` bitwise equal to the same
    kernel run through the three-pass schedule, at every cell bucket
    width, with the limiter on (as in the cells) and off."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.gwt_adam import kernel as kg

    for (L, rows, n), use_limiter in [(b, True) for b in CELL_BUCKETS] + [
            (CELL_BUCKETS[0], False)]:
        m = 4 * kg.fused_row_block(rows, n, LEVEL)
        k = jax.random.key(n + m)
        g = (jax.random.normal(k, (L, m, n)) * 1e-3).astype(jnp.bfloat16)
        p = (jax.random.normal(jax.random.fold_in(k, 1), (L, m, n))
             * 0.02).astype(jnp.bfloat16)
        na = n >> LEVEL
        ms = jax.random.normal(jax.random.fold_in(k, 2), (L, m, na)) * 1e-4
        vs = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                       (L, m, na))) * 1e-8
        pn = jnp.arange(L, dtype=jnp.float32) * 0.3
        args = (g, p, ms, vs, pn, jnp.float32(0.0025), jnp.float32(0.0))
        run = lambda: jax.device_get(jax.jit(functools.partial(
            kg.gwt_adam_tile_fused, level=LEVEL, gamma=1.01,
            use_limiter=use_limiter, weight_decay=False))(*args))
        new = run()
        with three_pass_schedule():
            old = run()
        tag = f"schedule bf16 {(L, m, n)} limiter={use_limiter}"
        bad = {}
        for name, a, b in zip(("new_p", "m", "v", "new_norm"), new, old):
            a, b = np.asarray(a), np.asarray(b)
            bad[name] = int((a.view(f"u{a.itemsize}")
                             != b.view(f"u{b.itemsize}")).sum())
        say(f"parity {tag}: elements whose bits differ {bad}")
        check(not any(bad.values()), f"parity {tag}: {bad}")
        del g, p, ms, vs, new, old


def leaf_parity():
    """The fused f32-moment update as the optimizer runs it, one launch
    per leaf on the leaves' own buffers over the bucket's stacked
    moments (``ops._leaf_by_leaf``), returns ``new_p, m, v, new_norm``
    bitwise equal to one launch over the stacked bucket, at every
    multi-leaf cell bucket width, fed bf16 ``g``/``p`` with the limiter
    on.  Both take the same step size: one computed eagerly and one
    inside a jit may differ in an ulp, which moves ``new_p``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.gwt_adam import kernel as kg
    from repro.kernels.gwt_adam import ops as gops

    kw = dict(level=LEVEL, gamma=1.01, use_limiter=True,
              weight_decay=False)
    scalars = (jnp.float32(0.0025), jnp.float32(0.0))
    once = jax.jit(functools.partial(kg.gwt_adam_tile_fused, **kw))
    by_leaf = jax.jit(lambda g, p, ms, vs, pn, *sc: gops._leaf_by_leaf(
        functools.partial(kg.gwt_adam_tile_fused, **kw),
        [g[j:j + 1] for j in range(g.shape[0])],
        [p[j:j + 1] for j in range(p.shape[0])], (ms, vs), (pn,), sc))
    for L, rows, n in CELL_BUCKETS:
        if L < 2:
            continue
        m = 4 * kg.fused_row_block(rows, n, LEVEL)
        k = jax.random.key(n + m + 1)
        g = (jax.random.normal(k, (L, m, n)) * 1e-3).astype(jnp.bfloat16)
        p = (jax.random.normal(jax.random.fold_in(k, 1), (L, m, n))
             * 0.02).astype(jnp.bfloat16)
        na = n >> LEVEL
        ms = jax.random.normal(jax.random.fold_in(k, 2), (L, m, na)) * 1e-4
        vs = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                       (L, m, na))) * 1e-8
        pn = jnp.arange(L, dtype=jnp.float32) * 0.3
        want = jax.device_get(once(g, p, ms, vs, pn, *scalars))
        new_ps, (m2, v2), norm = jax.device_get(
            by_leaf(g, p, ms, vs, pn, *scalars))
        tag = f"per-leaf launches bf16 {(L, m, n)}"
        bad = {}
        for name, a, b in zip(("new_p", "m", "v", "new_norm"),
                              (np.concatenate(new_ps), m2, v2, norm),
                              want):
            a, b = np.asarray(a), np.asarray(b)
            bad[name] = int((a.view(f"u{a.itemsize}")
                             != b.view(f"u{b.itemsize}")).sum())
        say(f"parity {tag}: elements whose bits differ {bad}")
        check(not any(bad.values()), f"parity {tag}: {bad}")
        del g, p, ms, vs, want, new_ps, m2, v2


# ---------------------------------------------------------------------------
# (c) training through the launcher
# ---------------------------------------------------------------------------

def _train_argv(codec, steps, batch, extra=(), arch=ARCH):
    return ["--arch", arch, "--optimizer", "gwt", "--level", str(LEVEL),
            "--state-codec", codec, "--steps", str(steps), "--batch",
            str(batch), "--seq", str(SEQ), "--log-every", "10",
            "--kernel-impl", "auto", "--workers", "0", *extra]


def compiled_step_check(codec: str, batch: int):
    """Compile the launcher's train step on its own: every GWT bucket must
    lower to a Pallas kernel (no jnp oracle), and report its memory."""
    import jax
    import jax.numpy as jnp
    from repro import compat, configs
    from repro.launch import train
    from repro.models import lm

    impl = compat.resolve_kernel_impl("auto")
    say(f"{codec}: kernel_impl resolved to {impl!r}")
    check(impl == "pallas", f"kernel_impl resolved to {impl!r}, not pallas")
    cfg = configs.get_config(ARCH)
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    opt = train.make_optimizer("gwt", 0.01, 20, level=LEVEL, alpha=0.25,
                               host="adam", impl=impl, state_codec=codec)
    ost = jax.eval_shape(opt.init, params)
    toks = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32)
    t0 = time.time()
    compiled = jax.jit(lm.make_train_step(cfg, opt),
                       donate_argnums=(0, 1)).lower(
        params, ost, {"tokens": toks, "labels": toks}).compile()
    hlo = compiled.as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    gwt = [b for b in opt.engine.plan(params).buckets
           if b.rule.kind.startswith("gwt")]
    gwt_buckets = [b.name for b in gwt]
    gwt_leaves = sum(len(b.indices) for b in gwt)
    ma = compiled.memory_analysis()
    say(f"{codec}: step compiled in {time.time() - t0:.1f}s; "
        f"{len(gwt_buckets)} GWT buckets {gwt_buckets}; "
        f"{n_kernels} tpu_custom_call in the step HLO")
    say(f"{codec}: memory_analysis batch={batch} seq={SEQ}: "
        f"argument {ma.argument_size_in_bytes} B, output "
        f"{ma.output_size_in_bytes} B, alias {ma.alias_size_in_bytes} B, "
        f"temp {ma.temp_size_in_bytes} B")
    check(n_kernels >= 1, "no tpu_custom_call in the train step's HLO")
    check(n_kernels == gwt_leaves,
          f"{n_kernels} Pallas kernels for {gwt_leaves} GWT leaves")


def check_losses(losses, tag):
    check(len(losses) > 0, f"{tag}: no losses")
    check(all(math.isfinite(x) for x in losses), f"{tag}: non-finite loss")
    q = max(1, len(losses) // 4)
    tail = sum(losses[-q:]) / q
    say(f"{tag}: first loss {losses[0]!r}, mean of last {q} {tail!r}")
    check(tail < losses[0], f"{tag}: loss did not fall "
                            f"({losses[0]} -> {tail})")


def train_run(argv, tag):
    from repro.launch import train
    t0 = time.time()
    params, opt_state, losses = train.main(argv)
    losses = [float(x) for x in losses]
    wall = time.time() - t0
    say(f"{tag}: {len(losses)} steps in {wall:.1f}s wall (compile "
        f"included); losses {losses}")
    return params, opt_state, losses


def one_chip():
    import jax
    t0 = time.time()
    kernel_parity()
    schedule_parity()
    leaf_parity()
    say(f"kernel parity passed in {time.time() - t0:.1f}s")
    for codec, steps in (("f32", 20), ("int8", 10)):
        compiled_step_check(codec, BATCH)
        params, opt_state, losses = train_run(
            _train_argv(codec, steps, BATCH), f"train {codec}")
        check_losses(losses, f"train {codec}")
        del params, opt_state
        gc.collect()
        stats = jax.devices()[0].memory_stats() or {}
        say(f"{codec}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
        say(f"{codec}: tokens/step {BATCH * SEQ}")


# ---------------------------------------------------------------------------
# four chips: compressed vs exact DP reduction vs one-chip accumulation
# ---------------------------------------------------------------------------

def _placed_on(tree):
    import jax
    devs, sharded = set(), 0
    for leaf in jax.tree.leaves(tree):
        devs |= set(leaf.sharding.device_set)
        sharded += not leaf.sharding.is_fully_replicated
    return devs, sharded


def four_chips():
    import jax
    steps = 8
    runs = {}
    for name, extra in (
            ("compressed", ["--mesh", "4", "--dp-reduce", "compressed",
                            "--dp-detail-dtype", "bfloat16"]),
            ("exact", ["--mesh", "4", "--dp-reduce", "exact"]),
            ("one_chip_accum4", ["--accum", "4"])):
        params, opt_state, losses = train_run(
            _train_argv("f32", steps, 4 * FOUR_CHIP_BATCH,
                        ["--lr", str(FOUR_CHIP_LR), *extra],
                        arch=FOUR_CHIP_ARCH), f"4chip {name}")
        check_losses(losses, f"4chip {name}")
        devs, sharded = _placed_on((params, opt_state))
        say(f"4chip {name}: state on {len(devs)} devices, "
            f"{sharded} leaves sharded")
        if name != "one_chip_accum4":
            check(devs == set(jax.devices()[:4]),
                  f"4chip {name}: state on {len(devs)} devices, not 4")
            check(sharded > 0, f"4chip {name}: no leaf is sharded")
        runs[name] = losses
        del params, opt_state
        gc.collect()
    for name, tol in (("compressed", COMPRESSED_REL_TOL),
                      ("one_chip_accum4", EXACT_REL_TOL)):
        rel = max(abs(a - b) / abs(b)
                  for a, b in zip(runs[name], runs["exact"]))
        say(f"4chip {name} vs exact: max relative loss diff {rel!r} "
            f"(tolerance {tol})")
        check(rel <= tol, f"4chip {name} vs exact: {rel} > {tol}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--parity", action="store_true",
                    help="one chip: run the bf16 schedule and per-leaf "
                    "launch parity alone")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}; run this "
              f"script from its checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        info = device_info(args.chips)
        from repro.launch.cache import enable_compile_cache
        say("compile cache", enable_compile_cache())
        t0 = time.time()
        if args.chips == 4:
            four_chips()
        elif args.parity:
            schedule_parity()
            leaf_parity()
        else:
            one_chip()
        say(f"all phases passed in {time.time() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
