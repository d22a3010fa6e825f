"""Decoder-only LM driver: parameter construction (init / logical-axes /
abstract via one Builder-driven code path), scan-over-periods stack,
train / prefill / decode steps.

The layer stack is ``lax.scan`` over *period groups* (DESIGN.md §7):
compile time and HLO size are O(1) in depth; the roofline analyzer
multiplies while-body costs by the trip count.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks, rope as rope_lib
from repro.models.layers import (Axes, Builder, cross_entropy, embed_apply,
                                 embed_init, logits_apply, rms_norm, softcap,
                                 wsc as _wsc)
from repro.runtime.context import MeshContext

# one line kept: compiled kernels record the line numbers of calls below


def _sqrt_group(n_periods: int) -> int:
    """Group size for two-level remat: the divisor of n closest to √n
    (1 = plain single-level scan; only used for deep stacks)."""
    if n_periods < 32:
        return 1
    best = 1
    for g in range(2, n_periods + 1):
        if n_periods % g == 0 and abs(g - math.isqrt(n_periods)) \
                < abs(best - math.isqrt(n_periods)):
            best = g
    return best if best > 1 else 1


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _stack(b: Builder, n: int, fn):
    """Stack ``n`` copies of ``fn(builder)`` along a leading 'layers' axis."""
    if b.mode == "init":
        keys = jax.random.split(b._next_key(), n)
        return jax.vmap(lambda k: fn(Builder("init", k, b.dtype)))(keys)
    one = fn(b)
    if b.mode == "axes":
        return jax.tree.map(lambda a: Axes(("layers",) + a.names), one)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), one)


def _build(cfg, mode: str, key=None):
    b = Builder(mode, key, jnp.dtype(cfg.dtype))
    p: Dict[str, Any] = {"embed": embed_init(b, cfg.vocab, cfg.d_model,
                                             cfg.tie_embeddings)}

    def period(bb: Builder):
        return {f"b{i}": blocks.block_init(bb, cfg, kind)
                for i, kind in enumerate(cfg.pattern)}

    if cfg.n_periods > 0:
        p["layers"] = _stack(b, cfg.n_periods, period)
    if cfg.rem_layers:
        p["rem"] = {f"b{i}": blocks.block_init(b, cfg, cfg.pattern[i])
                    for i in range(cfg.rem_layers)}
    p["final_norm"] = b.param((cfg.d_model,), (None,), init="zeros")
    return p


def init(cfg, key) -> Dict[str, Any]:
    return _build(cfg, "init", key)


def param_axes(cfg) -> Dict[str, Any]:
    return _build(cfg, "axes")


def abstract_params(cfg) -> Dict[str, Any]:
    return _build(cfg, "abstract")


def param_count(cfg) -> int:
    return sum(int(jnp.prod(jnp.asarray(l.shape)))
               for l in jax.tree.leaves(abstract_params(cfg)))


# ---------------------------------------------------------------------------
# KV / recurrent caches
# ---------------------------------------------------------------------------

def _cache_maker(mode: str, default_dtype):
    def mk(shape, axes, dtype):
        dtype = dtype or default_dtype
        if mode == "init":
            return jnp.zeros(shape, dtype)
        if mode == "axes":
            return Axes(tuple(axes))
        return jax.ShapeDtypeStruct(shape, dtype)
    return mk


def _build_cache(cfg, mode: str, B: int, max_len: int):
    mk = _cache_maker(mode, jnp.dtype(cfg.dtype))

    def period_cache():
        return {f"b{i}": blocks.block_cache(mk, cfg, kind, B, max_len)
                for i, kind in enumerate(cfg.pattern)}

    cache: Dict[str, Any] = {}
    if cfg.n_periods > 0:
        one = period_cache()
        if mode == "axes":
            cache["layers"] = jax.tree.map(
                lambda a: Axes(("layers",) + a.names), one)
        elif mode == "abstract":
            cache["layers"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((cfg.n_periods,) + s.shape,
                                               s.dtype), one)
        else:
            cache["layers"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape).copy(), one)
    if cfg.rem_layers:
        cache["rem"] = {f"b{i}": blocks.block_cache(mk, cfg, cfg.pattern[i],
                                                    B, max_len)
                        for i in range(cfg.rem_layers)}
    if mode == "axes":
        cache["pos"] = Axes(())
    elif mode == "abstract":
        cache["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


def init_cache(cfg, B: int, max_len: int):
    return _build_cache(cfg, "init", B, max_len)


def abstract_cache(cfg, B: int, max_len: int):
    return _build_cache(cfg, "abstract", B, max_len)


def cache_axes(cfg, B: int = 1, max_len: int = 2):
    return _build_cache(cfg, "axes", B, max_len)


def _build_paged_caches(cfg, mode: str, num_pages: int, page_size: int,
                        quant: Optional[str]):
    mk = _cache_maker(mode, jnp.dtype(cfg.dtype))

    def period_cache():
        return {f"b{i}": blocks.block_paged_cache(mk, cfg, kind, num_pages,
                                                  page_size, quant)
                for i, kind in enumerate(cfg.pattern)}

    cache: Dict[str, Any] = {}
    if cfg.n_periods > 0:
        one = period_cache()
        if mode == "abstract":
            cache["layers"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((cfg.n_periods,) + s.shape,
                                               s.dtype), one)
        else:
            cache["layers"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (cfg.n_periods,) + x.shape).copy(), one)
    if cfg.rem_layers:
        cache["rem"] = {f"b{i}": blocks.block_paged_cache(
            mk, cfg, cfg.pattern[i], num_pages, page_size, quant)
            for i in range(cfg.rem_layers)}
    return cache


def init_paged_caches(cfg, num_pages: int, page_size: int,
                      kv_quant: Optional[str] = None):
    """Shared serving arenas: one ``(num_pages, page_size, KV, hd)`` pool
    per K and V per block, stacked over scan periods exactly like the
    dense decode caches so the scan-carry path is reused unchanged.
    ``kv_quant='int8'`` swaps each pool for ``{"q": int8, "scale": f32}``
    (repro.serve.kv encodings).  No ``pos``/``page_table`` entries — the
    engine owns those and passes them per call."""
    return _build_paged_caches(cfg, "init", num_pages, page_size, kv_quant)


def abstract_paged_caches(cfg, num_pages: int, page_size: int,
                          kv_quant: Optional[str] = None):
    return _build_paged_caches(cfg, "abstract", num_pages, page_size,
                               kv_quant)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg, params, tokens: jax.Array, *, mode: str = "train",
            caches=None, mrope_positions=None, ctx: MeshContext = None
            ) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (logits, new_caches, aux_loss).  ``ctx`` pins the mesh and
    kernel backend explicitly; ``None`` adopts the ambient mesh (CPU unit
    tests).

    Serving (paged) variant: when ``caches`` carries a ``"page_table"``
    entry, ``caches["layers"]`` holds shared page pools (repro.serve.kv),
    ``caches["pos"]`` is a per-slot length VECTOR, and two extra modes
    apply — ``decode`` scatters one token per slot into its pages, and
    ``chunk_prefill`` pages in one slot's (1, C) prompt chunk at global
    positions ``pos[0]..pos[0]+C-1`` and returns the FULL chunk logits
    (the engine needs the prompt-final position, which may land mid-chunk
    when the last chunk is padded).
    """
    if ctx is None:
        ctx = MeshContext.ambient()
    B, S = tokens.shape
    # SP residuals (see constrain_batch): measured a net LOSS on the 256-chip
    # dry-run (deepseek collective 34.8s -> 187s from involuntary resharding;
    # EXPERIMENTS.md §Perf hypothesis log) — opt-in only.
    seq_par = mode == "train" and os.environ.get("REPRO_SEQ_PARALLEL") == "1"
    x = constrain_batch(embed_apply(params["embed"], tokens, cfg.d_model),
                        seq=seq_par, ctx=ctx)
    pos = caches["pos"] if caches is not None else None
    page_table = caches.get("page_table") if caches is not None else None

    if page_table is not None:
        if mode not in ("decode", "chunk_prefill"):
            raise ValueError(f"paged caches serve decode/chunk_prefill "
                             f"only, got mode={mode!r}")
        # per-slot positions: each slot rotates at its OWN fill level
        positions = pos[:, None] + (jnp.arange(S)[None, :]
                                    if mode == "chunk_prefill" else 0)
    elif mode == "decode":
        positions = jnp.broadcast_to(pos, (B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.mrope_sections:
        if mrope_positions is None:
            mrope_positions = jnp.broadcast_to(positions, (3, B, S))
        cos, sin = rope_lib.mrope_angles(mrope_positions, cfg.head_dim,
                                         cfg.rope_theta, cfg.mrope_sections)
    else:
        cos, sin = rope_lib.rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}

    def apply_period(x, pparams, pcache, pattern):
        new_pc = {}
        aux_sum = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pattern):
            c = pcache[f"b{i}"] if pcache is not None else None

            def one_block(bp, xx, cc, kind=kind):
                return blocks.block_apply(bp, cfg, kind, xx, cos, sin,
                                          mode=mode, cache=cc, pos=pos,
                                          page_table=page_table)
            if cfg.remat and mode == "train" and len(pattern) > 1:
                # layer-level nested remat: the period-level backward
                # otherwise keeps ALL blocks' recomputed intermediates live
                # (measured 28 GiB on Jamba's 8-layer period w/ 4 MoE blocks)
                one_block = jax.checkpoint(one_block)
            x, nc, aux = one_block(pparams[f"b{i}"], x, c)
            x = constrain_batch(x, seq=seq_par, ctx=ctx)
            new_pc[f"b{i}"] = nc
            aux_sum = aux_sum + aux
        return x, new_pc, aux_sum

    if cfg.n_periods > 0 and mode in ("decode", "chunk_prefill") \
            and caches is not None:
        # Decode: the cache rides the scan CARRY (in-place donation-friendly
        # aliasing); as xs/ys the stacked cache cannot alias through the
        # while loop — measured +cache-size temp (16 GiB on deepseek
        # decode_32k; EXPERIMENTS.md §Perf).
        def dec_body(carry, xs):
            x, aux, cache_st = carry
            pparams, idx = xs
            pcache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0,
                                                       keepdims=False),
                cache_st)
            x, new_pc, aux_p = apply_period(x, pparams, pcache, cfg.pattern)
            cache_st = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), idx, 0), cache_st, new_pc)
            return (x, aux + aux_p, cache_st), None

        (x, aux_total, new_stacked), _ = jax.lax.scan(
            dec_body, (x, aux_total, caches["layers"]),
            (params["layers"], jnp.arange(cfg.n_periods)))
        new_caches["layers"] = new_stacked
    elif cfg.n_periods > 0:
        def body(carry, xs):
            x, aux = carry
            pparams, pcache = xs
            x, new_pc, aux_p = apply_period(x, pparams, pcache, cfg.pattern)
            return (x, aux + aux_p), new_pc

        body_fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
        pcaches = caches["layers"] if caches is not None else None
        xs = (params["layers"], pcaches)
        group = _sqrt_group(cfg.n_periods) if (cfg.remat and mode == "train") \
            else 1
        if group > 1:
            # two-level (√n) remat: only n/G outer boundaries stay live
            # through the backward pass; inner saves are G-bounded transients.
            def outer_body(carry, xs_g):
                # the inner body is checkpointed too: otherwise the inner
                # scan's AD saves ALL group members' layer intermediates
                # during the outer-group backward (measured 16 GiB on
                # qwen2-vl's group of 8 × ~2 GiB/layer).
                return jax.lax.scan(jax.checkpoint(body), carry, xs_g)

            outer_fn = jax.checkpoint(outer_body)
            xs_g = jax.tree.map(
                lambda a: a.reshape((cfg.n_periods // group, group)
                                    + a.shape[1:]), xs)
            (x, aux_total), stacked_pc = jax.lax.scan(
                outer_fn, (x, aux_total), xs_g)
            stacked_pc = jax.tree.map(
                lambda a: a.reshape((cfg.n_periods,) + a.shape[2:]),
                stacked_pc)
        else:
            (x, aux_total), stacked_pc = jax.lax.scan(body_fn, (x, aux_total),
                                                      xs)
        new_caches["layers"] = stacked_pc

    if cfg.rem_layers:
        rc = caches["rem"] if caches is not None else None
        x, new_rc, aux_r = apply_period(x, params["rem"], rc,
                                        cfg.pattern[:cfg.rem_layers])
        aux_total = aux_total + aux_r
        new_caches["rem"] = new_rc

    if mode == "prefill":
        x = x[:, -1:]  # only the last position's logits are consumed —
        # full-sequence logits at 32k×(unsharded 256k vocab) cost 33 GiB/dev
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_apply(params["embed"], x)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    if caches is not None:
        inc = 1 if mode == "decode" else (S if mode == "chunk_prefill" else 0)
        new_caches["pos"] = pos + inc
        if page_table is not None:
            new_caches["page_table"] = page_table
        return logits, new_caches, aux_total
    if mode == "prefill":
        new_caches["pos"] = jnp.asarray(S, jnp.int32)
        return logits, new_caches, aux_total
    return logits, None, aux_total


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch, ctx: MeshContext = None) -> jax.Array:
    logits, _, aux = forward(cfg, params, batch["tokens"], mode="train",
                             mrope_positions=batch.get("mrope_positions"),
                             ctx=ctx)
    return cross_entropy(logits, batch["labels"]) + cfg.router_aux_coef * aux


def constrain_batch(x, bdim: int = 0, seq: bool = False, seq_dim: int = 1,
                    ctx: MeshContext = None):
    """Pin the batch dim of an activation to the DP axes (no-op if absent).

    ``seq=True`` additionally shards the sequence dim over 'model'
    (Megatron-style sequence parallelism): applied at *period boundaries*
    so the scan-carry residuals — the dominant live-range at depth 95 —
    are 16× smaller; XLA re-gathers at the next block's matmuls, turning
    the TP all-reduce into all-gather + reduce-scatter (same wire bytes).
    """
    if ctx is None:
        ctx = MeshContext.ambient()
    if not ctx.axis_names:
        return x
    dp = ctx.dp_axes(x.shape[bdim])
    spec = [None] * x.ndim
    if dp is not None:
        spec[bdim] = dp
    if seq and ctx.has_axis("model") \
            and x.shape[seq_dim] % ctx.axis_size("model") == 0:
        spec[seq_dim] = "model"
    if all(s is None for s in spec):
        return x
    return _wsc(x, *spec, ctx=ctx)


def microbatch_split(batch: Dict[str, jax.Array], accum: int,
                     ctx: MeshContext = None) -> Dict[str, jax.Array]:
    """Split the global batch into ``accum`` microbatches with a
    *shard-preserving* layout: ``(B,) -> (mb, accum) -> swap -> (accum, mb)``
    maps microbatch ``a``, row ``m`` to global row ``m·accum + a`` — each
    device keeps exactly its own rows, so the split inserts ZERO collectives
    (a dynamic_slice along the data-sharded dim would gather the batch —
    measured 16× per-device inflation; see EXPERIMENTS.md §Dry-run notes).
    """
    out = {}
    for k, v in batch.items():
        if k == "mrope_positions":                   # (3, B, S): batch dim 1
            mb = v.shape[1] // accum
            r = v.reshape(3, mb, accum, v.shape[2]).transpose(2, 0, 1, 3)
            out[k] = _wsc(r, None, None, "data", None, ctx=ctx)  # (accum, 3, mb, S)
        else:                                        # (B, ...)
            mb = v.shape[0] // accum
            r = v.reshape(mb, accum, *v.shape[1:]).swapaxes(0, 1)
            out[k] = _wsc(r, None, "data", *([None] * (v.ndim - 1)), ctx=ctx)
    return out


def _contiguous_microbatches(batch: Dict[str, jax.Array], accum: int
                             ) -> Dict[str, jax.Array]:
    """Split a (device-local) batch into ``accum`` CONTIGUOUS row blocks:
    ``(B,) -> (accum, B/accum)``.  Inside ``shard_map`` the data is already
    local, so — unlike :func:`microbatch_split`'s strided shard-preserving
    layout — contiguity costs nothing, and it is what makes the logical
    shard grid independent of the device count: shard ``s`` always holds
    global rows ``[s·B/S, (s+1)·B/S)`` whether ``s`` indexes a device, an
    accumulation step, or a mix."""
    out = {}
    for k, v in batch.items():
        if k == "mrope_positions":                   # (3, B, S): batch dim 1
            if v.shape[1] % accum:
                raise ValueError(f"local batch {v.shape[1]} not divisible "
                                 f"by accum_steps={accum}")
            mb = v.shape[1] // accum
            out[k] = v.reshape(3, accum, mb, v.shape[2]).transpose(1, 0, 2, 3)
        else:                                        # (B, ...)
            if v.shape[0] % accum:
                raise ValueError(f"local batch {v.shape[0]} not divisible "
                                 f"by accum_steps={accum}")
            mb = v.shape[0] // accum
            out[k] = v.reshape(accum, mb, *v.shape[1:])
    return out


def make_sharded_train_step(cfg, optimizer, loss, *, ctx: MeshContext,
                            dp_reduce, accum_steps: int = 1, shardings=None,
                            donate: bool = False):
    """Mesh-aware train step: the data-parallel gradient reduction runs
    *manually* — per-device gradients inside ``shard_map`` over the DP
    axes, reduced by :func:`repro.distributed.compression
    .compressed_psum_mean` (exact f32 ``psum`` when
    ``dp_reduce.detail_dtype is None``; wavelet-compressed otherwise).
    Everything outside the shard_map (optimizer update, constraint
    pinning) stays under GSPMD; a 'model' axis, if present, is left to
    GSPMD *inside* too (shard_map auto axes), so TP composes.

    Numerics contract: the gradient is the mean over ``dp_size ×
    accum_steps`` contiguous logical shards, per-shard grads summed
    shard-order-sequentially (the accumulation scan within a device, the
    device-order ``psum`` across).  Because the CPU/TPU all-reduce sums in
    device order, a run on D devices with accum A is *bitwise* equal to a
    run on 1 device with accum D·A when A == 1 — the topology-equivalence
    tier in tests/test_sharded_train.py pins exactly that.

    ``shardings`` (a :class:`repro.distributed.sharding.StepShardings`)
    pins inputs and outputs: batch to its DP layout, params/opt_state to
    the FSDP layout (or replicated).  ``donate=True`` jits with
    ``donate_argnums=(0, 1)`` exactly like the auto-sharded step.

    Pure-DP meshes only: leaving a TP 'model' axis to GSPMD as a
    shard_map *auto* axis miscompiles on the pinned jax/XLA 0.4.x (hard
    ``IsManualSubgroup`` check abort in hlo_sharding_util once the real
    model graph is inside) — rejected here with a real error instead.
    TP meshes keep the auto-sharded step (``dp_reduce=None``).
    """
    from repro.distributed import compression
    if isinstance(dp_reduce, str):
        dp_reduce = compression.DPReduceSpec.parse(dp_reduce)
    if dp_reduce is None:
        raise ValueError("dp_reduce None/'none' means the auto-sharded "
                         "step — call make_train_step, which routes here "
                         "only for a real DPReduceSpec")
    if ctx is None or ctx.mesh is None or not ctx.dp_axis_names:
        raise ValueError("make_sharded_train_step needs a MeshContext with "
                         "a 'data' axis (use make_mesh_context)")
    if ctx.auto_axis_names:
        raise ValueError(
            f"dp_reduce needs a pure-DP mesh (('data',) or ('pod', "
            f"'data')), got axes {ctx.axis_names}: leaving "
            f"{ctx.auto_axis_names} to GSPMD inside shard_map trips an "
            f"XLA manual-subgroup check on the pinned jax 0.4.x — use "
            f"dp_reduce=None (auto-sharded step) for TP meshes")
    dp_axes = ctx.dp_axis_names
    axis = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    dp_size = ctx.dp_size
    # error feedback (DESIGN.md §3 / --dp-error-feedback): each device
    # keeps the residue its detail-band quantization discarded and adds it
    # back next step.  The residue is per-device state, carried OUTSIDE the
    # optimizer as ``opt_state = {"opt": <real>, "dp_ef": <residue>}``
    # (leaves ``(dp_size, *param_shape)`` f32, sharded over the DP axis) —
    # see ``compression.ef_init`` / ``ef_state_shardings``.
    ef_on = bool(getattr(dp_reduce, "error_feedback", False)) \
        and not dp_reduce.exact
    # inside the manual region every sharding constraint must be a no-op:
    # hand the forward a mesh-less context instead of letting wsc degrade
    inner_ctx = MeshContext(mesh=None, kernel_impl=ctx.kernel_impl)
    # wavelet split of the wire reduction follows the session's kernel
    # backend: pallas/interpret fuses the detail quantize into the DWT
    # launch (compression.reduce_terms impl kwarg)
    from repro import compat
    wire_impl = compat.resolve_kernel_impl(ctx.kernel_impl or "auto")

    def batch_spec(k: str, v) -> jax.sharding.PartitionSpec:
        bdim = 1 if k == "mrope_positions" else 0
        spec = [None] * v.ndim
        spec[bdim] = axis
        return jax.sharding.PartitionSpec(*spec)

    def local_grads(params, lbatch, ef=None):
        micro = _contiguous_microbatches(lbatch, accum_steps)

        def body(carry, mb):
            gsum, lsum = carry
            l, g = jax.value_and_grad(
                lambda p: loss(cfg, p, mb, ctx=inner_ctx))(params)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
            return (gsum, lsum + l), None

        # named scopes (DESIGN.md §12) label the compiled ops; no op changes
        with jax.named_scope("train.fwd_bwd"):
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros(())), micro)
        with jax.named_scope("train.dp_reduce"):
            gmean = jax.tree.map(lambda a: a / accum_steps, gsum)
            lmean = jax.lax.psum(lsum / accum_steps, axis) / dp_size
            if not ef_on:
                grads = jax.tree.map(
                    functools.partial(compression.compressed_psum_mean,
                                      axis_name=axis, level=dp_reduce.level,
                                      detail_dtype=dp_reduce.detail_dtype,
                                      impl=wire_impl), gmean)
                return grads, lmean
            g_leaves, treedef = jax.tree.flatten(gmean)
            e_leaves = treedef.flatten_up_to(ef)
            pairs = [compression.compressed_psum_mean_ef(
                g, e[0], axis_name=axis, level=dp_reduce.level,
                detail_dtype=dp_reduce.detail_dtype, impl=wire_impl)
                for g, e in zip(g_leaves, e_leaves)]
            grads = jax.tree_util.tree_unflatten(treedef,
                                                 [p[0] for p in pairs])
            new_ef = jax.tree_util.tree_unflatten(
                treedef, [p[1][None] for p in pairs])
            return grads, lmean, new_ef

    def train_step(params, opt_state, batch):
        ef_state = None
        if ef_on:
            if not (isinstance(opt_state, dict)
                    and set(opt_state) == {"opt", "dp_ef"}):
                raise ValueError(
                    "error-feedback train step expects opt_state = "
                    "{'opt': <optimizer state>, 'dp_ef': "
                    "compression.ef_init(params, dp_size)}")
            ef_state, opt_state = opt_state["dp_ef"], opt_state["opt"]
        if shardings is not None:
            params = jax.tree.map(jax.lax.with_sharding_constraint,
                                  params, shardings.params)
            if shardings.opt is not None:
                opt_state = jax.tree.map(jax.lax.with_sharding_constraint,
                                         opt_state, shardings.opt)
            batch = {k: jax.lax.with_sharding_constraint(v,
                                                         shardings.batch[k])
                     for k, v in batch.items()}
        from repro import compat
        P = jax.sharding.PartitionSpec
        param_specs = jax.tree.map(lambda _: P(), params)
        in_specs = (param_specs,
                    {k: batch_spec(k, v) for k, v in batch.items()})
        out_specs = (param_specs, P())
        args = (params, batch)
        if ef_on:
            ef_specs = jax.tree.map(
                lambda e: P(axis, *([None] * (e.ndim - 1))), ef_state)
            in_specs += (ef_specs,)
            out_specs += (ef_specs,)
            args += (ef_state,)
        fn = compat.shard_map(local_grads, ctx.mesh,
                              in_specs=in_specs, out_specs=out_specs)
        if ef_on:
            grads, loss_mean, new_ef = fn(*args)
        else:
            grads, loss_mean = fn(*args)
        with jax.named_scope("train.fwd_bwd"):
            grads = jax.tree.map(lambda g: g.astype(cfg.dtype), grads)
        if shardings is not None:
            # pin the (replicated) reduced grads to the parameter layout so
            # the update partitions like the state it writes
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, shardings.params)
        with jax.named_scope("train.update"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        if shardings is not None:
            new_params = jax.tree.map(jax.lax.with_sharding_constraint,
                                      new_params, shardings.params)
            if shardings.opt is not None:
                new_opt = jax.tree.map(jax.lax.with_sharding_constraint,
                                       new_opt, shardings.opt)
        if ef_on:
            new_opt = {"opt": new_opt, "dp_ef": new_ef}
        return new_params, new_opt, {"loss": loss_mean}

    if donate:
        return jax.jit(train_step, donate_argnums=(0, 1))
    return train_step


def make_train_step(cfg, optimizer, accum_steps: int = 1,
                    grad_shardings=None, ctx: MeshContext = None,
                    donate: bool = False, dp_reduce=None, shardings=None,
                    loss=None, taps: bool = False):
    """Gradient-accumulated train step: ``batch`` is the GLOBAL batch; a
    shard-preserving reshape feeds a microbatch ``lax.scan``.

    ``dp_reduce`` (a ``repro.distributed.compression.DPReduceSpec`` or
    ``'exact'`` / ``'compressed'``) switches to the mesh-aware sharded
    path — see :func:`make_sharded_train_step`; ``shardings`` rides along
    to pin params/opt_state/batch placement.

    ``grad_shardings`` (optional NamedSharding tree like params): pins each
    microbatch's bf16 gradients to the parameter sharding *before* the f32
    accumulation — the cross-data reduce-scatter then moves bf16, not f32
    (half the dominant DP wire bytes), and the f32 accumulator itself is
    fully sharded.

    ``donate=True`` returns the step already jitted with
    ``donate_argnums=(0, 1)``: XLA aliases the ``(params, opt_state)``
    input buffers into the outputs, so params + optimizer state stay
    single-buffered across steps instead of double-buffered (~2× peak
    state memory without it).  The caller must rebind, not reuse, the
    arrays it passes in.  ``donate=False`` keeps the historical behaviour
    of returning the raw traceable function.

    ``taps=True`` routes the update through the optimizer's
    ``tapped_update`` channel (``repro.optim.engine``; DESIGN.md §12) and
    adds the per-bucket observability scalars to the metrics dict as
    ``metrics["taps"]`` — same trace, no extra launches.  Ignored (with
    tap-free metrics) when the optimizer exposes no tapped channel; not
    threaded through the sharded ``dp_reduce`` path.
    """
    loss = loss_fn if loss is None else loss  # `loss=`: swap the objective
    if isinstance(dp_reduce, str):
        from repro.distributed.compression import DPReduceSpec
        dp_reduce = DPReduceSpec.parse(dp_reduce)  # 'none' -> None
    if dp_reduce is not None:
        if taps:
            raise ValueError("taps=True is not supported on the sharded "
                             "dp_reduce path — run taps-off or drop "
                             "dp_reduce")
        return make_sharded_train_step(cfg, optimizer, loss, ctx=ctx,
                                       dp_reduce=dp_reduce,
                                       accum_steps=accum_steps,
                                       shardings=shardings, donate=donate)
    taps = taps and getattr(optimizer, "tapped_update", None) is not None

    def train_step(params, opt_state, batch):
        # resolve the ambient fallback at trace time, not build time: the
        # launcher may build the step outside the mesh context and jit it in
        c = ctx if ctx is not None else MeshContext.ambient()

        def accum_body(carry, mb):
            gsum, lsum = carry
            l, g = jax.value_and_grad(
                lambda p: loss(cfg, p, mb, ctx=c))(params)
            if grad_shardings is not None:
                g = jax.tree.map(jax.lax.with_sharding_constraint, g,
                                 grad_shardings)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + l), None

        # named scopes (DESIGN.md §12) label the compiled ops for the
        # profiler's trace; they change no op
        with jax.named_scope("train.fwd_bwd"):
            micro = microbatch_split(batch, accum_steps, ctx=c)
            if accum_steps == 1:
                # one microbatch: the f32 accumulator would hold g + 0
                # (exact) at 4 bytes per parameter — 5 GB of a 16 GB chip
                # at llama-1b
                lsum, grads = jax.value_and_grad(
                    lambda p: loss(cfg, p,
                                   jax.tree.map(lambda x: x[0], micro),
                                   ctx=c))(params)
                if grad_shardings is not None:
                    grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                         grads, grad_shardings)
                grads = jax.tree.map(lambda g: g.astype(cfg.dtype), grads)
            else:
                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params)
                if grad_shardings is not None:
                    g0 = jax.tree.map(jax.lax.with_sharding_constraint, g0,
                                      grad_shardings)
                (gsum, lsum), _ = jax.lax.scan(accum_body,
                                               (g0, jnp.zeros(())), micro)
                grads = jax.tree.map(
                    lambda g: (g / accum_steps).astype(cfg.dtype), gsum)
        with jax.named_scope("train.update"):
            if taps:
                new_params, new_opt, tp = optimizer.tapped_update(
                    grads, opt_state, params)
                return new_params, new_opt, {"loss": lsum / accum_steps,
                                             "taps": tp}
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": lsum / accum_steps}

    if donate:
        return jax.jit(train_step, donate_argnums=(0, 1))
    return train_step


def make_prefill_step(cfg, ctx: MeshContext = None):
    def prefill_step(params, batch):
        logits, caches, _ = forward(cfg, params, batch["tokens"],
                                    mode="prefill",
                                    mrope_positions=batch.get("mrope_positions"),
                                    ctx=ctx)
        return logits[:, -1], caches
    return prefill_step


def make_decode_step(cfg, ctx: MeshContext = None):
    def decode_step(params, caches, batch):
        logits, new_caches, _ = forward(
            cfg, params, batch["tokens"], mode="decode", caches=caches,
            mrope_positions=batch.get("mrope_positions"), ctx=ctx)
        return logits[:, -1], new_caches
    return decode_step


def make_paged_decode_step(cfg, ctx: MeshContext = None):
    """One serving decode tick: ``tokens (num_slots, 1)`` — every slot,
    every tick (fixed shape for jit; inactive slots carry trash-page
    tables and get masked out by ``kv_valid``).  Returns
    ``(last-position logits (num_slots, V), new_pools)`` — the pools are
    the only mutated state, so the engine jits this with
    ``donate_argnums=(1,)`` and rebinds."""
    def step(params, pools, page_table, lens, tokens):
        caches = dict(pools)
        caches["pos"] = lens
        caches["page_table"] = page_table
        logits, new_caches, _ = forward(cfg, params, tokens, mode="decode",
                                        caches=caches, ctx=ctx)
        return logits[:, -1], {k: new_caches[k] for k in pools}
    return step


def make_chunk_prefill_step(cfg, ctx: MeshContext = None):
    """Page in ONE slot's next prompt chunk: ``tokens (1, C)`` at global
    positions ``filled[0]..filled[0]+C-1`` (``page_table`` is that slot's
    single row, ``(1, max_pages)``).  Returns the full ``(1, C, V)`` chunk
    logits plus the updated pools — same donation contract as the decode
    step."""
    def step(params, pools, page_table, filled, tokens):
        caches = dict(pools)
        caches["pos"] = filled
        caches["page_table"] = page_table
        logits, new_caches, _ = forward(cfg, params, tokens,
                                        mode="chunk_prefill", caches=caches,
                                        ctx=ctx)
        return logits, {k: new_caches[k] for k in pools}
    return step
