"""GWT — Gradient Wavelet Transform optimizer (the paper's Algorithm 1).

Per eligible 2-D (or stacked ``(L, m, n)``) weight ``W`` with transform axis
width ``n`` divisible by ``2^l``::

    [A_t, D_t]  = G_t · H^l                      (multi-level DHT)
    M^R, V^R    = host-optimizer moments on A_t  (memory: shapes of A_t)
    Ã_t         = M^R / (√V^R + ε)
    D̃_k        = D_k · upsample(1/(√V^R+ε))     (scale consistency)
    G̃_t        = [Ã_t, D̃_t] · Hᵀ               (inverse DHT — full rank!)
    G̃_t        = NormGrowthLimiter(G̃_t)         (γ = 1.01)
    W_{t+1}     = W_t − η_t · α · G̃_t            (η_t: bias-corrected lr)

Ineligible leaves (embeddings, lm-head, norms, 1-D) run plain Adam at the
base lr — the paper's module-wise strategy.  ``level=0`` reduces exactly to
the host optimizer (tested).

The per-leaf routing is declared as rules over the shared bucketed engine
(``repro.optim.engine``): same-shaped eligible leaves share one bucket,
whose state is stacked ``(L, …)``, and — on the fused path — go through
``kernels/gwt_adam/ops.fused_write_update`` in one call per bucket, one
kernel launch per leaf on the leaf's own buffers.

``impl`` selects the kernel backend: ``'pallas'`` routes eligible-leaf
updates through the fused TPU kernel (`repro.kernels.gwt_adam`),
``'interpret'`` validates that lowering on CPU, ``'jnp'`` uses the pure
butterfly, and ``'auto'`` (default) resolves per platform via
``repro.compat`` — launchers pass ``MeshContext.kernel_impl`` explicitly.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import haar, limiter
from repro.optim import engine, hosts as hosts_lib
from repro.optim.base import Optimizer, default_eligible, flatten_with_paths
from repro.optim.schedules import Schedule, constant


class _Mode:
    PLAIN = "plain"       # host-ineligible: plain Adam on the full tensor
    LAST = "gwt_last"     # DHT along axis -1
    FIRST = "gwt_first"   # DHT along axis -2 (transposed)


def _leaf_mode(path: str, leaf, level: int,
               eligible: Callable[[str, jax.Array], bool]) -> str:
    block = 1 << level
    if level == 0 or not eligible(path, leaf):
        return _Mode.PLAIN
    if leaf.ndim >= 2 and leaf.shape[-1] % block == 0:
        return _Mode.LAST
    if leaf.ndim >= 2 and leaf.shape[-2] % block == 0:
        return _Mode.FIRST
    return _Mode.PLAIN


def gwt(lr: Schedule | float,
        level: int = 2,
        alpha: float = 0.25,
        host: str = "adam",
        host_kwargs: Optional[dict] = None,
        gamma: float = limiter.DEFAULT_GAMMA,
        use_limiter: bool = True,
        eligible: Callable[[str, jax.Array], bool] = None,
        weight_decay: float = 0.0,
        state_dtype=jnp.float32,
        wavelet: str = "haar",
        impl: str = "auto",
        bucketed: bool = True,
        state_shardings=None,
        state_codec="f32") -> Optimizer:
    """Build the GWT optimizer. ``host`` in {'adam','adam_mini','muon'};
    ``wavelet`` in {'haar' (paper), 'db2' (beyond-paper Daubechies-4)};
    ``state_shardings`` forwards per-bucket NamedSharding hints (from
    ``distributed.sharding.gwt_state_shardings(...)['buckets']``) to the
    engine so init/update keep optimizer state on the mesh layout.
    ``state_codec`` ('f32'|'int8') selects the moment substrate
    (``repro.optim.codec``): int8 composes multiplicatively with the
    wavelet subspace — host moments live on the ``A_l`` band AND are
    stored blocked-quantized.  On the fused kernel path the requantize
    epilogue runs inside the kernel (``ops.fused_write_update_q8``)."""
    from repro.optim import codec as codec_lib
    if wavelet not in ("haar", "db2"):
        raise ValueError(f"unknown wavelet {wavelet!r}")
    impl = compat.resolve_kernel_impl(impl)
    cdc = codec_lib.get_codec(state_codec)
    quant = not cdc.passthrough
    fwd = haar.haar_forward if wavelet == "haar" else haar.db2_forward
    inv = haar.haar_inverse if wavelet == "haar" else haar.db2_inverse
    if isinstance(lr, (int, float)):
        lr = constant(lr)
    host_kwargs = dict(host_kwargs or {})
    host_kwargs.setdefault("state_dtype", state_dtype)
    h = hosts_lib.make_host(host, **host_kwargs)
    # Ineligible leaves always run Adam (paper's module-wise strategy), even
    # for a MUON host (matches MUON-for-2D + Adam-for-rest practice).
    plain = hosts_lib.adam(state_dtype=state_dtype) if host == "muon" else h
    elig = eligible or default_eligible
    use_fused = impl != "jnp" and h.name == "adam" and wavelet == "haar"
    # the fused kernel takes the Adam coefficients explicitly — mirror the
    # host's (hosts.adam defaults), so host_kwargs overrides are honored on
    # every backend, not just the jnp core
    adam_kw = {k: host_kwargs.get(k, d)
               for k, d in (("b1", 0.9), ("b2", 0.999), ("eps", 1e-6))}

    def _gwt_core(g, hstate, step):
        a, details = fwd(g, level)
        precond_a, dscale, lr_mult, hstate = h.update(a, hstate, step)
        if dscale is None:
            tilde_d = list(details)
        else:
            tilde_d = [d * haar.detail_scale_upsample(dscale, level, level - i)
                       for i, d in enumerate(details)]
        g_tilde = inv(precond_a, tilde_d)
        return g_tilde, lr_mult, hstate

    def _apply(p, delta, lr_t, lr_mult, eff_alpha):
        step_size = (lr_t * lr_mult * eff_alpha).astype(jnp.float32)
        new_p = p.astype(jnp.float32) - step_size * delta.astype(jnp.float32)
        if weight_decay:
            new_p = new_p - lr_t * weight_decay * p.astype(jnp.float32)
        return new_p.astype(p.dtype)

    # -- plain rule: host optimizer on the full tensor ----------------------
    def plain_update(g, p, state, step, leaf_id):
        delta, _, lr_mult, hstate = plain.update(g, state["host"], step)
        return _apply(p, delta, lr(step), lr_mult, 1.0), {"host": hstate}

    plain_rule = engine.LeafRule(
        kind=_Mode.PLAIN, init=lambda p: {"host": plain.init(p)},
        update=plain_update, slots={"host": plain.slots})

    # -- GWT rules: DHT along axis -1 (LAST) or -2 (FIRST) ------------------
    def make_gwt_rule(mode: str) -> engine.LeafRule:
        swap = mode == _Mode.FIRST

        def init(p):
            g_shape = tuple(p.shape) if not swap \
                else tuple(p.shape[:-2]) + (p.shape[-1], p.shape[-2])
            a_shape = g_shape[:-1] + (g_shape[-1] >> level,)
            return {"host": h.init(jax.ShapeDtypeStruct(a_shape, state_dtype)),
                    "prev_norm": jnp.zeros((), jnp.float32)}

        def update(g, p, state, step, leaf_id):
            if use_fused:
                # the fused launch on a one-leaf bucket; a coded state
                # arrives here decoded, so the f32 launch serves either
                # codec
                new_ps, out = vector_update(
                    [g], [p], jax.tree.map(lambda a: a[None], state), step,
                    jnp.reshape(leaf_id, (1,)))
                return new_ps[0], jax.tree.map(lambda a: a[0], out)
            gt = jnp.swapaxes(g, -1, -2) if swap else g
            g_tilde, lr_mult, hstate = _gwt_core(gt, state["host"], step)
            if swap:
                g_tilde = jnp.swapaxes(g_tilde, -1, -2)
            out = {"host": hstate, "prev_norm": state["prev_norm"]}
            if use_limiter:
                g_tilde, out["prev_norm"] = limiter.limit(
                    g_tilde, state["prev_norm"], gamma)
            return _apply(p, g_tilde, lr(step), lr_mult, alpha), out

        def vector_update(gs, ps, state, step, leaf_ids):
            # Fused-write megakernel: one launch per leaf of the bucket
            # performs DWT→Adam→inverse→limit→param-write on the leaf's
            # own g and p buffers, its slab of the stacked moments
            # updated in place — the limiter, bias-corrected step, and
            # weight decay all run in the kernel epilogue, so g̃ never
            # round-trips HBM.
            from repro.kernels.gwt_adam import ops as gwt_ops  # lazy
            new_ps, new_norm, hstate = gwt_ops.fused_write_update(
                gs, ps, state["host"], step, state["prev_norm"],
                lr_t=lr(step), alpha=alpha, weight_decay=weight_decay,
                gamma=gamma, use_limiter=use_limiter, level=level,
                swap=swap, impl=impl, **adam_kw)
            return new_ps, {"host": hstate, "prev_norm": new_norm}

        def vector_update_q8(gs, ps, state, step, leaf_ids, codec_key):
            # codec-native fused-write path: each leaf's launch
            # dequantizes its blocked moments, updates, requantizes, AND
            # applies limit+step+write — decoded f32 moments and g̃
            # never round-trip HBM.  Slot salts (m=0, v=1) match
            # codec.map_slots' sorted-key order, so this path and the
            # generic scan wrap produce the same rounding bits.
            from repro.kernels.gwt_adam import ops as gwt_ops  # lazy
            new_ps, new_norm, hstate = gwt_ops.fused_write_update_q8(
                gs, ps, state["host"], step, codec_key, leaf_ids,
                state["prev_norm"], lr_t=lr(step), alpha=alpha,
                weight_decay=weight_decay, gamma=gamma,
                use_limiter=use_limiter, level=level, block=cdc.block,
                swap=swap, impl=impl, **adam_kw)
            return new_ps, {"host": hstate, "prev_norm": new_norm}

        def taps(g_stk, p_stk, new_p_stk, old_st, new_st, step):
            # Observability taps (DESIGN.md §12), traced only inside
            # tapped_update (the TrainLoop runs it once per chunk, on the
            # log_every boundary step).  Band energies come from the
            # approx averaging chain alone: the DHT is orthonormal, so
            # the detail energy is Parseval's remainder ssq(g) - ssq(A_l)
            # — no detail bands materialized.  Limiter taps piggyback on
            # the norm pass the update already ran — ``prev_norm`` IS the
            # fused kernel's norm output — so the post-limit update norm
            # and clip rate cost no new passes.
            gt = jnp.swapaxes(g_stk, -1, -2) if swap else g_stk
            gt32 = gt.astype(jnp.float32)
            a = haar.haar_approx(gt32, level) if wavelet == "haar" \
                else fwd(gt32, level)[0]
            band_a = jnp.sum(a * a)
            out = {"band_a_ssq": band_a,
                   "band_d_ssq": jnp.sum(gt32 * gt32) - band_a}
            if use_limiter:
                old_pn = old_st["prev_norm"]
                new_pn = new_st["prev_norm"]
                clipped = limiter.clip_flags(old_pn, new_pn, gamma)
                nleaves = g_stk.shape[0]
                out["gnorm_ssq"] = jnp.sum(new_pn * new_pn)
                out["clip_count"] = jnp.sum(clipped.astype(jnp.float32))
                out["clip_rate"] = out["clip_count"] / jnp.float32(nleaves)
            return out

        vu, native = None, False
        if use_fused:
            vu, native = (vector_update_q8, True) if quant \
                else (vector_update, False)
        return engine.LeafRule(
            kind=mode, init=init, update=update, vector_update=vu,
            slots={"host": h.slots, "prev_norm": False},
            codec_native=native, taps=taps)

    gwt_last = make_gwt_rule(_Mode.LAST)
    gwt_first = make_gwt_rule(_Mode.FIRST)
    rules = {_Mode.PLAIN: plain_rule, _Mode.LAST: gwt_last,
             _Mode.FIRST: gwt_first}

    return engine.build(
        lambda path, leaf: rules[_leaf_mode(path, leaf, level, elig)],
        bucketed=bucketed, state_shardings=state_shardings,
        codec=cdc)


# ---------------------------------------------------------------------------
# Memory accounting (paper Table I / Table XI): optimizer-state bytes.
# ---------------------------------------------------------------------------

def _host_elements(shape, host: str) -> int:
    """State elements a host keeps for one tensor of ``shape``: Adam 2× (M+V),
    MUON 1× (momentum only), Adam-mini a full M plus one V per row."""
    size = 1
    for s in shape:
        size *= s
    if host == "muon":
        return size
    if host == "adam_mini":
        rows = size // shape[-1] if len(shape) >= 2 else 1
        return size + rows
    return 2 * size


def state_memory_bytes(params, level: int,
                       eligible: Callable[[str, jax.Array], bool] = None,
                       bytes_per_el: int = 2, host: str = "adam") -> Dict[str, int]:
    """Analytic optimizer-state memory: GWT leaves keep host states on the
    ``A_l`` band (``size/2^l`` elements), plain leaves host states on the
    full tensor.  Host multiplier: Adam 2× (M+V), MUON 1× (M only; plain
    leaves still run Adam), Adam-mini ``1× + 1/row`` (full M, per-row V).

    For *exact* per-optimizer accounting use
    ``repro.optim.engine.state_bytes(optimizer, params)``.
    """
    elig = eligible or default_eligible
    acc = {"gwt_bytes": 0, "plain_bytes": 0, "gwt_params": 0, "plain_params": 0}
    plain_host = "adam" if host == "muon" else host
    paths, leaves, _ = flatten_with_paths(params)
    for path, p in zip(paths, leaves):
        mode = _leaf_mode(path, p, level, elig)
        if mode == _Mode.PLAIN:
            acc["plain_bytes"] += _host_elements(tuple(p.shape),
                                                 plain_host) * bytes_per_el
            acc["plain_params"] += p.size
        else:
            width = (p.shape[-1] if mode == _Mode.LAST
                     else p.shape[-2]) >> level
            a_shape = (p.size // (width << level), width)
            acc["gwt_bytes"] += _host_elements(a_shape, host) * bytes_per_el
            acc["gwt_params"] += p.size
    acc["total_bytes"] = acc["gwt_bytes"] + acc["plain_bytes"]
    return acc
