"""jit'd wrapper for the fused GWT-Adam kernel, with backend dispatch and
leading-batch handling: any leading dims — stacked ``(L, m, n)`` scan
parameters *and* the optimizer engine's shape buckets — are flattened and
vmapped, so one call serves a whole bucket (one launch per bucket, not per
leaf).

``fused_update`` is the entry point used by ``repro.core.gwt`` when
``impl='pallas'`` (the GWT rules' ``vector_update``: the engine hands it
the full ``(L, m, n)`` stack in a single call).  Semantics match
``repro.core.gwt._gwt_core`` exactly (tested leaf-by-leaf); the
norm-growth limiter stays in the caller (vmapped per leaf).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.kernels.gwt_adam import kernel, ref


def _tile_fn(impl: str, level: int, b1: float, b2: float, eps: float):
    impl = compat.resolve_kernel_impl(impl)
    if impl == "pallas":
        return functools.partial(kernel.gwt_adam_tile, level=level, b1=b1,
                                 b2=b2, eps=eps)
    if impl == "interpret":
        return functools.partial(kernel.gwt_adam_tile, level=level, b1=b1,
                                 b2=b2, eps=eps, interpret=True)
    return functools.partial(ref.gwt_adam_tile, level=level, b1=b1, b2=b2,
                             eps=eps)


def fused_update(g: jax.Array, state: dict, step: jax.Array, *,
                 level: int, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-6, impl: str = "auto"
                 ) -> Tuple[jax.Array, jax.Array, dict]:
    """Returns ``(g_tilde, lr_mult, new_state)`` — drop-in for the jnp core.

    ``impl``: auto|pallas|interpret|jnp — 'auto' resolves per platform via
    repro.compat (launchers pass MeshContext.kernel_impl explicitly).
    Resolution happens OUTSIDE the jitted body: 'auto' as a static jit arg
    would freeze the REPRO_KERNEL_IMPL env read into the trace cache."""
    impl = compat.resolve_kernel_impl(impl)
    return _fused_update(g, state, step, level=level, b1=b1, b2=b2, eps=eps,
                         impl=impl)


@functools.partial(jax.jit, static_argnames=("level", "b1", "b2", "eps", "impl"))
def _fused_update(g, state, step, *, level, b1, b2, eps, impl):
    _count_schedule(g)
    fn = _tile_fn(impl, level, b1, b2, eps)
    if g.ndim > 2:  # stacked scan leaves (L, m, n)
        lead = g.shape[:-2]
        g2 = g.reshape((-1,) + g.shape[-2:])
        m2 = state["m"].reshape((-1,) + state["m"].shape[-2:])
        v2 = state["v"].reshape((-1,) + state["v"].shape[-2:])
        gt, m, v, _ = jax.vmap(fn)(g2, m2, v2)
        gt = gt.reshape(lead + gt.shape[-2:])
        m = m.reshape(lead + m.shape[-2:])
        v = v.reshape(lead + v.shape[-2:])
    else:
        gt, m, v, _ = fn(g, state["m"], state["v"])
    t = step.astype(jnp.float32) + 1.0
    lr_mult = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return gt, lr_mult, {"m": m, "v": v}


# ---------------------------------------------------------------------------
# Fused-write (megakernel) path: limiter + bias-corrected apply + weight
# decay + parameter write move INTO the launch — one kernel call per bucket
# consumes (g, p, m, v, prev_norm) and emits (new_p, new_m, new_v,
# new_norm); g̃ never round-trips HBM.
# ---------------------------------------------------------------------------

def _step_scalars(step, lr_t, alpha, weight_decay, b1, b2):
    """Bias-corrected step size and weight-decay coefficient, computed
    outside the kernel exactly as ``core.gwt._apply`` does (term order
    matters for bitwise parity with the staged path)."""
    t = step.astype(jnp.float32) + 1.0
    lr_mult = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    step_size = (lr_t * lr_mult * alpha).astype(jnp.float32)
    wd_coef = jnp.asarray(lr_t * weight_decay, jnp.float32)
    return step_size, wd_coef


def _on_each_device(call):
    """Under an ambient mesh, run the kernel whole on every device.

    GSPMD cannot partition a Mosaic kernel, so a bucket whose state is
    sharded (FSDP) would not compile.  A ``shard_map`` with replicated
    specs gathers the bucket's operands, runs the same launch on each
    device and hands back replicated results, which the caller's sharding
    constraints slice again.  The update stays exactly the single-device
    one; what it costs is the gathers and the repeated work."""
    mesh = compat.get_abstract_mesh()
    if mesh is None:
        return call

    def run(*args):
        specs = tuple(P() for _ in args)
        return compat.shard_map(call, mesh, in_specs=specs,
                                out_specs=P())(*args)
    return run


def _count_schedule(g: jax.Array) -> None:
    """At trace time, one sample of the counter ``gwt.kernel.one_pass``
    (category ``gwt``) for this bucket: whether its gradient takes the
    one-pass lane shuffles (bf16, ``kernel._core_phases``) or the
    three-pass ones (any other dtype), in buckets and in gradient
    elements.  The samples of a traced step sum to its split."""
    one = g.dtype == jnp.bfloat16
    obs.get().counter("gwt.kernel.one_pass", cat="gwt",
                      buckets_one_pass=int(one),
                      elements_one_pass=g.size if one else 0,
                      buckets_three_pass=int(not one),
                      elements_three_pass=0 if one else g.size)


def _norm_shapes(g):
    """Normalize a leaf stack to ``(L, rows, n)``: 2-D single leaves gain a
    unit leaf axis; 3-D+ leaves merge extra dims into the row axis (the
    transform is per-row and the limiter norm per-leaf, so row-merging is
    exact — and for q8 it preserves the codec's row-major flat order)."""
    lead2 = g.ndim == 2
    if lead2:
        g = g[None]
    shape = g.shape
    if g.ndim > 3:
        g = g.reshape(g.shape[0], -1, g.shape[-1])
    return g, shape, lead2


def fused_write_update(g: jax.Array, p: jax.Array, state: dict,
                       step: jax.Array, prev_norm: jax.Array, *,
                       lr_t, alpha: float, weight_decay: float,
                       gamma: float, use_limiter: bool, level: int,
                       b1: float = 0.9, b2: float = 0.999,
                       eps: float = 1e-6, impl: str = "auto"):
    """One launch per bucket: DWT→Adam→inverse→limit→param-write.

    Returns ``(new_p, new_norm, new_state)``.  ``impl='jnp'`` routes to the
    tiled ``ref.gwt_adam_fused`` oracle with the SAME row-block choice as
    the kernel, so interpret/pallas bitwise-match it.

    The launch runs under the named scope ``gwt.kernel`` (DESIGN.md §12),
    opened outside the jit: the Mosaic custom call takes its HLO name from
    the innermost name-stack entry, which stays ``_fused_write_update``."""
    impl = compat.resolve_kernel_impl(impl)
    with jax.named_scope("gwt.kernel"):
        return _fused_write_update(
            g, p, state["m"], state["v"], prev_norm, step, lr_t,
            alpha=alpha, weight_decay=weight_decay, gamma=gamma,
            use_limiter=use_limiter, level=level, b1=b1, b2=b2, eps=eps,
            impl=impl)


@functools.partial(jax.jit, static_argnames=(
    "alpha", "weight_decay", "gamma", "use_limiter", "level",
    "b1", "b2", "eps", "impl"))
def _fused_write_update(g, p, m_st, v_st, prev_norm, step, lr_t, *,
                        alpha, weight_decay, gamma, use_limiter, level,
                        b1, b2, eps, impl):
    from repro.kernels.gwt_adam import kernel, ref  # noqa: F811 — local
    _count_schedule(g)
    step_size, wd_coef = _step_scalars(step, lr_t, alpha, weight_decay,
                                       b1, b2)
    with jax.named_scope("optim.pack"):
        g3, gshape, lead2 = _norm_shapes(g)
        p3, _, _ = _norm_shapes(p)
        m3, _, _ = _norm_shapes(m_st)
        v3, _, _ = _norm_shapes(v_st)
        pn = prev_norm.reshape(g3.shape[0])
    L, mm, nn = g3.shape
    kw = dict(level=level, gamma=gamma, use_limiter=use_limiter,
              weight_decay=weight_decay != 0, b1=b1, b2=b2, eps=eps)
    if impl in ("pallas", "interpret"):
        new_p, m, v, new_norm = _on_each_device(functools.partial(
            kernel.gwt_adam_tile_fused, interpret=impl == "interpret",
            **kw))(g3, p3, m3, v3, pn, step_size, wd_coef)
    else:
        new_p, m, v, new_norm = ref.gwt_adam_fused(
            g3, p3, m3, v3, pn, step_size, wd_coef,
            bm=kernel.fused_row_block(mm, nn, level), **kw)
    with jax.named_scope("optim.pack"):
        new_p = new_p.reshape(gshape)
        mshape = gshape[:-1] + (nn >> level,)
        m, v = m.reshape(mshape), v.reshape(mshape)
        if lead2:
            new_p, m, v = new_p[0], m[0], v[0]
            new_norm = new_norm.reshape(())
    return new_p, new_norm, {"m": m, "v": v}


def fused_write_update_q8(g: jax.Array, p: jax.Array, state: dict,
                          step: jax.Array, key: jax.Array,
                          leaf_ids: jax.Array, prev_norm: jax.Array, *,
                          lr_t, alpha: float, weight_decay: float,
                          gamma: float, use_limiter: bool, level: int,
                          block: int = 64, b1: float = 0.9,
                          b2: float = 0.999, eps: float = 1e-6,
                          impl: str = "auto"):
    """``fused_write_update`` over blocked-int8 moments: dequant → update →
    stochastic requant AND limit+apply+write all inside the launch.  The
    codec's per-row blocks tile every shape, so ``pallas``/``interpret``
    always run the kernel.  Returns ``(new_p, new_norm, new_state)`` in
    the encoded layout; the launch runs under ``gwt.kernel`` as there."""
    impl = compat.resolve_kernel_impl(impl)
    with jax.named_scope("gwt.kernel"):
        return _fused_write_update_q8(
            g, p, state["m"]["q"], state["m"]["scale"],
            state["v"]["q"], state["v"]["scale"], prev_norm, step, key,
            leaf_ids, lr_t, alpha=alpha, weight_decay=weight_decay,
            gamma=gamma, use_limiter=use_limiter, level=level, block=block,
            b1=b1, b2=b2, eps=eps, impl=impl)


@functools.partial(jax.jit, static_argnames=(
    "alpha", "weight_decay", "gamma", "use_limiter", "level", "block",
    "b1", "b2", "eps", "impl"))
def _fused_write_update_q8(g, p, qm, sm, qv, sv, prev_norm, step, key,
                           leaf_ids, lr_t, *, alpha, weight_decay, gamma,
                           use_limiter, level, block, b1, b2, eps, impl):
    from repro.kernels.gwt_adam import kernel, ref  # noqa: F811 — local
    from repro.optim import codec as codec_lib
    _count_schedule(g)
    step_size, wd_coef = _step_scalars(step, lr_t, alpha, weight_decay,
                                       b1, b2)
    with jax.named_scope("optim.pack"):
        g3, gshape, lead2 = _norm_shapes(g)
        p3, _, _ = _norm_shapes(p)
        qm3, _, _ = _norm_shapes(qm)
        qv3, _, _ = _norm_shapes(qv)
        L, mm, nn = g3.shape
        sm2, sv2 = sm.reshape(L, -1, mm), sv.reshape(L, -1, mm)
    salt_m = codec_lib.slot_salt(key, step, 0, leaf_ids).reshape(L)
    salt_v = codec_lib.slot_salt(key, step, 1, leaf_ids).reshape(L)
    pn = prev_norm.reshape(L)
    kw = dict(level=level, block=block, gamma=gamma,
              use_limiter=use_limiter, weight_decay=weight_decay != 0,
              b1=b1, b2=b2, eps=eps)
    if impl in ("pallas", "interpret"):
        new_p, qm2, smo, qv2, svo, new_norm = _on_each_device(
            functools.partial(kernel.gwt_adam_tile_fused_q8,
                              interpret=impl == "interpret", **kw))(
            g3, p3, qm3, sm2, qv3, sv2, salt_m, salt_v, pn, step_size,
            wd_coef)
    else:
        new_p, qm2, smo, qv2, svo, new_norm = ref.gwt_adam_fused_q8(
            g3, p3, qm3, sm2, qv3, sv2, salt_m, salt_v, pn, step_size,
            wd_coef, bm=kernel.q8_row_block(mm, nn, level, block), **kw)
    with jax.named_scope("optim.pack"):
        new_p = new_p.reshape(gshape)
        qshape = gshape[:-1] + (nn >> level,)
        qm2, qv2 = qm2.reshape(qshape), qv2.reshape(qshape)
        smo, svo = smo.reshape(sm.shape), svo.reshape(sv.shape)
        if lead2:
            new_p, qm2, qv2 = new_p[0], qm2[0], qv2[0]
            new_norm = new_norm.reshape(())
    return new_p, new_norm, {"m": {"q": qm2, "scale": smo},
                             "v": {"q": qv2, "scale": svo}}
