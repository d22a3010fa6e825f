"""Pure-jnp oracle for the fused GWT-Adam kernel (Algorithm 1 inner loop).

Jitted as a whole so the oracle and the (whole-body-compiled) Pallas
kernel see identical XLA fusion/contraction decisions: run eagerly, each
op rounds separately and near-cancelling approximation coefficients can
land one f32 ulp away from the kernel's — which the ``1/(√V+ε)`` detail
scaling then amplifies across a bf16 rounding boundary (a single-element
8192-magnitude mismatch at ~2^20 magnitudes).

The kernel's lane shuffles are matmuls (``repro.kernels.lanes``), so each
butterfly level starts from a materialized array, and XLA:CPU may contract
the jnp butterfly's multiply of one level into the next level's add (an
FMA) where the kernel cannot.  The oracle therefore runs the kernel's own
per-tile math (``kernel._dht_adam_core``, compiled by XLA, so with the
bf16 schedule's fences) on whole leaves; what it checks
independently is the tiling, the two-phase limiter norm, the write chain,
the int8 requantize and the SMEM/aliasing plumbing.  The shuffles
themselves are pinned bitwise against jnp reshapes (tests/test_kernels.py)
and the whole update against the ``core.haar`` butterfly within a stated
tolerance.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.gwt_adam import kernel


@functools.partial(jax.jit, static_argnames=("level", "b1", "b2", "eps"))
def _tile(g: jax.Array, m_st: jax.Array, v_st: jax.Array, *,
          level: int, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-6) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One leaf's G̃ (in ``g``'s dtype) and new moments."""
    out, m, v = kernel._dht_adam_core(g, m_st.astype(jnp.float32),
                                      v_st.astype(jnp.float32), level, b1,
                                      b2, eps, xla=True)
    return out.astype(g.dtype), m.astype(m_st.dtype), v.astype(v_st.dtype)


@functools.partial(jax.jit, static_argnames=("level", "block", "b1", "b2",
                                             "eps"))
def _tile_q8(g: jax.Array, qm: jax.Array, sm: jax.Array,
             qv: jax.Array, sv: jax.Array,
             salt_m: jax.Array, salt_v: jax.Array, *,
             level: int, block: int, b1: float = 0.9,
             b2: float = 0.999, eps: float = 1e-6):
    """q8 oracle: blocked-int8 moments in, blocked-int8 moments out.

    Dequantize → :func:`_tile` math → stochastic requantize with the
    caller-supplied per-slot salts (``repro.optim.codec`` hash — the same
    bits the Pallas epilogue and the engine's generic scan wrap produce).
    The dequantize multiplies by the kernel's per-element scale expansion
    (values equal to ``codec.blocked_dequant``'s broadcast, but XLA may
    fold ``β·(q·s)`` differently around a broadcast and round an ulp off).
    Returns ``(gt, qm', sm', qv', sv')``.
    """
    from repro.optim import codec as codec_lib
    rows, na = qm.shape

    def dequant(q, s):
        return q.astype(jnp.float32) * kernel._expand_scales(
            s.reshape(-1, rows).T, na, block)

    m_st, v_st = dequant(qm, sm), dequant(qv, sv)
    out, m, v = kernel._dht_adam_core(g, m_st, v_st, level, b1, b2, eps,
                                      xla=True)
    qm2, sm2 = codec_lib.blocked_quant(m, salt_m, block)
    qv2, sv2 = codec_lib.blocked_quant(v, salt_v, block)
    return (out.astype(g.dtype), qm2, sm2, qv2, sv2)


# ---------------------------------------------------------------------------
# Fused-write (megakernel) oracles.  These replicate the kernel's exact
# computation *shape* — per-(bm, n) row-stripe ssq partials accumulated
# left-to-right — so the interpret backend bitwise-matches them: the only
# order-sensitive op in the whole fused chain is the norm reduction, and
# pinning its association to the kernel's tiling makes the parity exact
# rather than ulp-close.  ``bm`` must be the kernel's row-block choice
# (ops.py passes ``kernel.fused_row_block`` / ``kernel.q8_row_block``).
# ---------------------------------------------------------------------------

def _tiled_norm(gt: jax.Array, bm: int, phases: int = 1) -> jax.Array:
    """‖gt‖ via the kernel's reduction order: per (bm, n) row stripe, one
    ``jnp.sum`` of the squares of each stride phase ``t[:, j::phases]`` in
    turn, added left to right into the stripe's partial, and the partials
    added sequentially.  ``phases`` is ``2^level`` for a bf16 G̃ in the
    f32-moment kernel, whose norm pass sums its unmerged phases
    (``kernel._body_fused``), and 1 (the whole stripe) otherwise.  A
    partial last stripe is zero-padded to ``bm`` rows, as the kernel masks
    its missing rows."""
    xr = gt.astype(jnp.float32)
    pad = -xr.shape[0] % bm
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    acc = None
    for k in range(xr.shape[0] // bm):
        t = xr[k * bm:(k + 1) * bm]
        part = None
        for j in range(phases):
            tj = t[:, j::phases]
            s = jnp.sum(tj * tj)
            part = s if part is None else part + s
        acc = part if acc is None else acc + part
    return jnp.sqrt(acc)


def _limit_write(gt, p, prev, step_size, wd_coef, *, gamma, use_limiter,
                 weight_decay, bm, phases=1):
    if use_limiter:
        norm = _tiled_norm(gt, bm, phases)
        scale = kernel._limiter_scale(norm, prev, gamma)
        new_norm = jnp.where(norm > 0, norm * scale, prev)
    else:
        scale = jnp.float32(1.0)
        new_norm = prev
    limited = gt * scale.astype(gt.dtype)
    p32 = p.astype(jnp.float32)
    new_p = p32 - step_size * limited.astype(jnp.float32)
    if weight_decay:
        new_p = new_p - wd_coef * p32
    return new_p.astype(p.dtype), new_norm


@functools.partial(jax.jit, static_argnames=(
    "level", "gamma", "use_limiter", "weight_decay", "bm", "b1", "b2", "eps"))
def gwt_adam_fused(g: jax.Array, p: jax.Array, m_st: jax.Array,
                   v_st: jax.Array, prev_norm: jax.Array,
                   step_size: jax.Array, wd_coef: jax.Array, leaf0=0, *,
                   level: int, gamma: float, use_limiter: bool,
                   weight_decay: bool, bm: int, b1: float = 0.9,
                   b2: float = 0.999, eps: float = 1e-6):
    """Fused-write oracle over ``(Lg, m, n)`` leaves whose moments are
    slabs ``leaf0 …`` of a bucket's stack (``kernel.gwt_adam_tile_fused``
    semantics).  Returns ``(new_p, new_m, new_v, new_norm)`` with
    ``new_norm`` f32 ``(Lg,)``.

    ``p``/``m``/``v`` ride the ``lax.scan`` carry and are updated leaf-by-
    leaf with in-place dynamic-update-slice, so one leaf's working set is
    the only live temp and donated inputs alias straight through to the
    outputs — the one-launch dataflow the kernel has, visible to XLA
    buffer assignment."""
    phases = 1 << level if g.dtype == jnp.bfloat16 else 1

    def body(carry, xs):
        p_c, m_c, v_c = carry
        gl, pnl, l = xs
        s = l + leaf0
        gt, m, v = _tile(
            gl, jax.lax.dynamic_index_in_dim(m_c, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_c, s, 0, keepdims=False),
            level=level, b1=b1, b2=b2, eps=eps)
        new_p, new_norm = _limit_write(
            gt, jax.lax.dynamic_index_in_dim(p_c, l, 0, keepdims=False),
            pnl, step_size, wd_coef, gamma=gamma, use_limiter=use_limiter,
            weight_decay=weight_decay, bm=bm, phases=phases)
        p_c = jax.lax.dynamic_update_index_in_dim(p_c, new_p, l, 0)
        m_c = jax.lax.dynamic_update_index_in_dim(m_c, m, s, 0)
        v_c = jax.lax.dynamic_update_index_in_dim(v_c, v, s, 0)
        return (p_c, m_c, v_c), new_norm
    idx = jnp.arange(g.shape[0], dtype=jnp.int32)
    (p, m_st, v_st), norms = jax.lax.scan(
        body, (p, m_st, v_st), (g, prev_norm, idx))
    return p, m_st, v_st, norms


@functools.partial(jax.jit, static_argnames=(
    "level", "block", "gamma", "use_limiter", "weight_decay", "bm",
    "b1", "b2", "eps"))
def gwt_adam_fused_q8(g: jax.Array, p: jax.Array, qm: jax.Array,
                      sm: jax.Array, qv: jax.Array, sv: jax.Array,
                      salt_m: jax.Array, salt_v: jax.Array,
                      prev_norm: jax.Array, step_size: jax.Array,
                      wd_coef: jax.Array, leaf0=0, *, level: int,
                      block: int, gamma: float, use_limiter: bool,
                      weight_decay: bool, bm: int, b1: float = 0.9,
                      b2: float = 0.999, eps: float = 1e-6):
    """q8 fused-write oracle (blocked-int8 moments in/out).  Returns
    ``(new_p, qm', sm', qv', sv', new_norm)``.

    Same ``lax.scan`` carry structure and ``leaf0`` slabs as
    :func:`gwt_adam_fused` — ``p``/``qm``/``sm``/``qv``/``sv`` update
    in-place leaf-by-leaf so donated inputs alias through and one leaf
    bounds the live temps."""
    def body(carry, xs):
        p_c, qm_c, sm_c, qv_c, sv_c = carry
        gl, saltml, saltvl, pnl, l = xs
        s = l + leaf0
        at = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False)
        gt, qm2, sm2, qv2, sv2 = _tile_q8(
            gl, at(qm_c, s), at(sm_c, s), at(qv_c, s), at(sv_c, s), saltml,
            saltvl, level=level, block=block, b1=b1, b2=b2, eps=eps)
        new_p, new_norm = _limit_write(
            gt, at(p_c, l), pnl, step_size, wd_coef, gamma=gamma,
            use_limiter=use_limiter, weight_decay=weight_decay, bm=bm)
        upd = jax.lax.dynamic_update_index_in_dim
        return ((upd(p_c, new_p, l, 0), upd(qm_c, qm2, s, 0),
                 upd(sm_c, sm2, s, 0), upd(qv_c, qv2, s, 0),
                 upd(sv_c, sv2, s, 0)), new_norm)
    idx = jnp.arange(g.shape[0], dtype=jnp.int32)
    (p, qm, sm, qv, sv), norms = jax.lax.scan(
        body, (p, qm, sm, qv, sv), (g, salt_m, salt_v, prev_norm, idx))
    return p, qm, sm, qv, sv, norms
