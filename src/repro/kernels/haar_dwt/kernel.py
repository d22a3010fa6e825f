"""Pallas TPU kernel: blocked multi-level Haar DWT (forward & inverse).

TPU adaptation (vs the paper's conv-based ptwt on GPU): the level-k Haar
coefficient ``j`` depends only on input columns ``[j·2^k, (j+1)·2^k)`` —
the transform is *block-local*.  A ``(bm, bn)`` VMEM tile whose width is a
multiple of ``2^l`` is therefore fully self-contained: one HBM read of the
gradient tile produces every band with no cross-tile communication.  All
levels run while the tile is VMEM-resident (HBM traffic = 1× read + 1×
write, vs ``l`` passes for a level-at-a-time implementation).

Grid: ``(cdiv(m, bm), cdiv(n, bn))``.  Outputs are one array per band —
``A_l: (m, n/2^l)``, ``D_k: (m, n/2^k)`` — each with its own BlockSpec, so
the global band layout falls out of the index maps (no strided HBM writes).
Widths up to 2048 take one full-width column block; wider rows take
blocks of a multiple of ``128·2^l`` columns so every band block is
lane-aligned, and a partial last block only produces out-of-range
columns, which are masked on write.

The butterfly's lane pairing (even/odd columns) is a product with a 0/1
selection matrix on the MXU (``repro.kernels.lanes``), exact in f32, so
every band is bitwise the jnp butterfly's (``core.haar``).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes

INV_SQRT2 = 0.7071067811865476
_MAX_BN = 2048


def _fwd_body(level: int, g_ref, *out_refs):
    a = g_ref[...].astype(jnp.float32)
    details: List[jax.Array] = []
    for _ in range(level):
        even, odd = lanes.deinterleave(a)
        a = (even + odd) * INV_SQRT2
        details.append((even - odd) * INV_SQRT2)
    details.reverse()  # [D_l, ..., D_1]
    out_refs[0][...] = a.astype(out_refs[0].dtype)
    for ref, d in zip(out_refs[1:], details):
        ref[...] = d.astype(ref.dtype)


def _inv_body(level: int, a_ref, *rest):
    d_refs, out_ref = rest[:-1], rest[-1]
    x = a_ref[...].astype(jnp.float32)
    for d_ref in d_refs:  # D_l first
        d = d_ref[...].astype(jnp.float32)
        x = lanes.interleave((x + d) * INV_SQRT2, (x - d) * INV_SQRT2)
    out_ref[...] = x.astype(out_ref.dtype)


def _pick_blocks(m: int, n: int, level: int) -> Tuple[int, int]:
    """``(bm, bn)`` for the ``(cdiv(m, bm), cdiv(n, bn))`` grid: full
    width up to 2048 columns, else the widest multiple of ``128·2^l`` that
    fits; rows bounded by the VMEM budget (input + bands + butterfly
    temporaries ≈ 16 f32 copies of the tile, double buffers included)."""
    unit = 128 << level
    bn = n if n <= _MAX_BN else max(unit, _MAX_BN // unit * unit)
    return lanes.row_block(m, 16 * bn * 4), bn


def _grid_call(body, m: int, n: int, level: int, in_widths, out_widths,
               out_dtypes, interpret: bool):
    """One ``pallas_call`` over a row × column grid; ``in_widths`` /
    ``out_widths`` are each operand's width as a right shift of ``n``
    (0 = the full row, ``k`` = band ``n >> k``)."""
    bm, bn = _pick_blocks(m, n, level)
    spec = lambda k: pl.BlockSpec((bm, bn >> k), lambda i, j: (i, j))
    return pl.pallas_call(
        body,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[spec(k) for k in in_widths],
        out_specs=[spec(k) for k in out_widths],
        out_shape=[jax.ShapeDtypeStruct((m, n >> k), dt)
                   for k, dt in zip(out_widths, out_dtypes)],
        interpret=interpret,
    )


def _band_shifts(level: int) -> List[int]:
    """Width shifts of ``(A_l, D_l, ..., D_1)``."""
    return [level] + list(range(level, 0, -1))


def _check(n: int, level: int) -> None:
    if n % (1 << level) != 0:
        raise ValueError(f"n={n} not divisible by 2^{level}")


def haar_dwt_fwd(g: jax.Array, level: int, *, interpret: bool = False
                 ) -> Tuple[jax.Array, ...]:
    """Returns ``(A_l, D_l, ..., D_1)``; 2-D input ``(m, n)``."""
    return haar_dwt_fwd_q(g, level, g.dtype, approx_dtype=g.dtype,
                          interpret=interpret)


def haar_dwt_fwd_q(g: jax.Array, level: int, detail_dtype, *,
                   approx_dtype=jnp.float32,
                   interpret: bool = False) -> Tuple[jax.Array, ...]:
    """Fused DWT + wire quantize: ``(A_l f32, D_l..D_1 detail_dtype)``.

    The wire path's ``reduce_terms`` splits the gradient and narrows the
    detail bands for the all-reduce.  Staged, that materializes every band
    in f32 before a second pass re-reads and narrows them; here the cast
    happens in-register at the tile write (``_fwd_body`` casts each band
    to its out-ref dtype), so the f32 detail intermediates never touch
    HBM — one launch emits the exact wire payload."""
    m, n = g.shape
    _check(n, level)
    bands = _band_shifts(level)
    dtypes = [approx_dtype] + [detail_dtype] * level
    return tuple(_grid_call(functools.partial(_fwd_body, level), m, n,
                            level, [0], bands, dtypes, interpret)(g))


def haar_dwt_inv(a: jax.Array, details: Sequence[jax.Array], *,
                 interpret: bool = False) -> jax.Array:
    """Inverse: ``(A_l, [D_l..D_1]) -> (m, n)``."""
    level = len(details)
    m, na = a.shape
    n = na << level
    out, = _grid_call(functools.partial(_inv_body, level), m, n, level,
                      _band_shifts(level), [0], [a.dtype],
                      interpret)(a, *details)
    return out
