"""Lane shuffles and row tiling shared by the Pallas kernels.

The Haar butterfly pairs neighbouring columns, i.e. neighbouring *lanes* of
a TPU vector register.  Mosaic does not lower the obvious jnp forms of that
shuffle (``x.reshape(m, w/2, 2)``, ``jnp.stack([e, o], -1).reshape(...)``)
nor a lane-strided ``pl.ds``.  It does lower a matmul, so each shuffle here
is a product with a constant 0/1 selection matrix on the MXU, taken 256
input lanes (or 128 output pairs) at a time so the matrices stay small:

* ``deinterleave(x) -> (x[:, 0::2], x[:, 1::2])``
* ``interleave(e, o) -> z`` with ``z[:, 0::2] = e``, ``z[:, 1::2] = o``
* ``repeat_lanes(x, r) == jnp.repeat(x, r, axis=-1)``
* ``fence(x) == x``, through identity selections

The MXU multiplies bf16.  A bf16 operand takes one pass: each output lane
receives its one selected value times 1 and zeros otherwise, accumulated
in f32, which is exact.  An f32 operand is first cut into three bf16
parts whose sum is exactly the operand (``_split3``), so it takes three
passes, whose products add back to the selected f32 value bit for bit.
Per element moved, one pass of ``deinterleave`` or ``interleave`` costs
512 MXU FLOPs (a 256 x 128 selection per 256 inputs, or per 256
outputs); the shuffles keep their operand's dtype.  A non-finite value
in a 256-lane piece turns that piece's row to NaN.

``row_block`` bounds a kernel's row tile by its VMEM working set; the
kernels run a ``pl.cdiv`` grid over rows, so the last tile may be partial
(its out-of-range rows are masked on write, and reductions mask them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_PIECE = 256        # input lanes per deinterleave matmul (2 x 128 outputs)
_HI_MASK = -65536   # 0xFFFF0000: keeps sign, exponent and 7 mantissa bits

# Working-set target for one grid step, double-buffered blocks and the
# body's f32 temporaries included.  The v5e compiler's default scoped
# VMEM limit is 16 MiB; the compile tests (tests/test_tpu_compile.py)
# check every kernel against it at llama-1b shapes.
VMEM_BUDGET = 12 * 1024 * 1024


def _split3(x: jax.Array):
    """``x == hi + mid + lo`` exactly, each part representable in bf16
    (truncation, so a convert to bf16 is exact whatever the backend)."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32) & _HI_MASK
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    hi = top(x)
    r = x - hi
    mid = top(r)
    lo = r - mid
    return [t.astype(jnp.bfloat16) for t in (hi, mid, lo)]


def _select_dot(x: jax.Array, rows: int, cols: int, pick) -> jax.Array:
    """``x @ S`` with ``S[r, c] = pick(r, c)`` (0/1), exact in f32: one
    MXU pass for a bf16 ``x``, three for any other dtype."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    s = pick(r, c).astype(jnp.bfloat16)
    dot = lambda a: jnp.dot(a, s, preferred_element_type=jnp.float32)
    if x.dtype == jnp.bfloat16:
        return dot(x)
    hi, mid, lo = _split3(x.astype(jnp.float32))
    return (dot(hi) + dot(mid)) + dot(lo)


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def deinterleave(x: jax.Array):
    """``(x[:, 0::2], x[:, 1::2])`` of a 2-D f32 or bf16 value (even
    width), in ``x``'s dtype."""
    w = x.shape[1]
    even, odd = [], []
    for s in range(0, w, _PIECE):
        p = x[:, s:min(w, s + _PIECE)]
        pw = p.shape[1]
        even.append(_select_dot(p, pw, pw // 2, lambda r, c: r == 2 * c))
        odd.append(_select_dot(p, pw, pw // 2, lambda r, c: r == 2 * c + 1))
    return _cat(even).astype(x.dtype), _cat(odd).astype(x.dtype)


def interleave(e: jax.Array, o: jax.Array) -> jax.Array:
    """Inverse of :func:`deinterleave`: ``(m, w), (m, w) -> (m, 2w)``, in
    ``e``'s dtype (``e`` and ``o`` share it)."""
    w = e.shape[1]
    out = []
    for s in range(0, w, _PIECE // 2):
        pe = e[:, s:min(w, s + _PIECE // 2)]
        po = o[:, s:min(w, s + _PIECE // 2)]
        pw = pe.shape[1]
        out.append(_select_dot(pe, pw, 2 * pw, lambda r, c: c == 2 * r)
                   + _select_dot(po, pw, 2 * pw, lambda r, c: c == 2 * r + 1))
    return _cat(out).astype(e.dtype)


def fence(x: jax.Array) -> jax.Array:
    """``x`` itself, through identity selections on the MXU: bitwise a
    copy, and a boundary that XLA fuses nothing across, as the shuffles
    are.  No multiply before it is contracted with an add after it."""
    w = x.shape[1]
    out = []
    for s in range(0, w, _PIECE):
        p = x[:, s:min(w, s + _PIECE)]
        pw = p.shape[1]
        out.append(_select_dot(p, pw, pw, lambda r, c: r == c))
    return _cat(out).astype(x.dtype)


def repeat_lanes(x: jax.Array, reps: int) -> jax.Array:
    """``jnp.repeat(x, reps, axis=-1)`` for a power-of-two ``reps``."""
    while reps > 1:
        x = interleave(x, x)
        reps //= 2
    return x


def row_block(m: int, row_bytes: int, align: int = 32,
              cap: int = 512) -> int:
    """Row-tile height for a ``pl.cdiv(m, bm)`` grid: the largest power of
    two in ``[align, cap]`` whose tile fits :data:`VMEM_BUDGET`, or ``m``
    itself when that is no taller (a full-extent block is always legal).
    ``align`` = 32 keeps int8 and f8 blocks on their native (32, 128)
    tiling."""
    bm = cap
    while bm > align and bm * row_bytes > VMEM_BUDGET:
        bm //= 2
    return m if m <= bm else bm


def global_rows(shape, row0) -> jax.Array:
    """Row index within the whole array of each element of a tile that
    starts at row ``row0`` (``< m`` marks the rows a partial tile holds)."""
    return row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def masked_ssq(x: jax.Array, row0, m: int) -> jax.Array:
    """``sum(x*x)`` over the rows of a tile that lie inside the array."""
    inside = global_rows(x.shape, row0) < m
    return jnp.sum(jnp.where(inside, x * x, 0.0))
