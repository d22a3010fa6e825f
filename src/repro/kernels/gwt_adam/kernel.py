"""Pallas TPU kernel: FUSED GWT-Adam update (the paper's Algorithm 1 inner
loop, beyond-paper fusion).

Per ``(bm, n)`` row stripe of the gradient, in a single VMEM residency:

    forward Haar butterfly (all ``l`` levels)      [bands stay in registers]
    M ← β₁M + (1−β₁)A ;  V ← β₂V + (1−β₂)A²        [moment tiles n/2^l wide]
    Ã = M/(√V+ε) ;  D̃_k = D_k · repeat(1/(√V+ε))
    inverse butterfly → G̃ tile
    partial ‖G̃‖² per tile                          [for the norm-growth limiter]
    P ← P − step · limit(G̃) [− wd · P]

The detail bands and G̃ are *never* materialized in HBM — the paper's
"temporary information generated during the wavelet transform"
observation (§V), taken to its architectural conclusion.  One pass reads
G and P, reads and writes M, V (at ``1/2^l`` of the width) and writes P:
with bf16 G and P and f32 moments at level 2, 10 B per gradient element.
With the norm-growth limiter on (the default) the kernel makes two
passes.  Phase 0 sums ‖G̃‖² and reads only G, M and V: the P input and
the P, M, V output windows stay pinned on the leaf's first row block, so
it fetches no P tile and writes nothing back (4 B per element).  Phase 1
recomputes the tile and writes (10 B), so 14 B per gradient element in
all.

The butterfly's even/odd lane pairing is a product with a 0/1 selection
matrix on the MXU (``repro.kernels.lanes``), exact, so every tile is
bitwise the jnp butterfly's.  A bf16 operand takes one MXU pass, an f32
one three.  So a bf16 gradient tile runs the butterfly's arithmetic on
its stride-``2^l`` phases, split off and merged back in bf16
(``_core_phases``: 2,048 MXU FLOPs per element at level 2); any other
dtype runs it on f32 bands (``_core_shuffled``: 5,376).  Both give the
same bits.  The norm pass of a bf16 tile sums the squares of G̃'s
phases before they are merged, so it skips the merge (1,024).  Stripes
span the full row (a block whose last dimension is the array's is
always tile-legal, whatever the width); the row tile is bounded by the
VMEM budget and the grid is ``pl.cdiv(m, bm)``, so a partial last tile
masks its out-of-range rows out of every norm.
Scalars (per-leaf limiter state, step size, weight decay, rounding salts)
and the per-tile norm partials live in SMEM.

**Fused-write megakernel** (``gwt_adam_tile_fused{,_q8}``): the full
DWT→Adam→inverse→limit→param-write chain for ``(Lg, m, n)`` leaves in ONE
launch.  The leaf axis is folded into the grid (no vmap); the per-leaf
``‖G̃‖`` reduction runs as a two-phase pass over the row tiles with the
SMEM ``new_norm`` output as the accumulator, and the epilogue applies the
norm-growth limiter, the bias-corrected step size, and weight decay before
writing the parameter tile.  The moments are a bucket's whole
``(L, m, n >> level)`` stack, updated in place: the launch's leaf ``l``
owns slab ``leaf0 + l`` (a scalar-prefetched offset that only the
moments' index maps read), so ``ops.py`` runs one launch per leaf on the
leaf's own gradient and parameter buffers, and all leaves of a shape
share one compiled kernel.

The q8 variant keeps the moments in the int8 codec's layout
(``repro.optim.codec``): per-row blocks of ``block`` A-band elements, one
f32 scale each, so a row tile always holds whole blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes
from repro.optim import codec as codec_lib

INV_SQRT2 = 0.7071067811865476
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_TEMPS = 16  # f32 copies of a stripe the butterfly keeps live (VMEM estimate)


def _adam(a, m_st, v_st, b1, b2, eps, xla):
    """Adam's moments on the approximation band, and ``1/(√V+ε)``.

    Under XLA each product is fenced (:func:`lanes.fence`): XLA:CPU
    otherwise contracts one of the two products of a moment's sum into
    its add (an FMA), and which one depends on how the surrounding
    program fused, so the same tile could round an ulp apart in two
    programs (one launch per leaf or per bucket, kernel or oracle)."""
    fence = lanes.fence if xla else (lambda t: t)
    m = fence(b1 * m_st) + fence((1.0 - b1) * a)
    v = fence(b2 * v_st) + fence((1.0 - b2) * a * a)
    return m, v, 1.0 / (jnp.sqrt(v) + eps)


def _dht_adam_core(x, m_st, v_st, level, b1, b2, eps, xla=False,
                   merge=True):
    """Forward butterfly → Adam on A → scaled-detail inverse butterfly of
    a raw gradient tile ``x``.  Shared by the f32 and the q8 (blocked-int8
    moments) bodies and by ``ref.py``.  Returns ``(out, m, v)``: ``out``
    is G̃ in ``x``'s dtype for a bf16 ``x`` (:func:`_core_phases`), in f32
    otherwise (:func:`_core_shuffled`); rounding it to ``x``'s dtype gives
    the same bits either way.  ``merge=False`` returns ``out`` as a list
    of tiles that :func:`merge_phases` merges into G̃: a bf16 ``x``'s
    ``2^l`` stride phases ``G̃[:, j::2^l]``, not yet interleaved, or
    ``[G̃]`` for any other dtype.  ``xla``: the body is compiled by XLA
    (interpret mode, the oracles), not by Mosaic."""
    if x.dtype == jnp.bfloat16:
        out, m, v = _core_phases(x, m_st, v_st, level, b1, b2, eps, xla)
        return (merge_phases(out) if merge else out), m, v
    out, m, v = _core_shuffled(x.astype(jnp.float32), m_st, v_st, level, b1,
                               b2, eps, xla)
    return (out if merge else [out]), m, v


def split_phases(x, level: int) -> list:
    """The ``2^level`` stride phases ``[x[:, j::2^level]]`` of a tile, by
    ``level`` rounds of :func:`lanes.deinterleave` (exact)."""
    ph = [x]                     # ph[j] == x[:, j::len(ph)]
    for _ in range(level):
        halves = [lanes.deinterleave(p) for p in ph]
        ph = [e for e, _ in halves] + [o for _, o in halves]
    return ph


def merge_phases(ph: list):
    """Inverse of :func:`split_phases`: the tile whose stride phases are
    ``ph``, by ``log2(len(ph))`` rounds of :func:`lanes.interleave`."""
    while len(ph) > 1:           # ph[j] == tile[:, j::len(ph)]
        half = len(ph) // 2
        ph = [lanes.interleave(ph[j], ph[j + half]) for j in range(half)]
    return ph[0]


def _core_shuffled(x, m_st, v_st, level, b1, b2, eps, xla=False):
    """The f32 schedule: each level's even/odd split and merge is a lane
    shuffle of an f32 band (three MXU passes each, 5,376 MXU FLOPs per
    element at level 2, the level-1 details' ``repeat_lanes`` included)."""
    a = x
    details = []
    for _ in range(level):
        even, odd = lanes.deinterleave(a)
        a = (even + odd) * INV_SQRT2
        details.append((even - odd) * INV_SQRT2)

    m, v, inv_denom = _adam(a, m_st, v_st, b1, b2, eps, xla)
    x = m * inv_denom
    for k in range(level, 0, -1):
        d_t = details[k - 1] * lanes.repeat_lanes(inv_denom, 1 << (level - k))
        x = lanes.interleave((x + d_t) * INV_SQRT2, (x - d_t) * INV_SQRT2)
    return x, m, v


def _core_phases(x, m_st, v_st, level, b1, b2, eps, xla):
    """The bf16 schedule: the butterfly's arithmetic runs on the ``2^l``
    stride phases ``x[:, j::2^l]``, each as wide as the moments.

    ``l`` rounds of ``deinterleave`` split the bf16 tile into its phases
    (one MXU pass each: the values stay bf16).  In phase form a band of
    level ``k`` is its ``2^(l-k)`` phases, and level ``k+1``'s phase ``j``
    is ``(B[2j] ± B[2j+1])·c`` of level ``k``'s: the f32 ops of
    :func:`_core_shuffled` on the same elements in the same order, so the
    bands, the moments and every output element are bitwise its own.  The
    details of every level are scaled by ``1/(√V+ε)`` phase by phase, with
    no ``repeat_lanes``.  Returns the output phases rounded to bf16
    (``out[j] == G̃[:, j::2^l]``); :func:`merge_phases` interleaves them in
    ``l`` one-pass rounds, and a permutation commutes with the rounding.
    At level 2 the split takes 1,024 MXU FLOPs per element and the merge
    as many.

    Under XLA a :func:`lanes.fence` stands wherever
    :func:`_core_shuffled` puts a shuffle between two f32 ops: between
    the forward levels, after each inverse level, and on the ``1/(√V+ε)``
    that scales the details below level ``l``.  Without them XLA:CPU
    contracts a multiply into the next level's add (an FMA) where the f32
    schedule cannot, and the interpret-mode kernel and the oracles drift
    from it by an ulp (an ``optimization_barrier`` does not stop that).
    Mosaic runs no fence; the chip parity phase of ``chip_smoke.py`` pins
    the compiled kernel to the f32 schedule's bits."""
    fence = lanes.fence if xla else (lambda t: t)
    band = [p.astype(jnp.float32) for p in split_phases(x, level)]
    details = []
    for k in range(level):
        if k:
            band = [fence(b) for b in band]
        even, odd = band[0::2], band[1::2]
        band = [(e + o) * INV_SQRT2 for e, o in zip(even, odd)]
        details.append([(e - o) * INV_SQRT2 for e, o in zip(even, odd)])

    m, v, inv_denom = _adam(band[0], m_st, v_st, b1, b2, eps, xla)
    y = [m * inv_denom]
    for k in range(level, 0, -1):
        scale = inv_denom if k == level else fence(inv_denom)
        nxt = []
        for yj, dj in zip(y, details[k - 1]):
            d_t = dj * scale
            nxt += [(yj + d_t) * INV_SQRT2, (yj - d_t) * INV_SQRT2]
        y = [fence(u) for u in nxt]

    return [yj.astype(x.dtype) for yj in y], m, v


def _row_bytes(n: int, level: int, stream_cols: float) -> int:
    """VMEM bytes per stripe row: the streamed blocks (``stream_cols``
    bytes per gradient column, double-buffered) plus f32 temporaries."""
    return int(2 * stream_cols * n + _TEMPS * 4 * n)


def _check_width(n: int, level: int) -> None:
    if n % (1 << level) != 0:
        raise ValueError(f"n={n} not divisible by 2^{level}")


# ---------------------------------------------------------------------------
# Fused-write megakernel: one launch per leaf (or per stack of leaves)
# does DWT -> Adam -> inverse -> norm-growth limiter -> parameter write.
# ---------------------------------------------------------------------------

def fused_row_block(m: int, n: int, level: int) -> int:
    """Row-tile height of the f32-moment fused-write kernel: full-width
    stripes (G, P in, P out; M, V in and out at ``n >> level``), sized for
    f32 parameters under the VMEM budget.  ``ref.gwt_adam_fused`` takes
    the same value to reproduce the kernel's norm reduction order."""
    return lanes.row_block(m, _row_bytes(n, level, 12 + 16 / (1 << level)))


def q8_row_block(m: int, n: int, level: int, block: int) -> int:
    """Row-tile height of the q8 fused-write kernel: int8 moments and one
    f32 scale per ``block`` moments.  Codec blocks never straddle rows, so
    every row tile holds whole blocks and every shape tiles; the scales
    of a tile are an ``(nbr, bm)`` block, so ``bm`` is a multiple of 128
    (their lane axis) unless it spans all rows."""
    scales = 4 * 4 * 8 * -(-(n >> level) // (block * 8))
    return lanes.row_block(
        m, _row_bytes(n, level, 12 + 4 / (1 << level) + scales / n),
        align=128)


def _limiter_scale(norm, prev, gamma: float):
    """The norm-growth limiter ratio — term-for-term ``core.limiter.limit``
    (bitwise parity with the oracle is a test invariant)."""
    safe_prev = jnp.where(prev > 0, prev, norm)
    return jnp.where(norm > gamma * safe_prev,
                     gamma * safe_prev / jnp.maximum(norm, 1e-30),
                     jnp.float32(1.0))


def _body_fused(level: int, b1: float, b2: float, eps: float, xla: bool,
                gamma: float, use_limiter: bool, wd: bool, rows: int,
                leaf0_ref, pn_ref, sc_ref, g_ref, p_ref, m_ref, v_ref,
                p_out_ref, m_out_ref, v_out_ref, norm_ref):
    """Grid ``(Lg, phases, gm)`` — leaf outermost, row tiles innermost; the
    SMEM ``norm_ref[l]`` doubles as the cross-tile ssq accumulator.  Phase
    0 accumulates ``‖G̃_l‖²`` and writes nothing; phase 1 recomputes the
    tile (the op is bandwidth-bound — recompute is cheaper than an HBM
    round trip of G̃) and applies limiter + step + weight decay + write.
    ``use_limiter=False`` runs the single write phase only.  ``sc_ref``
    holds ``(step_size, wd_coef)``; ``leaf0_ref`` is read by the moments'
    index maps alone."""
    leaf = pl.program_id(0)
    phase = pl.program_id(1)
    i = pl.program_id(2)
    gm = pl.num_programs(2)
    x = g_ref[0]
    outs, m, v = _dht_adam_core(x, m_ref[0].astype(jnp.float32),
                                v_ref[0].astype(jnp.float32),
                                level, b1, b2, eps, xla, merge=False)
    prev = pn_ref[leaf]

    def write(scale):
        gt = merge_phases(outs).astype(g_ref.dtype)
        limited = gt * scale.astype(gt.dtype)
        p32 = p_ref[0].astype(jnp.float32)
        new_p = p32 - sc_ref[0] * limited.astype(jnp.float32)
        if wd:
            new_p = new_p - sc_ref[1] * p32
        p_out_ref[0] = new_p.astype(p_out_ref.dtype)
        m_out_ref[0] = m.astype(m_out_ref.dtype)
        v_out_ref[0] = v.astype(v_out_ref.dtype)

    if not use_limiter:
        write(jnp.float32(1.0))
        norm_ref[leaf] = prev  # limiter off: prev_norm passes through
        return

    @pl.when(phase == 0)
    def _():
        # The norm pass: G̃'s squares phase by phase, unmerged, in the
        # order ref._tiled_norm states.  The p input and the p/m/v output
        # windows are pinned on the leaf's first row block through this
        # phase (_fused_specs), and on the TPU a window is copied back to
        # HBM only when the next grid step maps another block: the
        # unwritten windows leave VMEM after phase 1's tile 0 has written
        # them, and p is fetched once, for that tile.
        part = None
        for o in outs:
            s = lanes.masked_ssq(o.astype(g_ref.dtype).astype(jnp.float32),
                                 i * x.shape[0], rows)
            part = s if part is None else part + s
        acc = jnp.where(i == 0, jnp.float32(0.0), norm_ref[leaf])
        norm_ref[leaf] = acc + part

    @pl.when(phase == 1)
    def _():
        norm = jnp.sqrt(norm_ref[leaf])
        scale = _limiter_scale(norm, prev, gamma)
        write(scale)

        @pl.when(i == gm - 1)
        def _():
            # zero-norm step preserves limiter history (core.limiter)
            norm_ref[leaf] = jnp.where(norm > 0, norm * scale, prev)


def _stripe(bm: int, w: int, slab: bool = False,
            pinned: bool = False) -> pl.BlockSpec:
    """Row stripe ``(1, bm, w)`` over the ``(Lg, phases, gm)`` grid: of
    the launch's leaf ``l``, or (``slab``) of a bucket's stacked moments
    at the leaf's slab ``leaf0 + l``.  ``pinned``: phase 0 maps every row
    tile to the first block (row index ``i * ph``), phase 1 tile ``i``."""
    row = (lambda ph, i: i * ph) if pinned else (lambda ph, i: i)
    if slab:
        return pl.BlockSpec((1, bm, w), lambda l, ph, i, leaf0: (
            l + leaf0[0], row(ph, i), 0))
    return pl.BlockSpec((1, bm, w), lambda l, ph, i, leaf0: (
        l, row(ph, i), 0))


def _fused_specs(bm: int, n: int, level: int, use_limiter: bool):
    """``(in_specs, out_specs)`` of :func:`gwt_adam_tile_fused`'s g, p, m,
    v inputs and p, m, v outputs.  With the limiter on, the p input and
    every output are pinned through phase 0 (:func:`_stripe`); g, m and v
    stream in both phases."""
    na = n >> level
    ins = [_stripe(bm, n), _stripe(bm, n, pinned=use_limiter),
           _stripe(bm, na, slab=True), _stripe(bm, na, slab=True)]
    outs = [_stripe(bm, n, pinned=use_limiter),
            _stripe(bm, na, slab=True, pinned=use_limiter),
            _stripe(bm, na, slab=True, pinned=use_limiter)]
    return ins, outs


def _leaf0(leaf0) -> jax.Array:
    return jnp.asarray(leaf0, jnp.int32).reshape(1)


def gwt_adam_tile_fused(g: jax.Array, p: jax.Array, m_st: jax.Array,
                        v_st: jax.Array, prev_norm: jax.Array,
                        step_size: jax.Array, wd_coef: jax.Array,
                        leaf0=0, *, level: int, gamma: float,
                        use_limiter: bool, weight_decay: bool,
                        b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-6, interpret: bool = False):
    """Fused-write update of the ``(Lg, m, n)`` leaves ``g``/``p`` in ONE
    launch.

    ``m_st``/``v_st``: a bucket's stacked moments ``(L, m, n >> level)``;
    leaf ``l`` of the launch updates slab ``leaf0 + l`` (an i32 scalar)
    in place, and the other slabs pass through untouched.  With
    ``Lg == L`` and ``leaf0 = 0`` one launch updates the whole bucket.
    ``prev_norm``: f32 ``(Lg,)`` per-leaf limiter state; ``step_size`` /
    ``wd_coef``: f32 scalars (bias-corrected lr·α and lr·weight_decay,
    computed by ops.py).  Returns ``(new_p, new_m, new_v, new_norm)``
    with ``new_m``/``new_v`` the whole stack and ``new_norm`` f32
    ``(Lg,)``.
    """
    L, mm, nn = g.shape
    _check_width(nn, level)
    bm = fused_row_block(mm, nn, level)
    phases = 2 if use_limiter else 1
    scalars = jnp.stack([jnp.asarray(step_size, jnp.float32),
                         jnp.asarray(wd_coef, jnp.float32)])
    ins, outs = _fused_specs(bm, nn, level, use_limiter)
    return pl.pallas_call(
        functools.partial(_body_fused, level, b1, b2, eps, interpret, gamma,
                          use_limiter, weight_decay, mm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(L, phases, pl.cdiv(mm, bm)),
            in_specs=[_SMEM, _SMEM] + ins,
            out_specs=outs + [_SMEM]),
        out_shape=[
            jax.ShapeDtypeStruct((L, mm, nn), p.dtype),
            jax.ShapeDtypeStruct(m_st.shape, m_st.dtype),
            jax.ShapeDtypeStruct(v_st.shape, v_st.dtype),
            jax.ShapeDtypeStruct((L,), jnp.float32),
        ],
        # in-place write semantics: p/m/v are updated in their own
        # buffers (each tile reads its block before writing it; phase 0's
        # pinned windows are written back only after phase 1 wrote them),
        # and the moment slabs of other leaves keep their values.  NOT
        # prev_norm→new_norm: phase 0 accumulates ssq into the norm output
        # while phase 1 still reads the history from pn_ref — aliasing
        # them would clobber it.
        input_output_aliases={4: 0, 5: 1, 6: 2},
        interpret=interpret,
    )(_leaf0(leaf0), prev_norm.astype(jnp.float32), scalars, g, p, m_st,
      v_st)


def _expand_scales(s: jax.Array, width: int, block: int) -> jax.Array:
    """Per-element scale ``(bm, width)`` from per-block scales
    ``(bm, ceil(width/block))``: lane selects, no reshape."""
    bid = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], width), 1) // block
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    out = jnp.zeros((s.shape[0], width), jnp.float32)
    for j in range(s.shape[1]):
        sj = jnp.sum(jnp.where(col == j, s, 0.0), axis=1, keepdims=True)
        out = jnp.where(bid == j, sj, out)
    return out


def _requant(arr, salt, row0, block: int):
    """``codec.blocked_quant`` of a row tile: returns ``(q int8, scales)``
    with scales ``(bm, ceil(width/block))``.  The rounding bits hash the
    element's row-major index in the whole leaf, so any tiling (and the
    phase-1 recompute) requantizes identically."""
    bm, width = arr.shape
    nbr = -(-width // block)
    bid = jax.lax.broadcasted_iota(jnp.int32, arr.shape, 1) // block
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, nbr), 1)
    mag = jnp.abs(arr)
    scales = jnp.zeros((bm, nbr), jnp.float32)
    inv = jnp.zeros(arr.shape, jnp.float32)
    for j in range(nbr):
        amax = jnp.max(jnp.where(bid == j, mag, 0.0), axis=1, keepdims=True)
        sj = amax * jnp.float32(1.0 / 127.0)
        scales = jnp.where(col == j, sj, scales)
        inv = jnp.where(bid == j, jnp.where(sj > 0, 1.0 / sj, 0.0), inv)
    y = arr * inv
    idx = (lanes.global_rows(arr.shape, row0) * width
           + jax.lax.broadcasted_iota(jnp.int32, arr.shape, 1))
    lo = jnp.floor(y)
    q = lo + (codec_lib.uniform01(salt, idx) < (y - lo)).astype(jnp.float32)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), scales


def _body_fused_q8(level: int, b1: float, b2: float, eps: float, xla: bool,
                   gamma: float, use_limiter: bool, wd: bool, block: int,
                   rows: int, leaf0_ref, pn_ref, sc_ref, salt_ref,
                   g_ref, p_ref, qm_ref, sm_ref, qv_ref, sv_ref,
                   p_out_ref, qm_out_ref, sm_out_ref, qv_out_ref,
                   sv_out_ref, norm_ref):
    """q8 sibling of ``_body_fused``: blocked-int8 moments are dequantized
    in the prologue and stochastically requantized in the write phase (the
    rounding bits are a pure function of (salt, flat index), so the
    phase-1 recompute requantizes identically).  ``salt_ref`` holds the
    per-leaf m salts then the v salts, as int32 bit patterns."""
    leaf = pl.program_id(0)
    phase = pl.program_id(1)
    i = pl.program_id(2)
    gm = pl.num_programs(2)
    nleaves = pl.num_programs(0)
    x = g_ref[0]
    bm = x.shape[0]
    na = x.shape[1] >> level

    def dequant(q_ref, s_ref):
        return q_ref[0].astype(jnp.float32) * _expand_scales(
            jnp.transpose(s_ref[0]), na, block)

    out, m, v = _dht_adam_core(x, dequant(qm_ref, sm_ref),
                               dequant(qv_ref, sv_ref), level, b1, b2, eps,
                               xla)
    gt = out.astype(g_ref.dtype)
    prev = pn_ref[leaf]

    def write(scale):
        limited = gt * scale.astype(gt.dtype)
        p32 = p_ref[0].astype(jnp.float32)
        new_p = p32 - sc_ref[0] * limited.astype(jnp.float32)
        if wd:
            new_p = new_p - sc_ref[1] * p32
        p_out_ref[0] = new_p.astype(p_out_ref.dtype)
        for arr, salt, q_out, s_out in (
                (m, salt_ref[leaf], qm_out_ref, sm_out_ref),
                (v, salt_ref[nleaves + leaf], qv_out_ref, sv_out_ref)):
            q, sc = _requant(arr, salt, i * bm, block)
            q_out[0] = q
            s_out[0] = jnp.transpose(sc)

    if not use_limiter:
        write(jnp.float32(1.0))
        norm_ref[leaf] = prev
        return

    part = lanes.masked_ssq(gt.astype(jnp.float32), i * bm, rows)

    @pl.when(phase == 0)
    def _():
        acc = jnp.where(i == 0, jnp.float32(0.0), norm_ref[leaf])
        norm_ref[leaf] = acc + part
        # On the TPU an output window is copied back to HBM whenever the
        # next grid step maps another block, written or not.  These
        # windows stream with the row tiles and alias their inputs, so
        # left unwritten they would clobber, with undefined VMEM, the
        # state phase 1 re-reads: pass the inputs through unmodified
        # (interpret mode masks this; the chip parity phase of
        # chip_smoke.py pins it).
        p_out_ref[0] = p_ref[0]
        qm_out_ref[0] = qm_ref[0]
        sm_out_ref[0] = sm_ref[0]
        qv_out_ref[0] = qv_ref[0]
        sv_out_ref[0] = sv_ref[0]

    @pl.when(phase == 1)
    def _():
        norm = jnp.sqrt(norm_ref[leaf])
        scale = _limiter_scale(norm, prev, gamma)
        write(scale)

        @pl.when(i == gm - 1)
        def _():
            norm_ref[leaf] = jnp.where(norm > 0, norm * scale, prev)


def gwt_adam_tile_fused_q8(g: jax.Array, p: jax.Array, qm: jax.Array,
                           sm: jax.Array, qv: jax.Array, sv: jax.Array,
                           salt_m: jax.Array, salt_v: jax.Array,
                           prev_norm: jax.Array, step_size: jax.Array,
                           wd_coef: jax.Array, leaf0=0, *, level: int,
                           block: int, gamma: float, use_limiter: bool,
                           weight_decay: bool, b1: float = 0.9,
                           b2: float = 0.999, eps: float = 1e-6,
                           interpret: bool = False):
    """Fused-write q8 update of the ``(Lg, m, n)`` leaves in one launch.

    ``qm/qv``: a bucket's int8 ``(L, m, n>>level)``; ``sm/sv``: f32
    ``(L, nbr, m)`` per-row-block scales (``codec.blocked_quant`` layout),
    each updated in place at slab ``leaf0 + l`` as in
    :func:`gwt_adam_tile_fused`; ``salt_m/salt_v``: uint32 ``(Lg,)``
    per-leaf slot salts.  Returns ``(new_p, qm', sm', qv', sv',
    new_norm)``.
    """
    L, mm, nn = g.shape
    _check_width(nn, level)
    bm = q8_row_block(mm, nn, level, block)
    na = nn >> level
    nbr = -(-na // block)
    phases = 2 if use_limiter else 1
    scalars = jnp.stack([jnp.asarray(step_size, jnp.float32),
                         jnp.asarray(wd_coef, jnp.float32)])
    salts = jax.lax.bitcast_convert_type(
        jnp.concatenate([jnp.asarray(salt_m, jnp.uint32).reshape(L),
                         jnp.asarray(salt_v, jnp.uint32).reshape(L)]),
        jnp.int32)
    qtile = _stripe(bm, na, slab=True)
    stile = pl.BlockSpec((1, nbr, bm),
                         lambda l, ph, i, leaf0: (l + leaf0[0], 0, i))
    tiles = [_stripe(bm, nn), _stripe(bm, nn), qtile, stile, qtile, stile]
    return pl.pallas_call(
        functools.partial(_body_fused_q8, level, b1, b2, eps, interpret,
                          gamma, use_limiter, weight_decay, block, mm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(L, phases, pl.cdiv(mm, bm)),
            in_specs=[_SMEM, _SMEM, _SMEM] + tiles,
            out_specs=tiles[1:] + [_SMEM]),
        out_shape=[
            jax.ShapeDtypeStruct((L, mm, nn), p.dtype),
            jax.ShapeDtypeStruct(qm.shape, jnp.int8),
            jax.ShapeDtypeStruct(sm.shape, jnp.float32),
            jax.ShapeDtypeStruct(qv.shape, jnp.int8),
            jax.ShapeDtypeStruct(sv.shape, jnp.float32),
            jax.ShapeDtypeStruct((L,), jnp.float32),
        ],
        # in-place p and int8 payload/scale updates (reads precede writes
        # within each tile; phase 0 writes the inputs through unchanged).
        # prev_norm is deliberately NOT aliased to new_norm — see
        # gwt_adam_tile_fused.
        input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3, 9: 4},
        interpret=interpret,
    )(_leaf0(leaf0), prev_norm.astype(jnp.float32), scalars, salts, g, p,
      qm, sm, qv, sv)
