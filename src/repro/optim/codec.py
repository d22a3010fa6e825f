"""Pluggable optimizer-state substrate: the ``StateCodec`` layer.

Every :class:`~repro.optim.engine.LeafRule` declares which arrays of its
per-leaf state are *moment slots* (``LeafRule.slots`` — a bool pytree
mirroring the state structure).  The engine stores slot arrays through a
codec:

* ``f32`` — passthrough (default).  The engine skips the codec entirely,
  so updates are bitwise-identical to the pre-codec engine.
* ``int8`` — blocked 8-bit: each row of a slot array (its last axis) is
  quantized in blocks of ``block`` elements against a per-block absmax
  scale (``scale = absmax/127``), with **stochastic rounding** so repeated
  requantization stays unbiased (FOAM / bitsandbytes-style).  The encoded
  slot is ``{"q": int8 (original shape), "scale": f32 (nbr, rows)}``
  with ``nbr = ceil(width/block)`` blocks per row — ~``1/4 +
  1/(4·block)`` of the f32 bytes.  Blocks never straddle rows, so a
  kernel that tiles rows holds whole blocks whatever the width, and the
  scales of a row tile are a lane-dense ``(nbr, bm)`` block.

Rounding randomness is **counter-based**, not ``jax.random``: a
murmur-style uint32 mixing hash of ``(codec_key, step, slot_idx, leaf_id,
element_idx)``.  Consequences the rest of the stack relies on:

* identical bits under ``lax.scan``, unrolled, vmapped, and Pallas
  execution (plain uint32 arithmetic, no backend RNG state);
* preempt/resume is bitwise: ``codec_key`` lives in ``opt_state`` (saved
  in every checkpoint) and ``step`` is the optimizer step, so a resumed
  run requantizes with exactly the interrupted run's bits;
* traceable under ``jax.eval_shape`` (state accounting needs no key).

The hash/round helpers are module-level so the fused Pallas kernel
(``repro.kernels.gwt_adam.kernel``) can reuse them inside its requant
epilogue — one definition of the bits, every backend agrees.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 64

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9


def _fmix(h: jax.Array) -> jax.Array:
    """murmur3 finalizer: bijective uint32 avalanche mix."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> 16)
    return h


def _fold(h: jax.Array, x) -> jax.Array:
    return _fmix(h ^ (jnp.asarray(x).astype(jnp.uint32) * jnp.uint32(_GOLD)))


def make_key(seed: int) -> jax.Array:
    """Concrete uint32 codec key from an integer seed (stored in
    ``opt_state["codec_key"]``; constant over a run)."""
    return _fold(jnp.uint32(0x8BADF00D), jnp.uint32(seed & 0xFFFFFFFF))


def slot_salt(key, step, slot: int, leaf_id) -> jax.Array:
    """Per-(key, step, slot, leaf) salt; elementwise over ``leaf_id`` so a
    vector of leaf ids yields a vector of salts."""
    return _fold(_fold(_fold(jnp.asarray(key, jnp.uint32), step),
                       jnp.uint32(slot)), leaf_id)


def uniform01(salt, idx: jax.Array) -> jax.Array:
    """Deterministic uniforms in [0, 1): hash of (salt, element index),
    24 mantissa-exact bits."""
    bits = _fmix(jnp.asarray(salt, jnp.uint32)
                 ^ (idx.astype(jnp.uint32) * jnp.uint32(_GOLD)))
    # via int32: the 24-bit value is exact there, and Mosaic has no
    # uint32 -> f32 conversion
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(1.0 / (1 << 24))


# ---------------------------------------------------------------------------
# Blocked int8 quantization with stochastic rounding
# ---------------------------------------------------------------------------

def _rows_width(shape) -> tuple:
    """``(rows, width)`` of the row view the codec blocks: the last axis is
    the row; a scalar is one row of one element."""
    width = int(shape[-1]) if shape else 1
    rows = 1
    for d in shape[:-1]:
        rows *= int(d)
    return rows, width


def scale_shape(shape, block: int = DEFAULT_BLOCK) -> tuple:
    """``(blocks per row, rows)`` — the encoded scale array's shape."""
    rows, width = _rows_width(tuple(shape))
    return (max(1, -(-width // block)), rows)


def blocked_quant(x: jax.Array, salt, block: int = DEFAULT_BLOCK,
                  rounding: str = "stochastic"):
    """``x -> (q int8 (x.shape), scale f32 (nbr, rows))``; per-row blocks.

    Each row (last axis) is cut into ``nbr = ceil(width/block)`` blocks,
    the last one short when ``block`` does not divide the width;
    ``scale[j, r]`` belongs to block ``j`` of row ``r``.  ``scale = absmax/127``
    per block; elements are divided by their block's scale and
    stochastically rounded (``floor(y) + (u < frac(y))`` with ``u =
    uniform01(salt, flat_idx)``, ``flat_idx`` the element's row-major
    index) — unbiased, error ≤ one quantum (= scale).  All-zero blocks
    encode as ``scale = 0`` exactly.

    ``rounding="nearest"`` rounds to the nearest level instead (``salt``
    is ignored): half the worst-case error, but biased under repeated
    requantization — right for write-once payloads (the serving KV cache,
    which encodes each entry exactly once), wrong for optimizer moments.
    """
    shape = tuple(x.shape)
    rows, width = _rows_width(shape)
    nbr = max(1, -(-width // block))
    xf = x.astype(jnp.float32).reshape(rows, width)
    if nbr * block != width:
        xf = jnp.pad(xf, ((0, 0), (0, nbr * block - width)))
    blocks = xf.reshape(rows, nbr, block)
    absmax = jnp.max(jnp.abs(blocks), axis=2)
    scale = absmax * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0).astype(jnp.float32)
    y = blocks * inv[..., None]
    if rounding == "nearest":
        q = jnp.round(y)
    elif rounding == "stochastic":
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, nbr * block), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, nbr * block), 0)
        idx = (row * width + col).reshape(rows, nbr, block)
        lo = jnp.floor(y)
        q = lo + (uniform01(salt, idx) < (y - lo)).astype(jnp.float32)
    else:
        raise ValueError(f"rounding {rounding!r}: expected 'stochastic' "
                         "or 'nearest'")
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    q = q.reshape(rows, nbr * block)[:, :width].reshape(shape)
    return q, scale.T


def blocked_dequant(q: jax.Array, scale: jax.Array,
                    block: int = DEFAULT_BLOCK) -> jax.Array:
    shape = tuple(q.shape)
    rows, width = _rows_width(shape)
    nbr = max(1, -(-width // block))
    qf = q.astype(jnp.float32).reshape(rows, width)
    if nbr * block != width:
        qf = jnp.pad(qf, ((0, 0), (0, nbr * block - width)))
    out = (qf.reshape(rows, nbr, block)
           * scale.astype(jnp.float32).reshape(nbr, rows).T[..., None])
    return out.reshape(rows, nbr * block)[:, :width].reshape(shape)


# ---------------------------------------------------------------------------
# Codecs
# ---------------------------------------------------------------------------

class F32Codec:
    """Passthrough: slots are stored exactly as the rule produced them.
    The engine special-cases ``passthrough`` and never even calls these."""

    name = "f32"
    passthrough = True

    def init(self, x):
        return x

    def encode(self, x, salt):
        return x

    def decode(self, enc):
        return enc


class BlockedInt8Codec:
    """Blocked absmax int8 with stochastic rounding (see module doc)."""

    name = "int8"
    passthrough = False

    def __init__(self, block: int = DEFAULT_BLOCK):
        self.block = block

    def init(self, x):
        # zeros encode exactly (scale 0) — built structurally, no hashing,
        # so rule init stays traceable under eval_shape without a key.
        return {"q": jnp.zeros(tuple(x.shape), jnp.int8),
                "scale": jnp.zeros(scale_shape(x.shape, self.block),
                                   jnp.float32)}

    def encode(self, x, salt):
        q, scale = blocked_quant(x, salt, self.block)
        return {"q": q, "scale": scale}

    def decode(self, enc):
        return blocked_dequant(enc["q"], enc["scale"], self.block)


CODECS = {"f32": F32Codec, "int8": BlockedInt8Codec,
          "blocked_int8": BlockedInt8Codec}


def get_codec(codec) -> Any:
    """Name or instance -> codec instance."""
    if isinstance(codec, str):
        if codec not in CODECS:
            raise ValueError(
                f"unknown state codec {codec!r}; choices: {sorted(CODECS)}")
        return CODECS[codec]()
    return codec


# ---------------------------------------------------------------------------
# Slot-tree traversal: apply the codec to the True leaves of a rule's
# ``slots`` mask.  Rule states here are dicts/bare arrays only; slot
# indices are assigned in sorted-key order (matching jax's dict-key
# ordering) so the generic scan path and hand-fused kernels agree on
# which salt quantizes which moment.
# ---------------------------------------------------------------------------

def map_slots(mask, state, fn):
    """``fn(slot_idx, slot_value)`` on each True mask leaf; other values
    pass through.  ``mask`` must mirror ``state``'s dict structure."""
    counter = [0]

    def rec(m, s):
        if m is True:
            i = counter[0]
            counter[0] += 1
            return fn(i, s)
        if m is None or m is False:
            return s
        if not isinstance(m, dict):
            raise TypeError(f"slots mask node {type(m).__name__}: expected "
                            "bool or dict")
        return {k: rec(m[k], s[k]) for k in sorted(s.keys())}

    return rec(mask, state)


def tree_init(codec, mask, state):
    if codec.passthrough or mask is None:
        return state
    return map_slots(mask, state, lambda i, s: codec.init(s))


def tree_decode(codec, mask, state):
    if codec.passthrough or mask is None:
        return state
    return map_slots(mask, state, lambda i, s: codec.decode(s))


def tree_encode(codec, mask, state, key, step, leaf_id):
    if codec.passthrough or mask is None:
        return state
    return map_slots(
        mask, state,
        lambda i, s: codec.encode(s, slot_salt(key, step, i, leaf_id)))
