"""Mixture-of-Experts FFN: a top-k softmax router over ``n_experts`` and
SwiGLU experts, with the Switch-style load-balancing auxiliary loss and
optional shared (always-on) experts (Qwen-MoE).

Two dispatch paths, picked by the mesh the layer runs under:

* **Experts local** (no mesh, or a mesh whose expert axis is not split):
  dropless grouped matmuls.  The (token, k) pairs are sorted by expert,
  pairs whose expert this chip does not hold sorted past the end; the held
  experts' rows go through one grouped matmul per product (Megablox
  ``gmm``/``tgmm`` on the TPU, ``jax.lax.ragged_dot`` elsewhere), then
  back to token order, weighted by the gates.  In the dispatch buffer each
  held expert's group starts on a row tile and spans whole tiles, at
  least one, the last spanning the rest, so the grouped matmuls visit each
  row tile once whatever the routing: a buffer's work is set by its shape.
  The buffer is capped at twice the held experts' share of the pairs when
  the chip holds a share, with a buffer for every pair taken instead
  (``lax.cond``) when the held groups do not fit the cap, so no skew can
  drop a pair.  The combine and the dispatch's transpose read one buffer
  row per pair.  ``experts_held`` makes the layer a chip's share of an
  expert-parallel deployment: it holds experts ``0..experts_held-1``,
  routes over all ``n_experts`` (the gates renormalised over the top-k,
  held or not) and adds only what its own experts give; the exchange with
  the chips holding the others is not part of this layer.
* **Experts sharded** over the mesh's ``model`` axis (the ``expert``
  logical axis): GShard one-hot einsum dispatch with ``capacity_factor``
  at token-chunk granularity: the one-hot einsums shard like any matmul
  under GSPMD; pairs past an expert's capacity are dropped.

``expert_padding`` pads the expert WEIGHTS (router unchanged) so a 16-∤
expert count still shards evenly over a 16-way model axis; padded experts
are never routed.

The sublayer runs under the named scope ``moe`` (router, top-k and aux in
``moe.route``; sort, group sizes and gather in ``moe.dispatch``; the
grouped matmuls in ``moe.experts``; back to token order in
``moe.combine``), and each traced layer samples the counter ``moe.layer``.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import compat, obs
from repro.models.layers import Builder, mlp_init, mlp_apply, wsc


def n_held(cfg) -> int:
    """Experts whose weights the layer holds (padding not counted)."""
    return cfg.experts_held or cfg.n_experts


def moe_init(b: Builder, cfg) -> dict:
    d, dff = cfg.d_model, cfg.d_ff_expert
    E = n_held(cfg) + cfg.expert_padding  # padded experts never routed
    p = {
        "router": b.param((d, cfg.n_experts), ("embed", None)),
        "w_gate": b.param((E, d, dff), ("expert", "embed", "expert_mlp")),
        "w_up": b.param((E, d, dff), ("expert", "embed", "expert_mlp")),
        "w_down": b.param((E, dff, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(b, d, cfg.n_shared_experts * dff)
    return p


def experts_sharded() -> bool:
    """Whether the ambient mesh splits the expert axis (its ``model``
    axis, which the ``expert`` rule maps to, has more than one device)."""
    mesh = compat.get_abstract_mesh()
    return mesh is not None and dict(mesh.shape).get("model", 1) > 1


def moe_apply(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    with jax.named_scope("moe"):
        if experts_sharded():
            if n_held(cfg) != cfg.n_experts:
                raise NotImplementedError(
                    "experts_held is a chip's share on one device; on a mesh "
                    "the model axis shards the whole expert set")
            out, aux = _moe_sharded(p, cfg, x)
        else:
            out, aux = _moe_grouped(p, cfg, x)
        if cfg.n_shared_experts:
            out = out + mlp_apply(p["shared"], x)
        return out, aux


def _route(p, cfg, xt: jax.Array):
    """Router logits and softmax in f32 over all ``n_experts``, the top-k
    with its gates renormalised, the pairs per expert and the Switch aux
    loss ``E · Σ_e f_e · P_e``."""
    E, K = cfg.n_experts, cfg.top_k
    T = xt.shape[0]
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                # renormalize
    counts = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32).sum((0, 1))
    aux = E * jnp.sum(counts.astype(jnp.float32) / T * probs.mean(0))
    return gate_vals, expert_idx, counts, aux


# ---------------------------------------------------------------------------
# Experts local: dropless grouped matmuls
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(xt, sel, pos, held, k: int):
    """Row ``j`` of the buffer: the token of pair ``sel[j]`` (pairs are
    ``token * k + slot``).  Its transpose is a gather too — each held
    pair's row back from ``pos``, its row in the buffer, summed over the
    token's ``k`` — where a plain gather's would be a scatter-add; pairs
    not held (``held`` false) have no row and are left out."""
    return jnp.take(xt, sel // k, axis=0, mode="clip")


def _dispatch_fwd(xt, sel, pos, held, k):
    return _dispatch(xt, sel, pos, held, k), (pos, held)


def _dispatch_bwd(k, res, g):
    pos, held = res
    T = pos.shape[0] // k
    dx = jnp.take(g, pos, axis=0, mode="clip").reshape(T, k, g.shape[-1])
    dx = jnp.where(held.reshape(T, k, 1), dx.astype(jnp.float32), 0.0)
    return dx.sum(1).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, gate_vals, held, pos, sel, valid):
    """Back to token order, weighted by the gates: ``out[t] = Σ_k
    gate[t, k] · ys[pos[t·K + k]]`` over the token's held pairs (f32).
    The transpose reads the buffer's rows (``sel``, ``valid`` where a row
    holds a pair) from the tokens' cotangents, a gather of the buffer's
    rows, not of every pair."""
    T, K = held.shape
    y = jnp.take(ys, pos, axis=0, mode="clip").reshape(T, K, ys.shape[-1])
    y = jnp.where(held[..., None], y.astype(jnp.float32), 0.0)
    return jnp.sum(gate_vals[..., None] * y, axis=1)


def _combine_fwd(ys, gate_vals, held, pos, sel, valid):
    return (_combine(ys, gate_vals, held, pos, sel, valid),
            (ys, gate_vals, held, pos, sel, valid))


def _combine_bwd(res, g):
    ys, gate_vals, held, pos, sel, valid = res
    T, K = held.shape
    gs = jnp.take(g, sel // K, axis=0, mode="clip")            # (rows, d)
    w = jnp.where(valid, gate_vals.reshape(-1)[sel], 0.0)
    g_ys = (w[:, None] * gs).astype(ys.dtype)
    # rows that hold no pair may be undefined: masked below, never summed
    dots = jnp.sum(gs * ys.astype(jnp.float32), axis=-1)
    g_gate = jnp.where(held, jnp.take(dots, pos, mode="clip").reshape(T, K),
                       0.0)
    return g_ys, g_gate.astype(gate_vals.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


_GMM_ROWS = 128
_HELD_SLACK = 2.0   # capped buffer: rows for twice the held experts' share


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Megablox tiles: 128 rows (the buffer's groups start on them), the
    whole contraction and output width up to 2048 (one weight block per
    expert stays in VMEM across its row tiles)."""
    return _GMM_ROWS, min(k, 2048), min(n, 2048)


def grouped_matmul(lhs, rhs, group_sizes):
    """The first ``sum(group_sizes)`` rows of ``lhs``, grouped by
    ``group_sizes`` (one entry per ``rhs`` matrix), each group times its
    ``rhs`` (G, k, n) matrix.  Rows past the groups are left undefined:
    every consumer masks them.  On the TPU the Megablox Pallas kernels
    (``gmm``, and ``tgmm`` for the weight gradient), whose grids visit only
    the groups' row tiles; elsewhere ``jax.lax.ragged_dot``."""
    impl = compat.resolve_kernel_impl(None)
    if impl in ("pallas", "interpret"):
        from jax.experimental.pallas.ops.tpu.megablox import ops as mblx
        m = lhs.shape[0]
        pad = -m % _GMM_ROWS            # the kernel tiles whole row tiles
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = mblx.gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling,
                       None, None, False, impl == "interpret")
        return out[:m]
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def buffer_rows(cfg, pairs: int) -> Tuple[int, ...]:
    """Rows of the dispatch buffers the layer compiles, smallest first: a
    buffer capped at twice the held experts' share of the ``pairs`` where
    that is fewer than all of them, and one with a row for every pair,
    taken when the held pairs do not fit the cap.  Each has a row tile
    more per held expert than the pairs it is sure to hold
    (:func:`pair_rows`), for :func:`_spans`."""
    H, E = n_held(cfg), cfg.n_experts
    tiles = lambda n: math.ceil(n / _GMM_ROWS) * _GMM_ROWS
    cap, full = tiles(pairs * H / E * _HELD_SLACK), tiles(pairs)
    pad = H * _GMM_ROWS
    return (cap + pad, full + pad) if cap < full else (full + pad,)


def pair_rows(cfg, rows: int) -> int:
    """The pairs a buffer of ``rows`` rows holds whatever the routing."""
    return rows - n_held(cfg) * _GMM_ROWS


def _spans(sizes):
    """Rows each held expert's group spans in the buffer: whole row tiles
    of the grouped matmuls, at least one."""
    return jnp.maximum(1, -(-sizes // _GMM_ROWS)) * _GMM_ROWS


def _fill(spans, rows: int):
    """The last group also spans the rest of the buffer."""
    return spans.at[-1].add(rows - jnp.sum(spans))


def _place(rows: int, key, order, inv, sizes):
    """The buffer of ``rows`` rows: each held expert's pairs in a group
    that starts on a row tile and spans whole tiles, at least one, the
    last group spanning the rest.  The grouped matmuls then visit every
    row tile once, for any routing whose groups fit: the same work at
    every step.  Returns ``(sel, pos, valid, group_sizes)``: row ``j``
    holds pair ``sel[j]`` where ``valid[j]`` (token 0's otherwise, whose
    output is masked and whose cotangent is zero), and held pair ``p``
    sits in row ``pos[p]``."""
    H = sizes.shape[0]
    group = _fill(_spans(sizes), rows)
    start = jnp.cumsum(group) - group
    first = jnp.cumsum(sizes) - sizes           # in expert order
    j = jnp.arange(rows, dtype=jnp.int32)
    g = jnp.sum(j[:, None] >= (start + group)[None, :-1], axis=1,
                dtype=jnp.int32)                # the group of row j
    local = j - start[g]
    valid = local < sizes[g]
    sel = jnp.where(valid, jnp.take(order, first[g] + local, mode="clip"),
                    0)
    e = jnp.minimum(key, H - 1)
    pos = start[e] + inv - first[e]
    return sel, pos, valid, group


def _experts_at(rows: int):
    """The held experts' output over a dispatch buffer of ``rows`` rows
    (:func:`_place`), which holds every held pair when their groups
    fit."""
    def f(xt, gate_vals, w_gate, w_up, w_down, key, order, inv, sizes):
        T, K = gate_vals.shape
        held = (key < sizes.shape[0]).reshape(T, K)
        with jax.named_scope("moe.dispatch"):
            sel, pos, valid, group = _place(rows, key, order, inv, sizes)
            xs = _dispatch(xt, sel, pos, held, K)              # (rows, d)
        with jax.named_scope("moe.experts"):
            h = jax.nn.silu(grouped_matmul(xs, w_gate, group)) \
                * grouped_matmul(xs, w_up, group)
            ys = grouped_matmul(h, w_down, group)              # (rows, d)
        with jax.named_scope("moe.combine"):
            return _combine(ys, gate_vals, held, pos, sel, valid)
    return f


def _by_buffer(rows, sizes, fn, *args):
    """``fn(r)(*args)`` for the smallest buffer of ``rows`` that the
    groups of the held pairs (``sizes`` of them) fit, picked on the
    device."""
    if len(rows) == 1:
        return fn(rows[0])(*args)
    return jax.lax.cond(jnp.sum(_spans(sizes)) <= rows[0], fn(rows[0]),
                        fn(rows[1]), *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(rows, xt, gate_vals, w_gate, w_up, w_down, key, order,
                  inv, sizes):
    """Σ over each token's held pairs of gate · expert(x), through the
    smallest of the ``rows`` buffers that fits.  Its only residuals are its
    arguments: the backward runs the chosen buffer's forward again inside
    its own branch (the layer is rematerialised anyway).  Differentiated
    through, ``lax.cond`` would hand each branch's residuals out of both,
    the capped branch filling the full buffer's with zeros."""
    return _by_buffer(rows, sizes, _experts_at, xt, gate_vals, w_gate, w_up,
                      w_down, key, order, inv, sizes)


def _held_experts_fwd(rows, *args):
    return _held_experts(rows, *args), args


def _held_experts_bwd(rows, args, g):
    key, order, inv, sizes = args[5:]

    def grads(r):
        def vjp(g, *diff):
            f = lambda *d: _experts_at(r)(*d, key, order, inv, sizes)
            return jax.vjp(f, *diff)[1](g)
        return vjp
    return (*_by_buffer(rows, sizes, grads, g, *args[:5]),
            None, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _moe_grouped(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    K, H = cfg.top_k, n_held(cfg)
    T = B * S
    rows = buffer_rows(cfg, T * K)
    xt = x.reshape(T, d)
    obs.get().counter("moe.layer", cat="moe", tokens=T,
                      experts_routed=cfg.n_experts, experts_held=H,
                      top_k=K, pairs=T * K,
                      buffer_rows=pair_rows(cfg, max(rows)),
                      capped_rows=pair_rows(cfg, min(rows)))
    with jax.named_scope("moe.route"):
        gate_vals, expert_idx, counts, aux = _route(p, cfg, xt)
    with jax.named_scope("moe.dispatch"):
        # pairs of experts not held sort past the held groups
        key = jnp.where(expert_idx < H, expert_idx, H).reshape(T * K)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
    w = [p[name][:H] for name in ("w_gate", "w_up", "w_down")]  # no padding
    out = _held_experts(rows, xt, gate_vals, *w, key, order, inv,
                        counts[:H])
    return out.astype(x.dtype).reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Experts sharded over the mesh: GShard one-hot dispatch with capacity
# ---------------------------------------------------------------------------

_MOE_CHUNK_TOKENS = 8192  # global tokens per dispatch chunk


def _moe_sharded(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Long sequences are processed in token chunks (scan): the (T, E, C)
    dispatch buffers scale with the chunk, not the sequence.  Chunking is
    exact for the outputs; the Switch aux loss becomes a per-chunk average
    (gradient-equivalent in expectation)."""
    B, S, d = x.shape
    total = B * S
    if total > _MOE_CHUNK_TOKENS and S % (_MOE_CHUNK_TOKENS // B or 1) == 0 \
            and _MOE_CHUNK_TOKENS >= B:
        sc = _MOE_CHUNK_TOKENS // B
        xcs = x.reshape(B, S // sc, sc, d).swapaxes(0, 1)

        # checkpointed chunk body: without it the chunk scan's AD residuals
        # stack every chunk's (E, C, dff) expert activations
        @jax.checkpoint
        def step_inner(xc):
            return _moe_onehot(p, cfg, xc)

        def step(_, xc):
            out_c, aux_c = step_inner(xc)
            return None, (out_c, aux_c)

        _, (outs, auxs) = jax.lax.scan(step, None, xcs)
        return outs.swapaxes(0, 1).reshape(B, S, d), auxs.mean()
    return _moe_onehot(p, cfg, x)


def _moe_onehot(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_pad = E + cfg.expert_padding
    T = B * S
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        gate_vals, expert_idx, _, aux = _route(p, cfg, xt)

    with jax.named_scope("moe.dispatch"):
        C = max(1, math.ceil(T * K / E * cfg.capacity_factor))
        # slot of each (token, k) inside its expert's queue (order-preserving)
        onehot = jax.nn.one_hot(expert_idx.reshape(T * K), E,
                                dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)            # (T·K, E)
        slot = jnp.take_along_axis(pos, expert_idx.reshape(T * K, 1),
                                   axis=1)[:, 0]
        slot = jnp.where(slot < C, slot, C).reshape(T, K)      # C = dropped
        oh_e = (jax.nn.one_hot(expert_idx.reshape(T * K), E_pad,
                               dtype=x.dtype).reshape(T, K, E_pad))
        oh_c = jax.nn.one_hot(slot, C + 1, dtype=x.dtype)[..., :C]
        disp = jnp.einsum("tke,tkc->tec", oh_e, oh_c)
        comb = jnp.einsum("tke,tkc,tk->tec", oh_e, oh_c,
                          gate_vals.astype(x.dtype))
        xe = wsc(jnp.einsum("td,tec->ecd", xt, disp), "model")  # EP-sharded

    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        ye = wsc(jnp.einsum("ecf,efd->ecd", h, p["w_down"]), "model")
    with jax.named_scope("moe.combine"):
        out = jnp.einsum("ecd,tec->td", ye, comb)
    return out.reshape(B, S, d), aux
