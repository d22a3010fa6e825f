"""jit'd wrappers for the fused GWT-Adam kernel, with backend dispatch.

``fused_write_update{,_q8}`` are the GWT rules' ``vector_update``
(``repro.core.gwt``): the optimizer engine hands them a bucket's
gradient and parameter *leaves* with the bucket's stacked ``(L, …)``
state, and they make one fused-write launch per leaf on the leaf's own
buffers (``(1, rows, n)`` bitcast views), updating the leaf's slab of
the stacked moments in place.  No stacked copy of the gradients or
parameters is built, and no new parameter is sliced back out of one.
The limiter, the bias-corrected step, weight decay and the parameter
write all run inside the launch, so g̃ never round-trips HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.kernels.gwt_adam import kernel, ref


def _step_scalars(step, lr_t, alpha, weight_decay, b1, b2):
    """Bias-corrected step size and weight-decay coefficient, computed
    outside the kernel exactly as ``core.gwt._apply`` does (term order
    matters for bitwise parity with the jnp reference)."""
    t = step.astype(jnp.float32) + 1.0
    lr_mult = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    step_size = (lr_t * lr_mult * alpha).astype(jnp.float32)
    wd_coef = jnp.asarray(lr_t * weight_decay, jnp.float32)
    return step_size, wd_coef


def _on_each_device(call):
    """Under an ambient mesh, run the kernel whole on every device.

    GSPMD cannot partition a Mosaic kernel, so a bucket whose state is
    sharded (FSDP) would not compile.  A ``shard_map`` with replicated
    specs gathers the bucket's operands, runs the same launches on each
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


def _count_schedule(gs) -> None:
    """At trace time, one sample of the counter ``gwt.kernel.one_pass``
    (category ``gwt``) for this bucket: whether its gradient takes the
    one-pass lane shuffles (bf16, ``kernel._core_phases``) or the
    three-pass ones (any other dtype), in buckets and in gradient
    elements.  The samples of a traced step sum to its split."""
    one = gs[0].dtype == jnp.bfloat16
    n = sum(g.size for g in gs)
    obs.get().counter("gwt.kernel.one_pass", cat="gwt",
                      buckets_one_pass=int(one),
                      elements_one_pass=n if one else 0,
                      buckets_three_pass=int(not one),
                      elements_three_pass=0 if one else n)


def _count_in_place(gs, swap: bool) -> None:
    """At trace time, one sample of the counter ``gwt.kernel.in_place``
    (category ``gwt``) for this bucket, in gradient elements: those whose
    gradient and parameter the launches read, and whose parameter they
    write, in the leaves' own buffers (``elements_in_place``), and those
    that go through a copy first (``elements_stacked``: a FIRST-orient
    leaf's transpose)."""
    n = sum(g.size for g in gs)
    obs.get().counter("gwt.kernel.in_place", cat="gwt",
                      elements_in_place=0 if swap else n,
                      elements_stacked=n if swap else 0)


def _rows(x: jax.Array, swap: bool) -> jax.Array:
    """A leaf as the kernel's ``(1, rows, n)``: the transform runs along
    the last axis (the second to last for a FIRST-orient leaf, which is
    transposed), and the other axes merge into rows (a bitcast of a
    row-major leaf), which is exact: the transform is per row and the
    limiter norm per leaf."""
    if swap:
        x = jnp.swapaxes(x, -1, -2)
    return x.reshape(1, -1, x.shape[-1])


def _unrows(x: jax.Array, shape, swap: bool) -> jax.Array:
    """Inverse of :func:`_rows` for a leaf of ``shape``."""
    if not swap:
        return x.reshape(shape)
    t = tuple(shape[:-2]) + (shape[-1], shape[-2])
    return jnp.swapaxes(x.reshape(t), -1, -2)


def _leaf_by_leaf(launch, g3, p3, state, per_leaf, scalars):
    """One ``launch`` per leaf ``j``: its ``(1, rows, n)`` gradient and
    parameter, the bucket's stacked ``state`` arrays (threaded through
    the launches, which update slab ``j`` in place), slice ``j`` of each
    ``(L,)`` array of ``per_leaf`` and the shared ``scalars``.  Returns
    ``(new_ps, state, new_norm)`` with ``new_norm`` restacked ``(L,)``."""
    new_ps, norms = [], []
    for j, (g, p) in enumerate(zip(g3, p3)):
        out = launch(g, p, *state, *(a[j:j + 1] for a in per_leaf),
                     *scalars, j)
        new_ps.append(out[0])
        state = out[1:-1]
        norms.append(out[-1])
    return new_ps, tuple(state), jnp.concatenate(norms)


def _views(gs, ps, swap: bool):
    """The leaves as the kernel's ``(1, rows, n)``, and ``(rows, n)``."""
    g3 = [_rows(g, swap) for g in gs]
    return g3, [_rows(p, swap) for p in ps], g3[0].shape[1:]


def fused_write_update(gs, ps, state: dict, step: jax.Array,
                       prev_norm: jax.Array, *, lr_t, alpha: float,
                       weight_decay: float, gamma: float, use_limiter: bool,
                       level: int, swap: bool = False, b1: float = 0.9,
                       b2: float = 0.999, eps: float = 1e-6,
                       impl: str = "auto"):
    """DWT→Adam→inverse→limit→param-write of one bucket, one launch per
    leaf.

    ``gs``/``ps``: the bucket's gradient and parameter leaves, each read
    (and the parameter written) in its own buffer; ``state``: the
    bucket's stacked moments ``{"m", "v"}`` and ``prev_norm`` its
    ``(L,)`` limiter state, both updated in place.  ``swap``: the leaves
    are FIRST-orient (transform along axis -2).  Returns ``(new_ps,
    new_norm, new_state)``.  ``impl='jnp'`` routes each leaf to the tiled
    ``ref.gwt_adam_fused`` oracle with the SAME row-block choice as the
    kernel, so interpret/pallas bitwise-match it.

    The launches run under the named scope ``gwt.kernel`` (DESIGN.md
    §12).  Each is a call of the jitted :func:`_fused_write_update`: the
    Mosaic custom calls take their HLO name from that innermost
    name-stack entry, and leaves of one shape share its lowering."""
    impl = compat.resolve_kernel_impl(impl)
    _count_schedule(gs)
    _count_in_place(gs, swap)
    with jax.named_scope("gwt.kernel"):
        step_size, wd_coef = _step_scalars(step, lr_t, alpha, weight_decay,
                                           b1, b2)
        with jax.named_scope("optim.pack"):
            g3, p3, (mm, nn) = _views(gs, ps, swap)
            st3 = tuple(a.reshape(len(gs), mm, nn >> level)
                        for a in (state["m"], state["v"]))
        launch = functools.partial(
            _fused_write_update, level=level, gamma=gamma,
            use_limiter=use_limiter, weight_decay=weight_decay != 0, b1=b1,
            b2=b2, eps=eps, impl=impl)
        new_p, (m, v), new_norm = _leaf_by_leaf(
            launch, g3, p3, st3, (prev_norm.reshape(len(gs)),),
            (step_size, wd_coef))
        with jax.named_scope("optim.pack"):
            new_ps = [_unrows(x, g.shape, swap) for x, g in zip(new_p, gs)]
            m, v = m.reshape(state["m"].shape), v.reshape(state["v"].shape)
    return new_ps, new_norm, {"m": m, "v": v}


@functools.partial(jax.jit, static_argnames=(
    "level", "gamma", "use_limiter", "weight_decay", "b1", "b2", "eps",
    "impl"))
def _fused_write_update(g, p, m, v, prev_norm, step_size, wd_coef, leaf0,
                        *, level, gamma, use_limiter, weight_decay, b1, b2,
                        eps, impl):
    """One leaf's launch over its slab ``leaf0`` of the stacked moments."""
    kw = dict(level=level, gamma=gamma, use_limiter=use_limiter,
              weight_decay=weight_decay, b1=b1, b2=b2, eps=eps)
    if impl in ("pallas", "interpret"):
        return _on_each_device(functools.partial(
            kernel.gwt_adam_tile_fused, interpret=impl == "interpret",
            **kw))(g, p, m, v, prev_norm, step_size, wd_coef, leaf0)
    _, mm, nn = g.shape
    return ref.gwt_adam_fused(g, p, m, v, prev_norm, step_size, wd_coef,
                              leaf0, bm=kernel.fused_row_block(mm, nn, level),
                              **kw)


def fused_write_update_q8(gs, ps, state: dict, step: jax.Array,
                          key: jax.Array, leaf_ids: jax.Array,
                          prev_norm: jax.Array, *, lr_t, alpha: float,
                          weight_decay: float, gamma: float,
                          use_limiter: bool, level: int, block: int = 64,
                          swap: bool = False, b1: float = 0.9,
                          b2: float = 0.999, eps: float = 1e-6,
                          impl: str = "auto"):
    """``fused_write_update`` over blocked-int8 moments: dequant → update →
    stochastic requant AND limit+apply+write all inside each leaf's
    launch.  The codec's per-row blocks tile every shape, so
    ``pallas``/``interpret`` always run the kernel.  Returns ``(new_ps,
    new_norm, new_state)`` in the encoded layout; the launches run under
    ``gwt.kernel`` as there, each a call of :func:`_fused_write_update_q8`."""
    from repro.optim import codec as codec_lib
    impl = compat.resolve_kernel_impl(impl)
    _count_schedule(gs)
    _count_in_place(gs, swap)
    L = len(gs)
    enc = (state["m"]["q"], state["m"]["scale"], state["v"]["q"],
           state["v"]["scale"])
    with jax.named_scope("gwt.kernel"):
        step_size, wd_coef = _step_scalars(step, lr_t, alpha, weight_decay,
                                           b1, b2)
        salts = tuple(codec_lib.slot_salt(key, step, s, leaf_ids).reshape(L)
                      for s in (0, 1))
        with jax.named_scope("optim.pack"):
            g3, p3, (mm, nn) = _views(gs, ps, swap)
            st3 = tuple(a.reshape(L, mm, nn >> level) if a.dtype == jnp.int8
                        else a.reshape(L, -1, mm) for a in enc)
        launch = functools.partial(
            _fused_write_update_q8, level=level, block=block, gamma=gamma,
            use_limiter=use_limiter, weight_decay=weight_decay != 0, b1=b1,
            b2=b2, eps=eps, impl=impl)
        new_p, st3, new_norm = _leaf_by_leaf(
            launch, g3, p3, st3, salts + (prev_norm.reshape(L),),
            (step_size, wd_coef))
        with jax.named_scope("optim.pack"):
            new_ps = [_unrows(x, g.shape, swap) for x, g in zip(new_p, gs)]
            qm, sm, qv, sv = (a.reshape(b.shape) for a, b in zip(st3, enc))
    return new_ps, new_norm, {"m": {"q": qm, "scale": sm},
                              "v": {"q": qv, "scale": sv}}


@functools.partial(jax.jit, static_argnames=(
    "level", "block", "gamma", "use_limiter", "weight_decay", "b1", "b2",
    "eps", "impl"))
def _fused_write_update_q8(g, p, qm, sm, qv, sv, salt_m, salt_v, prev_norm,
                           step_size, wd_coef, leaf0, *, level, block,
                           gamma, use_limiter, weight_decay, b1, b2, eps,
                           impl):
    """One leaf's q8 launch over its slab ``leaf0`` of the stacked
    payloads and scales."""
    kw = dict(level=level, block=block, gamma=gamma,
              use_limiter=use_limiter, weight_decay=weight_decay, b1=b1,
              b2=b2, eps=eps)
    args = (g, p, qm, sm, qv, sv, salt_m, salt_v, prev_norm, step_size,
            wd_coef, leaf0)
    if impl in ("pallas", "interpret"):
        return _on_each_device(functools.partial(
            kernel.gwt_adam_tile_fused_q8, interpret=impl == "interpret",
            **kw))(*args)
    _, mm, nn = g.shape
    return ref.gwt_adam_fused_q8(
        *args, bm=kernel.q8_row_block(mm, nn, level, block), **kw)
