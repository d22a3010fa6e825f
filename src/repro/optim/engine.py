"""Leaf-plan + bucketed execution engine shared by all optimizer families.

The module-wise strategy ("GWT on attention/MLP, Adam elsewhere") used to be
re-implemented as an unrolled Python loop over pytree leaves in three places
(``core/gwt.py``, ``optim/standard.py``, ``optim/lowrank.py``).  That bloats
the jitted trace linearly with layer count and invokes the fused kernel once
per leaf.  This engine replaces all three loops:

1. **LeafPlan** — computed once per ``init``/``update`` trace from the param
   *structure* (paths + shapes + dtypes only, so it is identical under
   ``jax.eval_shape`` and inside ``jit``): every '/'-joined leaf path is
   assigned a :class:`LeafRule` by the optimizer's ``assign`` function.

2. **Buckets** — leaves with identical ``(rule.kind, rule.sig, shape,
   dtype)`` are grouped.  E.g. all 12 ``layers/*/mlp/w1`` matrices of a
   deep config become one ``(12, m, n)`` stack.  Bucket names are stable
   and path-keyed — ``"<kind>__<first-leaf-path>"`` — so checkpoints
   save/restore by name, not by flatten order.

3. **Execution** — one ``jax.lax.scan`` over the stacked leading axis per
   bucket (the scan body is traced *once* regardless of layer count), or a
   single call when the rule provides ``vector_update``: it takes the
   bucket's gradient and parameter *leaves* with its stacked state and
   returns the new leaves, so no stacked copy of them is built (the fused
   Pallas GWT-Adam rule makes one launch per leaf on the leaf's own
   buffers, updating its slab of the stacked moments in place).

State layout::

    {"step": i32[],
     ["codec_key": u32[],]                # quantizing codecs only
     "buckets": {"<kind>__<path>": <stacked per-leaf state pytree>, ...}}

The per-leaf state inside a bucket is exactly what the pre-engine
optimizers stored per leaf, so migration from the legacy
``{"step", "leaves": (...,)}`` tuple layout is a pure regrouping
(:meth:`Engine.migrate_legacy` / :meth:`Engine.to_legacy`).

**State substrate (DESIGN.md §8):** rules declare which state arrays are
*moment slots* (``LeafRule.slots``); :func:`build` takes a ``codec``
(``repro.optim.codec``) and stores slot arrays encoded — dequantize →
update → requantize fused into the per-bucket scan body (or handed whole
to a ``codec_native`` ``vector_update``, e.g. the fused GWT-Adam q8
kernel).  The default ``f32`` codec short-circuits every wrapper, so its
update graphs are bitwise-identical to the pre-codec engine.  Migration
between codecs on resume is :func:`transcode`.

Custom rules: pass any ``assign(path, leaf) -> LeafRule`` to :func:`build`
(see DESIGN.md and the README rule table).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.optim import codec as codec_lib
from repro.optim.base import Optimizer, flatten_with_paths


class LeafRule(NamedTuple):
    """How one leaf updates.

    * ``kind`` — rule family name (``plain`` / ``gwt_last`` / ``gwt_first``
      / ``lowrank`` / ``sgd`` / ``muon`` / custom); becomes the bucket-name
      prefix.
    * ``sig`` — extra static signature: leaves bucket together only when
      their ``(kind, sig, shape, dtype)`` all match.  Hyperparameters that
      vary *between leaves of one optimizer* must be in ``sig``.
    * ``init(leaf) -> state`` — per-leaf state pytree (arrays only) from an
      array or ``ShapeDtypeStruct``.
    * ``update(g, p, state, step, leaf_id) -> (new_p, new_state)`` — one
      leaf's update.  ``leaf_id`` is the i32 flatten-order index (used e.g.
      by APOLLO's per-leaf random projector).
    * ``vector_update`` — optional ``(gs, ps, state_stk, step, leaf_ids)
      -> (new_ps, new_state_stk)`` over a whole bucket in one call: the
      bucket's gradient and parameter leaves (lists, flatten order) and
      its stacked ``(L, ...)`` state; used instead of the scan when
      present (fused kernels, which then read and write each leaf in its
      own buffer).
    * ``slots`` — bool pytree mirroring the per-leaf state structure:
      True marks a *moment slot* the state codec may re-encode (int8 etc.).
      ``None`` = no slots; the codec never touches this rule's state.
    * ``codec_native`` — the rule's ``vector_update`` handles encoded
      slots itself (signature grows a trailing ``codec_key``); the engine
      passes the encoded bucket straight through instead of wrapping with
      generic decode/encode (the fused GWT-Adam q8 kernel requantizes in
      its epilogue).
    * ``taps`` — optional observability hook ``(g_stk, p_stk, new_p_stk,
      old_state_stk, new_state_stk, step) -> {name: f32 scalar}`` adding
      rule-specific scalars (wavelet band energy, limiter clip count) to
      the bucket's generic taps.  States arrive in *stored* layout —
      encoded slots stay encoded — so taps piggyback on already-computed
      results (e.g. the fused kernel's ``prev_norm`` pass) instead of
      re-deriving them.  Only runs inside ``Optimizer.tapped_update``;
      the plain ``update`` graph never traces it (DESIGN.md §12).
    """

    kind: str
    init: Callable[[Any], Any]
    update: Callable[..., Tuple[jax.Array, Any]]
    sig: Tuple = ()
    vector_update: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    slots: Any = None
    codec_native: bool = False
    taps: Optional[Callable[..., Any]] = None


class Bucket(NamedTuple):
    name: str
    rule: LeafRule
    indices: Tuple[int, ...]   # positions in flatten order
    paths: Tuple[str, ...]
    template: Any              # ShapeDtypeStruct of the (shared) leaf shape


class LeafPlan(NamedTuple):
    buckets: Tuple[Bucket, ...]
    paths: Tuple[str, ...]
    n_leaves: int


def build_plan(assign: Callable[[str, Any], LeafRule], params) -> LeafPlan:
    """Group leaves into buckets of identical ``(kind, sig, shape, dtype)``.

    Depends only on paths/shapes/dtypes — safe to recompute at trace time.
    """
    paths, leaves, _ = flatten_with_paths(params)
    groups: dict = {}
    for i, (path, leaf) in enumerate(zip(paths, leaves)):
        rule = assign(path, leaf)
        key = (rule.kind, rule.sig, tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
        if key in groups:
            groups[key][1].append(i)
        else:
            groups[key] = (rule, [i])
    buckets = []
    for rule, idxs in sorted(groups.values(), key=lambda g: g[1][0]):
        first = paths[idxs[0]].replace("/", ".")
        lf = leaves[idxs[0]]
        buckets.append(Bucket(name=f"{rule.kind}__{first}", rule=rule,
                              indices=tuple(idxs),
                              paths=tuple(paths[i] for i in idxs),
                              template=jax.ShapeDtypeStruct(
                                  tuple(lf.shape), jnp.dtype(lf.dtype))))
    return LeafPlan(tuple(buckets), tuple(paths), len(paths))


def _stack_states(per_leaf: Sequence[Any]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_leaf)


def _slice_state(state, j: int):
    return jax.tree_util.tree_map(lambda a: a[j], state)


class Engine:
    """Plan/migration companion of an engine-built :class:`Optimizer`."""

    def __init__(self, assign: Callable[[str, Any], LeafRule],
                 bucketed: bool = True, codec="f32", codec_seed: int = 0):
        self.assign = assign
        self.bucketed = bucketed
        self.codec = codec_lib.get_codec(codec)
        self.codec_seed = codec_seed
        self._validated: set = set()  # (kind, sig, shape, dtype) probed OK

    def plan(self, params) -> LeafPlan:
        plan = build_plan(self.assign, params)
        self._validate(plan)
        return plan

    def _validate(self, plan: LeafPlan) -> None:
        """Fail at build time — with the leaf path — when a rule cannot
        handle a leaf it was assigned (e.g. a wavelet rule forced onto a
        non-divisible recurrent kernel).  ``eval_shape`` probes ``init`` and
        one raw-state ``update`` per distinct ``(kind, sig, shape, dtype)``
        signature, so the error surfaces before any scan/jit trace and the
        steady-state cost is a memoized set lookup."""
        for b in plan.buckets:
            leaf = jax.ShapeDtypeStruct(b.template.shape, b.template.dtype)
            key = (b.rule.kind, b.rule.sig, leaf.shape, str(leaf.dtype))
            if key in self._validated:
                continue

            def probe(p):
                st = b.rule.init(p)
                g = jnp.zeros(p.shape, p.dtype)
                step = jnp.zeros((), jnp.int32)
                return b.rule.update(g, p, st, step, 0)

            try:
                jax.eval_shape(probe, leaf)
            except Exception as e:  # noqa: BLE001 — re-raise with the path
                raise ValueError(
                    f"rule {b.rule.kind!r} cannot handle leaf "
                    f"{b.paths[0]!r} (shape={tuple(leaf.shape)}, "
                    f"dtype={leaf.dtype}): {e}") from e
            self._validated.add(key)

    def codec_key(self) -> Optional[jax.Array]:
        """The concrete uint32 rounding key ``init`` stores in
        ``opt_state["codec_key"]`` (None for passthrough codecs)."""
        if self.codec.passthrough:
            return None
        return codec_lib.make_key(self.codec_seed)

    # -- legacy tuple-layout interop ---------------------------------------
    def legacy_like(self, params):
        """Abstract state in the pre-engine layout ``{"step", "leaves"}``
        (per-leaf states as a flatten-order tuple) — used as the ``like``
        tree when restoring an old checkpoint.  Legacy checkpoints predate
        the codec layer, so states here are raw (f32) regardless of this
        engine's codec; transcode after migrating.  ShapeDtypeStruct
        leaves: no allocation."""
        def build(p):
            paths, leaves, _ = flatten_with_paths(p)
            per_leaf = tuple(self.assign(pa, l).init(l)
                             for pa, l in zip(paths, leaves))
            return {"step": jnp.zeros((), jnp.int32), "leaves": per_leaf}
        return jax.eval_shape(build, params)

    def migrate_legacy(self, old_state, params):
        """Regroup a legacy ``{"step", "leaves": (...,)}`` state into the
        named bucket layout (values are untouched, only stacked)."""
        plan = self.plan(params)
        leaves = old_state["leaves"]
        buckets = {b.name: _stack_states([leaves[i] for i in b.indices])
                   for b in plan.buckets}
        return {"step": old_state["step"], "buckets": buckets}

    def to_legacy(self, state, params):
        """Inverse of :meth:`migrate_legacy` (downgrade path / tests)."""
        plan = self.plan(params)
        per_leaf = [None] * plan.n_leaves
        for b in plan.buckets:
            st = state["buckets"][b.name]
            for j, i in enumerate(b.indices):
                per_leaf[i] = _slice_state(st, j)
        return {"step": state["step"], "leaves": tuple(per_leaf)}


def _constrain_bucket(state, sharding_tree):
    """Pin one bucket's stacked state to its NamedSharding tree (a
    per-bucket hint from ``distributed.sharding.gwt_state_shardings``).
    Works eagerly, under ``jit``, and under ``eval_shape`` — NamedSharding
    leaves carry their own mesh, so no ambient context is needed.  A hint
    that doesn't fit the state — wrong structure (stale optimizer config,
    wrong dict level) or shape-incompatible specs — is a caller bug and
    raises rather than silently skipping placement."""
    if sharding_tree is None:
        return state
    if (jax.tree_util.tree_structure(state)
            != jax.tree_util.tree_structure(sharding_tree)):
        raise ValueError(
            f"state_shardings hint structure "
            f"{jax.tree_util.tree_structure(sharding_tree)} does not match "
            f"bucket state {jax.tree_util.tree_structure(state)} — pass "
            f"gwt_state_shardings(...)['buckets'] for the SAME "
            f"level/host/eligible configuration")
    return jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                  state, sharding_tree)


def _decode_stacked(codec, mask, st):
    return jax.vmap(lambda s: codec_lib.tree_decode(codec, mask, s))(st)


def _encode_stacked(codec, mask, st, key, step, lids):
    return jax.vmap(
        lambda s, lid: codec_lib.tree_encode(codec, mask, s, key, step,
                                             lid))(st, lids)


def _codec_taps(ns) -> dict:
    """Generic int8-substrate taps from an *encoded* stacked bucket state:
    saturation rate (fraction of ``q`` codes at the ±127 rails — persistent
    saturation means the blocked absmax scale is pinned by outliers) and
    the max block absmax (``scale·127``).  Empty for unencoded buckets."""
    sat = None
    total = 0
    absmax = None
    for path, leaf in zip(*flatten_with_paths(ns)[:2]):
        tail = path.rsplit("/", 1)[-1]
        if tail == "q" and leaf.dtype == jnp.int8:
            hits = jnp.sum((jnp.abs(leaf.astype(jnp.int32)) >= 127)
                           .astype(jnp.float32))
            sat = hits if sat is None else sat + hits
            total += int(leaf.size)
        elif tail == "scale" and leaf.dtype == jnp.float32:
            mx = jnp.max(leaf)
            absmax = mx if absmax is None else jnp.maximum(absmax, mx)
    if total == 0:
        return {}
    out = {"q8_sat_rate": sat / jnp.float32(total)}
    if absmax is not None:
        out["q8_absmax"] = absmax * jnp.float32(127.0)
    return out


def build(assign: Callable[[str, Any], LeafRule],
          bucketed: bool = True, state_shardings=None,
          codec="f32", codec_seed: int = 0) -> Optimizer:
    """Build an :class:`Optimizer` from a leaf-rule assignment.

    ``bucketed=True`` (default) executes one scan / vectorized kernel call
    per bucket; ``bucketed=False`` unrolls leaf-by-leaf (the pre-engine
    reference semantics — same state layout, used in equivalence tests).

    ``state_shardings`` — optional per-bucket sharding hints: a dict
    ``{bucket_name: NamedSharding tree}`` (the ``"buckets"`` entry of
    ``distributed.sharding.gwt_state_shardings``).  ``init`` places each
    bucket's stacked state on its hinted layout and ``update`` re-pins the
    new state, so the sharded train path never round-trips optimizer
    state through an unconstrained (GSPMD's-choice) layout.

    ``codec`` — state-substrate codec (name or instance, see
    ``repro.optim.codec``).  Rule state arrays marked in ``rule.slots``
    are stored encoded; decode → update → requantize happens per leaf
    inside the scan body (never materializing a decoded bucket), or inside
    a ``codec_native`` rule's own fused ``vector_update``.  ``codec_seed``
    derives the stochastic-rounding key carried in the state.
    """
    eng = Engine(assign, bucketed, codec=codec, codec_seed=codec_seed)
    cdc = eng.codec
    quant = not cdc.passthrough
    hints = state_shardings or {}

    def init(params):
        plan = eng.plan(params)
        _, leaves, _ = flatten_with_paths(params)

        def leaf_init(rule, leaf):
            st = rule.init(leaf)
            return codec_lib.tree_init(cdc, rule.slots, st) if quant else st

        buckets = {
            b.name: _constrain_bucket(
                _stack_states([leaf_init(b.rule, leaves[i])
                               for i in b.indices]),
                hints.get(b.name))
            for b in plan.buckets}
        out = {"step": jnp.zeros((), jnp.int32), "buckets": buckets}
        if quant:
            out["codec_key"] = eng.codec_key()
        return out

    def _run(grads, state, params, with_taps: bool):
        # ``with_taps`` is a Python-level flag resolved at trace time: the
        # False trace is op-for-op the pre-taps update graph, so the plain
        # ``update`` channel stays bitwise-identical (DESIGN.md §12).
        step = state["step"]
        key = state.get("codec_key")
        plan = eng.plan(params)
        _, gleaves, treedef = flatten_with_paths(grads)
        pleaves = jax.tree_util.tree_leaves(params)
        new_leaves = [None] * plan.n_leaves
        new_buckets = {}
        taps: dict = {}
        for b in plan.buckets:
            st = state["buckets"][b.name]
            lids = jnp.asarray(b.indices, jnp.int32)
            coded = quant and b.rule.slots is not None

            def leaf_update(g, p, s, lid, rule=b.rule, coded=coded):
                # dequant -> update -> requant, fused per leaf: the decoded
                # f32 moments live only inside this body's trace.
                if coded:
                    s = codec_lib.tree_decode(cdc, rule.slots, s)
                new_p, ns = rule.update(g, p, s, step, lid)
                if coded:
                    ns = codec_lib.tree_encode(cdc, rule.slots, ns, key,
                                               step, lid)
                return new_p, ns

            gs = [gleaves[i] for i in b.indices]
            ps = [pleaves[i] for i in b.indices]
            if not bucketed:
                outs = [leaf_update(g, p, _slice_state(st, j), lids[j])
                        for j, (g, p) in enumerate(zip(gs, ps))]
                new_ps = [o[0] for o in outs]
                ns = _stack_states([o[1] for o in outs])
            elif b.rule.vector_update is not None:
                # leaves in, leaves out: the rule reads and writes each
                # leaf where it lives and only the state is stacked
                if coded and b.rule.codec_native:
                    new_ps, ns = b.rule.vector_update(gs, ps, st, step,
                                                      lids, key)
                elif coded:
                    dec = _decode_stacked(cdc, b.rule.slots, st)
                    new_ps, ns = b.rule.vector_update(gs, ps, dec, step,
                                                      lids)
                    ns = _encode_stacked(cdc, b.rule.slots, ns, key, step,
                                         lids)
                else:
                    new_ps, ns = b.rule.vector_update(gs, ps, st, step,
                                                      lids)
            else:
                # ``optim.pack`` names the ops that bring a bucket's leaves
                # into the stacked layout and back (DESIGN.md §12)
                with jax.named_scope("optim.pack"):
                    g_stk, p_stk = jnp.stack(gs), jnp.stack(ps)

                def body(_, xs):
                    g, p, s, lid = xs
                    return None, leaf_update(g, p, s, lid)
                _, (np_stk, ns) = jax.lax.scan(
                    body, None, (g_stk, p_stk, st, lids))
                with jax.named_scope("optim.pack"):
                    new_ps = [np_stk[j] for j in range(len(gs))]
            if with_taps:
                # the taps read the bucket stacked; only the tapped
                # update builds these stacks
                g_stk, p_stk, np_stk = (jnp.stack(x) for x in
                                        (gs, ps, new_ps))
                g32 = g_stk.astype(jnp.float32)
                d32 = np_stk.astype(jnp.float32) - p_stk.astype(jnp.float32)
                tp = {"grad_ssq": jnp.sum(g32 * g32),
                      "update_ssq": jnp.sum(d32 * d32)}
                if coded:
                    tp.update(_codec_taps(ns))
                if b.rule.taps is not None:
                    tp.update(b.rule.taps(g_stk, p_stk, np_stk, st, ns,
                                          step))
                for k, v in tp.items():
                    taps[f"{b.name}/{k}"] = jnp.asarray(v, jnp.float32)
            new_buckets[b.name] = _constrain_bucket(ns, hints.get(b.name))
            for i, new_p in zip(b.indices, new_ps):
                new_leaves[i] = new_p
        out = {"step": step + 1, "buckets": new_buckets}
        if quant:
            out["codec_key"] = key
        return jax.tree_util.tree_unflatten(treedef, new_leaves), out, taps

    def update(grads, state, params):
        new_params, out, _ = _run(grads, state, params, with_taps=False)
        return new_params, out

    def tapped_update(grads, state, params):
        """``update`` plus per-bucket observability scalars — the on-device
        tap channel (DESIGN.md §12).  Taps read each bucket's grads and
        params stacked, and build those stacks themselves; the unrolled
        reference engine exposes no tapped channel."""
        return _run(grads, state, params, with_taps=True)

    return Optimizer(init, update, engine=eng,
                     tapped_update=tapped_update if bucketed else None)


def transcode(state, params, src: Optimizer, dst: Optimizer):
    """Re-encode an optimizer state between codecs (``--resume`` across a
    ``--state-codec`` change): decode every slot with ``src``'s codec,
    re-encode with ``dst``'s.  Both optimizers must share the same rule
    assignment (same model/optimizer config) — only the substrate differs.
    Values are preserved up to the destination codec's quantization."""
    eng_s, eng_d = src.engine, dst.engine
    plan = eng_s.plan(params)
    step = state["step"]
    key = eng_d.codec_key()
    new_buckets = {}
    for b in plan.buckets:
        st = state["buckets"][b.name]
        if b.rule.slots is not None and not eng_s.codec.passthrough:
            st = _decode_stacked(eng_s.codec, b.rule.slots, st)
        if b.rule.slots is not None and not eng_d.codec.passthrough:
            lids = jnp.asarray(b.indices, jnp.int32)
            st = _encode_stacked(eng_d.codec, b.rule.slots, st, key, step,
                                 lids)
        new_buckets[b.name] = st
    out = {"step": step, "buckets": new_buckets}
    if key is not None:
        out["codec_key"] = key
    return out


def state_bytes(optimizer: Optimizer, params) -> int:
    """Exact optimizer-state bytes via ``eval_shape`` — no analytic model,
    correct for every host/rule combination (train.py's accounting).
    Codec-aware for free: ``init`` builds the encoded layout (int8 ``q`` +
    f32 scales), so the abstract tree already has the substrate's dtypes."""
    abstract = jax.eval_shape(optimizer.init, params)
    return sum(l.size * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(abstract))


def jit_update(optimizer: Optimizer, donate: bool = True):
    """Jit the bucketed ``update`` with ``(grads, state)`` donated.

    The bucketed stacks then update in place — one live copy of the
    optimizer state instead of old+new double-buffering, and the gradient
    buffers are recycled into the outputs.  ``params`` (arg 2) is never
    donated here: standalone-update callers usually still own it.  Inside
    a donated *train step* the whole ``(params, opt_state)`` pair aliases
    through (see ``lm.make_train_step(donate=True)``)."""
    return jax.jit(optimizer.update,
                   donate_argnums=(0, 1) if donate else ())


def live_update_bytes(compiled) -> Optional[int]:
    """Peak live bytes of a compiled update/train-step executable:
    ``arguments + outputs − donation aliases + temporaries``, straight
    from XLA's buffer assignment.  ``None`` when the backend exposes no
    ``memory_analysis`` (the benchmark then skips the donation check)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
