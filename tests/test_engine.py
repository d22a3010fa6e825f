"""Leaf-plan bucketed engine: equivalence vs the per-leaf reference,
bucket grouping, legacy-checkpoint migration, and trace-size sublinearity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.optim import engine


def layered_params(n_layers=4, d=16, f=32, vocab=10):
    """≥4-layer smoke model: per-layer attn/mlp leaves + embed/norm."""
    k = jax.random.key(0)
    p = {"embed": jax.random.normal(jax.random.fold_in(k, 99), (vocab, d)),
         "norm": jnp.ones((d,))}
    for i in range(n_layers):
        kk = jax.random.fold_in(k, i)
        p[f"layer_{i}"] = {
            "attn": {"wq": jax.random.normal(jax.random.fold_in(kk, 0),
                                             (d, d)) * 0.1,
                     "wo": jax.random.normal(jax.random.fold_in(kk, 1),
                                             (d, d)) * 0.1},
            "mlp": {"w1": jax.random.normal(jax.random.fold_in(kk, 2),
                                            (d, f)) * 0.1,
                    "w2": jax.random.normal(jax.random.fold_in(kk, 3),
                                            (f, d)) * 0.1}}
    return p


def run_steps(opt, params, steps=3):
    st = opt.init(params)
    upd = jax.jit(opt.update)
    p = params
    for i in range(steps):
        g = jax.tree.map(lambda x: x * 0.01 + 0.001 * (i + 1), params)
        p, st = upd(g, st, p)
    return p, st


CASES = [
    ("adam", {}), ("adam_mini", {}), ("muon", {}), ("sgd", {}),
    ("galore", {"rank": 4, "update_gap": 2}),
    ("apollo", {"rank": 4, "update_gap": 2}),
    ("fira", {"rank": 4, "update_gap": 2}),
    ("gwt", {"level": 2}),
    ("gwt", {"level": 1, "host": "adam_mini"}),
    ("gwt", {"level": 2, "host": "muon"}),
    ("gwt", {"level": 2, "wavelet": "db2"}),
    ("gwt", {"level": 2, "impl": "interpret"}),  # fused vector_update path
]


@pytest.mark.parametrize("name,kw", CASES)
def test_bucketed_matches_per_leaf_reference(name, kw):
    """One scan/fused call per bucket == unrolled per-leaf loop.

    Bitwise for every family except GWT, where XLA fuses the Haar
    butterfly differently inside the scan body (≤1 f32 ulp observed)."""
    params = layered_params()
    pb, sb = run_steps(optim.make(name, lr=0.01, **kw), params)
    pu, su = run_steps(optim.make(name, lr=0.01, bucketed=False, **kw),
                       params)
    assert (jax.tree_util.tree_structure(sb)
            == jax.tree_util.tree_structure(su))
    tol = {} if name != "gwt" else {"atol": 1e-6, "rtol": 1e-6}
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pu)):
        if tol:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(sb), jax.tree.leaves(su)):
        if tol:
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), **tol)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_groups_same_shape_leaves():
    params = layered_params(n_layers=12)
    opt = optim.make("gwt", lr=0.01, level=2)
    plan = opt.engine.plan(params)
    by_name = {b.name: b for b in plan.buckets}
    # 12 layers × (wq, wo same shape) -> one (24, d, d) bucket; w1/w2 pairs
    # bucket separately (different shapes); embed+norm run plain.
    st = opt.init(params)
    shapes = {name: jax.tree.leaves(s)[0].shape[0]
              for name, s in st["buckets"].items()}
    assert shapes["gwt_last__layer_0.attn.wo"] == 24
    assert shapes["gwt_last__layer_0.mlp.w1"] == 12
    assert shapes["gwt_last__layer_0.mlp.w2"] == 12
    assert sum(len(b.indices) for b in plan.buckets) == plan.n_leaves
    # bucket names are path-keyed and stable across re-planning
    assert set(by_name) == set(shapes)
    assert [b.name for b in opt.engine.plan(params).buckets] \
        == [b.name for b in plan.buckets]


def test_legacy_checkpoint_migrates_to_buckets(tmp_path):
    """Save under the pre-engine per-leaf tuple layout, restore + migrate
    into the bucketed layout, continue training identically."""
    from repro.checkpoint.manager import CheckpointManager, StructureMismatch
    params = layered_params()
    grads = jax.tree.map(lambda p: p * 0.01 + 0.001, params)
    for name, kw in [("gwt", {"level": 2}), ("adam", {}),
                     ("galore", {"rank": 4, "update_gap": 2})]:
        opt = optim.make(name, lr=0.01, **kw)
        p, st = run_steps(opt, params)
        legacy = opt.engine.to_legacy(st, params)  # old on-disk layout
        cm = CheckpointManager(str(tmp_path / name))
        cm.save(3, {"params": p, "opt": legacy}, blocking=True)
        # new-layout restore must fail loudly, not silently misreshape
        with pytest.raises(StructureMismatch):
            cm.restore(None, {"params": p, "opt": st})
        like = {"params": p, "opt": opt.engine.legacy_like(params)}
        saved, start = cm.restore(None, like)
        assert start == 3
        migrated = opt.engine.migrate_legacy(saved["opt"], params)
        for a, b in zip(jax.tree.leaves(migrated), jax.tree.leaves(st)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # one more step from the migrated state == from the original
        p1, _ = jax.jit(opt.update)(grads, migrated, saved["params"])
        p2, _ = jax.jit(opt.update)(grads, st, p)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_new_layout_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    params = layered_params()
    opt = optim.make("gwt", lr=0.01, level=2)
    p, st = run_steps(opt, params)
    cm = CheckpointManager(str(tmp_path))
    cm.save(7, {"params": p, "opt": st}, blocking=True)
    saved, start = cm.restore(None, {"params": p, "opt": st})
    assert start == 7
    for a, b in zip(jax.tree.leaves(saved["opt"]), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trace_size_sublinear_in_layers():
    """One scan body per bucket: jaxpr equations grow sublinearly in layer
    count, while the per-leaf loop grows ~linearly."""
    def eqns(n_layers, bucketed):
        opt = optim.make("gwt", lr=0.01, level=2, impl="jnp",
                         bucketed=bucketed)
        params = layered_params(n_layers)
        grads = jax.tree.map(lambda p: p * 0.01, params)
        st = opt.init(params)
        return len(jax.make_jaxpr(opt.update)(grads, st, params).eqns)

    b4, b16 = eqns(4, True), eqns(16, True)
    u4, u16 = eqns(4, False), eqns(16, False)
    assert b16 < u16 / 4, (b16, u16)           # bucketed is much smaller
    assert (b16 - b4) < (u16 - u4) / 4         # and grows much slower
    assert b16 / b4 < 16 / 4                   # sublinear in layer count


def test_state_bytes_exact_accounting():
    params = layered_params()
    n = sum(p.size for p in jax.tree.leaves(params))
    adam_bytes = engine.state_bytes(optim.make("adam", lr=1e-3), params)
    assert adam_bytes == 2 * n * 4 + 4  # m+v f32 per element (+step i32)
    # sgd keeps half of adam
    sgd_bytes = engine.state_bytes(optim.make("sgd", lr=1e-3), params)
    assert sgd_bytes == n * 4 + 4
    # gwt-2 compresses eligible leaves 4x
    gwt_bytes = engine.state_bytes(optim.make("gwt", lr=1e-3, level=2),
                                   params)
    assert gwt_bytes < adam_bytes / 2


def test_state_memory_bytes_adam_mini_host():
    """Adam-mini keeps a full M but only a per-row V — not 2× elements."""
    from repro.core.gwt import state_memory_bytes
    params = {"mlp": {"w": jnp.ones((16, 64))}}
    level = 2
    mem = state_memory_bytes(params, level, host="adam_mini")
    a_elems = 16 * (64 >> level)      # A_l band: (16, 16)
    assert mem["gwt_bytes"] == (a_elems + 16) * 2   # M + per-row V, bf16
    # ...and matches the engine's exact accounting structurally
    opt = optim.make("gwt", lr=1e-3, level=level, host="adam_mini")
    st = opt.init(params)
    host = st["buckets"]["gwt_last__mlp.w"]["host"]
    assert host["m"].shape == (1, 16, 16)
    assert host["v"].shape == (1, 16, 1)


def test_custom_rule_registration():
    """README example: a custom rule plugs into the engine unchanged."""
    sign_sgd = engine.LeafRule(
        kind="sign_sgd",
        init=lambda p: jnp.zeros((), jnp.float32),
        update=lambda g, p, s, step, leaf_id: (
            (p - 0.1 * jnp.sign(g)).astype(p.dtype), s + 1))
    opt = engine.build(lambda path, leaf: sign_sgd)
    params = layered_params(n_layers=2)
    grads = jax.tree.map(jnp.ones_like, params)
    st = opt.init(params)
    p2, st2 = jax.jit(opt.update)(grads, st, params)
    np.testing.assert_allclose(
        np.asarray(p2["layer_0"]["mlp"]["w1"]),
        np.asarray(params["layer_0"]["mlp"]["w1"]) - 0.1, rtol=1e-6)
    assert int(st2["step"]) == 1
    assert all(float(v[0]) == 1.0 for v in jax.tree.leaves(st2["buckets"]))


def test_default_eligible_has_no_block_param():
    """Eligibility is pure name/rank policy; divisibility by 2^level lives
    in _leaf_mode only, so the two can't disagree."""
    import inspect
    from repro.optim.base import default_eligible
    assert list(inspect.signature(default_eligible).parameters) \
        == ["path", "leaf"]
    assert default_eligible("layer/mlp/w", jnp.ones((6, 6)))
    assert not default_eligible("embed", jnp.ones((6, 6)))
    assert not default_eligible("layer/mlp/w", jnp.ones((6,)))


def test_bucketed_gwt_backend_sweep(kernel_impl):
    """Backend-sweep tier (conftest fixture): the bucketed GWT engine —
    including the fused vector_update path — matches the per-leaf jnp
    reference under every swept kernel impl."""
    params = layered_params()
    pb, sb = run_steps(optim.make("gwt", lr=0.01, level=2,
                                  impl=kernel_impl), params)
    pu, su = run_steps(optim.make("gwt", lr=0.01, level=2, bucketed=False,
                                  impl="jnp"), params)
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(sb), jax.tree.leaves(su)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("level", [1, 2, 4])
@pytest.mark.parametrize("orient", ["last", "first"])
def test_fused_write_level_orientation_sweep(level, orient):
    """Megakernel parity tier, optimizer level: the fused-write path
    (interpret) matches the staged per-leaf jnp engine across transform
    levels and both orientations.  FIRST-orient leaves ((32, 7): last
    axis indivisible) exercise the swap-in/swap-out of both g and p
    around the fused write; tolerance matches the existing GWT tier —
    the two paths schedule the Haar butterfly differently."""
    shape = (16, 64) if orient == "last" else (32, 7)
    k = jax.random.key(41)
    params = {"blk": {"mlp": {
        "w1": jax.random.normal(k, shape) * 0.1,
        "w2": jax.random.normal(jax.random.fold_in(k, 1), shape) * 0.1}}}
    pf, sf = run_steps(optim.make("gwt", lr=0.01, level=level,
                                  impl="interpret"), params)
    pj, sj = run_steps(optim.make("gwt", lr=0.01, level=level,
                                  impl="jnp"), params)
    bucket = f"gwt_{orient}__blk.mlp.w1"
    assert bucket in sf["buckets"], list(sf["buckets"])
    assert sf["buckets"][bucket]["host"]["m"].shape[0] == 2  # stacked pair
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pj)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sj)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_state_sharding_hint_structure_mismatch_raises():
    """A per-bucket placement hint whose structure drifted from the bucket
    state (wrong dict level, stale optimizer config) must fail loudly at
    init, not silently skip placement (the sharded train path depends on
    state being born on its mesh layout)."""
    params = {"mlp": {"w": jnp.zeros((8, 16))}}
    bad = {"gwt_last__mlp.w": {"host": 0}}      # missing m/v + prev_norm
    opt = optim.make("gwt", lr=1e-3, level=2, state_shardings=bad)
    with pytest.raises(ValueError, match="state_shardings hint"):
        opt.init(params)
    # hints for bucket names that don't exist are simply unused
    opt2 = optim.make("gwt", lr=1e-3, level=2,
                      state_shardings={"gwt_last__nope": {"host": 0}})
    st = opt2.init(params)
    assert "gwt_last__mlp.w" in st["buckets"]


def _outer_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs it calls, except the
    bodies of Pallas kernels (which work on VMEM tiles, not on leaves)."""
    from jax.extend import core as jcore
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _outer_eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _outer_eqns(sub)


@pytest.mark.parametrize("orient", ["last", "first"])
def test_fused_update_takes_leaves_where_they_live(orient):
    """On the fused path the update hands the kernel each GWT leaf's own
    gradient and parameter: ``opt.update`` concatenates no leaves (its
    only concatenates gather scalars: a bucket's per-leaf limiter norms,
    a launch's step size and weight decay), and
    the trace-time counter ``gwt.kernel.in_place`` counts every GWT
    element in place — or, for FIRST-orient leaves, whose transpose is a
    copy, every one through a copy."""
    from repro import obs
    shapes = {"last": ((16, 16), (16, 32), (2, 32, 16)),
              "first": ((16, 7), (32, 7), (2, 32, 7))}[orient]
    k = jax.random.key(5)
    params = {f"layer_{i}": {f"w{j}": jax.random.normal(
        jax.random.fold_in(k, 3 * i + j), s) for j, s in enumerate(shapes)}
        for i in range(3)}
    opt = optim.make("gwt", lr=0.01, level=2, impl="interpret")
    st = opt.init(params)
    assert all(name.startswith(f"gwt_{orient}__") for name in st["buckets"])
    jax.clear_caches()   # the kernel wrapper's counters fire when traced
    tracer = obs.Tracer()
    obs.configure(tracer=tracer)
    try:
        jaxpr = jax.make_jaxpr(opt.update)(params, st, params)
    finally:
        obs.shutdown()
    cats = [e for e in _outer_eqns(jaxpr.jaxpr)
            if e.primitive.name == "concatenate"]
    assert cats and all(e.outvars[0].aval.ndim == 1 for e in cats), \
        [e.outvars[0].aval for e in cats]
    samples = [e["args"] for e in tracer.events
               if e["name"] == "gwt.kernel.in_place"]
    assert len(samples) == len(st["buckets"]) == 3
    total = sum(x.size for x in jax.tree.leaves(params))
    copied = total if orient == "first" else 0
    assert sum(s["elements_in_place"] for s in samples) == total - copied
    assert sum(s["elements_stacked"] for s in samples) == copied
