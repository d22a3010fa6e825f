"""GWT optimizer (Algorithm 1) behaviour tests + baseline optimizers."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import limiter

gwt_mod = importlib.import_module("repro.core.gwt")


def make_params(key=0):
    k = jax.random.key(key)
    return {"mlp": {"w1": jax.random.normal(k, (16, 32)) * 0.1,
                    "w2": jax.random.normal(jax.random.fold_in(k, 1),
                                            (32, 16)) * 0.1},
            "embed": jax.random.normal(jax.random.fold_in(k, 2), (10, 16)),
            "norm": jnp.ones((16,))}


def test_level0_equals_host_adam():
    params = make_params()
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.01, params)
    o0 = optim.make("gwt", lr=0.01, level=0, alpha=1.0, use_limiter=False)
    oa = optim.make("adam", lr=0.01)
    s0, sa = o0.init(params), oa.init(params)
    p0, p1 = params, params
    for _ in range(3):
        p0, s0 = jax.jit(o0.update)(grads, s0, p0)
        p1, sa = jax.jit(oa.update)(grads, sa, p1)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_state_memory_matches_table1():
    """Table I: GWT optimizer states = mn/2^{l-1} elements on GWT leaves."""
    params = make_params()
    for level in (1, 2, 3):
        mem = gwt_mod.state_memory_bytes(params, level)
        gwt_elems = (16 * 32 + 32 * 16)  # the two eligible mlp mats
        # Table I: states = mn/2^{l-1} elements (M^R+V^R) -> x2 bytes (bf16)
        assert mem["gwt_bytes"] == gwt_elems // (1 << (level - 1)) * 2
        # embed (10x16) + norm (16) run plain Adam: 2 states full size
        assert mem["plain_bytes"] == 2 * (10 * 16 + 16) * 2


def test_module_wise_policy():
    """Embeddings/norms stay uncompressed (paper's module-wise strategy)."""
    params = make_params()
    o = optim.make("gwt", lr=0.01, level=2)
    st = o.init(params)
    plan = o.engine.plan(params)
    for b in plan.buckets:
        bstate = st["buckets"][b.name]
        if any("mlp" in p for p in b.paths):
            assert b.rule.kind in ("gwt_last", "gwt_first"), b.name
            assert "prev_norm" in bstate, b.name
            for path in b.paths:
                w = params["mlp"][path.split("/")[1]]
                m = bstate["host"]["m"]
                assert (m.shape[-1] * 4 == w.shape[-1]
                        or m.shape[-2] * 4 == w.shape[-2]), b.name
        else:
            assert b.rule.kind == "plain", b.name
            assert "prev_norm" not in bstate, b.name


def test_transform_axis_fallback():
    """Last axis not divisible -> transform along first axis."""
    params = {"mlp": {"w": jnp.ones((32, 6))}}  # 6 % 4 != 0, 32 % 4 == 0
    o = optim.make("gwt", lr=0.01, level=2)
    st = o.init(params)
    m = st["buckets"]["gwt_first__mlp.w"]["host"]["m"]
    assert m.shape == (1, 6, 8)  # stacked, swapped, halved twice
    g = {"mlp": {"w": jnp.ones((32, 6)) * 0.1}}
    p2, _ = jax.jit(o.update)(g, st, params)
    assert p2["mlp"]["w"].shape == (32, 6)
    assert not np.any(np.isnan(np.asarray(p2["mlp"]["w"], np.float32)))


def test_norm_growth_limiter():
    u1 = jnp.ones((4, 4))
    lim1, n1 = limiter.limit(u1, jnp.zeros(()))   # first step: no limiting
    np.testing.assert_allclose(lim1, u1)
    big = jnp.ones((4, 4)) * 100.0
    lim2, n2 = limiter.limit(big, n1, gamma=1.01)
    np.testing.assert_allclose(float(jnp.linalg.norm(lim2)),
                               1.01 * float(n1), rtol=1e-5)
    small = jnp.ones((4, 4)) * 0.001
    lim3, _ = limiter.limit(small, n2)            # shrinking: untouched
    np.testing.assert_allclose(lim3, small)


def test_norm_growth_limiter_zero_update_keeps_prev():
    """An all-zero update (frozen leaf, masked step) must NOT reset the
    norm history: prev_norm carries through, so the next real update is
    still limited against the established trajectory instead of sailing
    through an accidentally-cleared limiter."""
    u1 = jnp.ones((4, 4))
    _, n1 = limiter.limit(u1, jnp.zeros(()))
    assert float(n1) > 0
    lim0, n0 = limiter.limit(jnp.zeros((4, 4)), n1, gamma=1.01)
    np.testing.assert_allclose(np.asarray(lim0), 0.0)
    assert float(n0) == float(n1)       # history preserved, not zeroed
    big = jnp.ones((4, 4)) * 100.0
    lim2, _ = limiter.limit(big, n0, gamma=1.01)
    np.testing.assert_allclose(float(jnp.linalg.norm(lim2)),
                               1.01 * float(n1), rtol=1e-5)


def test_gwt_spike_suppression():
    """NL keeps the update norm trajectory within gamma^t growth."""
    params = {"m": {"w": jnp.zeros((8, 16))}}
    o = optim.make("gwt", lr=0.1, level=2, gamma=1.01)
    st = o.init(params)
    prev_norm = None
    p = params
    for i in range(5):
        scale = 100.0 if i == 3 else 0.01   # gradient spike at step 3
        g = {"m": {"w": jnp.full((8, 16), scale)}}
        p_new, st = jax.jit(o.update)(g, st, p)
        delta = np.linalg.norm(np.asarray(p_new["m"]["w"] - p["m"]["w"],
                                          np.float32))
        if prev_norm is not None and prev_norm > 0:
            assert delta <= prev_norm * 1.01 * 1.05 + 1e-9, (i, delta)
        prev_norm = delta
        p = p_new


@pytest.mark.parametrize("name,kw", [
    ("adam", {}), ("adam_mini", {}), ("muon", {}), ("sgd", {}),
    ("galore", {"rank": 4, "update_gap": 5}),
    ("apollo", {"rank": 4, "update_gap": 5}),
    ("fira", {"rank": 4, "update_gap": 5}),
    ("gwt", {"level": 1}), ("gwt", {"level": 3}),
    ("gwt", {"level": 2, "host": "adam_mini"}),
    ("gwt", {"level": 2, "host": "muon"}),
])
def test_optimizers_converge_on_quadratic(name, kw):
    def loss_fn(params):
        return sum(jnp.sum((l - 0.5) ** 2) for l in jax.tree.leaves(params))

    from repro.optim.schedules import warmup_cosine
    # normalized-update optimizers need lr decay to settle on a quadratic
    o = optim.make(name, lr=warmup_cosine(0.05, 60, warmup_frac=0.05,
                                          final_frac=0.02), **kw)
    ps = {"mlp": {"w1": jax.random.normal(jax.random.key(0), (16, 32))}}
    st = o.init(ps)
    l0 = float(loss_fn(ps))
    upd = jax.jit(o.update)
    for _ in range(60):
        ps, st = upd(jax.grad(loss_fn)(ps), st, ps)
    assert float(loss_fn(ps)) < 0.9 * l0


def test_galore_projector_refresh():
    """Projection refreshes every update_gap steps (SVD under lax.cond)."""
    o = optim.make("galore", lr=0.01, rank=2, update_gap=3)
    params = {"mlp": {"w": jax.random.normal(jax.random.key(0), (8, 16))}}
    st = o.init(params)
    proj = lambda st: np.asarray(st["buckets"]["galore__mlp.w"]["proj"])
    g1 = {"mlp": {"w": jax.random.normal(jax.random.key(1), (8, 16))}}
    params, st = jax.jit(o.update)(g1, st, params)     # step0: refresh
    p_after_0 = proj(st)
    g2 = {"mlp": {"w": jax.random.normal(jax.random.key(2), (8, 16))}}
    params, st = jax.jit(o.update)(g2, st, params)     # step1: keep
    np.testing.assert_allclose(proj(st), p_after_0)
    params, st = jax.jit(o.update)(g2, st, params)     # step2: keep
    params, st = jax.jit(o.update)(g2, st, params)     # step3: refresh
    assert not np.allclose(proj(st), p_after_0)


def test_gwt_update_orthonormal_energy_invariant():
    """The pre-limiter GWT update in the wavelet domain has the same energy
    as in the original domain (H orthonormal) — property of Algorithm 1's
    reconstruction step."""
    from repro.core import haar
    from repro.optim import hosts
    g = jax.random.normal(jax.random.key(5), (32, 128))
    host = hosts.adam()
    a, ds = haar.haar_forward(g, 2)
    st = host.init(jax.ShapeDtypeStruct(a.shape, jnp.float32))
    pre, dsc, _, _ = host.update(a, st, jnp.int32(0))
    tilde = [d * haar.detail_scale_upsample(dsc, 2, 2 - i)
             for i, d in enumerate(ds)]
    gt = haar.haar_inverse(pre, tilde)
    e_wave = float(jnp.sum(pre**2) + sum(jnp.sum(t**2) for t in tilde))
    e_orig = float(jnp.sum(gt**2))
    np.testing.assert_allclose(e_wave, e_orig, rtol=1e-5)


def test_gwt_wavelet_choice_changes_subspace_not_memory():
    """haar vs db2: identical state shapes/memory, different subspace."""
    params = {"mlp": {"w": jax.random.normal(jax.random.key(1), (16, 64))}}
    g = {"mlp": {"w": jax.random.normal(jax.random.key(2), (16, 64)) * 0.1}}
    outs = {}
    for wavelet in ("haar", "db2"):
        o = optim.make("gwt", lr=0.01, level=2, wavelet=wavelet,
                       use_limiter=False)
        st = o.init(params)
        m = st["buckets"]["gwt_last__mlp.w"]["host"]["m"]
        assert m.shape == (1, 16, 16), wavelet
        p2, _ = jax.jit(o.update)(g, st, params)
        outs[wavelet] = np.asarray(p2["mlp"]["w"], np.float32)
    assert not np.allclose(outs["haar"], outs["db2"], atol=1e-6)


def test_gwt_handles_zero_gradients():
    params = {"mlp": {"w": jnp.ones((8, 16))}}
    g = {"mlp": {"w": jnp.zeros((8, 16))}}
    o = optim.make("gwt", lr=0.01, level=2)
    st = o.init(params)
    p2, st = jax.jit(o.update)(g, st, params)
    assert np.all(np.isfinite(np.asarray(p2["mlp"]["w"], np.float32)))
