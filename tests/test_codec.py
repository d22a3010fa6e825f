"""Optimizer-state substrate (repro.optim.codec): blocked-int8 property
tests, engine equivalence under the quantized codec, the
family × codec state_bytes sweep, and checkpoint transcoding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.optim import codec, engine

from test_engine import layered_params, run_steps


# ---------------------------------------------------------------------------
# codec property tests
# ---------------------------------------------------------------------------

def _salt(seed, step=3, slot=0, leaf=5):
    return codec.slot_salt(codec.make_key(seed), jnp.uint32(step),
                           slot, jnp.uint32(leaf))


def test_uniform01_range_and_determinism():
    salt = _salt(0)
    idx = jnp.arange(4096, dtype=jnp.uint32)
    u = codec.uniform01(salt, idx)
    assert float(u.min()) >= 0.0 and float(u.max()) < 1.0
    # decent spread (counter-based hash, not a constant)
    assert 0.4 < float(u.mean()) < 0.6
    assert (u == codec.uniform01(salt, idx)).all()
    assert not (u == codec.uniform01(_salt(1), idx)).all()


def test_stochastic_rounding_unbiased():
    """E[dequant(quant(x))] == x: averaged over many independent salts the
    rounding bias vanishes (the deterministic-rounding alternative would
    sit a half-quantum off for every value with frac != 0.5)."""
    # fixed scale: pin one element per block to the absmax
    x = jnp.full((256,), 0.34e-2).at[::64].set(1.27)
    salts = jax.vmap(lambda i: _salt(0, step=i))(jnp.arange(512))
    q, s = jax.vmap(lambda k: codec.blocked_quant(x, k, 64))(salts)
    dec = jax.vmap(lambda qq, ss: codec.blocked_dequant(qq, ss, 64))(q, s)
    mean = dec.mean(axis=0)
    scale = 1.27 / 127.0
    err = (mean - x)[jnp.arange(256) % 64 != 0]
    # per-element: 512 draws -> se ~ 0.022*scale; allow ~5 sigma
    assert float(jnp.abs(err).max()) < 0.12 * scale
    # across elements the signed bias must cancel (~7 sigma bound)
    assert abs(float(err.mean())) < 0.01 * scale


@pytest.mark.parametrize("shape", [(130,), (63,), (1,), (13, 10), (4, 3, 9)])
def test_roundtrip_error_within_block_scale(shape):
    k = jax.random.key(hash(shape) % (2 ** 31))
    x = jax.random.normal(k, shape) * 3.0
    q, s = codec.blocked_quant(x, _salt(0), 64)
    assert q.shape == shape and q.dtype == jnp.int8
    assert s.shape == codec.scale_shape(shape, 64)
    dec = codec.blocked_dequant(q, s, 64)
    # stochastic rounding moves at most one quantum == one per-block
    # scale; blocks run along each row (last axis), the last one short
    nbr, rows = s.shape
    width = shape[-1]
    err = jnp.abs(dec - x).reshape(rows, width)
    err = jnp.pad(err, ((0, 0), (0, nbr * 64 - width)))
    per_block = err.reshape(rows, nbr, 64).max(axis=2)
    assert (per_block <= s.T + 1e-7).all()


def test_fixed_salt_requant_deterministic():
    x = jax.random.normal(jax.random.key(3), (77,))
    q1, s1 = codec.blocked_quant(x, _salt(7), 64)
    q2, s2 = codec.blocked_quant(x, _salt(7), 64)
    assert (q1 == q2).all() and (s1 == s2).all()
    q3, _ = codec.blocked_quant(x, _salt(8), 64)
    assert not (q1 == q3).all()


def test_zero_blocks_exact():
    x = jnp.zeros((130,))
    q, s = codec.blocked_quant(x, _salt(0), 64)
    assert (q == 0).all() and (s == 0).all()
    assert (codec.blocked_dequant(q, s, 64) == 0).all()


def test_absmax_representable():
    """The block absmax itself round-trips to within float error of ±127
    quanta — clipping can't push it out of range."""
    x = jnp.concatenate([jnp.full((64,), -5.0), jnp.full((64,), 5.0)])
    q, s = codec.blocked_quant(x, _salt(0), 64)
    assert (jnp.abs(q.astype(jnp.int32)) == 127).all()
    assert jnp.allclose(codec.blocked_dequant(q, s, 64), x, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine equivalence under int8
# ---------------------------------------------------------------------------

Q8_CASES = [
    ("adam", {}), ("adam_mini", {}), ("muon", {}), ("sgd", {}),
    ("galore", {"rank": 4, "update_gap": 2}),
    ("apollo", {"rank": 4, "update_gap": 2}),
    ("fira", {"rank": 4, "update_gap": 2}),
    ("gwt", {"level": 2}),
]


@pytest.mark.parametrize("name,kw", Q8_CASES)
def test_bucketed_matches_unrolled_int8(name, kw):
    """The per-bucket scan wraps the leaf update in dequant→update→requant
    with per-(leaf, slot, step) salts — the same bits the unrolled
    per-leaf loop derives, so moments match BITWISE across layouts.
    Exception: GWT, where XLA fuses the Haar butterfly differently inside
    the scan body (≤1 f32 ulp, same as the f32 engine tier) — there an
    ulp near a rounding boundary may flip a quantum."""
    params = layered_params()
    p_b, st_b = run_steps(optim.make(name, lr=0.01, bucketed=True,
                                     state_codec="int8", **kw), params)
    p_u, st_u = run_steps(optim.make(name, lr=0.01, bucketed=False,
                                     state_codec="int8", **kw), params)
    if name == "gwt":
        def close(a, b):
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype == np.int8:
                assert np.abs(a.astype(np.int32)
                              - b.astype(np.int32)).max() <= 1
            elif a.size:
                np.testing.assert_allclose(a.astype(np.float32),
                                           b.astype(np.float32), rtol=1e-5,
                                           atol=1e-6)
        jax.tree.map(close, st_b, st_u)
    else:
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), st_b, st_u)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-6), p_b, p_u)


def test_f32_codec_is_identity():
    """state_codec='f32' is pure passthrough: identical state STRUCTURE
    and bitwise-identical values vs the codec-less default."""
    params = layered_params()
    for name, kw in [("adam", {}), ("gwt", {"level": 2})]:
        p0, st0 = run_steps(optim.make(name, lr=0.01, **kw), params)
        p1, st1 = run_steps(optim.make(name, lr=0.01, state_codec="f32",
                                       **kw), params)
        assert jax.tree_util.tree_structure(st0) == \
            jax.tree_util.tree_structure(st1)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), st0, st1)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), p0, p1)


def test_gwt_fused_q8_matches_generic_wrap(kernel_impl):
    """impl='jnp' runs the engine's generic codec wrap around the scan
    body; fused impls requantize inside the kernel epilogue with the same
    salts.  Moments may differ by ≤1 quantum only where the two paths'
    f32 accumulation order lands an ulp apart across a rounding
    boundary."""
    if kernel_impl == "jnp":
        pytest.skip("needs a fused impl to compare against the wrap")
    params = layered_params(n_layers=2, d=16, f=32)
    p_j, st_j = run_steps(optim.make("gwt", lr=0.01, level=2, impl="jnp",
                                     state_codec="int8"), params)
    p_f, st_f = run_steps(optim.make("gwt", lr=0.01, level=2,
                                     impl=kernel_impl,
                                     state_codec="int8"), params)

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=1e-5,
                                       atol=1e-5)
    jax.tree.map(close, st_j, st_f)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-4), p_j, p_f)


def test_gwt_fused_q8_3d_leaf_matches_generic_wrap():
    """3-D+ leaves (e.g. qwen GQA tensors): the codec blocks/salts over
    the leaf's row-major flat order, so the fused path must merge the
    extra dims into the row axis rather than vmapping over them —
    regression for a vmap-axis mismatch on (L, extra, m, n) buckets.
    Pinned to ``interpret`` (not the ``kernel_impl`` sweep) so the guard
    runs in the default tier."""
    kernel_impl = "interpret"
    key = jax.random.key(7)
    params = {"w3d": jax.random.normal(key, (2, 24, 16)) * 0.1,
              "w2d": jax.random.normal(jax.random.key(8), (16, 16)) * 0.1}
    p_j, st_j = run_steps(optim.make("gwt", lr=0.01, level=2, impl="jnp",
                                     state_codec="int8"), params)
    p_f, st_f = run_steps(optim.make("gwt", lr=0.01, level=2,
                                     impl=kernel_impl,
                                     state_codec="int8"), params)

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=1e-5,
                                       atol=1e-5)
    jax.tree.map(close, st_j, st_f)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-4), p_j, p_f)


@pytest.mark.parametrize("level,shape", [
    (1, (16, 64)), (2, (16, 64)), (4, (16, 64)),   # LAST orientation
    (2, (32, 7)),                                  # FIRST orientation
])
def test_gwt_fused_q8_level_orientation_sweep(level, shape):
    """Megakernel parity tier × int8 codec: the fused dequant→update→
    requant epilogue matches the generic codec wrap across transform
    levels and both orientations, with the same ≤1-quantum comparator as
    the q8 wrap tier.  Pinned to ``interpret`` so it runs by default."""
    k = jax.random.key(17)
    params = {"blk": {"mlp": {
        "w1": jax.random.normal(k, shape) * 0.1,
        "w2": jax.random.normal(jax.random.fold_in(k, 1), shape) * 0.1}}}
    p_j, st_j = run_steps(optim.make("gwt", lr=0.01, level=level,
                                     impl="jnp", state_codec="int8"),
                          params)
    p_f, st_f = run_steps(optim.make("gwt", lr=0.01, level=level,
                                     impl="interpret", state_codec="int8"),
                          params)

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=1e-5,
                                       atol=1e-5)
    jax.tree.map(close, st_j, st_f)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-4), p_j, p_f)


def test_gwt_fused_q8_nontileable_shape_uses_oracle():
    """A bucket whose flattened A-band (m·n_A = 48) is not a codec-block
    multiple once had to take the jnp oracle.  The codec now blocks each
    row on its own (a 4-wide row is one short block), so the kernel tiles
    the bucket whole and the fused impl runs it: the engine result must
    stay finite and match the generic wrap."""
    from repro.kernels.gwt_adam import kernel as kg
    assert kg.q8_row_block(12, 8, 1, 64) == 12
    params = {"blk": {"w": jax.random.normal(jax.random.key(23),
                                             (12, 8)) * 0.1}}
    p_j, st_j = run_steps(optim.make("gwt", lr=0.01, level=1,
                                     impl="jnp", state_codec="int8"),
                          params)
    p_f, st_f = run_steps(optim.make("gwt", lr=0.01, level=1,
                                     impl="interpret", state_codec="int8"),
                          params)
    assert np.isfinite(np.asarray(p_f["blk"]["w"], np.float32)).all()

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=1e-5,
                                       atol=1e-5)
    jax.tree.map(close, st_j, st_f)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-4), p_j, p_f)


def test_codec_key_advances_rounding_per_step():
    """Salts fold in the step: the same moment value requantized at two
    different steps draws different rounding bits (no frozen bias)."""
    x = jax.random.normal(jax.random.key(0), (256,))
    key = codec.make_key(0)
    q1, _ = codec.blocked_quant(
        x, codec.slot_salt(key, jnp.uint32(1), 0, jnp.uint32(0)), 64)
    q2, _ = codec.blocked_quant(
        x, codec.slot_salt(key, jnp.uint32(2), 0, jnp.uint32(0)), 64)
    assert not (q1 == q2).all()


# ---------------------------------------------------------------------------
# state_bytes sweep: 8 families x both codecs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kw", Q8_CASES)
def test_state_bytes_sweep(name, kw):
    """eval_shape accounting == realized bytes for both codecs, and int8
    strictly shrinks every moment-bearing family."""
    params = layered_params()
    sizes = {}
    for cdc in ("f32", "int8"):
        opt = optim.make(name, lr=0.01, state_codec=cdc, **kw)
        st = opt.init(params)
        claimed = engine.state_bytes(opt, params)
        realized = sum(l.size * l.dtype.itemsize
                       for l in jax.tree.leaves(st))
        assert claimed == realized
        sizes[cdc] = claimed
    assert sizes["int8"] < sizes["f32"]
    # int8 moments + f32 scales: at worst 1/4 + 1/(4*64) of the f32 bytes
    # for the moment slots, so even projector-heavy families shrink >25%
    assert sizes["int8"] < 0.75 * sizes["f32"]


# ---------------------------------------------------------------------------
# transcoding (checkpoint codec migration)
# ---------------------------------------------------------------------------

def test_transcode_f32_int8_roundtrip():
    params = layered_params()
    opt32 = optim.make("gwt", lr=0.01, level=2)
    opt8 = optim.make("gwt", lr=0.01, level=2, state_codec="int8")
    _, st32 = run_steps(opt32, params)

    st8 = engine.transcode(st32, params, opt32, opt8)
    like8 = jax.eval_shape(opt8.init, params)
    assert jax.tree_util.tree_structure(st8) == \
        jax.tree_util.tree_structure(like8)
    assert int(st8["step"]) == int(st32["step"])

    back = engine.transcode(st8, params, opt8, opt32)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(st32)

    # one quantization round trip: error bounded by the per-block scale
    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.size:
            tol = max(1e-7, np.abs(a).max() / 127.0 * 1.01)
            assert np.abs(a - b).max() <= tol
    jax.tree.map(close, st32["buckets"], back["buckets"])

    # stable under re-encoding: same dst codec key + step, input already on
    # the quantization grid -> identical codes; the block scale itself may
    # move one f32 ulp (absmax reconstructed as 127*s/127)
    st8b = engine.transcode(back, params, opt32, opt8)

    def stable(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            np.testing.assert_array_equal(a, b)
        elif a.size:
            np.testing.assert_allclose(a, b, rtol=1e-6)
    jax.tree.map(stable, st8, st8b)


def test_int8_states_still_step_after_transcode():
    params = layered_params(n_layers=2)
    opt32 = optim.make("adam", lr=0.01)
    opt8 = optim.make("adam", lr=0.01, state_codec="int8")
    _, st32 = run_steps(opt32, params)
    st8 = engine.transcode(st32, params, opt32, opt8)
    g = jax.tree.map(lambda x: x * 0.01, params)
    p2, st2 = opt8.update(g, st8, params)
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(p2))
    assert int(st2["step"]) == int(st8["step"]) + 1


# ---------------------------------------------------------------------------
# sharding mirrors the encoded layout
# ---------------------------------------------------------------------------

def test_gwt_state_shardings_match_encoded_structure():
    """gwt_state_shardings(state_codec='int8') must produce exactly one
    NamedSharding per leaf of the encoded opt_state (q + scale slots,
    codec_key included) — device_put of the real init succeeds leafwise."""
    from repro import compat, configs
    from repro.distributed import sharding as shr
    from repro.models import lm

    cfg = configs.get_smoke("llama-60m")
    mesh = compat.make_mesh((1,), ("data",))
    params_abs = lm.abstract_params(cfg)
    for cdc in ("f32", "int8"):
        sh = shr.gwt_state_shardings(params_abs, lm.param_axes(cfg), mesh,
                                     shr.train_rules(mesh), level=2,
                                     state_codec=cdc)
        opt = optim.make("gwt", lr=0.01, level=2, state_codec=cdc)
        st_abs = jax.eval_shape(opt.init, params_abs)
        assert jax.tree_util.tree_structure(
            jax.tree.map(lambda _: 0, sh)) == \
            jax.tree_util.tree_structure(jax.tree.map(lambda _: 0, st_abs))
