"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp ref oracle (assignment deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gwt_adam import kernel as kg, ops as gops, ref as rg
from repro.kernels.haar_dwt import kernel as kf, ref as rf

SHAPES_FWD = [(8, 128, 1), (32, 256, 2), (256, 512, 3), (16, 1024, 4),
              (128, 128, 2), (8, 256, 5), (40, 384, 1),
              # wider than one column block: a partial last column block
              (40, 2560, 2)]


@pytest.mark.parametrize("m,n,level", SHAPES_FWD)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_haar_dwt_fwd_inv_vs_ref(m, n, level, dtype):
    g = jax.random.normal(jax.random.key(1), (m, n), dtype)
    atol = 0.08 if dtype == jnp.bfloat16 else 1e-5
    outs_k = kf.haar_dwt_fwd(g, level, interpret=True)
    outs_r = rf.haar_dwt_fwd(g, level)
    assert outs_k[0].shape == (m, n >> level)
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol)
    rec = kf.haar_dwt_inv(outs_k[0], outs_k[1:], interpret=True)
    np.testing.assert_allclose(np.asarray(rec, np.float32),
                               np.asarray(g, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# Fused-write (megakernel) parity tier: one launch per leaf performs
# DWT→Adam→inverse→limit→param-write.  impl='jnp' routes to the tiled ref
# oracle whose norm reduction replicates the kernel's row-block
# association, so the whole core — moments, requantized q8 state,
# and the two-pass limiter norms — is BITWISE identical under interpret.
# Only the terminal write chain ``p - step·g̃`` may diverge: the
# interpret and jnp lowerings make independent FMA-contraction choices
# there, so new_p is pinned to a contraction error bound — elementwise
# |Δ| ≤ a few spacings of the operand magnitude — instead of equality.
# ---------------------------------------------------------------------------

FUSED_WRITE_SHAPES = [(1, 16, 128, 1), (3, 24, 64, 2), (2, 32, 512, 4),
                      # one leaf per launch, levels 1-4, up to 2048 wide
                      (1, 8, 128, 1), (1, 64, 512, 2), (1, 256, 2048, 3),
                      (1, 32, 256, 4)]


def _assert_write_parity(a, b, p_in, slack=4, butterfly=False):
    """new_p from two lowerings of the same write chain
    (``p - step·(g̃·coef) [- wd·p]``): each multiply/subtract is an FMA
    candidate the two backends contract independently, so the elementwise
    difference is a handful of rounding errors at the magnitude of the
    chain's operands (measured worst: 2.5 spacings at level 4; asserted
    ≤ ``slack`` spacings of the largest of |a|,|b|,|p_in|).

    ``butterfly`` also allows for a g̃ that the two lowerings computed
    with different contractions inside the inverse butterfly: each g̃
    element is then off by a few ulps of the butterfly's operands, which
    are at most about twice the row's largest |g̃|, and an element where
    the operands cancel keeps that absolute error.  So the bound gains
    ``slack`` f32 epsilons of the row's largest update ``|p_in - b|``."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    p_in = np.asarray(p_in, np.float32)
    mag = np.maximum(np.maximum(np.abs(a), np.abs(b)), np.abs(p_in))
    tol = slack * np.spacing(mag.astype(np.float32))
    if butterfly:
        row_upd = np.max(np.abs(p_in - b), axis=-1, keepdims=True)
        tol = tol + slack * np.finfo(np.float32).eps * row_upd
    diff = np.abs(a - b)
    bad = diff > tol
    assert not bad.any(), (int(bad.sum()), float(diff[bad].max()))


def _fused_write_inputs(L, m, n, level, dtype=jnp.float32):
    k = jax.random.key(6)
    g = jax.random.normal(k, (L, m, n), dtype)
    p = jax.random.normal(jax.random.fold_in(k, 1), (L, m, n), dtype)
    st = {"m": jnp.abs(jax.random.normal(jax.random.fold_in(k, 2),
                                         (L, m, n >> level))) * 0.1,
          "v": jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                         (L, m, n >> level))) * 0.01}
    # leaf 0 enters with prev_norm == 0 (first-step limiter case)
    pn = jnp.arange(L, dtype=jnp.float32) * 0.3
    return g, p, st, pn


def _fused_write_kw(level, **over):
    kw = dict(lr_t=jnp.float32(0.01), alpha=0.25, weight_decay=0.0,
              gamma=1.01, use_limiter=True, level=level)
    kw.update(over)
    return kw


@pytest.mark.parametrize("L,m,n,level", FUSED_WRITE_SHAPES)
@pytest.mark.parametrize("use_limiter", [True, False])
def test_fused_write_core_bitwise_vs_staged_oracle(L, m, n, level,
                                                   use_limiter):
    g, p, st, pn = _fused_write_inputs(L, m, n, level)
    kw = _fused_write_kw(level, use_limiter=use_limiter)
    pi, ni, si = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(2), pn,
                                         impl="interpret", **kw)
    pj, nj, sj = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(2), pn, impl="jnp", **kw)
    for tag, a, b in [("norm", ni, nj),
                      ("m", si["m"], sj["m"]), ("v", si["v"], sj["v"])]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=tag)
    _assert_write_parity(pi, pj, p)


def test_fused_write_bf16_params_vs_staged_oracle():
    """bf16 grads/params (f32 moments): the fused write rounds new_p to
    bf16 exactly once, same as the staged oracle — ≤1 bf16 ulp, bitwise
    in practice for weight_decay == 0."""
    g, p, st, pn = _fused_write_inputs(2, 16, 256, 2, dtype=jnp.bfloat16)
    kw = _fused_write_kw(2)
    pi, ni, si = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(1), pn,
                                         impl="interpret", **kw)
    pj, nj, sj = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(1), pn, impl="jnp", **kw)
    assert {x.dtype for x in pi} == {jnp.dtype(jnp.bfloat16)}
    bits_i = np.asarray(pi).view(np.uint16).astype(np.int32)
    bits_j = np.asarray(pj).view(np.uint16).astype(np.int32)
    assert np.abs(bits_i - bits_j).max() <= 1
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(nj))
    np.testing.assert_array_equal(np.asarray(si["m"]), np.asarray(sj["m"]))
    np.testing.assert_array_equal(np.asarray(si["v"]), np.asarray(sj["v"]))


def test_fused_write_weight_decay_within_fma_bound():
    """weight_decay != 0 adds one more FMA opportunity to the write chain
    (the decoupled ``- wd_coef·p`` term): new_p stays within the same
    contraction bound; everything upstream of the write stays bitwise."""
    g, p, st, pn = _fused_write_inputs(2, 32, 512, 4)
    kw = _fused_write_kw(4, weight_decay=0.01)
    pi, ni, si = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(2), pn,
                                         impl="interpret", **kw)
    pj, nj, sj = gops.fused_write_update(list(g), list(p), st,
                                         jnp.int32(2), pn, impl="jnp", **kw)
    _assert_write_parity(pi, pj, p)
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(nj))
    np.testing.assert_array_equal(np.asarray(si["m"]), np.asarray(sj["m"]))
    np.testing.assert_array_equal(np.asarray(si["v"]), np.asarray(sj["v"]))


def _q8_encoded_state(L, m, na, block=64, seed=9):
    from repro.optim import codec
    k = jax.random.key(seed)
    key = codec.make_key(0)
    leaf_ids = jnp.arange(L, dtype=jnp.uint32)
    step0 = jnp.uint32(0)
    mf = jnp.abs(jax.random.normal(jax.random.fold_in(k, 4),
                                   (L, m, na))) * 0.1
    vf = jnp.abs(jax.random.normal(jax.random.fold_in(k, 5),
                                   (L, m, na))) * 0.01
    enc = {"m": {"q": [], "scale": []}, "v": {"q": [], "scale": []}}
    for slot, src in ((0, mf), (1, vf)):
        name = "m" if slot == 0 else "v"
        for l in range(L):
            salt = codec.slot_salt(key, step0, slot, leaf_ids[l])
            q, s = codec.blocked_quant(src[l], salt, block)
            enc[name]["q"].append(q)
            enc[name]["scale"].append(s)
    st = {n: {"q": jnp.stack(enc[n]["q"]),
              "scale": jnp.stack(enc[n]["scale"])} for n in ("m", "v")}
    return st, key, leaf_ids


def test_fused_write_q8_bitwise_vs_staged_oracle():
    """int8-codec megakernel: dequant→update→requant AND limit+write in
    one launch.  The requantize is a pure function of (salt, flat index),
    so the int8 payloads and scales are bitwise vs the tiled oracle; the
    param write carries the usual single-FMA contraction bound."""
    L, m, n, level = 2, 16, 256, 2
    g, p, _, pn = _fused_write_inputs(L, m, n, level)
    st, key, leaf_ids = _q8_encoded_state(L, m, n >> level)
    kw = _fused_write_kw(level)
    pi, ni, si = gops.fused_write_update_q8(
        list(g), list(p), st, jnp.int32(1), key, leaf_ids, pn,
        impl="interpret", **kw)
    pj, nj, sj = gops.fused_write_update_q8(
        list(g), list(p), st, jnp.int32(1), key, leaf_ids, pn,
        impl="jnp", **kw)
    _assert_write_parity(pi, pj, p)
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(nj))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), si, sj)


def test_fused_write_q8_nontileable_falls_back_to_oracle():
    """A bucket whose flattened A band (m·n_A = 48) is not a codec-block
    multiple used to be routed to the jnp oracle.  The codec now blocks
    each row on its own, so the kernel tiles it like any other shape and
    no fallback remains: interpret runs the kernel, bitwise vs the
    oracle except for the param write's FMA contraction bound."""
    L, m, n, level = 1, 12, 8, 1
    assert kg.q8_row_block(m, n, level, 64) == m
    g, p, _, pn = _fused_write_inputs(L, m, n, level)
    st, key, leaf_ids = _q8_encoded_state(L, m, n >> level)
    kw = _fused_write_kw(level)
    pi, ni, si = gops.fused_write_update_q8(
        list(g), list(p), st, jnp.int32(1), key, leaf_ids, pn,
        impl="interpret", **kw)
    pj, nj, sj = gops.fused_write_update_q8(
        list(g), list(p), st, jnp.int32(1), key, leaf_ids, pn,
        impl="jnp", **kw)
    assert np.isfinite(np.asarray(pi)).all()
    _assert_write_parity(pi, pj, p)
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(nj))
    # At this 4-moment width XLA:CPU contracts the dequantize product
    # into the v update differently in the kernel's grid loop than in the
    # oracle's leaf scan (an FMA), so a block's v absmax — hence its
    # scale — can land one f32 ulp apart; the int8 payloads stay bitwise.
    for tag in ("m", "v"):
        np.testing.assert_array_equal(np.asarray(si[tag]["q"]),
                                      np.asarray(sj[tag]["q"]))
        np.testing.assert_array_max_ulp(np.asarray(si[tag]["scale"]),
                                        np.asarray(sj[tag]["scale"]),
                                        maxulp=1)


@pytest.mark.parametrize("state_codec,use_limiter", [
    ("f32", True), ("f32", False), ("int8", True)])
def test_fused_write_partial_row_tile_vs_staged_oracle(state_codec,
                                                       use_limiter):
    """200 rows under 32-row (f32) or 128-row (int8) tiles: the last tile
    is partial, the grid masks its missing rows out of the limiter norm
    and the write, and the result matches the oracle (which zero-pads its
    last stripe).  1020 moments per row is not a codec-block multiple, so
    the int8 leg also covers a short last block in every row."""
    L, m, n, level = 1, 200, 4080, 2
    bm = kg.fused_row_block(m, n, level)
    assert m > bm and m % bm and m % kg.q8_row_block(m, n, level, 64)
    g, p, st, pn = _fused_write_inputs(L, m, n, level)
    kw = _fused_write_kw(level, use_limiter=use_limiter)
    if state_codec == "int8":
        st, key, leaf_ids = _q8_encoded_state(L, m, n >> level)
        run = lambda impl: gops.fused_write_update_q8(
            list(g), list(p), st, jnp.int32(1), key, leaf_ids, pn,
            impl=impl, **kw)
    else:
        run = lambda impl: gops.fused_write_update(
            list(g), list(p), st, jnp.int32(2), pn, impl=impl, **kw)
    pi, ni, si = run("interpret")
    pj, nj, sj = run("jnp")
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(nj))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), si, sj)
    # With the limiter on, XLA:CPU fuses the kernel's recompute pass
    # differently from the oracle and contracts other multiply-adds of the
    # inverse butterfly, so g̃ moves by ulps of its operands (measured: 1404
    # of 816000 elements differ, the worst by 24 spacings of new_p).
    _assert_write_parity(pi, pj, p, butterfly=True)


# One launch per leaf: a bucket's leaves as the engine hands them over,
# ``(leaf shape, leaves)``: a pair of 80-row leaves whose last 64-row tile
# is partial, a four-dimensional expert-style pair (layers, experts,
# d_model, d_ff), and a single-leaf layer stack.
LEAF_BUCKETS = [((80, 2048), 2), ((2, 4, 16, 768), 2), ((3, 32, 512), 1)]


def _leaf_bucket(leaf, L, codec):
    """bf16 gradient and parameter leaves (the cells' dtypes), the
    bucket's stacked state in the engine's layout, and the ``(L, rows,
    n)`` views of the same arrays that one stacked launch takes."""
    from repro.optim import codec as codec_lib
    k = jax.random.key(sum(leaf) + L)
    g = jax.random.normal(k, (L,) + leaf, jnp.bfloat16)
    p = jax.random.normal(jax.random.fold_in(k, 1), (L,) + leaf,
                          jnp.bfloat16)
    n = leaf[-1]
    rows = g[0].size // n
    a_shape = (L,) + leaf[:-1] + (n >> 2,)
    mf = jax.random.normal(jax.random.fold_in(k, 2), a_shape) * 0.1
    vf = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3), a_shape)) * .01
    pn = jnp.arange(L, dtype=jnp.float32) * 0.3
    if codec == "f32":
        st = {"m": mf, "v": vf}
        stacked = (mf.reshape(L, rows, -1), vf.reshape(L, rows, -1))
    else:
        key = codec_lib.make_key(0)
        st = {}
        for slot, name, src in ((0, "m", mf), (1, "v", vf)):
            qs = [codec_lib.blocked_quant(
                src[j].reshape(rows, -1),
                codec_lib.slot_salt(key, jnp.uint32(0), slot, jnp.uint32(j)),
                64) for j in range(L)]
            st[name] = {"q": jnp.stack([q for q, _ in qs]),
                        "scale": jnp.stack([s for _, s in qs])}
        stacked = (st["m"]["q"], st["m"]["scale"], st["v"]["q"],
                   st["v"]["scale"])
    return (list(g), list(p), st, pn,
            (g.reshape(L, rows, n), p.reshape(L, rows, n)) + stacked)


@pytest.mark.parametrize("leaf,L", LEAF_BUCKETS)
@pytest.mark.parametrize("codec,use_limiter,wd", [
    ("f32", True, 0.0), ("f32", False, 0.0), ("f32", True, 0.01),
    ("int8", True, 0.01)])
def test_leaf_launches_bitwise_vs_stacked_bucket(leaf, L, codec,
                                                 use_limiter, wd):
    """The fused write as the engine runs it, one interpret-mode launch
    per leaf on the leaves' own buffers over the bucket's stacked state,
    returns new p, m and v (or their int8 payloads and scales) and the
    limiter norms bit for bit as the oracle ``ref.gwt_adam_fused{,_q8}``
    on the stacked bucket, and as one launch over the stacked bucket."""
    from repro.optim import codec as codec_lib
    gs, ps, st, pn, stacked = _leaf_bucket(leaf, L, codec)
    step = jnp.int32(3)
    kw = _fused_write_kw(2, use_limiter=use_limiter, weight_decay=wd)
    ss, wc = gops._step_scalars(step, kw["lr_t"], kw["alpha"], wd, 0.9,
                                0.999)
    k2 = dict(level=2, gamma=1.01, use_limiter=use_limiter,
              weight_decay=wd != 0)
    _, rows, n = stacked[0].shape
    if codec == "f32":
        new_ps, norm, new_st = gops.fused_write_update(
            gs, ps, st, step, pn, impl="interpret", **kw)
        got = [new_st["m"], new_st["v"]]
        args = stacked + (pn, ss, wc)
        want = rg.gwt_adam_fused(*args, bm=kg.fused_row_block(rows, n, 2),
                                 **k2)
        once = kg.gwt_adam_tile_fused(*args, interpret=True, **k2)
    else:
        key, lids = codec_lib.make_key(0), jnp.arange(L, dtype=jnp.uint32)
        new_ps, norm, new_st = gops.fused_write_update_q8(
            gs, ps, st, step, key, lids, pn, impl="interpret", **kw)
        got = [new_st["m"]["q"], new_st["m"]["scale"], new_st["v"]["q"],
               new_st["v"]["scale"]]
        salts = [codec_lib.slot_salt(key, step, s, lids) for s in (0, 1)]
        args = stacked + (*salts, pn, ss, wc)
        want = rg.gwt_adam_fused_q8(
            *args, bm=kg.q8_row_block(rows, n, 2, 64), block=64, **k2)
        once = kg.gwt_adam_tile_fused_q8(*args, block=64, interpret=True,
                                         **k2)
    assert [x.shape for x in new_ps] == [x.shape for x in gs]
    got = [jnp.stack(new_ps)] + got + [norm]
    for ref_out in (want, once):
        for i, (a, b) in enumerate(zip(got, ref_out)):
            a, b = np.asarray(a), np.asarray(b).reshape(np.shape(a))
            np.testing.assert_array_equal(a.view(f"u{a.itemsize}"),
                                          b.view(f"u{b.itemsize}"),
                                          err_msg=f"output {i}")


def test_fused_write_on_sharded_mesh_matches_one_device():
    """Under a mesh the kernel runs whole on every device (GSPMD cannot
    partition a Mosaic kernel): row-sharded (FSDP) operands give the
    one-device result, moments and norms bitwise."""
    from conftest import run_in_devices
    r = run_in_devices(4, """
        import sys
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.kernels.gwt_adam import ops as gops
        from test_kernels import (_assert_write_parity, _fused_write_inputs,
                                  _fused_write_kw)
        g, p, st, pn = _fused_write_inputs(2, 64, 256, 2)
        kw = _fused_write_kw(2)
        run = jax.jit(lambda g, p, st, pn: gops.fused_write_update(
            list(g), list(p), st, jnp.int32(2), pn, impl="interpret", **kw))
        one = run(g, p, st, pn)
        mesh = compat.make_mesh((4,), ("data",))
        rows = NamedSharding(mesh, P(None, "data", None))
        with compat.use_mesh(mesh):
            args = jax.device_put((g, p, st), rows) + (pn,)
            four = run(*args)
        (p1, n1, s1), (p4, n4, s4) = jax.device_get((one, four))
        np.testing.assert_array_equal(n1, n4)
        np.testing.assert_array_equal(s1["m"], s4["m"])
        np.testing.assert_array_equal(s1["v"], s4["v"])
        _assert_write_parity(p4, p1, p)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_wire_dwt_quantize_pack_bitwise_vs_jnp():
    """The wire-path sibling fusion: haar_dwt_fwd_q emits (A f32,
    D bf16/f8) in one launch, bitwise vs the jnp reduce_terms split."""
    from repro.kernels.haar_dwt import ops as dops
    g = jax.random.normal(jax.random.key(12), (24, 256), jnp.float32)
    for dt in (jnp.bfloat16, jnp.float8_e4m3fn):
        bk = dops.dwt_wire(g, 2, dt, impl="interpret")
        br = dops.dwt_wire(g, 2, dt, impl="jnp")
        assert bk[0].dtype == jnp.float32
        assert all(d.dtype == dt for d in bk[1:])
        for a, b in zip(bk, br):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_block_picker_constraints():
    """Row tiles are tile-legal (a multiple of 32 rows, or the full
    height), bounded for any height — including the 5461-row stripes of
    llama-1b's MLP buckets — and inside the VMEM budget."""
    from repro.kernels import lanes
    for (m, n, level) in [(8, 128, 1), (1024, 4096, 3), (333, 768, 2),
                          (32 * 5461, 2048, 2), (5461, 2040, 2)]:
        bms = [kg.fused_row_block(m, n, level),
               kg.q8_row_block(m, n, level, 64),
               kf._pick_blocks(m, n, level)[0]]
        for bm in bms:
            assert bm == m or (bm % 32 == 0 and bm < m), (m, n, bm)
            assert bm <= 512
        stream = 12 + 16 / (1 << level)
        assert bms[0] * kg._row_bytes(n, level, stream) \
            <= lanes.VMEM_BUDGET or bms[0] in (32, m)
        bn = kf._pick_blocks(m, n, level)[1]
        assert bn == n or bn % (128 << level) == 0


@pytest.mark.parametrize("width", [2, 8, 64, 124, 256, 510, 1020, 2040,
                                   2048])
def test_lane_shuffles_exact(width):
    """The MXU lane shuffles are exact: bitwise the jnp reshapes they
    replace, for 256-lane pieces, a short last piece and sub-piece
    widths alike, tiny and huge magnitudes included."""
    from repro.kernels import lanes
    x = jax.random.normal(jax.random.key(width), (16, width)) * 3.0
    x = x.at[0, 0].set(1e-30).at[1, 1].set(-3e30)
    e, o = jax.jit(lanes.deinterleave)(x)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(x[:, 0::2]))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(x[:, 1::2]))
    z = jax.jit(lanes.interleave)(e, o)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x))
    r = jax.jit(lambda v: lanes.repeat_lanes(v, 4))(e)
    np.testing.assert_array_equal(np.asarray(r),
                                  np.asarray(jnp.repeat(e, 4, axis=-1)))


@pytest.mark.parametrize("width", [256, 2048, 5504, 11008])
def test_lane_shuffles_one_pass_bf16_exact(width):
    """On bf16 the shuffles take one MXU pass (two matmuls per 256-lane
    piece, one per parity) and stay bitwise the jnp slices: a short last
    piece (5504 = 21.5 pieces) and tiny and huge magnitudes included."""
    from repro.kernels import lanes
    x = (jax.random.normal(jax.random.key(width), (16, width)) * 3.0
         ).astype(jnp.bfloat16)
    x = x.at[0, 0].set(1e-30).at[1, 1].set(-3e30).at[2, 2].set(3e38)
    e, o = jax.jit(lanes.deinterleave)(x)
    assert e.dtype == o.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(e), np.asarray(x[:, 0::2]))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(x[:, 1::2]))
    z = jax.jit(lanes.interleave)(e, o)
    assert z.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x))
    dots = lambda f, *a: str(jax.make_jaxpr(f)(*a)).count("dot_general")
    pieces = -(-width // 256)
    assert dots(lanes.deinterleave, x) == 2 * pieces
    assert dots(lanes.interleave, e, o) == 2 * pieces
    assert dots(lanes.deinterleave, x.astype(jnp.float32)) == 6 * pieces


def _three_pass_core(x, m_st, v_st, level, b1, b2, eps, xla=False):
    """The fused kernel's core as it was before the bf16 schedule: the
    tile through the f32 schedule's three-pass shuffles, G̃ rounded to the
    gradient's dtype at the end."""
    out, m, v = kg._core_shuffled(x.astype(jnp.float32), m_st, v_st, level,
                                  b1, b2, eps, xla)
    return out.astype(x.dtype), m, v


@pytest.mark.parametrize("n", [256, 2048, 11008])
@pytest.mark.parametrize("level", [1, 2, 3])
@pytest.mark.parametrize("use_limiter", [True, False])
def test_fused_write_bf16_schedule_bitwise_vs_three_pass(monkeypatch, n,
                                                         level, use_limiter):
    """Fed bf16 g/p, the interpret-mode fused kernel (one-pass shuffles,
    butterfly on the stride-2^l phases) returns new_p, m, v and new_norm
    bit for bit as the same kernel through the three-pass schedule.
    40 rows: two row tiles at width 11008, the last one partial."""
    k = jax.random.key(n + level)
    L, m = 2, 40
    g = jax.random.normal(k, (L, m, n), jnp.bfloat16)
    p = jax.random.normal(jax.random.fold_in(k, 1), (L, m, n), jnp.bfloat16)
    na = n >> level
    ms = jax.random.normal(jax.random.fold_in(k, 2), (L, m, na)) * 0.1
    vs = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                   (L, m, na))) * 0.01
    pn = jnp.arange(L, dtype=jnp.float32) * 0.3
    run = lambda: kg.gwt_adam_tile_fused(
        g, p, ms, vs, pn, jnp.float32(0.0025), jnp.float32(0.0),
        level=level, gamma=1.01, use_limiter=use_limiter,
        weight_decay=False, interpret=True)
    new = run()
    monkeypatch.setattr(kg, "_dht_adam_core", _three_pass_core)
    old = run()
    assert new[0].dtype == jnp.bfloat16
    for tag, a, b in zip(("new_p", "m", "v", "new_norm"), new, old):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a.view(f"u{a.itemsize}"),
                                      b.view(f"u{b.itemsize}"), err_msg=tag)


def test_fused_write_counts_one_pass_schedule():
    """At trace time each fused wrapper records, as the counter
    ``gwt.kernel.one_pass``, whether its bucket takes the one-pass (bf16)
    or the three-pass schedule, in buckets and gradient elements."""
    from repro import obs
    kw = _fused_write_kw(2)
    tracer = obs.Tracer()
    obs.configure(tracer=tracer)
    try:
        for dtype, L in ((jnp.bfloat16, 2), (jnp.float32, 1)):
            g, p, st, pn = _fused_write_inputs(L, 8, 256, 2, dtype=dtype)
            jax.eval_shape(lambda *a: gops.fused_write_update(
                *a, impl="interpret", **kw), list(g), list(p), st,
                jnp.int32(0), pn)
    finally:
        obs.shutdown()
    got = [e for e in tracer.events if e["name"] == "gwt.kernel.one_pass"]
    assert [e["cat"] for e in got] == ["gwt", "gwt"]
    assert [e["args"] for e in got] == [
        {"buckets_one_pass": 1.0, "elements_one_pass": 2 * 8 * 256.0,
         "buckets_three_pass": 0.0, "elements_three_pass": 0.0},
        {"buckets_one_pass": 0.0, "elements_one_pass": 0.0,
         "buckets_three_pass": 1.0, "elements_three_pass": 8 * 256.0}]
