"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates a REDUCED config, runs forward + one GWT train step +
(where applicable) prefill/decode, asserting shapes and finiteness."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, optim
from repro.models import encdec, lm

ARCHS = configs.ARCH_IDS


def _batch(cfg, key, B=2, S=64):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.mrope_sections:
        batch["mrope_positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
    if cfg.arch_class == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            key, (B, S // 4, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, key):
    cfg = configs.get_smoke(arch)
    B, S = 2, 64
    batch = _batch(cfg, key, B, S)
    mod = encdec if cfg.arch_class == "encdec" else lm
    params = mod.init(cfg, key)
    if cfg.arch_class == "encdec":
        enc = encdec.encode(cfg, params, batch["enc_embeds"])
        logits, _ = encdec.decode_stack(cfg, params, batch["tokens"], enc)
    else:
        logits, _, aux = lm.forward(cfg, params, batch["tokens"],
                                    mrope_positions=batch.get(
                                        "mrope_positions"))
        assert np.isfinite(float(aux))
    assert logits.shape == (B, S, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    opt = optim.make("gwt", lr=1e-3, level=2)
    st = opt.init(params)
    ts = jax.jit(mod.make_train_step(cfg, opt, accum_steps=2))
    params2, st, metrics = ts(params, st, batch)
    assert np.isfinite(float(metrics["loss"]))
    # parameters actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if configs.get_smoke(a).arch_class
                                  != "encdec"])
def test_decode_matches_full_forward(arch, key):
    """Incremental KV/recurrent-cache decode == sliced full forward."""
    cfg = configs.get_smoke(arch)
    B, S = 2, 32
    if cfg.window:
        S = max(S, cfg.window)  # ring-buffer handoff needs S % window == 0
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    params = lm.init(cfg, key)
    mrope = (jnp.broadcast_to(jnp.arange(S), (3, B, S))
             if cfg.mrope_sections else None)
    full_logits, _, _ = lm.forward(cfg, params, tokens, mode="train",
                                   mrope_positions=mrope)

    prefix = S - 4
    pre_tok = tokens[:, :prefix]
    pre_mrope = mrope[:, :, :prefix] if mrope is not None else None
    logits_p, cache, _ = lm.forward(cfg, params, pre_tok, mode="prefill",
                                    mrope_positions=pre_mrope)
    from repro.launch.serve import pad_cache
    cache = pad_cache(cache, S, window=cfg.window)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32),
        np.asarray(full_logits[:, prefix - 1], np.float32),
        atol=0.05, rtol=0.05)
    for t in range(prefix, S):
        step_mrope = (jnp.broadcast_to(jnp.asarray(t), (3, B, 1))
                      if cfg.mrope_sections else None)
        logits_d, cache, _ = lm.forward(
            cfg, params, tokens[:, t:t + 1], mode="decode", caches=cache,
            mrope_positions=step_mrope)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            atol=0.05, rtol=0.05, err_msg=f"{arch} decode step {t}")


def test_encdec_decode_matches_teacher_forcing(key):
    cfg = configs.get_smoke("seamless-m4t-large-v2")
    B, S = 2, 16
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    enc_embeds = jax.random.normal(key, (B, 8, cfg.d_model), jnp.bfloat16)
    params = encdec.init(cfg, key)
    enc = encdec.encode(cfg, params, enc_embeds)
    full_logits, _ = encdec.decode_stack(cfg, params, tokens, enc)

    prefix = S - 3
    logits_p, cache = encdec.decode_stack(cfg, params, tokens[:, :prefix],
                                          enc, mode="prefill")
    from repro.launch.serve import pad_cache
    # pad only the self-attention cache; cross KV must stay at enc length
    cache = {"dec": {"self": pad_cache(cache["dec"]["self"], S),
                     "cross": cache["dec"]["cross"]},
             "pos": cache["pos"]}
    for t in range(prefix, S):
        logits_d, cache = encdec.decode_stack(
            cfg, params, tokens[:, t:t + 1], None, mode="decode",
            caches=cache)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            atol=0.05, rtol=0.05)


def _scopes(op_name):
    """Name-stack components with the transformations' wrappers taken
    off: ``train.fwd_bwd/transpose(jvp(dot))`` -> train.fwd_bwd, dot."""
    return set(re.sub(r"[\w\-]+\(", "", op_name).replace(")", "")
               .split("/"))


@pytest.mark.parametrize("accum", [1, 2])
def test_train_step_scopes_label_the_compiled_ops(accum, key):
    """The step's named scopes (DESIGN.md §12) reach the compiled HLO's
    ``op_name`` metadata: forward/backward and the update label disjoint
    instructions, backward ops included; the bucketed engine's packing and
    the fused kernel's launch lie under the update."""
    cfg = configs.get_smoke("llama-60m")
    params = lm.init(cfg, key)
    opt = optim.make("gwt", lr=1e-3, level=2, impl="interpret")
    step = jax.jit(lm.make_train_step(cfg, opt, accum_steps=accum))
    hlo = step.lower(params, opt.init(params),
                     _batch(cfg, key, 2, 32)).compile().as_text()
    names = [(n, _scopes(n)) for n in re.findall(r'op_name="([^"]*)"', hlo)]
    fwd_bwd = [n for n, sc in names if "train.fwd_bwd" in sc]
    update = [sc for n, sc in names if "train.update" in sc]
    assert fwd_bwd and update
    assert not any("train.fwd_bwd" in sc for sc in update)
    assert any("transpose(" in n for n in fwd_bwd)       # backward ops
    assert any("optim.pack" in sc for sc in update)
    assert any("gwt.kernel" in sc for sc in update)


def test_param_builder_trees_consistent():
    """init / axes / abstract trees share structure & shapes (one builder)."""
    for arch in ARCHS:
        cfg = configs.get_smoke(arch)
        mod = encdec if cfg.arch_class == "encdec" else lm
        abst = mod.abstract_params(cfg)
        axes = mod.param_axes(cfg)
        ini = mod.init(cfg, jax.random.key(0))
        s_a = jax.tree_util.tree_structure(abst)
        from repro.models.layers import Axes
        s_x = jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, Axes))
        s_i = jax.tree_util.tree_structure(ini)
        assert s_a == s_i, arch
        assert str(s_x) == str(s_a), arch
        for sds, arr in zip(jax.tree.leaves(abst), jax.tree.leaves(ini)):
            assert sds.shape == arr.shape and sds.dtype == arr.dtype, arch
        for sds, ax in zip(jax.tree.leaves(abst),
                           jax.tree.leaves(axes, is_leaf=lambda x:
                                           isinstance(x, Axes))):
            assert len(ax.names) == len(sds.shape), (arch, ax, sds.shape)


def test_local_attention_equals_masked_direct(key):
    """Block-local sliding-window path == direct path with window mask."""
    from repro.models import attention
    cfg = configs.get_smoke("gemma2-9b")
    B, S = 2, 96  # 3 blocks of window=32
    q = jax.random.normal(key, (B, S, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 4, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, 4, 16))
    o_block = attention._local_block_attn(q, k, v, window=32, cap=0.0)
    o_direct = attention._direct_attn(q, k, v, causal_offset=0, window=32,
                                      cap=0.0)
    np.testing.assert_allclose(np.asarray(o_block), np.asarray(o_direct),
                               atol=2e-2, rtol=2e-2)


def test_flash_attention_equals_direct(key):
    from repro.models import attention
    B, S, H, hd = 1, 2048, 2, 32
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    o_flash = attention._flash_attn(q, k, v, q_chunk=256, kv_chunk=512)
    o_direct = attention._direct_attn(q, k, v, causal_offset=0, window=0,
                                      cap=0.0)
    np.testing.assert_allclose(np.asarray(o_flash), np.asarray(o_direct),
                               atol=2e-2, rtol=2e-2)


def test_moe_expert_padding_is_semantically_invisible(key):
    """expert_padding pads WEIGHTS only (EP divisibility); routed outputs
    must be bit-identical to the unpadded config given identical weights."""
    from repro.models import moe as moe_lib
    from repro.models.layers import Builder
    cfg0 = configs.get_smoke("qwen2-moe-a2.7b").with_(expert_padding=0)
    cfg4 = cfg0.with_(expert_padding=4)
    b = Builder("init", key, jnp.bfloat16)
    p0 = moe_lib.moe_init(Builder("init", key, jnp.bfloat16), cfg0)
    p4 = moe_lib.moe_init(Builder("init", key, jnp.bfloat16), cfg4)
    # copy the real experts' weights into the padded arrays
    E = cfg0.n_experts
    for k in ("w_gate", "w_up", "w_down"):
        p4[k] = p4[k].at[:E].set(p0[k])
    p4["router"] = p0["router"]
    if "shared" in p0:
        p4["shared"] = p0["shared"]
    x = jax.random.normal(key, (2, 16, cfg0.d_model), jnp.bfloat16)
    y0, aux0 = moe_lib.moe_apply(p0, cfg0, x)
    y4, aux4 = moe_lib.moe_apply(p4, cfg4, x)
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y4, np.float32), atol=1e-5)
    np.testing.assert_allclose(float(aux0), float(aux4), rtol=1e-6)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "qwen3-moe-30b-a3b"])
def test_moe_expert_padding_is_invisible_to_a_held_share(arch, key):
    """A chip holding the first experts of the router's set: padding its
    expert WEIGHTS leaves its routed outputs and aux loss as they were."""
    from repro.models import moe as moe_lib
    from repro.models.layers import Builder
    cfg0 = configs.get_smoke(arch).with_(expert_padding=0, experts_held=3)
    cfg4 = cfg0.with_(expert_padding=4)
    p0 = moe_lib.moe_init(Builder("init", key, jnp.bfloat16), cfg0)
    p4 = moe_lib.moe_init(Builder("init", key, jnp.bfloat16), cfg4)
    assert p0["w_up"].shape[0] == 3 and p4["w_up"].shape[0] == 7
    assert p0["router"].shape[1] == cfg0.n_experts
    assert p0["router"].dtype == jnp.bfloat16      # stored in the tree dtype
    for k in ("w_gate", "w_up", "w_down"):
        p4[k] = p4[k].at[:3].set(p0[k])
    p4["router"] = p0["router"]
    if "shared" in p0:
        p4["shared"] = p0["shared"]
    x = jax.random.normal(key, (2, 16, cfg0.d_model), jnp.bfloat16)
    y0, aux0 = moe_lib.moe_apply(p0, cfg0, x)
    y4, aux4 = moe_lib.moe_apply(p4, cfg4, x)
    np.testing.assert_array_equal(np.asarray(y0, np.float32),
                                  np.asarray(y4, np.float32))
    assert float(aux0) == float(aux4)


@pytest.mark.parametrize("skew", [False, True], ids=["capped", "overflow"])
def test_moe_capped_buffer_matches_the_full_one(skew, key, monkeypatch):
    """A chip holding 4 of 16 experts dispatches through a buffer capped
    at half the pairs (and a row tile per held expert) while its held
    groups fit, and through one for every pair when a skewed router sends
    them past the cap: either way the output and every gradient are the
    full buffer's.  Row tiles of 8 (the CPU's grouped matmul takes any)
    let the skewed groups outgrow the cap at this size."""
    from repro.models import moe as moe_lib
    from repro.models.layers import Builder
    monkeypatch.setattr(moe_lib, "_GMM_ROWS", 8)
    cfg = configs.get_smoke("qwen3-moe-30b-a3b").with_(
        n_experts=16, expert_padding=0, experts_held=4)
    p = moe_lib.moe_init(Builder("init", key, jnp.float32), cfg)
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.float32)
    if skew:        # every token's top-2 lands on held experts 1 and 2
        u = jax.random.normal(jax.random.fold_in(key, 1), (cfg.d_model,))
        x = x + 3.0 * u
        p["router"] = p["router"].at[:, 1].set(u).at[:, 2].set(0.9 * u)
    pairs = x.shape[0] * x.shape[1] * cfg.top_k
    tiles = 4 * moe_lib._GMM_ROWS
    rows = moe_lib.buffer_rows(cfg, pairs)
    assert rows == (pairs // 2 + tiles, pairs + tiles)
    assert [moe_lib.pair_rows(cfg, r) for r in rows] == [pairs // 2, pairs]
    _, _, counts, _ = moe_lib._route(p, cfg, x.reshape(-1, cfg.d_model))
    assert (int(counts[:4].sum()) > pairs // 2) == skew
    assert (int(jnp.sum(moe_lib._spans(counts[:4]))) > rows[0]) == skew
    w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x):
        out, aux = moe_lib.moe_apply(p, cfg, x)
        return jnp.sum(out * w) + aux

    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(moe_lib, "buffer_rows", lambda cfg, n: rows[-1:])
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for g, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("draw", [1, 2, 3])
def test_moe_grouped_work_does_not_follow_the_routing(draw, key, monkeypatch):
    """On a chip holding 4 of 16 experts, each router sends its own number
    of pairs to the held experts; the grouped matmuls run over every row of
    the capped buffer all the same, forward and in the backward's
    recomputation, in groups of whole row tiles, at least one each, so
    the layer does the same work for each."""
    from repro.models import moe as moe_lib
    from repro.models.layers import Builder
    cfg = configs.get_smoke("qwen3-moe-30b-a3b").with_(
        n_experts=16, expert_padding=0, experts_held=4)
    p = moe_lib.moe_init(Builder("init", jax.random.fold_in(key, draw),
                                 jnp.float32), cfg)
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.float32)
    pairs = x.shape[0] * x.shape[1] * cfg.top_k
    cap, _ = moe_lib.buffer_rows(cfg, pairs)
    _, _, counts, _ = moe_lib._route(p, cfg, x.reshape(-1, cfg.d_model))
    held = int(counts[:4].sum())
    assert 0 < held < cap
    seen = []
    real = moe_lib.grouped_matmul

    def recorded(lhs, rhs, sizes):
        jax.debug.callback(
            lambda n, m=lhs.shape[0]: seen.append((tuple(n.tolist()), m)),
            sizes)
        return real(lhs, rhs, sizes)
    monkeypatch.setattr(moe_lib, "grouped_matmul", recorded)
    jax.grad(lambda p: jnp.sum(moe_lib.moe_apply(p, cfg, x)[0]))(p)
    jax.effects_barrier()
    assert len(seen) == 6          # three products, forward and recomputed
    assert len(set(seen)) == 1
    (sizes, m), = set(seen)
    assert m == sum(sizes) == cap
    tile = moe_lib._GMM_ROWS
    assert all(n >= tile and n % tile == 0 for n in sizes)
