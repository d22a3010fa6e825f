"""End-to-end behaviour tests for the paper's system (replaces the
scaffold placeholder): training improves loss, GWT tracks Adam at a
fraction of state memory, and the paper's ablation axes behave as claimed.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, optim
from repro.data.pipeline import SyntheticLM
from repro.models import lm, lora
from repro.optim.schedules import warmup_cosine

gwt_mod = importlib.import_module("repro.core.gwt")

TINY = configs.LLAMA["llama-60m"].with_(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=256, vocab=512, name="tiny")


def _fit(step_fn, params, st, steps=40, seed=0, seq=64, batch=8, cfg=TINY):
    """``steps`` of ``step_fn`` on the Markov task of ``seed``; returns the
    trained params and the losses."""
    data = SyntheticLM(cfg.vocab, seq, batch, seed=seed)
    step_fn = jax.jit(step_fn)
    losses = []
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        params, st, m = step_fn(params, st, b)
        losses.append(float(m["loss"]))
    return params, losses


def _train(optimizer, steps=40, seed=0, seq=64, batch=8, cfg=TINY):
    params = lm.init(cfg, jax.random.key(seed))
    return _fit(lm.make_train_step(cfg, optimizer), params,
                optimizer.init(params), steps, seed, seq, batch, cfg)[1]


def _final(losses):
    """Final loss as the tracking bounds read it: the mean of the last
    tenth of the steps."""
    k = max(len(losses) // 10, 1)
    return sum(losses[-k:]) / k


def test_training_reduces_loss_gwt():
    losses = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    assert np.isfinite(losses).all()


def test_gwt_tracks_adam_quality():
    """Paper Table II: GWT-2 final loss within tolerance of full Adam (tiny
    proxy: 40 steps, same schedule; paper finds GWT *beats* Adam)."""
    adam_l = _train(optim.make("adam", lr=warmup_cosine(0.0025, 40)))
    gwt_l = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2))
    assert gwt_l[-1] < adam_l[-1] * 1.35, (adam_l[-1], gwt_l[-1])


def test_gwt_int8_tracks_gwt_f32():
    """int8-coded moments follow the f32 GWT-2 curve within 1.25x of its
    final loss.  At 40 steps this catches a codec that diverges or stalls,
    not a small rounding bias: the limiter-bounded update learns even from
    moments rebuilt every step."""
    f32 = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2))
    q8 = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2,
                           state_codec="int8"))
    assert _final(q8) <= 1.25 * _final(f32), (_final(f32), _final(q8))


def test_gwt_lora_tracks_adam_lora():
    """LoRA fine-tuning of an Adam-trained base on another Markov task:
    GWT-2 on the adapters' moments ends within 1.25x of Adam on them (the
    frozen base keeps no state, so only the adapters' moments differ)."""
    adam = optim.make("adam", lr=warmup_cosine(0.01, 40))
    params = lm.init(TINY, jax.random.key(0))
    base, _ = _fit(lm.make_train_step(TINY, adam), params, adam.init(params))
    finals = {}
    for name, kw in (("gwt", {"level": 2}), ("adam", {})):
        opt = lora.wrap_optimizer(
            optim.make(name, lr=warmup_cosine(0.01, 40), **kw))
        tree = lora.inject(base, 8, jax.random.key(777))
        step = lora.make_train_step(lm, TINY, opt, rank=8, alpha=16.0)
        losses = _fit(step, tree, opt.init(tree), seed=1)[1]
        finals[name] = _final(losses)
        assert finals[name] < losses[0], (name, losses[0], finals[name])
    assert finals["gwt"] <= 1.25 * finals["adam"], finals


def test_gwt_beats_galore_at_matched_memory():
    """Paper Table II: GWT-2 ≥ GaLore-1/4 at matched compression."""
    galore_l = _train(optim.make("galore", lr=warmup_cosine(0.01, 40),
                                 rank_frac=0.25, update_gap=20))
    gwt_l = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2))
    assert gwt_l[-1] < galore_l[-1] * 1.10, (galore_l[-1], gwt_l[-1])


def test_level_sweep_memory_monotone():
    """Table XII: higher level -> strictly less optimizer memory; loss
    stays finite and in a sane band (paper: l has little quality impact)."""
    params = lm.init(TINY, jax.random.key(0))
    mems = [gwt_mod.state_memory_bytes(params, l)["total_bytes"]
            for l in (0, 1, 2, 3)]
    assert mems == sorted(mems, reverse=True)
    finals = []
    for level in (1, 3):
        l = _train(optim.make("gwt", lr=warmup_cosine(0.01, 30), level=level),
                   steps=30)
        finals.append(l[-1])
        assert np.isfinite(l).all()
    assert abs(finals[0] - finals[1]) < 0.5 * max(finals)


def test_alpha_insensitivity():
    """Fig. 6: final loss stable for alpha well above 0.1 (the paper's
    stability region; at 30 proxy steps alpha=0.1 hasn't converged yet —
    effective-lr, not instability, so we test the paper's alpha>0.1 band)."""
    finals = []
    for alpha in (0.2, 0.25, 0.4):
        l = _train(optim.make("gwt", lr=warmup_cosine(0.01, 40), level=2,
                              alpha=alpha), steps=40)
        finals.append(l[-1])
    spread = (max(finals) - min(finals)) / max(finals)
    assert spread < 0.35, finals


def test_optimizer_agnostic_hosts():
    """Fig. 4: GWT trains under Adam-mini and MUON hosts too."""
    for host in ("adam_mini", "muon"):
        l = _train(optim.make("gwt", lr=warmup_cosine(0.01, 30), level=2,
                              host=host), steps=30)
        assert l[-1] < l[0], (host, l[0], l[-1])
        assert np.isfinite(l).all()


def test_gwt_full_dimensional_update():
    """§V: unlike GaLore, the GWT update is full-dimensional — a gradient
    direction orthogonal to the approximation subspace still updates W."""
    params = {"m": {"w": jnp.zeros((8, 16))}}
    # gradient with zero block-means (pure detail): lowpass == 0
    g = jnp.tile(jnp.asarray([1.0, -1.0]), (8, 8))
    from repro.core import haar
    assert float(jnp.abs(haar.lowpass(g, 2)).max()) < 1e-6
    o = optim.make("gwt", lr=0.01, level=2, use_limiter=False)
    st = o.init(params)
    p2, _ = jax.jit(o.update)({"m": {"w": g}}, st, params)
    assert float(jnp.abs(p2["m"]["w"]).max()) > 1e-6  # details flowed through


def test_serve_generate_runs():
    from repro.launch.serve import generate
    cfg = configs.get_smoke("qwen2.5-3b")
    params = lm.init(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    out = generate(cfg, params, toks, gen_len=4)
    assert out.shape == (2, 4)
