"""The MoE architecture (``bench/arch/moe.py``) against the program's
expert layer (``repro.models.moe``) on the CPU at a small size, on seeded
random weights: the loss and every gradient elementwise, the shares of an
expert-parallel layer adding up to the whole layer, the dropless dispatch
under a skewed router, and the harness running the MoE cell as new files
with its four readers."""

import dataclasses
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness, peaks, scopes, trace
from bench import weights as wlib
from bench.drivers.train import RunInfo, counter_sums, opt_settings
from bench.tests.tiny import BENCH, LIMITS, make_root

SEED = 2**33 + 7
CELL = "tinymoe.m32"
ARCH = harness.load_module(BENCH / "arch" / "moe.py")


@pytest.fixture
def key():
    return jax.random.key(0)


def tiny_config(held=4, routed=16):
    """The cell's configuration at a small size: every key as the cell's
    file has it but the widths, the depth, the experts and the vocabulary."""
    cfg = json.loads((BENCH / "configs" / "qwen3-moe-30b-a3b-l6.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=held,
               num_experts_per_tok=2, vocab_size=512, num_hidden_layers=2)
    cfg["published"] = dict(cfg["published"], num_experts=routed)
    return cfg


def tiny_spec(tmp_path, dtype="float32", **kw):
    path = tmp_path / "tinymoe.json"
    path.write_text(json.dumps(tiny_config(**kw)))
    return dataclasses.replace(ARCH.load_spec(path), dtype=dtype)


def traffic(seq=32, batch=4):
    tr = json.loads((BENCH / "traffic" / "s1024.json").read_text())
    tr.update(seq=seq, batch=batch)
    return tr


@pytest.mark.parametrize("undefined", ["zeros", "nan"])
def test_program_loss_and_gradients_match_the_reference(tmp_path,
                                                        monkeypatch,
                                                        undefined):
    """The program in float32 against the reference, every gradient
    element by element.  Both sides compute in float32 and differ only in
    the order of their sums (attention per head group here, the experts
    summed per token there): about 1e-6 of a leaf's largest element.  A
    token sent to the wrong expert, or a wrong gate, moves that expert's
    slice by its whole size, far past the tolerance of 1e-4 of the
    leaf's largest element.  The grouped matmuls run the buffer's rows
    past the held pairs as the last held group's, whose outputs the
    combine masks.  Left out of the groups instead, where a kernel leaves
    them undefined (on the TPU they hold whatever the memory held), and
    filled with NaN, they must reach neither the loss nor a gradient."""
    from repro.models import lm, moe
    if undefined == "nan":
        real = moe.grouped_matmul

        def nan_past_groups(lhs, rhs, sizes):
            out = real(lhs, rhs, sizes)
            rows = jnp.arange(out.shape[0])[:, None]
            return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)
        monkeypatch.setattr(moe, "grouped_matmul", nan_past_groups)
        monkeypatch.setattr(moe, "_fill", lambda sizes, rows: sizes)
    spec, tr = tiny_spec(tmp_path), traffic()
    flat = wlib.make_weights(ARCH, spec, SEED, 2)
    batch = data.make_source(SEED, spec.vocab, tr).batch(0)
    cfg = ARCH.program_config(spec, tr["seq"])
    prog_batch = {k: jnp.asarray(v) for k, v in batch.items()}
    loss, g = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, prog_batch))(wlib.nest(flat))
    g = wlib.flatten(g)
    ref = ARCH.Reference(spec, opt_settings(tr), tr["seq"])
    ref_loss, ref_g = ref.grads(flat, batch)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6)
    assert set(g) == set(ref_g) == {lf.path for lf in ARCH.layout(spec, 2)}
    for path, r in ref_g.items():
        r = np.asarray(r)
        tol = 1e-4 * np.max(np.abs(r))
        np.testing.assert_allclose(np.asarray(g[path]), r, rtol=0, atol=tol,
                                   err_msg=path)
    # every held expert of every layer is routed to and learns
    for name in ("w_gate", "w_up", "w_down"):
        per_expert = np.abs(np.asarray(ref_g[f"layers/b0/ffn/{name}"])).max(
            axis=(2, 3))
        assert np.all(per_expert > 0), name
    assert 0 < min(ref.held_share[0]) and max(ref.held_share[0]) < 1


def _layer(cfg, key, routed):
    from repro.models import moe
    from repro.models.layers import Builder
    p = moe.moe_init(Builder("init", key, jnp.float32),
                     cfg.with_(experts_held=routed))
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model),
                          jnp.float32)
    return p, x


def _share(p, s, held):
    """Shard ``s``'s part of the layer: its experts ``s·held ..`` first in
    the router's order (the layer holds experts ``0..held-1``), and their
    weights."""
    q = dict(p, router=jnp.roll(p["router"], -s * held, axis=1))
    for k in ("w_gate", "w_up", "w_down"):
        q[k] = p[k][s * held:(s + 1) * held]
    return q


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "qwen2-moe-a2.7b"])
def test_expert_shares_add_up_to_the_whole_layer(arch, key):
    """Four chips of an expert-parallel layer, each holding a quarter of
    the experts: their outputs, with what every chip computes alike (the
    shared experts) counted once, sum to the uncut layer's output."""
    from repro import configs
    from repro.models import moe
    routed, shards = 8, 4
    cfg = configs.get_smoke(arch).with_(n_experts=routed, expert_padding=0)
    held = routed // shards
    p, x = _layer(cfg, key, routed)
    whole, aux = moe.moe_apply(p, cfg, x)
    part = cfg.with_(experts_held=held)
    outs = [moe.moe_apply(_share(p, s, held), part, x) for s in range(shards)]
    total = sum(o for o, _ in outs)
    if cfg.n_shared_experts:
        from repro.models.layers import mlp_apply
        total = total - (shards - 1) * mlp_apply(p["shared"], x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    for _, a in outs:       # routing and balance are over all experts
        assert float(a) == pytest.approx(float(aux), rel=1e-5)


def _reference_layer(tmp_path, held, routed):
    spec = tiny_spec(tmp_path, held=held, routed=routed)
    tr = traffic()
    return spec, ARCH.Reference(spec, opt_settings(tr), tr["seq"])


def _ref_lp(p, held):
    lp = {"norm2": jnp.zeros((p["router"].shape[0],), jnp.float32),
          "ffn/router": p["router"]}
    for k in ("w_gate", "w_up", "w_down"):
        lp[f"ffn/{k}"] = p[k][:held]
    return lp


def test_shares_add_up_to_the_uncut_reference(tmp_path, key):
    """The same four shares against the reference's uncut layer."""
    from repro.models import moe
    from repro.models.layers import rms_norm
    routed, shards = 16, 4
    held = routed // shards
    spec, ref = _reference_layer(tmp_path, routed, routed)
    cfg = ARCH.program_config(spec, 32).with_(dtype="float32")
    p, x = _layer(cfg, key, routed)
    h = rms_norm(x, jnp.zeros((cfg.d_model,)), cfg.norm_eps)
    part = cfg.with_(experts_held=held)
    total = sum(moe.moe_apply(_share(p, s, held), part, h)[0]
                for s in range(shards))
    want, _, _ = ref._moe(_ref_lp(p, routed), x)
    np.testing.assert_allclose(np.asarray(x + total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("held", [16, 4])
def test_dropless_under_a_skewed_router(tmp_path, key, held):
    """A router that sends every token to expert 1 among its top-2: the
    program's layer equals the reference's (no pair is dropped); the
    capacity-bounded one-hot dispatch at capacity factor 1.25 does not."""
    from repro.models import moe
    from repro.models.layers import rms_norm
    routed = 16
    spec, ref = _reference_layer(tmp_path, held, routed)
    cfg = ARCH.program_config(spec, 32).with_(dtype="float32")
    p, x = _layer(cfg, key, routed)
    u = jax.random.normal(jax.random.fold_in(key, 2), (cfg.d_model,))
    x = x + 3.0 * u
    p["router"] = p["router"].at[:, 1].set(u)
    held_p = {k: (v[:held] if k.startswith("w_") else v)
              for k, v in p.items()}
    h = rms_norm(x, jnp.zeros((cfg.d_model,)), cfg.norm_eps)
    _, idx, counts, _ = moe._route(p, cfg, h.reshape(-1, cfg.d_model))
    T = h.shape[0] * h.shape[1]
    assert int(counts[1]) == T                       # every token picks 1
    out, _ = moe.moe_apply(held_p, cfg.with_(experts_held=held), h)
    want, _, _ = ref._moe(_ref_lp(p, held), x)
    np.testing.assert_allclose(np.asarray(x + out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    if held == routed:
        capped, _ = moe._moe_onehot(p, cfg.with_(capacity_factor=1.25), h)
        assert math.ceil(T * cfg.top_k / routed * 1.25) < T
        assert float(jnp.max(jnp.abs(x + capped - want))) > 1e-2


def test_drop_fault_changes_the_gradient(tmp_path):
    """The reference's ``drop`` fault (capacity 1.0 in token order) moves
    the expert gradients past the harness's tolerance at this size."""
    spec, tr = tiny_spec(tmp_path), traffic()
    flat = wlib.make_weights(ARCH, spec, SEED, 2)
    batch = data.make_source(SEED, spec.vocab, tr).batch(0)
    opt = opt_settings(tr)
    _, g = ARCH.Reference(spec, opt, tr["seq"]).grads(flat, batch)
    _, gd = ARCH.Reference(spec, opt, tr["seq"], fault="drop").grads(flat,
                                                                     batch)
    w = "layers/b0/ffn/w_up"
    assert float(jnp.max(jnp.abs(g[w] - gd[w]))) \
        > 1e-2 * float(jnp.max(jnp.abs(g[w])))


def make_moe_root(tmp_path):
    """The tiny benchmark root with the MoE cell added as new files: a
    configuration naming ``bench_arch: moe``, a traffic mix, the limits
    and the ``BENCHMARK.json`` entries."""
    root = make_root(tmp_path)
    b = root / "bench"
    (b / "configs" / "tinymoe.json").write_text(json.dumps(tiny_config()))
    (b / "traffic" / "m32.json").write_text(json.dumps(traffic()))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tinymoe",
                                 file="bench/configs/tinymoe.json"))
    bench["workloads"].append({"name": CELL, "config": "tinymoe",
                               "traffic": "m32", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_moe_cell_enters_as_new_files(tmp_path):
    """The architecture module, a configuration naming it, a traffic mix
    and the limits: the shared driver runs the cell, correct."""
    root = make_root(tmp_path / "a")
    before = {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
              if p.is_file()}
    root = make_moe_root(tmp_path / "b")
    changed = [p for p, v in before.items() if (root / p).read_bytes() != v
               and p.name != "BENCHMARK.json"]
    assert changed == []
    cell = harness.Cell(root, CELL)
    out = cell.driver().run(cell, seed=SEED, seconds=0.1, trace=False,
                            clock0=time.monotonic(), require_tpu=False,
                            compile_cache=False)
    assert out.correct, out.check
    assert out.run.arch.gmm_work is not None


def test_the_program_counts_its_dispatch_buffer(tmp_path):
    """The expert layer samples ``moe.layer`` at trace time: a buffer row
    for every routed pair."""
    from repro import obs
    from repro.models import lm
    spec, tr = tiny_spec(tmp_path, dtype="bfloat16"), traffic()
    cfg = ARCH.program_config(spec, tr["seq"])
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    sds = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), np.int32)
    tracer = obs.Tracer()
    obs.configure(tracer=tracer)
    try:
        jax.eval_shape(lambda p, b: lm.loss_fn(cfg, p, b), params,
                       {"tokens": sds, "labels": sds})
    finally:
        obs.shutdown()
    c = counter_sums(tracer.events)["moe.layer"]
    T = tr["batch"] * tr["seq"]
    assert c["buffer_rows"] == c["pairs"] == T * spec.top_k * (
        c["tokens"] / T)
    # 4 of 16 experts held: the capped buffer has rows for half the pairs
    assert c["capped_rows"] == c["pairs"] / 2
    read = harness.load_module(BENCH / "metrics"
                               / "moe_dropless_share.py").read
    run = RunInfo(arch=ARCH, spec=spec, traffic=tr, chips=1, peaks=None,
                  tokens_per_s=0.0, steps=1, trace=None,
                  counters=counter_sums(tracer.events))
    assert read(run) == 100.0


# a window superstep's HLO with the expert layer's scopes: a routing
# fusion, a dispatch gather, two grouped matmuls (forward and backward) and
# a combine, inside the layers' loop; the forward's grouped matmul inside
# the conditional that picks the dispatch buffer
MOE_HLO = '''
ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %while.4 = (s32[], f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/train.fwd_bwd/while"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/train.fwd_bwd/while/body/moe/moe.route/softmax"}
  %gather.2 = f32[8]{0} gather(%x.1, %i), metadata={op_name="jit(step)/train.fwd_bwd/while/body/moe/moe.dispatch/jit(_take)/gather"}
  %conditional.7 = f32[8]{0} conditional(%p, %x.1, %x.1), branch_computations={%b0, %b1}, metadata={op_name="jit(step)/train.fwd_bwd/while/body/moe/cond"}
  %gmm.3 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/train.fwd_bwd/while/body/moe/cond/branch_1_fun/moe.experts/jit(gmm)/pallas_call"}
  %tgmm.4 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/train.fwd_bwd/transpose(jvp(while))/body/moe/moe.experts/jit(tgmm)/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%f5, metadata={op_name="jit(step)/train.fwd_bwd/while/body/moe/moe.combine/mul"}
  %fusion.6 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%f6, metadata={op_name="jit(step)/train.fwd_bwd/while/body/attn/dot"}
  ROOT %copy.1 = f32[8]{0} copy(%x.1)
}
'''


def _moe_run(tmp_path, steps=2):
    w = "%while.4 = (s32[], f32[8]) while(%t)"
    ops = [(w, 0.0, 10.0),
           ("%fusion.1 = f32[8]{0} fusion(%x.1)", 0.5, 1.0),
           ("%gather.2 = f32[8]{0} gather(%x.1, %i)", 1.0, 1.5),
           ("%conditional.7 = f32[8]{0} conditional(%p, %x.1, %x.1)", 1.5,
            3.5),
           ("%gmm.3 = f32[8]{0} custom-call(%x.1)", 1.5, 3.5),
           ("%tgmm.4 = f32[8]{0} custom-call(%x.1)", 6.0, 8.0),
           ("%fusion.5 = f32[8]{0} fusion(%x.1)", 3.5, 4.0),
           ("%fusion.6 = f32[8]{0} fusion(%x.1)", 4.0, 6.0)]
    chip = trace.Chip("/device:TPU:0", ops, 10.0)
    t = scopes.Summary(window_s=10.0, chips=[chip], idle_gaps=[])
    spec = tiny_spec(tmp_path, dtype="bfloat16")
    return RunInfo(arch=ARCH, spec=spec, traffic=traffic(), chips=1,
                   peaks=peaks.peaks_for("TPU v5 lite"), tokens_per_s=0.0,
                   steps=steps, trace=t, hlo=MOE_HLO,
                   counters={"moe.layer": {"tokens": 256.0, "top_k": 4.0,
                                           "pairs": 1024.0,
                                           "buffer_rows": 1024.0}})


def test_moe_readers_on_a_hand_made_trace(tmp_path):
    read = lambda m: harness.load_module(
        BENCH / "metrics" / f"{m}.py").read(run)
    run = _moe_run(tmp_path)
    assert read("moe_ms") == pytest.approx(1e3 * 5.5 / 2)
    assert read("moe_dispatch_ms") == pytest.approx(1e3 * 1.5 / 2)
    flops, nbytes = ARCH.gmm_work(run.spec, run.traffic)
    least = max(nbytes / 819e9, flops / 197e12)
    assert read("moe_gmm_roofline") == pytest.approx(100 * least / 2.0)
    assert read("moe_dropless_share") == 100.0
    # a program without the expert layer's scopes and counter reads nothing
    run.hlo = MOE_HLO.replace("moe", "mlp")
    run.counters = {}
    for m in ("moe_ms", "moe_dispatch_ms", "moe_gmm_roofline",
              "moe_dropless_share"):
        assert read(m) is None, m


def test_gmm_work_counts_the_held_rows():
    """At the cell's size: 8,192 tokens × top-8 × 32/128 held = 16,384
    rows per layer; 3 products × 4 passes × 2·rows·2048·768 FLOPs over 6
    layers, and PaLM's model FLOPs per token for this chip's share."""
    spec = ARCH.load_spec(BENCH / "configs" / "qwen3-moe-30b-a3b-l6.json")
    tr = json.loads((BENCH / "traffic" / "s1024.json").read_text())
    flops, nbytes = ARCH.gmm_work(spec, tr)
    assert flops == 6 * 3 * 4 * 2 * 16384 * 2048 * 768
    assert nbytes == 6 * 3 * 4 * 2 * (16384 * (2048 + 768) + 32 * 2048 * 768)
    assert ARCH.model_flops_per_token(spec, 1024) == pytest.approx(1.797e9,
                                                                   rel=1e-3)
    leaves = {lf.path: lf for lf in ARCH.layout(spec, 2)}
    assert leaves["layers/b0/ffn/w_gate"].shape == (6, 32, 2048, 768)
    assert leaves["layers/b0/ffn/w_down"].axis == 3
    assert leaves["layers/b0/ffn/router"].shape == (6, 2048, 128)
    assert leaves["layers/b0/ffn/router"].rule == "adam"
    assert leaves["embed/lm_head"].shape == (2048, 37984)
