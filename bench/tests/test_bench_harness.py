"""The harness end to end on the CPU at a tiny size: files found by name,
a sound run comes out correct, the timed path broken underneath or the
fp8 control comes out not correct, and a machine without a TPU or a
directory without the program gives no result."""

import json
import shutil
import time

import jax
import pytest

from bench import check, counts, data, harness, peaks, trace
from bench import weights as wlib
from bench.arch import dense
from bench.drivers.train import CHECK_STEPS, opt_settings
from bench.tests.tiny import BENCH, CELL, LIMITS, make_root

SEED = 2**33 + 5     # past 32 bits: a seed is taken whole


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def run_tiny(root, **kw):
    return harness.run_cell(root, CELL, SEED, 0.1, False, time.monotonic(),
                            require_tpu=False, compile_cache=False, **kw)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    b = root / "bench"
    (b / "configs" / "tiny2.json").write_text(
        (b / "configs" / "tiny.json").read_text())
    tr = json.loads((b / "traffic" / "t32.json").read_text())
    tr["seq"] = 16
    (b / "traffic" / "t16.json").write_text(json.dumps(tr))
    (b / "limits" / "tiny2.t16.json").write_text(json.dumps(LIMITS))
    (b / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return run.steps\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny2",
                                 file="bench/configs/tiny2.json"))
    bench["workloads"].append({"name": "tiny2.t16", "config": "tiny2",
                               "traffic": "t16", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "tokens_per_s",
                               "workloads": ["tiny2.t16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(root, "tiny2.t16")
    assert cell.traffic["seq"] == 16
    assert cell.arch().load_spec(cell.config_path).name == "tiny2"
    assert cell.limits == LIMITS
    assert cell.driver().run.__module__.endswith("train")

    class Run:
        steps, trace, peaks, tokens_per_s = 7, None, None, 0.0
        scopes = idle_under = counters = None
    assert harness.read_per_layer(cell, Run()) == {
        "steps_seen": {"value": 7.0, "unit": "steps"}}
    # the metric is the new cell's alone
    assert "steps_seen" not in harness.read_per_layer(
        harness.Cell(root, CELL), Run())
    changed = [p for p, v in before.items() if p.read_bytes() != v
               and p.name != "BENCHMARK.json"]
    assert changed == []


def test_a_new_architecture_enters_as_new_files(tmp_path):
    """An architecture module, a configuration naming it, a traffic mix and
    the cell's limits: the shared driver runs the cell, and the counts the
    per-layer metrics read are the new module's."""
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    b = root / "bench"
    shutil.copy(BENCH / "tests" / "toy_arch.py", b / "arch" / "toy.py")
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["bench_arch"] = "toy"
    (b / "configs" / "toy.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "t32.json").read_text())
    tr["seq"] = 24
    (b / "traffic" / "t24.json").write_text(json.dumps(tr))
    (b / "limits" / "toy.t24.json").write_text(json.dumps(LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="toy",
                                 file="bench/configs/toy.json"))
    bench["workloads"].append({"name": "toy.t24", "config": "toy",
                               "traffic": "t24", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.Cell(root, "toy.t24")
    out = cell.driver().run(cell, seed=SEED, seconds=0.1, trace=False,
                            clock0=time.monotonic(), require_tpu=False,
                            compile_cache=False)
    assert out.correct, out.check
    run = out.run
    toy = run.arch
    assert toy.load_spec is not dense.load_spec
    assert not any("ffn" in lf.path for lf in toy.layout(run.spec, 2))
    # the counts come from the toy: a v5e's peaks and one kernel event of
    # 1 ms per step stand in for a chip's
    run.peaks = peaks.peaks_for("TPU v5 lite")
    kernel = ('%_fused_write_update.1 = bf16[8] custom-call(bf16[8] %g), '
              'custom_call_target="tpu_custom_call"')
    run.trace = trace.Summary(
        window_s=1.0, idle_gaps=[],
        chips=[trace.Chip("/device:TPU:0",
                          [(kernel, 0.0, 1e-3 * run.steps)], 0.5)])
    read = lambda m: cell.reader(m).read(run)
    flops = toy.model_flops_per_token(run.spec, 24)
    assert read("step_mfu") == pytest.approx(
        100.0 * flops * run.tokens_per_s / 197e12)
    dense_spec = dense.load_spec(cell.config_path)
    assert flops < dense.model_flops_per_token(dense_spec, 24)
    n = sum(lf.shape[0] * lf.shape[1] * (lf.shape[2] if len(lf.shape) > 2
                                         else 1)
            for lf in toy.layout(run.spec, 2) if lf.rule == "gwt")
    assert n < counts.gwt_elements(dense, dense_spec, 2)
    assert read("gwt_kernel_roofline") == pytest.approx(
        100.0 * max(10.0 * n / 819e9, 14.5 * n / 197e12) / 1e-3)
    changed = [p for p, v in before.items() if p.read_bytes() != v
               and p.name != "BENCHMARK.json"]
    assert changed == []


def test_tiny_cell_runs_and_is_correct(root):
    line = run_tiny(root)
    assert line["correct"], line["check"]
    assert set(line["metrics"]) == {"tokens_per_s", "peak_hbm_gb",
                                    "setup_s"}
    assert list(line)[-1] == "check"
    assert line["attempted"] >= 10 and line["failed"] == 0


def _unchanged_state(real):
    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return broken
    return make


def _half_batch(real):
    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half)
        return broken
    return make


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro.models import lm
    monkeypatch.setattr(lm, "make_train_step", fault(lm.make_train_step))
    line = run_tiny(root)
    assert not line["correct"], line["check"]


@pytest.fixture
def no_compile_cache():
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def test_fp8_control_is_not_correct(root, no_compile_cache):
    cell = harness.Cell(root, CELL)
    arch, tr = cell.arch(), cell.traffic
    spec, opt = arch.load_spec(cell.config_path), opt_settings(tr)
    weights = wlib.make_weights(arch, spec, SEED, opt["level"])
    batches = [data.make_source(SEED, spec.vocab, tr).batch(i)
               for i in range(CHECK_STEPS)]
    ref = arch.Reference(spec, opt, tr["seq"]).run(weights, batches)
    control = arch.Reference(spec, opt, tr["seq"], precision="fp8").run(
        weights, batches)
    correct, nums = check.compare(control, ref, cell.limits)
    assert not correct, nums
    same, _ = check.compare(ref, ref, cell.limits)
    assert same


def test_no_tpu_gives_no_result(capsys):
    rc = harness.main(["--workload", "qwen2.5-3b-l9.s256", "--seed", "1",
                       "--seconds", "1"],
                      time.monotonic(), root=harness.ROOT)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_directory_without_the_program_gives_no_result(root, capsys):
    rc = harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1"],
                      time.monotonic(), root=root)
    assert rc != 0
    assert capsys.readouterr().out == ""
