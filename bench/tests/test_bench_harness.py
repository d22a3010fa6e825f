"""The harness end to end on the CPU at a tiny size: files found by name,
a sound run comes out correct, the timed path broken underneath or the
fp8 control comes out not correct, and a machine without a TPU or a
directory without the program gives no result."""

import json
import time

import jax
import pytest

from bench import check, data, harness
from bench import weights as wlib
from bench.drivers.train import CHECK_STEPS, opt_settings
from bench.model import load_spec
from bench.reference import Reference
from bench.tests.tiny import CELL, LIMITS, make_root

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
    assert load_spec(cell.config_path).name == "tiny2"
    assert cell.limits == LIMITS
    assert cell.driver().run.__module__.endswith("train")

    class Run:
        steps, trace, peaks, tokens_per_s = 7, None, None, 0.0
    assert harness.read_per_layer(cell, Run()) == {
        "steps_seen": {"value": 7.0, "unit": "steps"}}
    # the metric is the new cell's alone
    assert "steps_seen" not in harness.read_per_layer(
        harness.Cell(root, CELL), Run())
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
    spec, tr = load_spec(cell.config_path), cell.traffic
    opt = opt_settings(tr)
    weights = wlib.make_weights(spec, SEED, opt["level"])
    batches = [data.make_source(SEED, spec.vocab, tr).batch(i)
               for i in range(CHECK_STEPS)]
    ref = Reference(spec, opt, tr["seq"]).run(weights, batches)
    control = Reference(spec, opt, tr["seq"], precision="fp8").run(
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
