"""The step split by the program's own names (``bench/scopes.py``), on
hand-made HLO text and traces, the per-layer metrics that read it, and
``bench/tools/layers.py`` on the tiny cell."""

import importlib.util

import pytest

from bench import harness, scopes, trace
from bench.drivers.train import RunInfo, counter_sums
from bench.tests.tiny import BENCH, CELL, make_root

HLO = '''
%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  ROOT %cos.0 = f32[8]{0} cosine(%param_0), metadata={op_name="jit(step)/train.fwd_bwd/transpose(jvp(while))/body/cos" stack_frame_id=2}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.4 = (s32[], f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(train.fwd_bwd)/while" source_file="lm.py" source_line=3}
  %fusion.7 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/train.fwd_bwd/transpose(jvp(while))/body/cos"}
  custom-call.2 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/train.update/gwt.kernel/pallas_call"}
  %transpose.5 = f32[8]{0} transpose(%x.1), dimensions={0}, metadata={op_name="jit(step)/train.update/optim.pack/transpose"}
  ROOT %copy.1 = f32[8]{0} copy(%x.1)
}
'''


def test_scope_map_reads_both_name_forms():
    m = scopes.scope_map(HLO)
    assert m["%while.4"] == m["while.4"] == "jit(step)/jvp(train.fwd_bwd)/while"
    assert m["custom-call.2"] == m["%custom-call.2"] \
        == "jit(step)/train.update/gwt.kernel/pallas_call"
    assert m["cos.0"].endswith("/body/cos")
    assert "copy.1" not in m and "%copy.1" not in m      # no metadata


def test_components_unwrap_transformations():
    assert scopes.components("jit(step)/transpose(jvp(train.fwd_bwd))/dot") \
        == ["step", "train.fwd_bwd", "dot"]
    assert "train.fwd_bwd" not in scopes.components("train.fwd_bwd_x/add")


def _chip(*ops):
    return trace.Chip("/device:TPU:0", [(n, s, e) for n, s, e in ops], 0.0)


def test_scope_seconds_counts_a_loop_and_its_body_once():
    m = scopes.scope_map(HLO)
    w = "%while.4 = (s32[], f32[8]) while(%t)"
    f = "%fusion.7 = f32[8]{0} fusion(%x.1), kind=kLoop"
    k = "%custom-call.2 = f32[8]{0} custom-call(%x.1)"
    c = "%copy.1 = f32[8]{0} copy(%x.1)"
    # the loop 0-4 s holds its body's fusion twice; the kernel 5-6 s
    one = _chip((w, 0.0, 4.0), (f, 0.5, 1.5), (f, 2.0, 3.0), (k, 5.0, 6.0),
                (c, 6.0, 7.0))
    two = _chip((w, 0.0, 2.0), (f, 0.5, 1.0), (k, 3.0, 5.0))
    s = trace.Summary(window_s=8.0, chips=[one, two], idle_gaps=[])
    assert scopes.scope_seconds(s, m, "train.fwd_bwd") == pytest.approx(3.0)
    assert scopes.scope_seconds(s, m, "train.update") == pytest.approx(1.5)
    assert scopes.scope_seconds(s, m, "gwt.kernel") == pytest.approx(1.5)
    assert scopes.scope_seconds(s, m, "optim.pack") == 0.0
    assert scopes.scope_seconds(s, {}, "train.fwd_bwd") == 0.0


def test_idle_under_attributes_a_gap_to_the_span_over_its_middle():
    host = [(trace.WINDOW, 0.0, 10.0), ("train.input_wait", 0.0, 3.0),
            ("shard_args", 3.2, 3.6), ("train.dispatch", 3.0, 4.0),
            ("train.block", 6.0, 9.5)]
    s = scopes.Summary(window_s=10.0, chips=[_chip(), _chip()],
                       idle_gaps=[], host_spans=host,
                       gap_spans=[(0.0, 2.0), (3.0, 3.8), (8.5, 10.0),
                                  (0.0, 1.0)])
    # the last gap's middle (9.25) lies under train.block
    assert scopes.idle_under(s, {"train.input_wait"}) == pytest.approx(1.5)
    assert scopes.idle_under(s, {"train.dispatch", "train.block"}) \
        == pytest.approx(1.15)
    assert scopes.idle_under(s, {"train.save"}) is None   # no such span


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "tools" / "layers.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_layers_tool_on_the_tiny_cell(tmp_path, monkeypatch):
    # the fused GWT-Adam kernel (in Pallas's interpreter), as on the chip:
    # the CPU's default pure-jnp update has no kernel and no counter
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    tool = _tool("bench_tool_layers")
    row = tool.layers(make_root(tmp_path), CELL, 5000000001, 0.1,
                      require_tpu=False, compile_cache=False)
    assert row["correct"]
    # the loop's spans reach the profiler's host plane
    assert {"train.input_wait", "train.place", "train.dispatch",
            "train.log", "train.block", "train.close"} <= set(row["spans"])
    # the window's program carries the step's scopes; the CPU has no
    # device plane, so no device time is read
    n = row["instructions"]
    assert n["train.fwd_bwd"] > 0 and n["train.update"] > 0
    assert n["optim.pack"] > 0
    assert row["ms_per_step"]["input_wait"] == 0.0
    assert row["ms_per_step"]["train.fwd_bwd"] == 0.0
    # the device-time readers find nothing on the CPU; the program's
    # trace-time counter is read: every bucket's gradient is bf16
    m = row["metrics"]
    assert not {"fwd_bwd_ms", "update_ms", "bucket_pack_ms",
                "input_wait_ms", "sync_wait_ms"} & set(m)
    assert m["gwt_one_pass_share"] == 100.0


def _recorded(steps=2):
    """A hand-made traced window of ``steps`` steps over ``HLO``, reduced
    as the training driver reduces it into its ``RunInfo``."""
    w = "%while.4 = (s32[], f32[8]) while(%t)"
    f = "%fusion.7 = f32[8]{0} fusion(%x.1), kind=kLoop"
    k = ('%custom-call.2 = f32[8]{0} custom-call(%x.1), '
         'custom_call_target="tpu_custom_call"')
    t5 = "%transpose.5 = f32[8]{0} transpose(%x.1), dimensions={0}"
    c = "%copy.1 = f32[8]{0} copy(%x.1)"
    chip = _chip((w, 1.0, 4.0), (f, 1.5, 2.5), (k, 5.0, 6.0),
                 (t5, 6.0, 6.4), (c, 6.4, 7.0))
    chip.busy_s = 5.0
    host = [(trace.WINDOW, 0.0, 10.0), ("train.input_wait", 0.0, 0.8),
            ("train.block", 7.0, 10.0), ("shard_args", 8.0, 9.5)]
    t = scopes.Summary(window_s=10.0, chips=[chip], idle_gaps=[],
                       host_spans=host,
                       gap_spans=[(0.0, 1.0), (4.0, 5.0), (7.0, 10.0)])
    scope_ms, idle_ms = scopes.step_split(t, HLO, steps)
    events = [{"ph": "X", "name": "train.dispatch", "args": {}}] + [
        {"ph": "C", "name": "gwt.kernel.one_pass",
         "args": {"elements_one_pass": one, "elements_three_pass": three,
                  "buckets_one_pass": float(one > 0),
                  "buckets_three_pass": float(three > 0)}}
        for one, three in ((300.0, 0.0), (0.0, 100.0))]
    return RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=steps, trace=t, scopes=scope_ms,
                   idle_under=idle_ms, counters=counter_sums(events),
                   hlo=HLO)


def test_layers_split_on_a_hand_made_trace():
    tool = _tool("bench_tool_layers_split")
    run = _recorded()
    row = tool.report(run)
    ms = row["ms_per_step"]
    assert ms["busy"] == pytest.approx(2500.0)
    assert ms["train.fwd_bwd"] == pytest.approx(1500.0)
    assert ms["train.update"] == pytest.approx(700.0)
    assert ms["gwt.kernel"] == pytest.approx(500.0)
    assert ms["optim.pack"] == pytest.approx(200.0)
    assert ms["unscoped"] == pytest.approx(300.0)          # the copy
    assert ms["input_wait"] == pytest.approx(500.0)
    assert ms["sync_wait"] == pytest.approx(1500.0)
    # the tool prints the driver's split, not one of its own
    assert {k: ms[k] for k in scopes.SCOPES} == run.scopes
    assert (ms["input_wait"], ms["sync_wait"]) == (run.idle_under["input"],
                                                   run.idle_under["sync"])
    assert row["unscoped_ops"][0]["op"] == "%copy.1"
    assert row["gaps"][0] == {"ms": 3000.0, "label": "shard_args",
                              "program_span": "train.block",
                              "spans": [("train.block", 0.0, 3000.0)]}
    assert [g["program_span"] for g in row["gaps"]] \
        == ["train.block", "train.input_wait", ""]           # 4-5 s: none
    assert row["spans"]["train.block"]["total_ms"] == pytest.approx(3000.0)
    assert row["instructions"]["train.fwd_bwd"] == 3


@pytest.mark.parametrize("name, value", [
    ("fwd_bwd_ms", 1500.0), ("update_ms", 700.0), ("bucket_pack_ms", 200.0),
    ("input_wait_ms", 500.0), ("sync_wait_ms", 1500.0),
    ("gwt_one_pass_share", 75.0)])
def test_scope_and_counter_readers_on_a_hand_made_trace(name, value):
    read = harness.load_module(BENCH / "metrics" / f"{name}.py").read
    run = _recorded()
    assert read(run) == pytest.approx(value)
    # an untraced run, or a trace with no device plane, reads nothing
    bare = RunInfo(arch=None, spec=None, traffic={}, chips=1, peaks=None,
                   tokens_per_s=0.0, steps=2, trace=None)
    assert read(bare) is None
    if name != "gwt_one_pass_share":
        run.trace.chips = []
        assert read(run) is None
