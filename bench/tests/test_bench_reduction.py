"""The trace reduction, the counters and the peaks table, on hand-made
inputs."""

import pytest

from bench import counts, peaks, trace
from bench.model import load_spec
from bench.tests.tiny import BENCH


def test_union_busy_and_gaps():
    ivs = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert trace.union(ivs, 0.0, 10.0) == [(1.0, 3.0), (5.0, 6.0),
                                           (9.0, 10.0)]
    assert trace.busy(ivs, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.gaps(ivs, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0),
                                          (6.0, 9.0)]
    assert trace.gaps([], 2.0, 3.0) == [(2.0, 3.0)]


def test_gap_label_is_the_innermost_host_span():
    host = [("bench.window", 0.0, 10.0), ("dispatch", 2.0, 8.0),
            ("fetch", 3.5, 4.5)]
    assert trace.label((3.0, 5.0), host) == "fetch"
    assert trace.label((6.0, 7.0), host) == "dispatch"
    assert trace.label((9.0, 9.5), host) == "bench.window"


def test_summary_kernel_sum_and_top_ops():
    # op events on the TPU are named by their whole HLO instruction
    k = ('%_fused_write_update.13 = (bf16[2,18432,11008]) custom-call('
         'bf16[2,18432,11008] %p), custom_call_target="tpu_custom_call"')
    other = ('%pallas_call.3 = f32[8] custom-call(f32[8] %x), '
             'custom_call_target="tpu_custom_call"')
    f = "%fusion.1 = bf16[8] fusion(bf16[8] %a), kind=kLoop"
    chips = [trace.Chip("/device:TPU:0",
                        [(f, 0.0, 0.5), (k, 0.5, 0.7), (k, 0.8, 0.9),
                         (other, 0.9, 1.0)], 0.8),
             trace.Chip("/device:TPU:1",
                        [(f, 0.0, 0.3), (k, 0.5, 0.6)], 0.4)]
    s = trace.Summary(window_s=1.0, chips=chips, idle_gaps=[])
    assert s.busy_s == pytest.approx(0.6)
    assert s.op_seconds(trace.is_gwt_kernel) == pytest.approx(0.2)
    top = dict(s.top_ops(width=20))
    assert top[f[:20]] == pytest.approx(0.4)
    assert top[k[:20]] == pytest.approx(0.2)


def test_model_flops_per_token_by_hand():
    q = load_spec(BENCH / "configs" / "qwen2.5-3b-l9.json")
    m = load_spec(BENCH / "configs" / "mistral-7b-l4.json")
    # Qwen2.5-3B layer: q 2048x2048, k and v 2048x256, o 2048x2048,
    # MLP 3 x 2048x11008; head 2048x151936 (tied, still a product)
    q_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert counts.matmul_params(q) == 9 * q_layer + 2048 * 151936
    assert counts.model_flops_per_token(q, 256) == (
        6 * (9 * q_layer + 2048 * 151936) + 12 * 9 * 16 * 128 * 256)
    # Mistral-7B layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # MLP 3 x 4096x14336; head 4096x32000
    m_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert counts.model_flops_per_token(m, 4096) == (
        6 * (4 * m_layer + 4096 * 32000) + 12 * 4 * 32 * 128 * 4096)


def test_gwt_kernel_minimum_bytes_and_flops_by_hand():
    # level 2, bf16 gradient and parameter, f32 moments on a quarter of
    # the width: 2 + 2*2 + 4*4/4 = 10 bytes per element
    assert counts.gwt_bytes_per_element(2, 2, 2) == 10.0
    # forward 2 + 1, inverse the same, Adam 11/4, details 3/4, ssq 2,
    # write 3
    assert counts.gwt_flops_per_element(2) == pytest.approx(14.5)
    q = load_spec(BENCH / "configs" / "qwen2.5-3b-l9.json")
    m = load_spec(BENCH / "configs" / "mistral-7b-l4.json")
    q_el = 9 * (2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
                + 2048 + 256 * 2)            # + the q/k/v bias stacks
    m_el = 4 * (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    assert counts.gwt_elements(q, 2) == q_el
    assert counts.gwt_elements(m, 2) == m_el
    assert counts.gwt_kernel_work(m, 2) == (14.5 * m_el, 10.0 * m_el)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v4")
