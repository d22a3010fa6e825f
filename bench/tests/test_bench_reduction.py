"""The trace reduction, the counters and the peaks table, on hand-made
inputs; the dense architecture's counts pinned."""

import pytest

from bench import counts, peaks, trace
from bench.arch import dense
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
    q = dense.load_spec(BENCH / "configs" / "qwen2.5-3b-l9.json")
    m = dense.load_spec(BENCH / "configs" / "mistral-7b-l4.json")
    # Qwen2.5-3B layer: q 2048x2048, k and v 2048x256, o 2048x2048,
    # MLP 3 x 2048x11008; head 2048x151936 (tied, still a product)
    q_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert dense.matmul_params(q) == 9 * q_layer + 2048 * 151936
    assert dense.model_flops_per_token(q, 256) == (
        6 * (9 * q_layer + 2048 * 151936) + 12 * 9 * 16 * 128 * 256)
    # Mistral-7B layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # MLP 3 x 4096x14336; head 4096x32000
    m_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert dense.model_flops_per_token(m, 4096) == (
        6 * (4 * m_layer + 4096 * 32000) + 12 * 4 * 32 * 128 * 4096)


def test_gwt_kernel_minimum_bytes_and_flops_by_hand():
    # level 2, bf16 gradient and parameter, f32 moments on a quarter of
    # the width: 2 + 2*2 + 4*4/4 = 10 bytes per element
    assert counts.gwt_bytes_per_element(2, 2, 2) == 10.0
    # forward 2 + 1, inverse the same, Adam 11/4, details 3/4, ssq 2,
    # write 3
    assert counts.gwt_flops_per_element(2) == pytest.approx(14.5)
    q = dense.load_spec(BENCH / "configs" / "qwen2.5-3b-l9.json")
    m = dense.load_spec(BENCH / "configs" / "mistral-7b-l4.json")
    q_el = 9 * (2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
                + 2048 + 256 * 2)            # + the q/k/v bias stacks
    m_el = 4 * (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    assert counts.gwt_elements(dense, q, 2) == q_el
    assert counts.gwt_elements(dense, m, 2) == m_el
    assert counts.gwt_kernel_work(dense, m, 2) == (14.5 * m_el, 10.0 * m_el)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v4")


# What the counts read before the architecture seam, for each cell's
# configuration and sequence: the leaves (path, stored shape, rule, layer
# stack, init, transform axis), PaLM's model FLOPs per token, and the GWT
# kernel's (FLOPs, bytes) per step at level 2.
_STACK = [("layers/b0/ffn/w_down", "F", "D", "gwt", "normal", 2),
          ("layers/b0/ffn/w_gate", "D", "F", "gwt", "normal", 2),
          ("layers/b0/ffn/w_up", "D", "F", "gwt", "normal", 2)]
PINNED = {
    ("qwen2.5-3b-l9", 256): (
        [("embed/embedding", (151936, 2048), "adam", False, "normal", None),
         ("final_norm", (2048,), "adam", False, "zeros", None)]
        + [(p, (9, {"F": 11008, "D": 2048}[a], {"F": 11008, "D": 2048}[b]),
            r, True, i, x) for p, a, b, r, i, x in _STACK]
        + [("layers/b0/mixer/bk", (9, 256), "gwt", True, "zeros", 1),
           ("layers/b0/mixer/bq", (9, 2048), "gwt", True, "zeros", 1),
           ("layers/b0/mixer/bv", (9, 256), "gwt", True, "zeros", 1),
           ("layers/b0/mixer/wk", (9, 2048, 256), "gwt", True, "normal", 2),
           ("layers/b0/mixer/wo", (9, 2048, 2048), "gwt", True, "normal", 2),
           ("layers/b0/mixer/wq", (9, 2048, 2048), "gwt", True, "normal", 2),
           ("layers/b0/mixer/wv", (9, 2048, 256), "gwt", True, "normal", 2),
           ("layers/b0/norm1", (9, 2048), "adam", True, "zeros", None),
           ("layers/b0/norm2", (9, 2048), "adam", True, "zeros", None)],
        6085410816.0, (10058012928.0, 6936560640.0)),
}
_MISTRAL = (
    [("embed/embedding", (32000, 4096), "adam", False, "normal", None),
     ("embed/lm_head", (4096, 32000), "adam", False, "normal", None),
     ("final_norm", (4096,), "adam", False, "zeros", None)]
    + [(p, (4, {"F": 14336, "D": 4096}[a], {"F": 14336, "D": 4096}[b]),
        r, True, i, x) for p, a, b, r, i, x in _STACK]
    + [("layers/b0/mixer/wk", (4, 4096, 1024), "gwt", True, "normal", 2),
       ("layers/b0/mixer/wo", (4, 4096, 4096), "gwt", True, "normal", 2),
       ("layers/b0/mixer/wq", (4, 4096, 4096), "gwt", True, "normal", 2),
       ("layers/b0/mixer/wv", (4, 4096, 1024), "gwt", True, "normal", 2),
       ("layers/b0/norm1", (4, 4096), "adam", True, "zeros", None),
       ("layers/b0/norm2", (4, 4096), "adam", True, "zeros", None)])
PINNED[("mistral-7b-l4", 4096)] = (_MISTRAL, 6826229760.0,
                                   (12650020864.0, 8724152320.0))
PINNED[("mistral-7b-l4", 256)] = (_MISTRAL, 6071255040.0,
                                  (12650020864.0, 8724152320.0))


@pytest.mark.parametrize("config, seq", sorted(PINNED))
def test_dense_counts_pinned(config, seq):
    leaves, flops, work = PINNED[(config, seq)]
    spec = dense.load_spec(BENCH / "configs" / f"{config}.json")
    got = [(lf.path, lf.shape, lf.rule, lf.stacked, lf.init, lf.axis)
           for lf in dense.layout(spec, 2)]
    assert got == leaves
    assert dense.model_flops_per_token(spec, seq) == flops
    assert counts.gwt_kernel_work(dense, spec, 2) == work
