"""Observability subsystem (DESIGN.md §12): metric sinks, span tracing,
on-device optimizer taps, and their TrainLoop / serve-engine plumbing.

The tap oracle tests compare values computed INSIDE the jitted
``tapped_update`` graph against independently jitted jnp reference
graphs and assert bitwise equality — CPU XLA is deterministic and both
graphs perform the same reductions in the same order.  Random
(non-degenerate) inputs matter here: constant inputs expose FMA
contraction differences between fused and unfused graphs in the last
ulp, which is exactly the noise the random draw keeps out of the
contract.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, optim
from repro.core import haar, limiter
from repro.obs import trace as obs_trace
from repro.obs.sink import JsonlSink, MemorySink, NullSink, Telemetry
from repro.optim.engine import _codec_taps
from repro.runtime.fault_tolerance import StepWatchdog, TrainLoop


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    """Tests install process-global sinks; always restore the null one."""
    yield
    obs.shutdown()


# ---------------------------------------------------------------------------
# Watchdog incident ring buffer
# ---------------------------------------------------------------------------

def _escalate(wd, n):
    """Feed geometrically growing blocked-phase samples: each is far above
    slow_factor x the EMA it left behind, so every sample past the first
    is an incident."""
    wd.block(1e-3)                 # seeds the EMA, no incident
    for k in range(n):
        wd.block(10.0 ** (k + 1))


def test_watchdog_ring_buffer_caps_records_keeps_exact_count():
    wd = StepWatchdog(slow_factor=2.0, log=lambda s: None, max_incidents=4)
    _escalate(wd, 10)
    assert wd.incidents == 10            # exact total (int back-compat)
    assert isinstance(wd.incidents, int)
    assert len(wd.incident_log) == 4     # ring keeps only the newest
    assert wd.incidents_dropped == 6
    assert [r["id"] for r in wd.incident_log] == [7, 8, 9, 10]
    assert all(r["phase"] == "blocked" for r in wd.incident_log)


def test_watchdog_summary_folds_ring_and_reaches_sink():
    sink = MemorySink()
    obs.configure(sink=sink)
    wd = StepWatchdog(slow_factor=2.0, log=lambda s: None, max_incidents=3)
    _escalate(wd, 5)
    s = wd.summary()
    assert s["incidents"] == 5
    assert s["incidents_dropped"] == 2
    assert s["incident_log"] == list(wd.incident_log)
    assert isinstance(s["incident_log"], list)  # JSON-serializable fold
    json.dumps(s["incident_log"])
    # every incident was also emitted live to the process-global sink
    live = [r for r in sink.records if r["kind"] == "watchdog_incident"]
    assert [r["id"] for r in live] == [1, 2, 3, 4, 5]


def test_watchdog_below_threshold_never_logs():
    wd = StepWatchdog(slow_factor=3.0, log=lambda s: None)
    for _ in range(20):
        wd.block(1e-3)
    assert wd.incidents == 0 and wd.incidents_dropped == 0


# ---------------------------------------------------------------------------
# On-device taps vs jnp oracles
# ---------------------------------------------------------------------------

def _tap_setup(seed=0, shape=(8, 16), codec="f32", impl=None, gamma=1.01):
    kw = {"state_codec": codec}
    if impl is not None:
        kw["impl"] = impl
    opt = optim.make("gwt", lr=1e-2, level=2, gamma=gamma, **kw)
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    params = {"w1": jax.random.normal(k1, shape, jnp.float32),
              "w2": jax.random.normal(k2, shape, jnp.float32)}
    grads = jax.tree.map(
        lambda _, k: jax.random.normal(k, shape, jnp.float32),
        params, {"w1": k3, "w2": jax.random.fold_in(k3, 1)})
    return opt, params, grads


def test_tapped_update_outputs_bitwise_identical_to_plain():
    """The metrics-off guarantee at the engine layer: taps are pure side
    outputs — params and state from ``tapped_update`` match ``update``
    bitwise."""
    opt, params, grads = _tap_setup()
    st = opt.init(params)
    p_a, st_a = jax.jit(opt.update)(grads, st, params)
    p_b, st_b, taps = jax.jit(opt.tapped_update)(grads, st, params)
    assert taps  # the side channel is actually populated
    jax.tree.map(np.testing.assert_array_equal, p_a, p_b)
    jax.tree.map(np.testing.assert_array_equal, st_a, st_b)


def test_tap_values_match_jnp_oracle(kernel_impl):
    """grad/update/band-energy taps == an independently jitted jnp
    reference, bitwise, on the fused-kernel backend under test."""
    opt, params, grads = _tap_setup(impl=kernel_impl)
    st = opt.init(params)
    new_p, new_st, taps = jax.jit(opt.tapped_update)(grads, st, params)
    (bname,) = {k.split("/")[0] for k in taps}
    swap = "first" in bname

    @jax.jit
    def oracle(g_stk, p_stk, np_stk, new_pn):
        g32 = g_stk.astype(jnp.float32)
        d32 = np_stk.astype(jnp.float32) - p_stk.astype(jnp.float32)
        gt32 = (jnp.swapaxes(g_stk, -1, -2) if swap
                else g_stk).astype(jnp.float32)
        # full-DWT reference: the tap's approx-chain-plus-Parseval
        # derivation must agree with it bitwise on the approx band
        a, _ = haar.haar_forward(gt32, 2)
        band_a = jnp.sum(a * a)
        return {"grad_ssq": jnp.sum(g32 * g32),
                "update_ssq": jnp.sum(d32 * d32),
                "band_a_ssq": band_a,
                "band_d_ssq": jnp.sum(gt32 * gt32) - band_a,
                "gnorm_ssq": jnp.sum(new_pn * new_pn)}

    stk = lambda t: jnp.stack([t["w1"], t["w2"]])  # noqa: E731
    ref = oracle(stk(grads), stk(params), stk(new_p),
                 new_st["buckets"][bname]["prev_norm"])
    for name, want in ref.items():
        got = taps[f"{bname}/{name}"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
    # Parseval: orthonormal haar splits grad energy across the bands
    np.testing.assert_allclose(
        float(taps[f"{bname}/band_a_ssq"] + taps[f"{bname}/band_d_ssq"]),
        float(taps[f"{bname}/grad_ssq"]), rtol=1e-5)


def test_clip_taps_track_forced_limiter_scenarios():
    """clip_rate is 0 on the first step (no history), 0 when the update
    norm shrinks, and 1 when it jumps back past gamma x prev.

    Adam normalizes per element, so the update norm tracks the number of
    ACTIVE elements (~sqrt(n)), not the gradient scale — dense -> sparse
    -> dense swings it by ~sqrt(n_elements) each way, far beyond
    gamma = 1.01."""
    opt, params, grads = _tap_setup()
    st = opt.init(params)
    upd = jax.jit(opt.tapped_update)
    sparse = jax.tree.map(
        lambda g: jnp.zeros_like(g).at[0, 0].set(1.0), grads)

    params, st, t1 = upd(grads, st, params)    # prev_norm == 0: no clip
    params, st, t2 = upd(sparse, st, params)   # norm collapses: no clip
    params, st, t3 = upd(grads, st, params)    # norm jumps back: clip all
    (bname,) = {k.split("/")[0] for k in t1}
    rates = [float(t[f"{bname}/clip_rate"]) for t in (t1, t2, t3)]
    counts = [float(t[f"{bname}/clip_count"]) for t in (t1, t2, t3)]
    assert rates == [0.0, 0.0, 1.0]
    assert counts == [0.0, 0.0, 2.0]     # two leaves in the bucket


def test_haar_approx_matches_forward_bitwise():
    g = jax.random.normal(jax.random.key(2), (3, 8, 16), jnp.float32)
    for level in (0, 1, 2, 3):
        want, _ = haar.haar_forward(g, level)
        np.testing.assert_array_equal(
            np.asarray(haar.haar_approx(g, level)), np.asarray(want))


def test_clip_flags_truth_table():
    g = 1.01
    prev = jnp.array([0.0, 1.0, 1.0, 1.0], jnp.float32)
    new = jnp.array([5.0, 1.0, 1.01, 2.0], jnp.float32)
    got = limiter.clip_flags(prev, new, g)
    # no history -> never clipped; growth below gamma -> not clipped;
    # landing on gamma x prev (what limit writes back) or above -> clipped
    assert got.tolist() == [False, False, True, True]


def test_codec_taps_match_state_recompute():
    opt, params, grads = _tap_setup(codec="int8")
    st = opt.init(params)
    _, new_st, taps = jax.jit(opt.tapped_update)(grads, st, params)
    (bname,) = {k.split("/")[0] for k in taps}
    sat = float(taps[f"{bname}/q8_sat_rate"])
    assert 0.0 <= sat <= 1.0
    # recompute eagerly from the returned encoded bucket state
    ref = _codec_taps(new_st["buckets"][bname])
    np.testing.assert_array_equal(np.asarray(taps[f"{bname}/q8_sat_rate"]),
                                  np.asarray(ref["q8_sat_rate"]))
    np.testing.assert_array_equal(np.asarray(taps[f"{bname}/q8_absmax"]),
                                  np.asarray(ref["q8_absmax"]))
    assert float(ref["q8_absmax"]) > 0.0


def test_unbucketed_engine_has_no_tap_channel():
    opt = optim.make("adam", lr=1e-2, bucketed=False)
    assert opt.tapped_update is None


# ---------------------------------------------------------------------------
# TrainLoop plumbing: boundary-sampled taps, metrics-off invariance
# ---------------------------------------------------------------------------

class _CountSource:
    """Deterministic toy data source: batch(step) == step."""

    def batch(self, step):
        return {"x": np.full((2,), step, np.float32)}


def _toy_steps():
    def step(p, s, batch):
        p = {"n": p["n"] + 1.0}
        return p, s, {"loss": jnp.sum(batch["x"]) + 0.0 * p["n"]}

    def tap_step(p, s, batch):
        p, s, m = step(p, s, batch)
        return p, s, {"loss": m["loss"], "taps": {"toy/n": p["n"]}}
    return step, tap_step


def test_trainloop_taps_sampled_at_log_boundaries_only():
    sink = MemorySink()
    obs.configure(sink=sink)
    step, tap_step = _toy_steps()
    loop = TrainLoop(step, None, _CountSource(), log_every=4, max_chunk=4,
                     log=lambda s: None, tap_step=tap_step)
    p, s, losses = loop.run({"n": jnp.float32(0)}, {}, num_steps=12)
    assert len(losses) == 12
    recs = [r for r in sink.records if r["kind"] == "train_step"]
    assert [r["step"] for r in recs] == list(range(1, 13))
    tapped = [r for r in recs if "toy/n" in r]
    # taps ride ONLY the chunk-boundary steps (1/chunk device cost)
    assert [r["step"] for r in tapped] == [4, 8, 12]
    assert [r["toy/n"] for r in tapped] == [4.0, 8.0, 12.0]


def test_trainloop_metrics_off_is_invariant_under_telemetry():
    """Same loop, no tap_step: configuring telemetry must not change a
    single computed value (records are observation, not perturbation)."""
    step, _ = _toy_steps()

    def run(with_sink):
        if with_sink:
            obs.configure(sink=MemorySink(), tracer=obs_trace.Tracer())
        else:
            obs.shutdown()
        loop = TrainLoop(step, None, _CountSource(), log_every=4,
                         max_chunk=4, log=lambda s: None)
        return loop.run({"n": jnp.float32(0)}, {}, num_steps=8)

    p0, _, l0 = run(False)
    p1, _, l1 = run(True)
    assert l0 == l1
    np.testing.assert_array_equal(np.asarray(p0["n"]), np.asarray(p1["n"]))


LOOP_SPANS = {"train.input_wait", "train.place", "train.dispatch_first",
              "train.dispatch", "train.log", "train.block", "train.close"}


def _run_two_chunks():
    step, _ = _toy_steps()
    loop = TrainLoop(step, None, _CountSource(), log_every=4, max_chunk=4,
                     log=lambda s: None)
    loop.run({"n": jnp.float32(0)}, {}, num_steps=8)


def test_trainloop_spans_reach_the_profiler_trace(tmp_path):
    """With the null Telemetry the loop's spans are still profiler
    annotations: a ``jax.profiler`` trace holds them on its host plane,
    on the clock of the annotation around the run."""
    from jax.profiler import ProfileData
    obs.shutdown()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            _run_two_chunks()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(ev.name, ev.start_ns, ev.end_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    (lo, hi), = [(s, e) for n, s, e in events if n == "test.window"]
    spans = [(n, s, e) for n, s, e in events if n.startswith("train.")]
    assert {n for n, _, _ in spans} == LOOP_SPANS
    assert all(lo <= s <= e <= hi for _, s, e in spans)
    # each loss fetch lies inside its log boundary
    logs = [(s, e) for n, s, e in spans if n == "train.log"]
    blocks = [(s, e) for n, s, e in spans if n == "train.block"]
    assert len(logs) == len(blocks) == 2
    assert all(a <= s <= e <= b for (a, b), (s, e) in zip(logs, blocks))
    # the first chunk length compiles; the second chunk reuses it
    assert [n for n, _, _ in spans if n.startswith("train.dispatch")] \
        == ["train.dispatch_first", "train.dispatch"]


def test_trainloop_spans_reach_a_configured_tracer():
    obs.configure(sink=MemorySink(), tracer=obs_trace.Tracer())
    _run_two_chunks()
    evs = obs.get().tracer.events
    assert {e["name"] for e in evs} == LOOP_SPANS
    assert {e["cat"] for e in evs} == {"train"}


# ---------------------------------------------------------------------------
# Trace export: schema round-trip
# ---------------------------------------------------------------------------

def test_trace_schema_roundtrip(tmp_path):
    tr = obs_trace.Tracer(process_name="test-proc")
    with tr.span("outer", cat="train", step=3) as args:
        with tr.span("inner", cat="train", tid=1):
            pass
        args["extra"] = 7            # body-added arg lands in the event
    tr.counter("sched", cat="serve", queue_depth=2, slots_busy=1.0)
    path = tr.write(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    obs_trace.validate(doc)          # the round-trip IS the schema check
    evs = doc["traceEvents"]
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 0,
                      "tid": 0, "args": {"name": "test-proc"}}
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["t0_unix"] > 0
    by_name = {e["name"]: e for e in evs[1:]}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["args"] == {"step": 3, "extra": 7}
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]
    assert by_name["sched"]["args"] == {"queue_depth": 2.0,
                                        "slots_busy": 1.0}
    # events come out time-sorted (Perfetto does not require it, humans
    # reading the JSON do)
    ts = [e["ts"] for e in evs[1:]]
    assert ts == sorted(ts)


def test_trace_validate_rejects_malformed():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
                           "pid": 0, "tid": 0}]}
    obs_trace.validate(ok)
    for mutate in ({"ph": "Z"}, {"ts": -1.0}, {"name": ""},
                   {"dur": None}):
        bad = {"traceEvents": [dict(ok["traceEvents"][0], **mutate)]}
        with pytest.raises(ValueError):
            obs_trace.validate(bad)
    with pytest.raises(ValueError):
        obs_trace.validate({"traceEvents": None})


# ---------------------------------------------------------------------------
# Sinks and the global registry
# ---------------------------------------------------------------------------

def test_jsonl_sink_header_provenance_and_seq(tmp_path):
    path = tmp_path / "m.jsonl"
    sink = JsonlSink(str(path), run={"cmd": "train", "arch": "x"})
    sink.emit({"kind": "train_step", "step": 1,
               "loss": jnp.float32(2.5)})   # device scalar -> json number
    sink.emit({"kind": "train_step", "step": 2, "loss": 2.25})
    sink.close()
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["kind"] == "run"
    assert recs[0]["run"] == {"cmd": "train", "arch": "x"}
    assert recs[0]["pid"] > 0
    assert [r["seq"] for r in recs] == [0, 1, 2]
    assert recs[1]["loss"] == 2.5 and "ts" in recs[1]
    # append-mode reopen: a resumed run extends the same file
    sink2 = JsonlSink(str(path), run={"cmd": "train", "resumed": True})
    sink2.emit({"kind": "train_step", "step": 3, "loss": 2.0})
    sink2.close()
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 5 and recs[3]["run"]["resumed"] is True


def test_jsonl_lines_readable_without_close(tmp_path):
    """Flush-per-record: a SIGKILLed run keeps every completed line."""
    sink = JsonlSink(str(tmp_path / "m.jsonl"), run={})
    sink.emit({"kind": "serve_request", "rid": 0})
    recs = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    assert [r["kind"] for r in recs] == ["run", "serve_request"]
    sink.close()


def test_console_log_routes_print_and_record(capsys):
    sink = MemorySink()
    tel = Telemetry(sink=sink)
    tel.log("step 10: loss=1.2345", kind="final_loss", loss=1.2345)
    assert capsys.readouterr().out == "step 10: loss=1.2345\n"
    assert sink.records == [{"kind": "final_loss",
                             "msg": "step 10: loss=1.2345",
                             "loss": 1.2345}]


def test_null_telemetry_is_inert_default():
    obs.shutdown()
    tel = obs.get()
    assert isinstance(tel.sink, NullSink) and not tel.enabled
    tel.emit("anything", x=1)        # no guard needed at call sites
    with tel.span("nothing", steps=4):
        pass
    tel.counter("nothing", x=1)


def test_configure_metrics_dir_builds_jsonl_and_trace(tmp_path):
    d = tmp_path / "metrics"
    tel = obs.configure(str(d), run={"cmd": "t"})
    assert tel is obs.get() and tel.enabled
    tel.emit("train_step", step=1, loss=1.0)
    with tel.span("train.dispatch", steps=2):
        pass
    obs.shutdown()
    recs = [json.loads(l) for l in open(d / "metrics.jsonl")]
    assert [r["kind"] for r in recs] == ["run", "train_step"]
    doc = json.load(open(d / "trace.json"))
    obs_trace.validate(doc)
    assert any(e["name"] == "train.dispatch" and e["cat"] == "train"
               for e in doc["traceEvents"])
    assert isinstance(obs.get().sink, NullSink)   # reset after shutdown


# ---------------------------------------------------------------------------
# Serve engine: per-request records emitted incrementally at retirement
# ---------------------------------------------------------------------------

def test_serve_engine_emits_request_records_at_retirement():
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, EngineConfig, Request

    sink = MemorySink()
    obs.configure(sink=sink, tracer=obs_trace.Tracer())
    cfg = configs.get_smoke("llama-60m")
    eng = Engine(cfg, lm.init(cfg, jax.random.key(0)),
                 EngineConfig(num_slots=2, page_size=8, max_ctx=16,
                              prefill_chunk=8))
    rng = np.random.RandomState(5)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, 6).tolist(),
                    max_gen=3) for i in range(3)]
    eng.run(reqs)
    recs = [r for r in sink.records if r["kind"] == "serve_request"]
    assert sorted(r["rid"] for r in recs) == [0, 1, 2]
    for r in recs:
        assert r["gen_tokens"] == 3 and r["prompt_tokens"] == 6
        assert 0.0 <= r["ttft_s"] <= r["latency_s"]
        assert r["done_s"] >= r["first_token_s"] >= r["admit_s"]
    # the run summary lands after every request record
    kinds = [r["kind"] for r in sink.records]
    assert kinds.index("serve_run") > max(
        i for i, k in enumerate(kinds) if k == "serve_request")
    # and the tracer saw serve-category spans + scheduler counters
    tr = obs.get().tracer
    cats = {e.get("cat") for e in tr.events}
    names = {e.get("name") for e in tr.events}
    assert "serve" in cats
    assert {"serve.prefill", "serve.decode", "sched"} <= names
