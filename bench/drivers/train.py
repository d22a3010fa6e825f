"""Driver of the training cells: GWT pre-training through the launcher's
own objects on one chip.

Set-up builds the program once — ``launch.train.make_optimizer('gwt',
...)``, ``models.lm.make_train_step`` and a
``runtime.fault_tolerance.TrainLoop`` (donated supersteps, the host
prefetch thread, loss fetched at ``log_every`` boundaries) — with weights
and token batches made from the seed by the benchmark.  It drives that
loop through its first steps, reading what the check compares (the losses,
the optimizer's moments after step 1, the parameters' change after the
last check step), then runs it on to step ``3 * log_every`` as a warm-up,
timing the last ``log_every`` steps to size the window.  The window runs the same loop on for whole ``log_every``
segments, enough of them to fill ``--seconds``, timed from the first
dispatch to the moment the last step's outputs are ready.  After the
window the peak device memory is read, the program's state freed, and the
architecture's plain reference trains the same first steps from the same
seed.  Everything architecture-specific — the configuration's reading, the
parameter layout, the program's configuration object and the reference —
comes from the configuration's module ``bench/arch/<bench_arch>.py``, so
this one driver runs every architecture.

In a ``--trace 1`` run the window is traced, and after it the window's
superstep is traced and compiled once more (:meth:`Program.traced_step`):
the device time of each of the program's named scopes and the idle time
under its host spans come from the trace and that compile's HLO text
(``bench/scopes.py``), and the program's trace-time counters from that
tracing.

The loop keeps the launcher's chunking: supersteps end on an absolute
grid of ``log_every`` steps (``TrainLoop``'s default ``max_chunk``), so
steps 1, 2-3 and 4-10 are supersteps of 1, 2 and 7 steps and the window's
are of ``log_every`` steps.  The check reads the state the 1- and 2-step
supersteps hand back; the window times the ``log_every``-step one.  They
are the same scan of the same step, built alike at other lengths, and set-up
compiles (or loads) every one of them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional

import numpy as np

from bench import check, data, peaks, scopes
from bench import trace as trace_lib
from bench import weights as wlib
from bench.harness import NoAccelerator
from bench.reference import slice_norms


@dataclasses.dataclass
class RunInfo:
    """What the per-layer readers (``bench/metrics``) read.  The last five
    are read in ``--trace 1`` runs only (``None`` otherwise)."""
    arch: Any                  # the architecture module (bench/model.py)
    spec: Any                  # its Spec of the configuration
    traffic: dict
    chips: int
    peaks: dict
    tokens_per_s: float
    steps: int
    trace: Optional[scopes.Summary]
    # device ms per step of each of scopes.SCOPES
    scopes: Optional[Dict[str, float]] = None
    # idle ms per step under each group of scopes.IDLE_UNDER
    idle_under: Optional[Dict[str, Optional[float]]] = None
    # trace-time counters: name -> value -> sum over the step's samples
    counters: Optional[Dict[str, Dict[str, float]]] = None
    hlo: Optional[str] = None  # the window superstep's compiled HLO text


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    device: dict
    run: RunInfo
    breakdown: Optional[dict]
    check: dict


def log(*parts):
    print("bench:", *parts, file=sys.stderr, flush=True)


CHECK_STEPS = 3      # steps the check compares: the reference trains these


def opt_settings(traffic: dict) -> dict:
    """The optimizer's settings, given alike to the program and to the
    reference (which implements GWT on an Adam host with f32 moments)."""
    o = traffic["optimizer"]
    if (o["name"], o["host"], o["state_codec"]) != ("gwt", "adam", "f32"):
        raise ValueError(f"the reference runs gwt/adam/f32, not "
                         f"{o['name']}/{o['host']}/{o['state_codec']}")
    return {k: o[k] for k in ("lr", "horizon", "level", "alpha", "b1", "b2",
                              "eps", "gamma")}


def check_layout(flat: dict, cfg) -> None:
    """The benchmark's weights must be the tree the program would build."""
    import jax
    from repro.models import lm
    want = wlib.flatten(jax.eval_shape(lambda: lm.init(cfg,
                                                       jax.random.key(0))))
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in flat.items()}
    want = {k: (tuple(v.shape), str(v.dtype)) for k, v in want.items()}
    if got != want:
        raise RuntimeError(f"bench weights do not match the program's tree: "
                           f"{sorted(set(got.items()) ^ set(want.items()))}")


def band_norms(plan, opt_state, b1: float) -> dict:
    """Per leaf and layer, the first gradient as the optimizer keeps it:
    ``‖m‖ / (1 - b1)`` after one step."""
    out = {}
    for b in plan.buckets:
        m = opt_state["buckets"][b.name]["host"]["m"]
        for j, path in enumerate(b.paths):
            out[path] = slice_norms(path, m[j]) / (1.0 - b1)
    return out


def change_norms(params: dict, arch, spec, seed: int, level: int) -> dict:
    """Per leaf and layer, ``‖p - p0‖`` with ``p0`` drawn again from the
    seed (the program's own first weights were donated)."""
    p0 = wlib.make_weights(arch, spec, seed, level)
    flat = wlib.flatten(params)
    out = {path: slice_norms(path, flat[path], p0[path]) for path in p0}
    del p0
    return out


def counter_sums(events) -> Dict[str, Dict[str, float]]:
    """The counter samples (``ph`` ``C``) of an ``obs.Tracer``'s events,
    summed per counter name and value."""
    out: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if ev["ph"] == "C":
            acc = out.setdefault(ev["name"], {})
            for k, v in ev["args"].items():
                acc[k] = acc.get(k, 0.0) + v
    return out


@contextlib.contextmanager
def no_compile_cache():
    """JAX's persistent compilation cache off inside the ``with``, back as
    it was after it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


class Watch:
    """Within its ``with``: how many programs JAX traced, and compiled or
    loaded from the compile cache, and how long the garbage collector held
    the interpreter."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.compiles, self.traces, self.gc_s, self._gc_t0 = 0, 0, 0.0, None

    def _event(self, event, secs, **kw):
        self.compiles += event == self.COMPILE
        self.traces += event == self.TRACE

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        import jax
        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._event)


class Program:
    """The program under test, built once and driven step by step."""

    def __init__(self, cell, require_tpu: bool, limiter: bool = True):
        """``limiter=False`` turns the optimizer's norm-growth limiter off
        (``bench/tools/calibrate.py`` isolates its share of a gap so)."""
        import jax
        from repro.launch import train as launcher
        from repro.launch.mesh import make_mesh_context
        from repro.models import lm
        from repro.runtime.fault_tolerance import TrainLoop
        self.arch = cell.arch()
        self.spec = self.arch.load_spec(cell.config_path)
        self.traffic = tr = cell.traffic
        self.opt = opt_settings(tr)
        self.cfg = self.arch.program_config(self.spec, tr["seq"])
        self.ctx = make_mesh_context(kernel_impl="auto")
        if require_tpu and self.ctx.kernel_impl != "pallas":
            raise RuntimeError(f"kernel impl resolved to "
                               f"{self.ctx.kernel_impl!r} on the TPU")
        o, opt = tr["optimizer"], self.opt
        self.optimizer = launcher.make_optimizer(
            o["name"], opt["lr"], opt["horizon"], level=opt["level"],
            alpha=opt["alpha"], host=o["host"],
            host_kwargs={k: opt[k] for k in ("b1", "b2", "eps")},
            gamma=opt["gamma"], use_limiter=limiter,
            impl=self.ctx.kernel_impl,
            state_codec=o["state_codec"])
        self.step_fn = lm.make_train_step(self.cfg, self.optimizer,
                                          accum_steps=tr["accum"],
                                          ctx=self.ctx)
        self.loop = TrainLoop(self.step_fn, None, None,
                              log_every=tr["log_every"], donate=True,
                              num_workers=0, log=log)
        self.jax = jax

    def first_steps(self, seed: int):
        """Fresh weights and state from ``seed``, driven through the check
        steps.  Returns ``(params, opt_state, readings)``."""
        spec, tr, level = self.spec, self.traffic, self.opt["level"]
        # (what ended, when): the set-up split that run() logs
        marks = [("interpreter, imports, TPU init, program build",
                  time.monotonic())]
        flat = wlib.make_weights(self.arch, spec, seed, level)
        check_layout(flat, self.cfg)
        params = wlib.nest(flat)
        del flat
        with self.ctx.activate():
            opt_state = self.optimizer.init(params)
        self.jax.block_until_ready((params, opt_state))
        marks.append(("weights and state", time.monotonic()))
        plan = self.optimizer.engine.plan(params)
        self.loop.data = data.make_source(seed, spec.vocab, tr)
        n = CHECK_STEPS
        with self.ctx.activate():
            params, opt_state, losses = self.loop.run(
                params, opt_state, start_step=0, num_steps=1)
            marks.append(("step 1 (compile or cache load)",
                          time.monotonic()))
            grad = band_norms(plan, opt_state, self.opt["b1"])
            params, opt_state, more = self.loop.run(
                params, opt_state, start_step=1, num_steps=n)
        readings = {"losses": list(losses) + list(more), "grad_band": grad,
                    "change": change_norms(params, self.arch, spec, seed,
                                           level)}
        marks.append((f"steps 2-{n} (compile or cache load) and the "
                      f"check's readings", time.monotonic()))
        self.marks = marks
        return params, opt_state, readings

    def run_to(self, params, opt_state, start: int, end: int):
        with self.ctx.activate():
            params, opt_state, losses = self.loop.run(
                params, opt_state, start_step=start, num_steps=end)
        self.jax.block_until_ready((params, opt_state))
        return params, opt_state, losses

    def chunk_at(self, step: int) -> int:
        """Length of the superstep that starts at ``step``."""
        return self.loop._chunk_end(step, step + self.traffic["log_every"]) \
            - step

    def window_batch(self, start: int) -> dict:
        """The shapes of the batches of the superstep that starts at
        ``start``."""
        tr = self.traffic
        sds = self.jax.ShapeDtypeStruct(
            (self.chunk_at(start), tr["batch"], tr["seq"]), np.int32)
        return {"tokens": sds, "labels": sds}

    def memory_analysis(self, params, opt_state, start: int) -> dict:
        """Bytes of the window's superstep (the one that starts at
        ``start``) by XLA's buffer assignment: the same program compiled
        once more (a compile-cache hit).  The allocator's
        ``peak_bytes_in_use`` does not count a program's temporaries on
        this chip, so the peak is the larger of the two."""
        comp = self.loop._superstep.lower(
            params, opt_state, self.window_batch(start)).compile()
        ma = comp.memory_analysis()
        return {k: int(getattr(ma, k + "_size_in_bytes"))
                for k in ("argument", "output", "alias", "temp")}

    def traced_step(self, params, opt_state, start: int) -> tuple:
        """``(hlo_text, counters)`` of the window's superstep, traced and
        compiled once more after the window; the timed program is not
        touched.  JAX's in-memory caches are cleared first, so that every
        jitted function of the step is traced again and the program's
        trace-time counters fire under an ``obs.Tracer``.  The compile
        bypasses the persistent cache, whose key leaves out the name stack:
        a cached executable carries the ``op_name``s of whichever build
        wrote it."""
        from repro import obs
        self.jax.clear_caches()
        tracer = obs.Tracer()
        obs.configure(tracer=tracer)
        try:
            lowered = self.loop._superstep.lower(params, opt_state,
                                                 self.window_batch(start))
        finally:
            obs.shutdown()
        with no_compile_cache():
            hlo = lowered.compile().as_text()
        return hlo, counter_sums(tracer.events)


def run(cell, *, seed: int, seconds: float, trace: bool, clock0: float,
        require_tpu: bool = True, keep_trace: str = "",
        compile_cache: bool = True) -> Outcome:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoAccelerator(f"cell {cell.name} needs {cell.chips} TPU "
                            f"chip(s); JAX found {len(devs)} "
                            f"{devs[0].platform} device(s)")
    if cell.chips != 1:
        raise NotImplementedError("this driver runs one-chip cells")
    if compile_cache:
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    prog = Program(cell, require_tpu)
    tr, spec, arch = prog.traffic, prog.spec, prog.arch
    params, opt_state, prog_readings = prog.first_steps(seed)
    every = tr["log_every"]
    # warm-up: the superstep up to the first log boundary, then one of the
    # window's length twice (it loads or compiles, then it is timed)
    params, opt_state, _ = prog.run_to(params, opt_state, CHECK_STEPS, every)
    marks = prog.marks + [("warm-up to the first log boundary",
                           time.monotonic())]
    params, opt_state, _ = prog.run_to(params, opt_state, every, 2 * every)
    t = time.monotonic()
    start = 3 * every
    params, opt_state, _ = prog.run_to(params, opt_state, 2 * every, start)
    per_step = (time.monotonic() - t) / every
    segments = max(1, math.ceil(seconds / (per_step * every)))
    steps = segments * every
    setup_s = time.monotonic() - clock0
    marks.append(("two supersteps of the window's length", time.monotonic()))
    ends = [clock0] + [t for _, t in marks]
    split = ", ".join(f"{name} {t1 - t0:.2f}s" for (name, t1), t0
                      in zip(marks, ends))
    log(f"set-up {setup_s:.2f}s: {split}; ~{per_step * 1e3:.1f} ms/step "
        f"in the warm-up; window of {steps} steps")

    # set-up's garbage is collected in set-up, not in the window
    t = time.monotonic()
    gc.collect()
    log(f"collected set-up's garbage in {time.monotonic() - t:.3f}s")
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host TraceMe spans only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with Watch() as watch, jax.profiler.TraceAnnotation(trace_lib.WINDOW):
        t0 = time.monotonic()
        params, opt_state, losses = prog.run_to(params, opt_state, start,
                                                start + steps)
        t1 = time.monotonic()
    if trace:
        jax.profiler.stop_trace()
    window = t1 - t0
    tokens_per_s = steps * tr["batch"] * tr["seq"] / window
    stats = devs[0].memory_stats() or {}
    allocator_peak = int(stats.get("peak_bytes_in_use", 0))
    failed = int(sum(not math.isfinite(x) for x in losses))
    t = time.monotonic()
    ma = prog.memory_analysis(params, opt_state, start)
    step_bytes = ma["argument"] + ma["output"] - ma["alias"] + ma["temp"]
    peak = max(allocator_peak, step_bytes)
    log(f"in the window: {watch.traces} traces, {watch.compiles} compiles "
        f"or cache loads, garbage collector {watch.gc_s:.3f}s")
    log(f"window {window:.3f}s, {tokens_per_s:.1f} tokens/s; allocator "
        f"peak {allocator_peak} B, step {step_bytes} B {ma} (read in "
        f"{time.monotonic() - t:.2f}s)")
    summary = hlo = counters = scope_ms = idle_ms = None
    if trace:
        t = time.monotonic()
        hlo, counters = prog.traced_step(params, opt_state, start)
        log(f"window superstep traced and compiled again in "
            f"{time.monotonic() - t:.2f}s; counters {counters}")
    del params, opt_state, prog
    gc.collect()

    if trace:
        summary = scopes.summarize(trace_lib.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        scope_ms, idle_ms = scopes.step_split(summary, hlo, steps)
        log(f"ms per step by scope {scope_ms}, idle under {idle_ms}")

    t = time.monotonic()
    ref = arch.Reference(spec, opt_settings(tr), tr["seq"]).run(
        wlib.make_weights(arch, spec, seed, tr["optimizer"]["level"]),
        [data.make_source(seed, spec.vocab, tr).batch(i)
         for i in range(CHECK_STEPS)])
    log(f"reference {time.monotonic() - t:.2f}s")
    left_out = sorted(f"{k}{np.flatnonzero(~v).tolist()}" for k, v in
                      check.moving_slices(ref["grad_full"]).items()
                      if not v.all())
    log("readings (compared where the cell's limits name them):",
        check.numbers(prog_readings, ref), "slices left out of the change:",
        left_out)
    correct, nums = check.compare(prog_readings, ref, cell.limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": [[n, s] for n, s in summary.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]}
    try:
        pk = peaks.peaks_for(devs[0].device_kind)
    except peaks.UnknownDevice:
        if require_tpu:
            raise
        pk = None
    info = RunInfo(arch=arch, spec=spec, traffic=tr, chips=cell.chips,
                   peaks=pk, tokens_per_s=tokens_per_s, steps=steps,
                   trace=summary, scopes=scope_ms, idle_under=idle_ms,
                   counters=counters, hlo=hlo)
    return Outcome(
        correct=correct, attempted=steps, failed=failed,
        end_to_end={"tokens_per_s": tokens_per_s, "peak_hbm_gb": peak / 1e9,
                    "setup_s": setup_s},
        device=device, run=info, breakdown=breakdown, check=nums)
