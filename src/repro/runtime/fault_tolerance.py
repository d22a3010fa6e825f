"""Fault-tolerant training runtime: pipelined superstep train loop with
buffer donation, preemption handling, auto-resume, a dispatch/block-split
step watchdog, and an elastic re-mesh hook.

Designed for the 1000+-node posture (DESIGN.md §4):

* **Pipelined supersteps**: the loop dispatches a ``lax.scan`` over a
  *chunk* of train steps per device call, with ``(params, opt_state)``
  donated across chunks — host python (batch stacking, dispatch) amortizes
  over the chunk and the optimizer state is single-buffered end to end.
  Loss lands in an on-device ``(k,)`` accumulator; the host fetches it only
  at ``log_every`` boundaries, so dispatch never serializes on a per-step
  ``float()`` sync.
* **Deterministic chunk grid**: chunk boundaries are *absolute* step
  numbers (next multiple of ``log_every`` / ``ckpt_every`` / ``max_chunk``
  / ``num_steps``), never relative to where a run started.  A resumed run
  therefore re-executes the exact same scan groupings as an uninterrupted
  one — bit-identical final params (tested in test_runtime_pipeline.py).
* **Preemption**: SIGTERM/SIGINT set a flag; the loop checkpoints
  synchronously at the current chunk boundary and exits 0 (the scheduler
  restarts the job, which auto-resumes from the latest committed step).
* **Snapshot-then-save**: periodic checkpoints are taken from an on-device
  copy (``CheckpointManager.save(snapshot=True)``) so the async writer
  never races the next chunk's buffer donation.
* **Watchdog**: separate EMAs for *dispatch* time (async enqueue — what the
  host pays per step) and *blocked* time (host stalled on device results at
  log/checkpoint boundaries).  Straggler incidents are flagged per phase;
  on a real pod this is where per-host attribution plugs in.
* **Elastic re-mesh**: ``CheckpointManager.restore(shardings=...)`` reshards
  on load, so a restart under a different device count only needs a new
  mesh + sharding tree (exercised in tests with different CPU device
  counts).
* **Data loading / eval**: ``num_workers > 0`` swaps the prefetch thread
  for shared-memory worker processes (``repro.data.workers``) behind the
  identical ``(index, batch)`` contract; ``evaluator``/``eval_every``
  stream held-out perplexity between chunks, with eval boundaries on the
  same absolute grid (DESIGN.md §5).
"""

from __future__ import annotations

import signal
import time
from collections import deque
from typing import Callable, List, Optional

from repro import obs


class PreemptionHandler:
    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self.requested = False
        self._orig = {}
        for s in signals:
            try:
                self._orig[s] = signal.signal(s, self._on_signal)
            except ValueError:  # non-main thread (tests)
                pass

    def _on_signal(self, signum, frame):
        self.requested = True

    def restore(self):
        for s, h in self._orig.items():
            signal.signal(s, h)


class StepWatchdog:
    """Two-phase straggler monitor.

    * ``start()`` / ``stop(step, n_steps)`` time the **dispatch** phase:
      how long the host spends enqueueing ``n_steps`` worth of work.  Under
      an async backend this is python + transfer overhead, NOT device
      compute — which is why it is tracked separately from
    * ``block(dt, n_steps)``: the **blocked** phase — host time stalled on
      device results (metric fetches at ``log_every``, snapshot syncs,
      blocking saves).  Device-side stragglers surface here.

    Each phase keeps a per-step EMA; a sample slower than
    ``slow_factor×EMA`` is logged with a monotonically-increasing incident
    id.  ``ema`` (dispatch) keeps its pre-split name for callers that only
    track one phase.

    Incident *records* land in ``incident_log``, a ring buffer capped at
    ``max_incidents`` (a pathological run — e.g. one straggling host in a
    large pod — can flag every chunk for days; the count stays exact while
    the records stay bounded, with ``incidents_dropped`` reporting the
    overflow).  ``incidents`` remains the total integer count.  Each
    incident is also emitted to the process-global metric sink
    (``repro.obs``) as a ``watchdog_incident`` record.
    """

    def __init__(self, slow_factor: float = 3.0, ema_alpha: float = 0.1,
                 log: Callable[[str], None] = print,
                 max_incidents: int = 64):
        self.slow_factor = slow_factor
        self.alpha = ema_alpha
        self.ema: Optional[float] = None         # dispatch s/step
        self.block_ema: Optional[float] = None   # blocked s/step
        self._incidents = 0
        self.incident_log: deque = deque(maxlen=max(int(max_incidents), 1))
        self.log = log
        self._t0: Optional[float] = None
        self._step = 0

    @property
    def incidents(self) -> int:
        """Total incident count (exact even after the ring drops records)."""
        return self._incidents

    @property
    def incidents_dropped(self) -> int:
        return self._incidents - len(self.incident_log)

    def _observe(self, phase: str, step: int, per_step: float,
                 ema: Optional[float]) -> float:
        if ema is not None and per_step > self.slow_factor * ema:
            self._incidents += 1
            rec = {"id": self._incidents, "step": step, "phase": phase,
                   "s_per_step": per_step, "ema": ema}
            self.incident_log.append(rec)
            obs.get().emit("watchdog_incident", **rec)
            self.log(f"[watchdog] step {step}: {phase} {per_step:.3f}s/step"
                     f" > {self.slow_factor:.1f}x EMA {ema:.3f}s "
                     f"(incident #{self._incidents})")
        return per_step if ema is None \
            else self.alpha * per_step + (1 - self.alpha) * ema

    def start(self):
        self._t0 = time.monotonic()

    def stop(self, step: int, n_steps: int = 1, record: bool = True) -> float:
        """``record=False`` returns the elapsed time without feeding the
        EMA — used for samples known to be unrepresentative (a chunk
        length's first dispatch includes its XLA compile; letting that
        seed the EMA would mask real stragglers for many chunks)."""
        dt = time.monotonic() - self._t0
        self._step = step
        if record:
            self.ema = self._observe("dispatch", step, dt / max(n_steps, 1),
                                     self.ema)
        return dt

    def block(self, dt: float, n_steps: int = 1, step: Optional[int] = None):
        self.block_ema = self._observe(
            "blocked", self._step if step is None else step,
            dt / max(n_steps, 1), self.block_ema)

    def summary(self) -> dict:
        return {"dispatch_s_per_step": self.ema,
                "blocked_s_per_step": self.block_ema,
                "incidents": self.incidents,
                "incidents_dropped": self.incidents_dropped,
                "incident_log": list(self.incident_log)}


class TrainLoop:
    """Checkpointed, preemption-safe, straggler-monitored loop around a
    train_step ``(params, opt_state, batch) -> (params, opt_state,
    metrics)``.  Used by launch/train.py, the examples, and ``bench/``.

    ``pipelined=True`` (default) wraps the step in a jitted
    scan-over-chunk *superstep* with ``donate_argnums=(params,
    opt_state)`` — pass the **un-jitted** step function (a pre-jitted one
    works too; it simply inlines).  The arrays passed to :meth:`run` are
    donated on the first dispatch and must not be reused by the caller
    (their shapes/dtypes stay readable).  ``donate=False`` opts out for
    callers that need the inputs afterwards.

    ``pipelined=False`` reproduces the pre-pipeline loop — one dispatch
    and one blocking ``float(loss)`` per step, synchronous batch fetch, no
    donation — and is the reference the pipelined loop is tested against
    (``tests/test_runtime_pipeline.py``).
    """

    def __init__(self, train_step, ckpt, data_source, *,
                 ckpt_every: int = 100, log_every: int = 10,
                 log: Callable[[str], None] = print,
                 pipelined: bool = True, donate: bool = True,
                 max_chunk: int = 16, save_final: bool = False,
                 batch_shardings=None, num_workers: int = 0,
                 evaluator=None, eval_every: int = 0, tap_step=None):
        self.train_step = train_step
        # optional tapped variant (lm.make_train_step(taps=True)): the
        # superstep scan runs it ONLY on the last iteration of each chunk
        # (a scan-body ``lax.cond`` on the step index), so the on-device
        # tap reductions cost 1/chunk of a per-step fusion while still
        # landing exactly on the log_every boundary where flush() fetches
        # them — same single dispatch, no extra launches or host syncs.
        # None -> the superstep graph is identical to the pre-obs loop
        # (the metrics-dir-unset bitwise guarantee).
        self.tap_step = tap_step
        self.ckpt = ckpt
        self.data = data_source
        self.ckpt_every = ckpt_every
        self.log_every = log_every
        self.log = log
        self.pipelined = pipelined
        self.donate = donate
        self.max_chunk = max(int(max_chunk), 1)
        self.save_final = save_final
        # data loading: 0 = background thread (Prefetcher); N > 0 = N
        # worker PROCESSES (repro.data.workers.ProcessPrefetcher) — same
        # (index, batch) protocol, so the desync check below is identical.
        # Batches are a pure function of the step, so worker count can
        # change across a resume without perturbing the stream.
        self.num_workers = int(num_workers)
        # held-out eval (repro.data.eval.Evaluator): runs between chunks
        # every `eval_every` steps — eval boundaries join the absolute
        # chunk grid, so enabling eval changes chunk partitioning (and
        # hence rounding) deterministically, identically across resumes.
        self.evaluator = evaluator
        self.eval_every = int(eval_every)
        # per-batch NamedSharding dict (the mesh-aware step's input
        # layout): host chunks are device_put straight onto the DP shards
        # — one H2D per device instead of a replicated upload that the
        # first sharding constraint immediately re-slices.
        self.batch_shardings = batch_shardings
        self._chunk_shardings = None  # leading scan axis added lazily
        self.watchdog = StepWatchdog(log=log)
        self.preempt = PreemptionHandler()
        self._superstep = None  # built lazily, reused across run() calls
        self._tap_keys = None   # tap names, recorded at superstep trace
        # chunk lengths dispatched by this loop: a new one compiles (or
        # loads from the compile cache), named in the trace as such
        self._dispatched: set = set()
        # Align the chunk grid to log_every when a reasonable divisor
        # exists: uniform chunk lengths mean ONE superstep compilation
        # instead of one per distinct length (log_every=20, max_chunk=16
        # would otherwise produce 16/4/12/8-step chunks, each compiled).
        # log_every boundaries cap chunks regardless, so the divisor only
        # has to be a decent fraction of min(max_chunk, log_every) — not
        # of max_chunk itself — to win; below that (e.g. prime log_every
        # smaller than max_chunk/2) mixed lengths amortize better than a
        # degenerate tiny uniform grid.
        g = self.max_chunk
        if log_every:
            cap = min(g, log_every)
            d = next((d for d in range(cap, 0, -1)
                      if log_every % d == 0), g)
            if d >= max(1, cap // 2):
                g = d
        self._grid = g

    # -- pipelined machinery -----------------------------------------------
    def _place(self, key: str, stacked):
        """Host (k, B, ...) chunk -> device.  With ``batch_shardings`` the
        chunk lands pre-sharded: the per-batch spec gains a replicated
        leading scan axis (every device sees every chunk index, only its
        own batch rows)."""
        import jax
        import jax.numpy as jnp
        if self.batch_shardings is None or key not in self.batch_shardings:
            return jnp.asarray(stacked)
        if self._chunk_shardings is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._chunk_shardings = {
                kk: NamedSharding(sh.mesh, P(None, *sh.spec))
                for kk, sh in self.batch_shardings.items()}
        return jax.device_put(stacked, self._chunk_shardings[key])

    def _build_superstep(self):
        import jax
        train_step = self.train_step
        tap_step = self.tap_step

        if tap_step is None:
            def superstep(params, opt_state, batches):
                def body(carry, batch):
                    p, s = carry
                    p, s, metrics = train_step(p, s, batch)
                    return (p, s), metrics["loss"]

                (params, opt_state), losses = jax.lax.scan(
                    body, (params, opt_state), batches)
                return params, opt_state, losses
        else:
            # Tapped superstep: same scan, but a lax.cond on the step
            # index routes the LAST iteration through the tapped step.
            # Keeping the boundary step inside the scan (vs a second
            # dispatch, or an unrolled final step after a k-1 scan)
            # measured cheapest on the step benchmark — one program, one
            # dispatch, and the tap reductions run once per chunk.  Off-
            # boundary iterations emit structural zeros for the tap ys so
            # both cond branches return identical pytrees.  The tap dict
            # is packed into ONE (T,) f32 vector (key order recorded at
            # trace time) so flush()'s device_get pulls two buffers per
            # chunk, not one per tap — a dict of ~30 scalar transfers
            # measured >1% of segment wall clock on its own.
            import jax.numpy as jnp

            def superstep(params, opt_state, batches):
                k = jax.tree_util.tree_leaves(batches)[0].shape[0]
                first = jax.tree_util.tree_map(lambda v: v[0], batches)
                spec = jax.eval_shape(
                    lambda p, s, b: tap_step(p, s, b)[2]["taps"],
                    params, opt_state, first)
                keys = sorted(spec)
                # trace-time side effect: tap names are static and
                # identical across chunk-length retraces
                self._tap_keys = keys
                zeros = jnp.zeros((len(keys),), jnp.float32)

                def body(carry, xs):
                    i, batch = xs
                    p, s = carry

                    def tapped(p, s):
                        p, s, m = tap_step(p, s, batch)
                        vec = jnp.stack(
                            [m["taps"][key].astype(jnp.float32)
                             for key in keys]) if keys else zeros
                        return p, s, m["loss"], vec

                    def plain(p, s):
                        p, s, m = train_step(p, s, batch)
                        return p, s, m["loss"], zeros

                    p, s, loss, taps = jax.lax.cond(
                        i == k - 1, tapped, plain, p, s)
                    return (p, s), (loss, taps)

                (params, opt_state), (losses, tapmat) = jax.lax.scan(
                    body, (params, opt_state), (jnp.arange(k), batches))
                return params, opt_state, (losses, tapmat[-1])

        kw = {"donate_argnums": (0, 1)} if self.donate else {}
        return jax.jit(superstep, **kw)

    def _chunk_end(self, step: int, num_steps: int) -> int:
        """Next chunk boundary AFTER ``step`` on the absolute grid.

        Boundaries are multiples of ``max_chunk`` / ``log_every`` /
        ``ckpt_every`` plus ``num_steps`` — a pure function of the step
        number, so a resumed run partitions the remaining steps exactly
        like the original run did (scan groupings, and hence float
        reduction order, are reproduced bit-for-bit)."""
        def nxt(every: int) -> int:
            return (step // every + 1) * every

        ends = [num_steps, nxt(self._grid)]
        if self.log_every:
            ends.append(nxt(self.log_every))
        if self.ckpt is not None and self.ckpt_every:
            ends.append(nxt(self.ckpt_every))
        if self.evaluator is not None and self.eval_every:
            ends.append(nxt(self.eval_every))
        return max(min(ends), step + 1)

    def _maybe_eval(self, step: int, params, k: int = 1):
        if self.evaluator is None or not self.eval_every \
                or step % self.eval_every:
            return
        t0 = time.monotonic()
        tel = obs.get()
        with tel.span("train.eval", step=step):
            r = self.evaluator(params, step)
        self.watchdog.block(time.monotonic() - t0, k)
        tel.emit("eval", step=step, loss=float(r["loss"]),
                 ppl=float(r["ppl"]), n_batches=self.evaluator.n_batches)
        self.log(f"step {step}: eval_loss={r['loss']:.4f} "
                 f"ppl={r['ppl']:.2f} ({self.evaluator.n_batches} batches)")

    def _save(self, step, params, opt_state, *, blocking=False,
              snapshot=False):
        self.ckpt.save(step, {"params": params, "opt": opt_state},
                       blocking=blocking, snapshot=snapshot)

    def _finalize(self, step, params, opt_state, preempted, last_saved):
        """Shared run epilogue: final blocking save (unless this step was
        just checkpointed, or the preempt path already saved it) + join
        the async writer."""
        if self.ckpt is None:
            return
        if self.save_final and not preempted and last_saved != step:
            self._save(step, params, opt_state, blocking=True)
        self.ckpt.wait()

    def run(self, params, opt_state, *, start_step: int = 0,
            num_steps: int = 100):
        if not self.pipelined:
            return self._run_eager(params, opt_state, start_step, num_steps)
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.data.pipeline import Prefetcher, stack_batches

        if self._superstep is None:
            self._superstep = self._build_superstep()

        tel = obs.get()
        losses: List[float] = []
        # device metric chunks pending one host fetch: (base_step, ys)
        # where ys is a (k,) loss vector or, on the tapped path,
        # ((k,) losses, (T,) tap vector sampled at the chunk's last step
        # — names in self._tap_keys, recorded when the superstep traced)
        window: list = []
        nwin = 0

        def flush():
            nonlocal window, nwin
            if not window:
                return
            t0 = time.monotonic()
            with tel.span("train.block", steps=nwin):
                fetched = jax.device_get([ys for _, ys in window])
            self.watchdog.block(time.monotonic() - t0, nwin)
            emit = getattr(tel.sink, "enabled", True)
            for (base, _), ys in zip(window, fetched):
                tapped = isinstance(ys, tuple)
                lv = np.asarray(ys[0] if tapped else ys)
                losses.extend(float(v) for v in lv)
                if not emit:
                    continue
                for j, lval in enumerate(lv):
                    rec = {"step": base + j + 1, "loss": float(lval)}
                    if tapped and j == len(lv) - 1:
                        rec.update(zip(self._tap_keys,
                                       np.asarray(ys[1], float).tolist()))
                    tel.emit("train_step", **rec)
            window, nwin = [], 0

        step = start_step
        if self.num_workers > 0:
            from repro.data.workers import ProcessPrefetcher
            pf = ProcessPrefetcher(self.data, start_step=step,
                                   depth=2 * self.max_chunk,
                                   num_workers=self.num_workers)
        else:
            pf = Prefetcher(self.data, start_step=step,
                            depth=2 * self.max_chunk)
        preempted = False
        last_saved = None
        compiled_sizes: set = set()   # chunk lengths whose compile is paid
        try:
            while step < num_steps:
                end = self._chunk_end(step, num_steps)
                k = end - step
                batches = []
                with tel.span("train.input_wait", steps=k):
                    for j in range(k):
                        i, b = next(pf)
                        if i != step + j:   # bit-determinism depends on this
                            raise RuntimeError(
                                f"data stream desync: got batch "
                                f"{i}, want {step + j}")
                        batches.append(b)
                with tel.span("train.place", steps=k):
                    chunk = {kk: self._place(kk, v)
                             for kk, v in stack_batches(batches).items()}
                self.watchdog.start()
                phase = "train.dispatch" if k in self._dispatched \
                    else "train.dispatch_first"
                with tel.span(phase, step=step, steps=k):
                    params, opt_state, lchunk = self._superstep(
                        params, opt_state, chunk)
                self._dispatched.add(k)
                dt = self.watchdog.stop(step, k,
                                        record=k in compiled_sizes)
                compiled_sizes.add(k)
                window.append((step, lchunk))
                nwin += k
                step = end
                if self.log_every and step % self.log_every == 0:
                    # the boundary: the loss fetch (train.block) and the
                    # log line, so no stretch of it is out of a span
                    with tel.span("train.log", step=step):
                        flush()
                        blocked = (self.watchdog.block_ema or 0) * 1e3
                        self.log(f"step {step}: loss={losses[-1]:.4f} "
                                 f"(dispatch {dt / k * 1e3:.1f}ms/step, "
                                 f"blocked {blocked:.1f}ms/step)")
                self._maybe_eval(step, params, k)
                if self.ckpt is not None and self.ckpt_every \
                        and step % self.ckpt_every == 0:
                    t0 = time.monotonic()
                    with tel.span("train.save", step=step):
                        self._save(step, params, opt_state, snapshot=True)
                    last_saved = step
                    self.watchdog.block(time.monotonic() - t0, k)
                if self.preempt.requested:
                    preempted = True
                    flush()
                    self.log(f"[preempt] checkpoint@{step} and exit")
                    if self.ckpt is not None:
                        self._save(step, params, opt_state, blocking=True)
                    break
        finally:
            with tel.span("train.close"):
                pf.close()
        flush()
        self._finalize(step, params, opt_state, preempted, last_saved)
        # fold the watchdog's phase split into the sink (ring-buffered
        # incident records included) so post-hoc analysis needs no stdout
        tel.emit("watchdog_summary", step=step, **self.watchdog.summary())
        return params, opt_state, losses

    # -- pre-pipeline reference loop ---------------------------------------
    def _run_eager(self, params, opt_state, start_step: int, num_steps: int):
        """The pre-pipeline semantics: sync fetch, one dispatch + one
        ``float(loss)`` host sync per step, undonated buffers.  Kept as the
        benchmark baseline and for callers that need per-step host
        control."""
        import jax
        step = start_step
        losses: List[float] = []
        last_saved = None
        while step < num_steps:
            batch = self.data.batch(step)
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            self.watchdog.start()
            params, opt_state, metrics = self.train_step(params, opt_state,
                                                         batch)
            self.watchdog.stop(step)
            t0 = time.monotonic()
            loss = float(metrics["loss"])
            self.watchdog.block(time.monotonic() - t0)
            losses.append(loss)
            step += 1
            if self.log_every and step % self.log_every == 0:
                self.log(f"step {step}: loss={loss:.4f}")
            self._maybe_eval(step, params)
            if self.ckpt is not None and self.ckpt_every \
                    and step % self.ckpt_every == 0:
                self._save(step, params, opt_state)
                last_saved = step
            if self.preempt.requested:
                self.log(f"[preempt] checkpoint@{step} and exit")
                if self.ckpt is not None:
                    self._save(step, params, opt_state, blocking=True)
                break
        self._finalize(step, params, opt_state, self.preempt.requested,
                       last_saved)
        return params, opt_state, losses
