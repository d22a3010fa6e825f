"""Continuous-batching scheduler over the slot-paged KV cache
(DESIGN.md §9).

One :class:`Engine` owns ``num_slots`` request slots, a shared page arena
per attention layer (:mod:`repro.serve.kv`), and exactly two compiled
functions — reused for the whole lifetime of the engine:

* ``chunk_prefill``: pages in ONE waiting request's next
  ``prefill_chunk`` prompt tokens (fixed ``(1, C)`` shape; the final
  short chunk is padded — padded positions land beyond the slot's length
  and are never valid before decode overwrites them);
* ``decode``: one greedy token for EVERY slot (fixed
  ``(num_slots, 1)`` shape; non-decoding slots carry the trash page
  table and a zero length, so their scatters land in page 0 and their
  garbage logits are simply not read).

Every scheduler tick interleaves both: admit arrived requests into free
slots (page allocation is a free-list pop), run one prefill chunk if any
slot is mid-prompt, then one decode step if any slot is generating.
Requests therefore join and leave the running batch *between decode
steps* — the continuous-batching property — instead of the static-wave
discipline (``static=True``: admit only when all slots are free, decode
only once every admitted prompt is fully paged in).

Both compiled steps are jitted with ``donate_argnums=(1,)``: the page
pools are the only mutated state and XLA aliases them in place, so the
persistent footprint is one arena regardless of how long the engine
runs.  The engine rebinds ``self.pools`` after every call — donated
buffers must never be reused.

Greedy decoding only: the engine exists to exercise and measure the
serving *runtime* — scheduling, page accounting, cache quantization —
not sampling strategies.  A request retires when it hits its ``max_gen``
bound, emits ``EngineConfig.eos_id``, or its generation ends with any of
``EngineConfig.stop_seqs`` — retirement frees the slot's pages
immediately, so a queued request can be admitted the very next tick.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm
from repro.serve import kv as kv_lib

FREE, PREFILL, DECODE = 0, 1, 2


@dataclass
class Request:
    """One serving request.  ``arrival`` is seconds after ``run()`` starts
    (0 = backlogged); the engine fills the telemetry fields."""
    rid: int
    prompt: Sequence[int]
    max_gen: int
    arrival: float = 0.0
    generated: List[int] = field(default_factory=list)
    t_admit: float = -1.0
    t_first: float = -1.0   # first generated token (end of prefill)
    t_done: float = -1.0


@dataclass
class EngineConfig:
    num_slots: int = 4
    page_size: int = 16
    max_ctx: int = 256          # per-request prompt + generation bound
    prefill_chunk: int = 32
    kv_quant: Optional[str] = None      # None | "int8"
    num_pages: Optional[int] = None     # default: every slot can fill up
    eos_id: Optional[int] = None        # retire the slot on this token
    stop_seqs: Sequence[Sequence[int]] = ()   # ...or on any of these tails

    @property
    def max_pages(self) -> int:
        return -(-self.max_ctx // self.page_size)

    def resolved_num_pages(self) -> int:
        return self.num_pages if self.num_pages is not None \
            else 1 + self.num_slots * self.max_pages


class Engine:
    def __init__(self, cfg, params, ecfg: Optional[EngineConfig] = None,
                 ctx=None):
        ecfg = ecfg or EngineConfig()
        if getattr(cfg, "arch_class", "decoder") == "encdec":
            raise NotImplementedError(
                "Engine serves decoder-only archs; enc-dec decoding lives "
                "in repro.models.encdec.decode_stack (see tests/"
                "test_models.py::test_encdec_decode_matches_teacher_forcing)")
        bad = [k for k in cfg.pattern if k.split("+")[0] != "attn"]
        if bad or (cfg.window or 0):
            raise NotImplementedError(
                f"paged serving covers full-attention decoder stacks; "
                f"pattern {cfg.pattern} window {cfg.window} has no "
                f"page-table layout (sliding windows ring-buffer, "
                f"recurrent mixers keep O(1) state)")
        if cfg.mrope_sections:
            raise NotImplementedError("paged serving does not thread "
                                      "multimodal rope position trees")
        np_ = ecfg.resolved_num_pages()
        if np_ < 1 + ecfg.max_pages:
            raise ValueError(
                f"num_pages={np_} cannot hold even one full request "
                f"({ecfg.max_pages} pages) plus the trash page")
        self.cfg, self.params, self.ecfg, self.ctx = cfg, params, ecfg, ctx
        self.num_pages = np_
        self.pools = lm.init_paged_caches(cfg, np_, ecfg.page_size,
                                          kv_quant=ecfg.kv_quant)
        # argmax is fused INTO the compiled steps: returning (V,)-wide
        # logits for an eager argmax costs one extra host dispatch per
        # tick, which at serving batch sizes is scheduler-dominating
        chunk = lm.make_chunk_prefill_step(cfg, ctx=ctx)
        decode = lm.make_paged_decode_step(cfg, ctx=ctx)

        def chunk_step(params, pools, pt, filled, tokens):
            logits, pools = chunk(params, pools, pt, filled, tokens)
            return jnp.argmax(logits[0], axis=-1), pools    # (C,) greedy

        def decode_step(params, pools, pt, lens, tokens):
            logits, pools = decode(params, pools, pt, lens, tokens)
            return jnp.argmax(logits, axis=-1), pools       # (num_slots,)

        self._chunk_step = jax.jit(chunk_step, donate_argnums=(1,))
        self._decode_step = jax.jit(decode_step, donate_argnums=(1,))
        self._tel = obs.get()   # re-resolved per run(); see there
        self.reset()

    # -- bookkeeping -------------------------------------------------------
    def reset(self):
        """Clear scheduler state between runs.  The pools are NOT zeroed:
        stale entries sit beyond every slot's ``kv_valid`` horizon, so
        correctness never depends on arena contents."""
        e = self.ecfg
        self.page_table = np.zeros((e.num_slots, e.max_pages), np.int32)
        self.lens = np.zeros((e.num_slots,), np.int32)
        self.free_pages = list(range(self.num_pages - 1, 0, -1))  # pop -> 1,2,..
        self.slots = [{"state": FREE, "req": None, "filled": 0,
                       "pages": [], "last": 0} for _ in range(e.num_slots)]

    def kv_bytes(self) -> int:
        return kv_lib.pool_bytes(self.pools)

    @classmethod
    def from_checkpoint(cls, cfg, ckpt_dir: str,
                        ecfg: Optional[EngineConfig] = None,
                        step: Optional[int] = None, ctx=None,
                        merge_lora: Optional[bool] = None,
                        lora_rank: int = 8,
                        lora_alpha: float = 16.0) -> "Engine":
        """Build an engine straight from a training checkpoint directory,
        loading only the params leaves (the optimizer state never touches
        host memory — ``CheckpointManager.restore_params``).

        Fine-tuned checkpoints hold a ``{"base", "lora"}`` tree instead of
        plain params; the engine's forward knows nothing about adapters,
        so they are merged into the base weights at load
        (:func:`repro.models.lora.merge`).  ``merge_lora=None``
        auto-detects from the checkpoint's run metadata (``--finetune
        lora`` runs stamp rank/alpha there); pass ``True`` with
        ``lora_rank``/``lora_alpha`` for checkpoints written without it."""
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(ckpt_dir)
        ft = mgr.saved_run(step).get("finetune") or {}
        if merge_lora is None:
            merge_lora = ft.get("mode") == "lora"
        if merge_lora:
            from repro.models import lora
            rank = int(ft.get("rank", lora_rank))
            alpha = float(ft.get("alpha", lora_alpha))
            like = jax.eval_shape(
                lambda p: lora.inject(p, rank, jax.random.key(0)),
                lm.abstract_params(cfg))
            tree, _ = mgr.restore_params(step, like, ctx=ctx)
            params = lora.merge(tree, alpha, rank)
        else:
            params, _ = mgr.restore_params(
                step, lm.abstract_params(cfg), ctx=ctx)
        return cls(cfg, params, ecfg, ctx=ctx)

    def warmup(self):
        """Trigger both compiles against the trash page so timed runs
        measure steady-state scheduling, not tracing."""
        e = self.ecfg
        _, self.pools = self._chunk_step(
            self.params, self.pools, jnp.zeros((1, e.max_pages), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, e.prefill_chunk), jnp.int32))
        _, self.pools = self._decode_step(
            self.params, self.pools,
            jnp.zeros((e.num_slots, e.max_pages), jnp.int32),
            jnp.zeros((e.num_slots,), jnp.int32),
            jnp.zeros((e.num_slots, 1), jnp.int32))

    # -- scheduling --------------------------------------------------------
    def _admit_one(self, req: Request, slot: int, now: float) -> bool:
        plen, cap = len(req.prompt), self.ecfg.max_ctx
        if plen + req.max_gen > cap:
            raise ValueError(f"request {req.rid}: prompt {plen} + gen "
                             f"{req.max_gen} exceeds max_ctx {cap}")
        need = -(-(plen + req.max_gen) // self.ecfg.page_size)
        if len(self.free_pages) < need:
            return False
        pages = [self.free_pages.pop() for _ in range(need)]
        self.page_table[slot, :] = kv_lib.TRASH_PAGE
        self.page_table[slot, :need] = pages
        self.lens[slot] = 0
        s = self.slots[slot]
        s.update(state=PREFILL, req=req, filled=0, pages=pages, last=0)
        req.t_admit = now
        return True

    def _admit(self, pending: deque, now: float, static: bool):
        if static and any(s["state"] != FREE for s in self.slots):
            return  # static waves: the whole batch drains before refill
        for slot, s in enumerate(self.slots):
            if not pending or pending[0].arrival > now:
                break
            if s["state"] != FREE:
                continue
            if not self._admit_one(pending[0], slot, now):
                break   # page pressure: keep FIFO order, wait for retires
            pending.popleft()

    def _finished(self, req: Request) -> bool:
        """max_gen bound, EOS token, or a stop-sequence tail — checked
        after every appended token (prefill's first token included), so a
        stopped slot frees its pages before the next admit pass."""
        if len(req.generated) >= req.max_gen:
            return True
        e = self.ecfg
        if e.eos_id is not None and req.generated \
                and req.generated[-1] == e.eos_id:
            return True
        return any(stop and len(req.generated) >= len(stop)
                   and req.generated[-len(stop):] == list(stop)
                   for stop in e.stop_seqs)

    def _retire(self, slot: int, now: float):
        s = self.slots[slot]
        self.free_pages.extend(sorted(s["pages"], reverse=True))
        self.page_table[slot, :] = kv_lib.TRASH_PAGE
        self.lens[slot] = 0
        req = s["req"]
        req.t_done = now
        # per-request record emitted AT retirement, not at end of run():
        # a killed run leaves one usable JSONL line per completed request
        # (the sink flushes per record), instead of losing everything to
        # the end-of-run percentile pass.
        self._tel.emit(
            "serve_request", rid=req.rid, slot=slot,
            prompt_tokens=len(req.prompt), gen_tokens=len(req.generated),
            arrival_s=req.arrival, admit_s=req.t_admit,
            first_token_s=req.t_first, done_s=req.t_done,
            ttft_s=req.t_first - req.t_admit,
            latency_s=req.t_done - req.arrival)
        s.update(state=FREE, req=None, filled=0, pages=[], last=0)

    def _prefill_tick(self, now) -> bool:
        slot = next((i for i, s in enumerate(self.slots)
                     if s["state"] == PREFILL), None)
        if slot is None:
            return False
        s = self.slots[slot]
        req, C = s["req"], self.ecfg.prefill_chunk
        plen = len(req.prompt)
        chunk = list(req.prompt[s["filled"]:s["filled"] + C])
        real = len(chunk)
        tokens = jnp.asarray([chunk + [0] * (C - real)], jnp.int32)
        with self._tel.span("serve.prefill", slot=slot,
                            rid=req.rid, tokens=real):
            greedy, self.pools = self._chunk_step(
                self.params, self.pools,
                jnp.asarray(self.page_table[slot:slot + 1]),
                jnp.asarray([s["filled"]], jnp.int32), tokens)
        s["filled"] += real
        if s["filled"] >= plen:
            # prompt fully paged in: its final position's greedy token is
            # in THIS chunk (possibly mid-chunk when the tail was padded)
            g0 = int(greedy[plen - 1 - (s["filled"] - real)])
            req.generated.append(g0)
            req.t_first = now()
            self.lens[slot] = plen
            if self._finished(req):
                self._retire(slot, now())
            else:
                s.update(state=DECODE, last=g0)
        return True

    def _decode_tick(self, now, static: bool) -> bool:
        active = [i for i, s in enumerate(self.slots)
                  if s["state"] == DECODE]
        if not active:
            return False
        if static and any(s["state"] == PREFILL for s in self.slots):
            return False  # static baseline: decode starts when the wave is in
        e = self.ecfg
        tokens = np.zeros((e.num_slots, 1), np.int32)
        pt = np.zeros_like(self.page_table)     # non-decode rows -> trash
        ln = np.zeros_like(self.lens)
        for i in active:
            tokens[i, 0] = self.slots[i]["last"]
            pt[i] = self.page_table[i]
            ln[i] = self.lens[i]
        with self._tel.span("serve.decode", active=len(active)):
            greedy, self.pools = self._decode_step(
                self.params, self.pools, jnp.asarray(pt), jnp.asarray(ln),
                jnp.asarray(tokens))
        nxt = np.asarray(greedy)
        for i in active:
            s = self.slots[i]
            self.lens[i] += 1
            tok = int(nxt[i])
            s["req"].generated.append(tok)
            s["last"] = tok
            if self._finished(s["req"]):
                self._retire(i, now())
        return True

    def run(self, requests: Sequence[Request], static: bool = False) -> dict:
        """Serve ``requests`` to completion under open-loop arrivals
        (each request joins the queue at its ``arrival`` offset, whether
        or not the engine is keeping up).  Returns aggregate stats; the
        per-request telemetry lands on the Request objects."""
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        # late-bound: the launcher configures the global Telemetry after
        # engine construction; ticks and _retire read self._tel
        tel = self._tel = obs.get()
        t0 = time.monotonic()
        now = lambda: time.monotonic() - t0
        arena = max(self.num_pages - 1, 1)   # page 0 is the trash page
        while pending or any(s["state"] != FREE for s in self.slots):
            self._admit(pending, now(), static)
            busy = self._prefill_tick(now)
            busy = self._decode_tick(now, static) or busy
            if busy and tel.tracer is not None:
                tel.counter(
                    "sched", cat="serve",
                    queue_depth=sum(r.arrival <= now() for r in pending),
                    slots_busy=sum(s["state"] != FREE for s in self.slots),
                    page_util=1.0 - len(self.free_pages) / arena)
            if not busy and pending:
                time.sleep(max(0.0, min(pending[0].arrival - now(), 0.02)))
        makespan = now()
        lat = sorted(r.t_done - r.arrival for r in requests)
        gen = sum(len(r.generated) for r in requests)
        pct = lambda p: lat[min(len(lat) - 1,
                                int(p / 100.0 * len(lat)))] if lat else 0.0
        stats = {"requests": len(requests),
                 "generated_tokens": gen,
                 "prompt_tokens": sum(len(r.prompt) for r in requests),
                 "makespan_s": makespan,
                 "requests_per_sec": len(requests) / makespan,
                 "tokens_per_sec": gen / makespan,
                 "p50_s": pct(50), "p99_s": pct(99)}
        tel.emit("serve_run", static=static, **stats)
        return stats
