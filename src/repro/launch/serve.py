"""Serving launcher: continuous-batching engine CLI plus the small
static-batch ``generate`` helper the tests and examples drive directly.

    # continuous batching over a slot-paged KV cache (DESIGN.md §9)
    PYTHONPATH=src python -m repro.launch.serve --arch llama-60m --smoke \
        --requests 16 --prompt-len 32 --gen 16 --num-slots 4

    # same, int8-quantized KV pages, serving a training checkpoint
    PYTHONPATH=src python -m repro.launch.serve --arch llama-60m --smoke \
        --ckpt runs/smoke/ckpt --kv-quant int8

The engine itself lives in :mod:`repro.serve.engine`; this module only
builds a workload and prints the stats.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.cache import enable_compile_cache
from repro.models import lm
from repro.runtime.context import MeshContext


def pad_cache(cache, max_len: int, window: int = 0):
    """Grow full-attention prefill caches (depth = prompt) to decode
    capacity ``max_len``.  Ring-buffer (window) caches stay at window size —
    their slot arithmetic requires prompt_len % window == 0 (asserted at
    prefill).

    KV leaves are identified by their dict key ('k'/'v' — unique to
    attention caches); the sequence axis is -3 of (…, S, KV, hd), which
    covers both scan-stacked (L, B, S, KV, hd) and flat (B, S, KV, hd)
    layouts.  Growing is one-way: leaves already at or above ``max_len``
    are left alone.  Callers about to decode should assert the result
    with :func:`ensure_capacity` — a decode write past the cache end
    silently clamps (wrong attention), it does not error.
    """
    def grow(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v") and x.ndim >= 4 \
                and x.shape[-3] < max_len and x.shape[-3] != window:
            pad = [(0, 0)] * x.ndim
            pad[-3] = (0, max_len - x.shape[-3])
            return jnp.pad(x, pad)
        return x
    return jax.tree_util.tree_map_with_path(grow, cache)


def ensure_capacity(cache, needed: int, window: int = 0):
    """Raise unless every full-attention KV leaf can hold ``needed``
    positions.

    ``dynamic_update_slice`` CLAMPS out-of-bounds start indices instead of
    erroring, so a decode past an undersized cache quietly overwrites the
    last cache row — attention then reads a corrupted history and the
    failure surfaces as subtly wrong logits far from the cause.  This
    check turns that into a loud error at the call site.  Ring-buffer
    leaves (depth == ``window``) are exempt: they wrap by construction.
    Returns ``cache`` so it can wrap a cache expression in-line."""
    def check(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v") and x.ndim >= 4 \
                and x.shape[-3] != window and x.shape[-3] < needed:
            raise ValueError(
                f"KV cache depth {x.shape[-3]} < {needed} required: decode "
                f"writes past the end silently clamp (wrong attention) — "
                f"grow the cache with pad_cache(cache, {needed}) first")
        return x
    jax.tree_util.tree_map_with_path(check, cache)
    return cache


def generate(cfg, params, tokens, gen_len: int, greedy: bool = True,
             key=None, ctx: MeshContext = None):
    B, S = tokens.shape
    prefill = jax.jit(lm.make_prefill_step(cfg, ctx=ctx))
    decode = jax.jit(lm.make_decode_step(cfg, ctx=ctx))
    logits, cache = prefill(params, {"tokens": tokens})
    cache = ensure_capacity(pad_cache(cache, S + gen_len, window=cfg.window),
                            S + gen_len, window=cfg.window)
    out = []
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(gen_len):
        out.append(nxt)
        logits, cache = decode(params, cache, {"tokens": nxt})
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def build_workload(n: int, vocab: int, max_prompt: int, max_gen: int,
                   rate: float, seed: int):
    """Mixed-length serving workload: prompts uniform in
    [max_prompt//4, max_prompt]; generation lengths BIMODAL — 75% short
    (~max_gen/16..max_gen/8, chat-style turns) and 25% long
    (3·max_gen/4..max_gen, completion-style) — the length skew that makes
    static waves idle their short-request slots behind the long tail.
    ``rate`` > 0 adds Poisson (exponential inter-arrival) open-loop
    arrivals at that many req/s; 0 backlogs everything at t=0."""
    from repro.serve.engine import Request
    rng = np.random.RandomState(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        plen = int(rng.randint(max(1, max_prompt // 4), max_prompt + 1))
        if rng.rand() < 0.25:
            glen = int(rng.randint(max(2, 3 * max_gen // 4), max_gen + 1))
        else:
            glen = int(rng.randint(max(1, max_gen // 16),
                                   max(2, max_gen // 8) + 1))
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        reqs.append(Request(
            rid=i, prompt=rng.randint(0, vocab, size=plen).tolist(),
            max_gen=glen, arrival=t if rate > 0 else 0.0))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="training checkpoint dir to serve (params-only "
                         "load); default: random init")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate (req/s); "
                         "0 = backlogged")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--kv-quant", default=None, choices=[None, "int8"])
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a request early when it generates this "
                         "token (default: max_gen-bounded only)")
    ap.add_argument("--merge-lora", action="store_true",
                    help="treat --ckpt as a --finetune lora checkpoint: "
                         "restore {'base','lora'} and serve the merged "
                         "weights (auto-detected when the checkpoint's "
                         "run metadata records the fine-tune)")
    ap.add_argument("--lora-rank", type=int, default=8,
                    help="adapter rank for --merge-lora on checkpoints "
                         "without recorded fine-tune metadata")
    ap.add_argument("--lora-alpha", type=float, default=16.0)
    ap.add_argument("--static", action="store_true",
                    help="static-wave admission: a wave drains before the next")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "pallas", "interpret", "jnp"])
    ap.add_argument("--metrics-dir", default="",
                    help="telemetry directory (sibling of train "
                         "--metrics-dir): per-request JSONL records -> "
                         "<dir>/metrics.jsonl (emitted at retirement, so "
                         "a killed run keeps its completed requests) and "
                         "per-tick Chrome-trace spans/counters (queue "
                         "depth, slot occupancy, page-arena utilization) "
                         "-> <dir>/trace.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    from repro import obs
    tel = obs.configure(args.metrics_dir or None,
                        run={"cmd": "serve", "arch": args.arch,
                             "ckpt": args.ckpt, "requests": args.requests,
                             "num_slots": args.num_slots,
                             "kv_quant": args.kv_quant,
                             "static": args.static, "seed": args.seed})
    ctx = MeshContext.create(kernel_impl=args.kernel_impl)

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.arch_class == "encdec":
        raise SystemExit(
            "the serving engine is decoder-only; enc-dec decoding lives in "
            "repro.models.encdec.decode_stack (exercised by tests/"
            "test_models.py::test_encdec_decode_matches_teacher_forcing)")

    from repro.serve.engine import Engine, EngineConfig
    ecfg = EngineConfig(num_slots=args.num_slots, page_size=args.page_size,
                        max_ctx=args.prompt_len + args.gen,
                        prefill_chunk=args.prefill_chunk,
                        kv_quant=args.kv_quant, eos_id=args.eos_id)
    if args.ckpt:
        eng = Engine.from_checkpoint(
            cfg, args.ckpt, ecfg, ctx=ctx,
            merge_lora=True if args.merge_lora else None,
            lora_rank=args.lora_rank, lora_alpha=args.lora_alpha)
    else:
        eng = Engine(cfg, lm.init(cfg, jax.random.key(args.seed)), ecfg,
                     ctx=ctx)
    reqs = build_workload(args.requests, cfg.vocab, args.prompt_len,
                          args.gen, args.rate, args.seed)
    try:
        eng.warmup()
        stats = eng.run(reqs, static=args.static)
        stats["kv_arena_bytes"] = eng.kv_bytes()
        stats["mode"] = "static" if args.static else "continuous"
        tel.emit("serve_summary", **stats)
        print(json.dumps(stats, indent=2, sort_keys=True))
    finally:
        obs.shutdown()   # writes <metrics-dir>/trace.json
    return stats


if __name__ == "__main__":
    main()
