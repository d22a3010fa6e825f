"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama-60m \
        --optimizer gwt --level 2 --steps 200 --batch 16 --seq 256 \
        --ckpt-dir /tmp/ckpt [--resume] [--data bytes]

Distributed (mesh-aware) training — the sharded path of DESIGN.md §3:

    python -m repro.launch.train ... --mesh 8 --dp-reduce compressed \
        --dp-level 2 [--dp-detail-dtype bfloat16] [--shard-params auto]

``--dp-reduce`` routes the data-parallel gradient reduction through
``shard_map`` + ``compressed_psum_mean`` (exact f32 psum or wavelet-
compressed wire format); ``--shard-params auto`` additionally pins
params/optimizer state to the FSDP/TP rule table.

On a real TPU pod this runs under ``jax.distributed.initialize()`` with the
production mesh; in the CPU container it runs single-device (or multi-device
via XLA_FLAGS) with the same code path.  Fault tolerance: SIGTERM →
synchronous checkpoint → exit 0; restart with ``--resume`` continues from
the latest committed step with the data stream aligned.
"""

from __future__ import annotations

import argparse
import math

import jax

from repro import configs, obs, optim
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import make_source
from repro.distributed.compression import DPReduceSpec
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh_context
from repro.models import encdec, lm
from repro.optim.schedules import warmup_cosine
from repro.runtime.fault_tolerance import TrainLoop


def make_optimizer(name: str, lr: float, steps: int, **kw) -> optim.Optimizer:
    sched = warmup_cosine(lr, steps)
    return optim.make(name, lr=sched, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--optimizer", default="gwt",
                    choices=["gwt", "adam", "adam_mini", "muon", "galore",
                             "apollo", "fira", "adarankgrad", "rso", "sgd"])
    ap.add_argument("--level", type=int, default=2)
    ap.add_argument("--host", default="adam",
                    choices=["adam", "adam_mini", "muon"])
    ap.add_argument("--state-codec", default="f32",
                    choices=["f32", "int8"],
                    help="optimizer-state substrate: 'f32' = raw moments "
                         "(bitwise-identical to the pre-codec engine), "
                         "'int8' = blocked 8-bit moments (per-64-block "
                         "absmax scale, stochastic rounding; composes "
                         "with any --optimizer).  --resume transcodes "
                         "when the checkpoint was written under the "
                         "other codec")
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--finetune", default="none", choices=["none", "lora"],
                    help="'lora': freeze the base model (zero optimizer "
                         "state via the engine's frozen rule) and train "
                         "injected low-rank adapters on the attention/MLP "
                         "projections; composes with any --optimizer/"
                         "--state-codec (the adapters' moments get "
                         "compressed/quantized)")
    ap.add_argument("--lora-rank", type=int, default=8)
    ap.add_argument("--lora-alpha", type=float, default=16.0)
    ap.add_argument("--base-ckpt", default="",
                    help="checkpoint dir holding the pre-trained base "
                         "(params-only restore via restore_params); with "
                         "--finetune lora the restored weights become the "
                         "frozen base")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "bytes", "corpus"])
    ap.add_argument("--corpus-dir", default="",
                    help="with --data corpus: a directory built by "
                         "`python -m repro.data.build_corpus` (mmap "
                         "token shards + index)")
    ap.add_argument("--workers", type=int, default=0,
                    help="data-loader worker PROCESSES (shared-memory "
                         "transport; 0 = in-process prefetch thread).  "
                         "Batches are a pure function of the step, so "
                         "worker count never changes the stream — safe "
                         "to vary across resumes")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate held-out loss/perplexity every N "
                         "steps (corpus eval split, or a disjoint "
                         "synthetic stream); 0 disables")
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="elastic mesh, e.g. '4x2' over (data, model); "
                         "empty = single device (or all devices over "
                         "'data' when --dp-reduce is set)")
    ap.add_argument("--dp-reduce", default="none",
                    choices=["none", "exact", "compressed"],
                    help="mesh-aware DP gradient reduction: 'exact' = f32 "
                         "psum inside shard_map, 'compressed' = wavelet "
                         "split (f32 approximation band, --dp-detail-dtype "
                         "details); 'none' keeps the auto-sharded step")
    ap.add_argument("--dp-level", type=int, default=2,
                    help="wavelet levels for --dp-reduce compressed "
                         "(wire bytes ~ 1/2^l f32 + (1-1/2^l) detail)")
    ap.add_argument("--dp-detail-dtype", default="bfloat16",
                    choices=["bfloat16", "float16", "float8_e4m3fn"],
                    help="detail-band wire dtype for --dp-reduce "
                         "compressed (the psum ships this dtype)")
    ap.add_argument("--dp-error-feedback", action="store_true",
                    help="with --dp-reduce compressed: keep each "
                         "device's quantization residue and add it back "
                         "before the next reduction (the compressed "
                         "mean's bias averages out instead of "
                         "persisting)")
    ap.add_argument("--shard-params", default="auto",
                    choices=["auto", "none"],
                    help="with --dp-reduce only (no effect otherwise — "
                         "plain mesh runs stay GSPMD-auto-sharded): "
                         "'auto' pins params/opt-state to the FSDP rule "
                         "table, 'none' keeps them replicated (classic "
                         "DP — the layout whose numerics are independent "
                         "of device count)")
    ap.add_argument("--no-donate", action="store_true",
                    help="keep (params, opt_state) undonated in the "
                         "pipelined loop.  Donation changes XLA's fusion "
                         "(and hence float rounding) per topology, so "
                         "cross-device-count bitwise reproducibility "
                         "requires it off; same-topology runs are "
                         "deterministic either way")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "pallas", "interpret", "jnp"],
                    help="fused-kernel backend (auto: pallas on TPU, "
                         "jnp elsewhere; REPRO_KERNEL_IMPL also works)")
    ap.add_argument("--metrics-dir", default="",
                    help="telemetry directory (DESIGN.md §12): JSONL "
                         "metric records -> <dir>/metrics.jsonl, Chrome-"
                         "trace spans -> <dir>/trace.json (open in "
                         "Perfetto), and the on-device training-dynamics "
                         "taps (band energy, clip rate, update norms) "
                         "joined to the step metrics.  Unset: telemetry "
                         "compiles away — training numerics stay "
                         "bitwise-identical")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tel = obs.configure(args.metrics_dir or None,
                        run={"cmd": "train", "arch": args.arch,
                             "optimizer": args.optimizer,
                             "level": args.level, "host": args.host,
                             "state_codec": args.state_codec,
                             "steps": args.steps, "seed": args.seed,
                             "finetune": args.finetune})

    dp_spec = DPReduceSpec.parse(args.dp_reduce, args.dp_level,
                                 args.dp_detail_dtype,
                                 error_feedback=args.dp_error_feedback)
    if args.mesh:
        try:
            shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh {args.mesh!r}: expected integers joined by "
                     "'x', e.g. '8' or '4x2' or '2x4x2'")
        if not 1 <= len(shape) <= 3:
            ap.error(f"--mesh {args.mesh!r}: 1-3 axes supported "
                     "((data), (data, model), (pod, data, model))")
        axes = (("data",), ("data", "model"),
                ("pod", "data", "model"))[len(shape) - 1]
        ctx = make_mesh_context(shape, axes, kernel_impl=args.kernel_impl)
    elif dp_spec is not None:
        # mesh-aware reduction without an explicit shape: all devices DP
        ctx = make_mesh_context((jax.device_count(),), ("data",),
                                kernel_impl=args.kernel_impl)
    else:
        ctx = make_mesh_context(kernel_impl=args.kernel_impl)
    if dp_spec is not None and ctx.auto_axis_names:
        ap.error(f"--dp-reduce {args.dp_reduce} needs a pure-DP mesh "
                 f"(single-axis '--mesh 8'), not {args.mesh!r}: the "
                 f"manual DP reduction cannot leave {ctx.auto_axis_names} "
                 f"to GSPMD on this JAX — drop --dp-reduce for TP meshes")

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.data == "corpus":
        # the embedding table must cover the corpus tokenizer: vocab is a
        # property of the data, so the model grows to fit (never shrinks)
        from repro.data.store import TokenStore
        if not args.corpus_dir:
            ap.error("--data corpus needs --corpus-dir (build one with "
                     "`python -m repro.data.build_corpus`)")
        corpus_vocab = TokenStore(args.corpus_dir).vocab_size
        if corpus_vocab > cfg.vocab:
            tel.log(f"model vocab {cfg.vocab} -> {corpus_vocab} "
                    f"(corpus tokenizer)", kind="vocab_grow",
                    old=cfg.vocab, new=corpus_vocab)
            cfg = cfg.with_(vocab=corpus_vocab)
    mod = encdec if cfg.arch_class == "encdec" else lm
    key = jax.random.key(args.seed)
    params = mod.init(cfg, key)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    finetune_lora = args.finetune == "lora"
    if finetune_lora and dp_spec is not None:
        ap.error("--finetune lora does not compose with --dp-reduce yet "
                 "(the sharded step reduces full-tree gradients; adapter-"
                 "only reduction is future work) — drop --dp-reduce")
    if args.base_ckpt:
        base_params, base_step = CheckpointManager(
            args.base_ckpt).restore_params(None, params)
        params = base_params
        tel.log(f"restored pre-trained base from {args.base_ckpt} "
                f"(step {base_step})", kind="base_restore",
                ckpt=args.base_ckpt, step=base_step)

    # Encoder-decoder batches carry the audio-frontend frame stub; the
    # adapter lives in the pipeline (WithEncoderFrames), not a monkey-patch.
    enc = cfg.arch_class == "encdec"
    source = make_source(args.data, cfg.vocab, args.seq, args.batch,
                         seed=args.seed, corpus_dir=args.corpus_dir,
                         enc_frames=args.seq // 4 if enc else 0,
                         enc_dim=cfg.d_model if enc else 0)

    # Data provenance stamped into every checkpoint manifest: a resume on
    # a different corpus (or order seed) must fail loudly, not train on.
    data_meta = {"kind": args.data, "order_seed": args.seed}
    if args.data == "corpus":
        data_meta["corpus_hash"] = source.store.corpus_hash \
            if not enc else source.source.store.corpus_hash

    # Mesh mode: build the three sharding trees once (params, opt state,
    # batch) and hand the GWT engine its per-bucket hints before init.
    shardings = None
    if dp_spec is not None:
        from repro.distributed import sharding as shr
        b0 = source.batch(0)
        batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in b0.items()}
        shardings = shr.train_step_shardings(
            cfg, mod, batch_abs, ctx.mesh, optimizer_name=args.optimizer,
            level=args.level, host=args.host,
            shard_params=args.shard_params == "auto",
            state_codec=args.state_codec)

    opt_kw = {"state_codec": args.state_codec}
    if args.optimizer == "gwt":
        opt_kw.update({"level": args.level, "alpha": args.alpha,
                       "host": args.host, "impl": ctx.kernel_impl})
        if shardings is not None and shardings.opt is not None:
            opt_kw["state_shardings"] = shardings.opt["buckets"]
    elif args.optimizer in ("galore", "apollo", "fira", "adarankgrad",
                            "rso"):
        opt_kw.update({"rank_frac": 0.25, "alpha": args.alpha})
    optimizer = make_optimizer(args.optimizer, args.lr, args.steps, **opt_kw)

    base_like = params  # full-Adam reference below counts the raw model
    if finetune_lora:
        from repro.models import lora
        params = lora.inject(params, args.lora_rank,
                             jax.random.fold_in(key, 777))
        optimizer = lora.wrap_optimizer(optimizer)
        n_adapter = sum(x.size for x in jax.tree.leaves(params["lora"]))
        tel.log(f"finetune=lora rank={args.lora_rank} "
                f"alpha={args.lora_alpha} "
                f"adapters={n_adapter/1e3:.1f}K params "
                f"({n_adapter/max(n_params, 1):.4f} of base)",
                kind="finetune", rank=args.lora_rank,
                alpha=args.lora_alpha, adapter_params=n_adapter)

    opt_shardings = None
    if shardings is not None:
        from repro.distributed.sharding import replicated_like
        params = jax.device_put(params, shardings.params)
        opt_shardings = shardings.opt if shardings.opt is not None else \
            replicated_like(jax.eval_shape(optimizer.init, params), ctx.mesh)
    with ctx.activate():
        opt_state = optimizer.init(params)
    if opt_shardings is not None:
        opt_state = jax.device_put(opt_state, opt_shardings)

    # Error feedback rides OUTSIDE the optimizer state proper:
    # opt_state = {"opt": ..., "dp_ef": per-device residue} (the sharded
    # step unwraps it; checkpoints save/restore the wrapped tree whole).
    ef_wrap = dp_spec is not None and dp_spec.error_feedback
    if ef_wrap:
        from repro.distributed import compression as dcomp
        ef0 = dcomp.ef_init(params, ctx.dp_size)
        ef_sh = dcomp.ef_state_shardings(ef0, ctx.mesh, ctx.dp_axis_names)
        ef0 = jax.device_put(ef0, ef_sh)
        opt_state = {"opt": opt_state, "dp_ef": ef0}
        opt_shardings = {"opt": opt_shardings, "dp_ef": ef_sh}

    # Exact accounting for the *actual* optimizer/host (eval_shape over the
    # real init — no Adam-shaped approximation for non-GWT runs), plus the
    # compound compression factor vs the full-Adam f32 reference point the
    # paper's memory tables are normalized to.
    from repro.optim.engine import state_bytes
    mem_bytes = state_bytes(optimizer, params)
    adam_f32_bytes = state_bytes(optim.make("adam", lr=args.lr), base_like)
    tel.log(f"arch={cfg.name} params={n_params/1e6:.1f}M "
            f"optimizer={args.optimizer} codec={args.state_codec} "
            f"opt_state={mem_bytes/2**20:.2f}MiB "
            f"({adam_f32_bytes/max(mem_bytes, 1):.1f}x smaller than "
            f"full-Adam f32 {adam_f32_bytes/2**20:.2f}MiB)",
            kind="memory", params=n_params, opt_state_bytes=mem_bytes,
            adam_f32_bytes=adam_f32_bytes)
    if dp_spec is not None:
        from repro.distributed.compression import tree_wire_bytes
        grads_abs = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
        full = tree_wire_bytes(grads_abs, None)
        now = tree_wire_bytes(grads_abs, dp_spec)
        tel.log(f"dp_reduce={args.dp_reduce} dp={ctx.dp_size} "
                f"wire={now/2**20:.1f}MiB/step vs exact "
                f"{full/2**20:.1f}MiB ({full/now:.2f}x)",
                kind="dp_wire", wire_bytes=now, exact_bytes=full)

    # Raw (un-jitted) step: TrainLoop compiles it inside its donated
    # scan-over-chunk superstep (runtime/fault_tolerance.py).
    tap_step = None
    if finetune_lora:
        from repro.models import lora
        train_step = lora.make_train_step(mod, cfg, optimizer,
                                          rank=args.lora_rank,
                                          alpha=args.lora_alpha,
                                          accum_steps=args.accum, ctx=ctx)
    else:
        # on-device taps ride with --metrics-dir; the sharded dp_reduce
        # step has no tapped channel yet, so mesh runs keep spans/records
        # but skip taps.  The tapped variant is a SECOND step fn handed
        # to TrainLoop: it runs only on each chunk's boundary step, so
        # the tap reductions never touch the scanned hot path.
        step_kw = dict(accum_steps=args.accum, ctx=ctx,
                       dp_reduce=dp_spec, shardings=shardings)
        train_step = mod.make_train_step(cfg, optimizer, **step_kw)
        if args.metrics_dir and dp_spec is None \
                and getattr(optimizer, "tapped_update", None) is not None:
            tap_step = mod.make_train_step(cfg, optimizer, taps=True,
                                           **step_kw)
    run_meta = {"data": data_meta, "state_codec": args.state_codec}
    if finetune_lora:
        # serving reads this to auto-merge the adapters back into the
        # base weights (Engine.from_checkpoint / serve --merge-lora)
        run_meta["finetune"] = {"mode": "lora", "rank": args.lora_rank,
                                "alpha": args.lora_alpha}
    ckpt = CheckpointManager(args.ckpt_dir, run_meta=run_meta) \
        if args.ckpt_dir else None
    # stamp the metrics stream with the same provenance the checkpoint
    # manifest records (data hash, codec, finetune config)
    tel.emit("run_meta", **run_meta)
    start = 0
    if args.resume and ckpt is not None and ckpt.latest_step() is not None:
        from repro.checkpoint.manager import StructureMismatch
        saved_data = ckpt.manifest().get("run", {}).get("data")
        if saved_data is not None:
            for k in ("kind", "corpus_hash", "order_seed"):
                if k in saved_data and saved_data[k] != data_meta.get(k):
                    raise SystemExit(
                        f"--resume provenance mismatch: checkpoint in "
                        f"{ckpt.dir} was trained with data {k}="
                        f"{saved_data[k]!r}, this run has "
                        f"{data_meta.get(k)!r} — refusing to continue on "
                        f"a different data stream")
        restore_sh = None if shardings is None else \
            {"params": shardings.params, "opt": opt_shardings}
        try:
            (state, start) = ckpt.restore(None, {"params": params,
                                                 "opt": opt_state},
                                          shardings=restore_sh, ctx=ctx)
        except StructureMismatch as e:
            # Two recoverable shapes of mismatch: a pre-engine checkpoint
            # (per-leaf tuple optimizer state, "'leaves'" in its treedef)
            # and a codec change (the saved manifest's run.state_codec
            # differs from --state-codec).  Anything else means the
            # optimizer/model config changed since the save — report
            # that, don't guess.  (Error-feedback runs postdate the
            # legacy layout and stay unmigrated either way.)
            from repro.optim import engine as engine_mod
            saved_codec = ckpt.saved_run().get("state_codec", "f32")
            legacy = "'leaves'" in ckpt.manifest().get("treedef", "")
            if ef_wrap or not (legacy or saved_codec != args.state_codec):
                raise StructureMismatch(
                    f"checkpoint in {ckpt.dir} is bucketed but does not "
                    f"match this run's optimizer state — did --optimizer/"
                    f"--level/--host or the model config change since it "
                    f"was saved? ({e})") from e
            if legacy:
                # legacy layouts are raw f32 by construction
                like = optimizer.engine.legacy_like(params)
            else:
                saved_opt = make_optimizer(args.optimizer, args.lr,
                                           args.steps,
                                           **{**opt_kw,
                                              "state_codec": saved_codec})
                like = jax.eval_shape(saved_opt.init, params)
            (state, start) = ckpt.restore(None, {"params": params,
                                                 "opt": like}, ctx=ctx)
            if legacy:
                state["opt"] = optimizer.engine.migrate_legacy(state["opt"],
                                                               params)
                tel.log("migrated legacy per-leaf optimizer state -> "
                        "buckets", kind="migrate")
                if args.state_codec != "f32":
                    f32_opt = make_optimizer(args.optimizer, args.lr,
                                             args.steps,
                                             **{**opt_kw,
                                                "state_codec": "f32"})
                    state["opt"] = engine_mod.transcode(
                        state["opt"], params, f32_opt, optimizer)
                    tel.log(f"transcoded optimizer state f32 -> "
                            f"{args.state_codec}", kind="transcode",
                            src="f32", dst=args.state_codec)
            else:
                state["opt"] = engine_mod.transcode(
                    state["opt"], params, saved_opt, optimizer)
                tel.log(f"transcoded optimizer state {saved_codec} -> "
                        f"{args.state_codec}", kind="transcode",
                        src=saved_codec, dst=args.state_codec)
                if opt_shardings is not None:
                    state["opt"] = jax.device_put(state["opt"],
                                                  opt_shardings)
        params, opt_state = state["params"], state["opt"]
        tel.log(f"resumed from step {start}", kind="resume", step=start)

    evaluator = None
    if args.eval_every:
        from repro.data.eval import make_lm_evaluator
        eval_src = make_source(args.data, cfg.vocab, args.seq, args.batch,
                               seed=args.seed, corpus_dir=args.corpus_dir,
                               split="eval",
                               enc_frames=args.seq // 4 if enc else 0,
                               enc_dim=cfg.d_model if enc else 0)
        eval_mod = mod
        if finetune_lora:
            from repro.models import lora
            eval_mod = lora.loss_module(mod, args.lora_alpha, args.lora_rank)
        evaluator = make_lm_evaluator(cfg, eval_mod, eval_src,
                                      n_batches=args.eval_batches, ctx=ctx)

    loop = TrainLoop(train_step, ckpt, source, ckpt_every=args.ckpt_every,
                     log_every=args.log_every, save_final=ckpt is not None,
                     donate=not args.no_donate,
                     num_workers=args.workers,
                     evaluator=evaluator, eval_every=args.eval_every,
                     batch_shardings=None if shardings is None
                     else shardings.batch, tap_step=tap_step)
    try:
        with ctx.activate():
            params, opt_state, losses = loop.run(params, opt_state,
                                                 start_step=start,
                                                 num_steps=args.steps)
        wd = loop.watchdog.summary()
        if wd["dispatch_s_per_step"] is not None:
            print(f"dispatch={wd['dispatch_s_per_step']*1e3:.1f}ms/step "
                  f"blocked={(wd['blocked_s_per_step'] or 0)*1e3:.1f}"
                  f"ms/step incidents={wd['incidents']}")
        if losses:
            k = max(1, len(losses) // 10)
            tel.log(f"final loss (mean of last {k}): "
                    f"{sum(losses[-k:]) / k:.4f}", kind="final_loss",
                    loss=sum(losses[-k:]) / k, window=k)
        if evaluator is not None and evaluator.history:
            s, v = evaluator.history[-1]
            tel.log(f"final eval (step {s}): loss={v:.4f} "
                    f"ppl={math.exp(min(v, 30.0)):.2f}", kind="final_eval",
                    step=s, loss=float(v))
    finally:
        # writes <metrics-dir>/trace.json and closes the JSONL sink (a
        # no-op for the null telemetry); resets the process-global handle
        obs.shutdown()
    return params, opt_state, losses


if __name__ == "__main__":
    main()
