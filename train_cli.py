#!/usr/bin/env python
"""End-to-end pretraining CLI (reference ``benchmarks/benchmark_litgpt.py``:
config × parallelism × precision sweeps with tokens/s + memory reporting).

Examples::

    # single chip (or CPU smoke), flagship config scaled down
    python train_cli.py --config tiny-llama-debug --steps 20

    # 8 virtual CPU devices, FSDP, bf16 params
    python train_cli.py --config tiny-llama-debug --mode fsdp --devices 8 \
        --virtual-cpu --steps 10

    # TP x FSDP with gradient accumulation
    python train_cli.py --mode tp_fsdp --devices 8 --virtual-cpu --accum 2

Modes map to the distributed API: ``none`` (single device), ``ddp``,
``fsdp`` (ZeRO-2), ``zero3`` (regather-in-backward), ``tp_fsdp``
(megatron rules x dim-0 shards), ``sp`` (ring-attention sequence
parallelism), ``pp`` (GPipe pipeline), ``ep`` (expert-parallel MoE
all_to_all; MoE configs only).  ``--quant int8`` runs forward GEMMs
dynamically int8-quantized with bf16/f32 grads (the TE-executor training
contract, reference transformer_engineex.py:183).  Prints per-step timings
and a final JSON summary line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="tiny-llama-debug", help="model config name (models/llama.py zoo)")
    ap.add_argument("--mode", default="none",
                    choices=["none", "ddp", "fsdp", "zero3", "tp_fsdp", "sp", "pp", "ep"])
    ap.add_argument("--fused-ce", action="store_true",
                    help="fuse the lm-head matmul into a chunked-vocab cross-entropy "
                         "(no materialized logits; Config.fused_head_ce)")
    ap.add_argument("--quant", default=None, choices=["int8", "fp8"],
                    help="quantized training: int8/fp8(e4m3) forward GEMMs, full-precision grads")
    ap.add_argument("--comm-combine-mb", type=float, default=None,
                    help="XLA collective-combining threshold in MiB (the bucket_size_in_mb analog)")
    ap.add_argument("--sp-impl", default="ring", choices=["ring", "ulysses"],
                    help="sequence-parallel attention: ring (ppermute K/V rotation) or "
                         "ulysses (all_to_all seq<->head re-shard)")
    ap.add_argument("--bucket", action="store_true",
                    help="pad batches to power-of-two (B, T) buckets so one compiled "
                         "program serves every shape inside a bucket")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--virtual-cpu", action="store_true", help="force N virtual CPU devices (no hardware needed)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None, help="sequence length (default: min(block_size, 128))")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1,
                    help="host-loop gradient accumulation (k calls to the grads/apply "
                         "entries per optimizer step)")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="IN-PROGRAM gradient accumulation: one donated program scans k "
                         "microbatches with a float32 accumulator (TrainStep modes; in pp "
                         "mode k rides the GPipe microbatch schedule instead)")
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat", default=None,
                    choices=["on", "off", "auto", "none", "attention", "full_block"],
                    help="activation rematerialization: on/off/auto (legacy) or a policy — "
                         "none, attention (recompute attention internals), full_block "
                         "(aggressive, residuals shrink toward the inputs; what zero3 forces)")
    ap.add_argument("--overlap", action="store_true",
                    help="bucketed-psum gradient collectives overlapping the backward "
                         "(pure-dp meshes; the torch-DDP bucket_cap_mb design)")
    ap.add_argument("--overlap-bucket-mb", type=float, default=4.0,
                    help="gradient bucket cap in MiB for --overlap")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint directory: with --checkpoint-every the async atomic "
                         "checkpointer writes here during the run; otherwise one final "
                         "save (orbax) lands here")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="dispatch an async atomic checkpoint every N optimizer steps "
                         "(train.checkpoint; 0 = off)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest committed checkpoint in --checkpoint-dir "
                         "(torn checkpoints are skipped with a structured warning); the "
                         "replayed loss curve is bit-identical to an undisturbed run")
    ap.add_argument("--telemetry", default=None,
                    help="per-step JSONL telemetry path (StepLogger: loss, step time, "
                         "tokens/sec, peak-bytes estimate; mirrored into the metrics registry)")
    ap.add_argument("--telemetry-grad-norm", action="store_true",
                    help="also log the global grad norm each step (runs one extra "
                         "grads-only step per logged step; TrainStep modes, accum=1)")
    args = ap.parse_args(argv)

    if args.virtual_cpu:
        from thunder_tpu._platform import force_cpu

        force_cpu(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from thunder_tpu import distributed as dist
    from thunder_tpu.models import llama

    devices = jax.devices()[: args.devices]
    assert len(devices) >= args.devices, f"need {args.devices} devices, have {len(jax.devices())}"

    cfg = llama.Config.from_name(
        args.config, **({"fused_head_ce": True} if args.fused_ce else {})
    )
    T = args.seq or min(cfg.block_size, 128)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    def init():
        return llama.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)

    log(f"config={cfg.name} n_layer={cfg.n_layer} n_embd={cfg.n_embd} "
        f"params={llama.param_count(jax.eval_shape(init))/1e6:.1f}M B={args.batch} T={T} "
        f"mode={args.mode} devices={args.devices} dtype={args.dtype}")

    idx = jax.random.randint(jax.random.PRNGKey(1), (args.batch, T), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (args.batch, T), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, T)
    optimizer = optax.adamw(args.lr)

    if args.mode in ("sp", "pp", "ep"):
        assert args.quant is None, "--quant needs a TrainStep mode (not sp/pp/ep)"
        assert not args.fused_ce, "--fused-ce needs a TrainStep mode (not sp/pp/ep)"
        assert args.comm_combine_mb is None, "--comm-combine-mb needs a TrainStep mode (not sp/pp/ep)"
        assert not args.bucket, "--bucket needs a TrainStep mode (not sp/pp/ep)"
        # sequence / pipeline / expert parallelism drive the shard_map-based
        # training losses directly: jax.value_and_grad through the shard_map
        # (grad sync comes out of the broadcast transpose), optax update jitted
        # alongside — one compiled program per step, like TrainStep
        params = init()
        if args.mode == "sp":
            assert T % args.devices == 0, f"--seq {T} must divide over sp={args.devices}"
            mesh = dist.make_mesh({"sp": args.devices}, devices=devices)
            train_params = params
            sp_loss = dist.ulysses_gpt_loss if args.sp_impl == "ulysses" else dist.sp_gpt_loss

            def loss_fn(p, i, t):
                return sp_loss(p, i, t, cos, sin, cfg, mesh=mesh)
        elif args.mode == "pp":
            pp = args.devices
            assert cfg.n_layer % pp == 0, f"n_layer {cfg.n_layer} must divide over pp={pp}"
            # --accum-steps rides the GPipe schedule: more microbatches
            # per step IS pipeline-parallel gradient accumulation (the
            # bubble shrinks as k grows); clamped to a divisor of the batch
            from thunder_tpu.train import pp_microbatches

            n_micro = pp_microbatches(
                args.accum_steps if args.accum_steps > 1 else 2, args.batch
            )
            mesh = dist.make_mesh({"pp": pp}, devices=devices)
            train_params = dist.place_pipeline_params(dist.stack_blocks(params), mesh)

            def loss_fn(p, i, t):
                return dist.pp_gpt_loss(p, i, t, cos, sin, cfg, mesh=mesh, n_micro=n_micro)
        else:  # ep
            assert cfg.mlp_class == "LLaMAMoE", (
                f"--mode ep needs a MoE config (e.g. tiny-moe-debug, mixtral-like); got {cfg.name}"
            )
            assert args.batch % args.devices == 0, (
                f"--batch {args.batch} must divide over ep={args.devices}"
            )
            mesh = dist.make_mesh({"ep": args.devices}, devices=devices)
            train_params = params

            def loss_fn(p, i, t):
                return dist.ep_gpt_loss(p, i, t, cos, sin, cfg, mesh=mesh)

        opt_state = optimizer.init(train_params)

        @jax.jit
        def sharded_step(p, o, i, t):
            loss, grads = jax.value_and_grad(loss_fn)(p, i, t)
            updates, o = optimizer.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        step = lambda p, o, i, t, c, s: sharded_step(p, o, i, t)
        accumulate = None
        train_step_obj = None
        params = train_params
    else:
        if args.mode == "none":
            mesh = dist.make_mesh({"dp": 1}, devices=devices[:1])
            rule = dist.ddp_shardings
        elif args.mode == "ddp":
            mesh = dist.make_mesh({"dp": args.devices}, devices=devices)
            rule = dist.ddp_shardings
        elif args.mode in ("fsdp", "zero3"):
            mesh = dist.make_mesh({"fsdp": args.devices}, devices=devices)
            rule = dist.fsdp_shardings
        else:  # tp_fsdp
            tp = 2 if args.devices % 2 == 0 else 1
            mesh = dist.make_mesh({"fsdp": args.devices // tp, "tp": tp}, devices=devices)
            rule = dist.llama_shardings
        # born placed: no leaf is ever whole on one device, so a model larger
        # than one chip can be initialised
        params = dist.init_sharded(init, lambda shapes: rule(shapes, mesh))

        def loss_fn(p, i, t, c, s):
            return llama.gpt_loss(p, i, t, c, s, cfg)

        remat_arg = (
            {"on": True, "off": False, "auto": "auto"}.get(args.remat, args.remat)
            if args.remat else not args.no_remat
        )
        train_step = dist.make_train_step(
            loss_fn, optimizer, mesh,
            remat=remat_arg,
            zero3=(args.mode == "zero3"),
            quant=args.quant, comm_combine_threshold_mb=args.comm_combine_mb,
            bucketer=llama.batch_bucketer(cfg) if args.bucket else None,
            accum_steps=args.accum_steps,
            overlap=args.overlap, overlap_bucket_mb=args.overlap_bucket_mb,
        )
        opt_state = train_step.init_optimizer_state(params)
        step = train_step
        accumulate = train_step.accumulate
        train_step_obj = train_step

    elastic = args.checkpoint_every > 0 or args.resume
    if elastic:
        assert args.checkpoint_dir, "--checkpoint-every/--resume need --checkpoint-dir"
        assert train_step_obj is not None, (
            "--checkpoint-every/--resume need a TrainStep mode (not sp/pp/ep)")
        assert args.accum == 1, "--checkpoint-every composes with --accum-steps, not --accum"

    t0 = time.perf_counter()
    if elastic:
        # the elastic loop is step-indexed: every step (including the first)
        # runs inside train_loop so a resumed run replays the exact same
        # step sequence — no out-of-band warmup step to desync the curve
        loss = None
    elif args.accum > 1:
        assert accumulate is not None, "--accum needs a TrainStep mode (not sp/pp/ep)"
        mb = args.batch // args.accum
        micro = [(idx[k * mb:(k + 1) * mb], tgt[k * mb:(k + 1) * mb], cos, sin) for k in range(args.accum)]
        params, opt_state, loss = accumulate(params, opt_state, micro)
    else:
        params, opt_state, loss = step(params, opt_state, idx, tgt, cos, sin)
    if loss is not None:
        jax.block_until_ready(loss)
        log(f"compile+first step: {time.perf_counter()-t0:.1f}s loss={float(loss):.4f}")

    # per-step telemetry (observability.telemetry.StepLogger): one JSONL
    # record per optimizer step, mirrored into the metrics registry.  The
    # peak-bytes estimate is static (del-aware liveness over the lowered
    # fw/bw traces), computed once — TrainStep modes only (sp/pp/ep drive
    # shard_map losses directly, no thunder trace to account)
    telemetry = None
    peak_bytes = None
    if args.telemetry:
        from thunder_tpu.observability.telemetry import StepLogger, trace_peak_bytes

        # run_start carries the FULL training config: a resumed run (or a
        # postmortem) must be able to reconstruct every knob from record 0
        telemetry = StepLogger(args.telemetry, meta={
            "config": cfg.name, "mode": args.mode, "devices": args.devices,
            "batch": args.batch, "seq": T, "dtype": args.dtype,
            "accum": args.accum, "quant": args.quant,
            "accum_steps": args.accum_steps,
            "remat": (args.remat or ("off" if args.no_remat else "on")),
            "overlap": bool(args.overlap),
            "overlap_bucket_mb": args.overlap_bucket_mb,
            "checkpoint_dir": args.checkpoint_dir,
            "checkpoint_every": args.checkpoint_every,
            "resume": bool(args.resume),
            "mesh_axes": dict(mesh.shape),
            "lr": args.lr,
        })
        if getattr(train_step_obj, "fw_trace", None) is not None:
            peak_bytes = max(
                trace_peak_bytes(train_step_obj.fw_trace),
                trace_peak_bytes(train_step_obj.bw_trace),
            )
        log(f"telemetry -> {args.telemetry}"
            + (f" (peak_bytes_estimate={peak_bytes})" if peak_bytes else ""))

    t0 = time.perf_counter()
    restarts = resumed_from = None
    if elastic:
        from thunder_tpu.observability.telemetry import trace_peak_bytes as _tpb
        from thunder_tpu.train import AsyncCheckpointer, restore_latest, train_loop

        # the config fingerprint in each manifest: resuming under silently
        # different knobs is a divergence, not a resume
        train_config = {"config": cfg.name, "mode": args.mode,
                        "devices": args.devices, "batch": args.batch, "seq": T,
                        "dtype": args.dtype, "accum_steps": args.accum_steps,
                        "lr": args.lr}
        start_step = 0
        if args.resume:
            got = restore_latest(args.checkpoint_dir,
                                 {"params": params, "opt_state": opt_state},
                                 config=train_config)
            if got is not None:
                start_step, state = got
                params, opt_state = state["params"], state["opt_state"]
                log(f"resumed from committed checkpoint step {start_step}")
            else:
                log("no committed checkpoint found; starting from scratch")
        resumed_from = start_step if args.resume else None

        t_prev = [time.perf_counter()]
        peak_holder = [peak_bytes]

        def on_step(s, loss_s):
            now = time.perf_counter()
            if telemetry is not None:
                if peak_holder[0] is None and getattr(train_step_obj, "fw_trace", None) is not None:
                    peak_holder[0] = max(_tpb(train_step_obj.fw_trace),
                                         _tpb(train_step_obj.bw_trace))
                telemetry.log_step(
                    s, loss=float(loss_s), step_time_s=now - t_prev[0],
                    tokens=args.batch * T, peak_bytes=peak_holder[0],
                )
            t_prev[0] = now

        with AsyncCheckpointer(args.checkpoint_dir, config=train_config) as ck:
            res = train_loop(
                step, params, opt_state, lambda s: (idx, tgt, cos, sin),
                steps=args.steps, start_step=start_step,
                checkpointer=ck, checkpoint_every=args.checkpoint_every,
                on_step=on_step,
            )
        params, opt_state = res.params, res.opt_state
        last = res.losses[-1] if res.losses and res.losses[-1] is not None else float("nan")
        restarts = res.restarts
        steps_done = max(args.steps - start_step, 1)
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
    else:
        last = loss
        for k in range(args.steps):
            t_step = time.perf_counter()
            if args.accum > 1:
                params, opt_state, last = accumulate(params, opt_state, micro)
            else:
                params, opt_state, last = step(params, opt_state, idx, tgt, cos, sin)
            if telemetry is not None:
                jax.block_until_ready(last)
                gn = None
                if args.telemetry_grad_norm and train_step_obj is not None and args.accum == 1:
                    import optax as _optax

                    _, g = train_step_obj.grads(params, opt_state, idx, tgt, cos, sin)
                    gn = float(_optax.global_norm(g))
                telemetry.log_step(
                    k,
                    loss=float(last),
                    grad_norm=gn,
                    step_time_s=time.perf_counter() - t_step,
                    tokens=args.batch * T,
                    peak_bytes=peak_bytes,
                )
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        steps_done = args.steps
    if telemetry is not None:
        telemetry.close()
    tps = args.batch * T * steps_done / dt

    if args.checkpoint_dir and not elastic:
        from thunder_tpu.distributed import save_checkpoint

        save_checkpoint(args.checkpoint_dir, {"params": params, "opt_state": opt_state}, step=args.steps)
        log(f"checkpoint saved to {args.checkpoint_dir}")

    from thunder_tpu._platform import device_info

    print(json.dumps({
        # the rate below is this device's: a CPU run measures the CPU
        "device": device_info(),
        "config": cfg.name, "mode": args.mode, "devices": args.devices,
        "quant": args.quant,
        "fused_ce": bool(args.fused_ce),
        "accum_steps": args.accum_steps,
        "remat": (args.remat or ("off" if args.no_remat else "on")),
        "overlap": bool(args.overlap),
        "checkpoint_every": args.checkpoint_every,
        "resumed_from": resumed_from, "restarts": restarts,
        "tokens_per_sec": round(tps, 1), "ms_per_step": round(dt / steps_done * 1e3, 2),
        "final_loss": round(float(last), 4),
    }))


if __name__ == "__main__":
    main()
