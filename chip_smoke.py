#!/usr/bin/env python3
"""The quickest proof that thunder_tpu still starts on the chip.

Drives both main paths once through the entry points a user calls, at the
published widths of ``Mistral-7B-like`` (32 heads, 8 KV groups, head 128,
FFN 14336, vocabulary 32000, window 4096; bfloat16).  Depth is the only cut
and every line of output states it; the weights are random, from a seed.

    python3 chip_smoke.py [--phases kernels,train,serve]

- **kernels**: every serving Pallas kernel against the jnp program it
  replaces (``serving.kernel_check``), compiled by Mosaic, not interpreted.
- **train**: ``dist.make_train_step`` — the path ``train_cli.py`` takes — a
  few steps on one fixed batch through ``train_loop``, T = 2048, over all
  local chips (``dp=1`` on one, ``fsdp=N`` on N).  Depth is what fills 0.3
  of the chips' memory with parameters and optimizer state, so on four
  chips that state is larger than one chip.
- **serve**: ``tt.serve(None, params, cfg)`` with ``attn`` at its default, a
  pool sized to the chip, requests of mixed prompt length run to completion
  and compared with solo ``generate()`` on the same chip.

It needs a TPU: without one it exits non-zero before building anything and
prints no result.  One process holds the chip; every phase runs in it, and
no phase is wrapped in a catch-all.  Each phase prints one JSON line naming
the device; the last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

CONFIG = "Mistral-7B-like"
SEQ_LEN = 2048
TRAIN_STEPS = 4
SERVE_LAYERS = 4
PROMPT_LENS = (37, 150, 260, 411, 700)
NEW_TOKENS = 32
# Shares of device memory.  The training state (parameters and AdamW's two
# moments, all bfloat16) gets 0.3 of all chips together: two layers on one
# chip, and on four chips a state larger than any one of them, so a run that
# really lives on chip 0 fails.  The serving pool's KV arenas get a quarter.
TRAIN_STATE_SHARE = 0.3
KV_ARENA_SHARE = 0.25
PHASES = ("kernels", "train", "serve")


def emit(device: dict, phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device,
                      "wall_s": round(time.perf_counter() - t0, 1), **fields}), flush=True)


def bytes_limit(dev) -> int:
    return dev.memory_stats()["bytes_limit"]


def memory(devices) -> list[dict]:
    return [{"id": d.id, **{k: d.memory_stats()[k]
                            for k in ("bytes_in_use", "peak_bytes_in_use")}}
            for d in devices]


def trace_symbols(trace) -> set[str]:
    names: set[str] = set()

    def walk(bsyms):
        for b in bsyms:
            names.add(b.sym.name)
            walk(b.subsymbols or ())

    walk(trace.bound_symbols)
    return names


def kernels_phase(device: dict, cfg) -> None:
    from thunder_tpu.serving.kernel_check import run_checks

    t0 = time.perf_counter()
    # 32 blocks of 16 slots: contexts up to ~480 tokens, and a window of 40
    # that has slid past most of them
    rows = run_checks(
        n_head=cfg.n_head, n_query_groups=cfg.n_query_groups, head_size=cfg.head_size,
        block_size=16, table_width=32, window=40)
    bad = [r for r in rows if not r["ok"]]
    emit(device, "kernels", t0, checks=rows)
    assert not bad, f"kernels disagree with their jnp references: {bad}"


def train_phase(device: dict, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from thunder_tpu import distributed as dist
    from thunder_tpu.executors import pallasex
    from thunder_tpu.models import llama
    from thunder_tpu.train import train_loop

    t0 = time.perf_counter()
    n = len(devices)
    full = llama.Config.from_name(CONFIG)

    def init(n_layer: int):
        return functools.partial(
            llama.init_params, llama.Config.from_name(CONFIG, n_layer=n_layer),
            jax.random.PRNGKey(0), dtype=jnp.bfloat16)

    def n_params(n_layer: int) -> int:
        return llama.param_count(jax.eval_shape(init(n_layer)))

    one = n_params(1)
    per_layer = n_params(2) - one
    budget = TRAIN_STATE_SHARE * sum(map(bytes_limit, devices)) / 6   # parameters it affords
    n_layer = int(min(max((budget - (one - per_layer)) // per_layer, 1), full.n_layer))
    total = one + (n_layer - 1) * per_layer
    cfg = llama.Config.from_name(CONFIG, n_layer=n_layer)
    if n == 1:
        mesh, rule = dist.make_mesh({"dp": 1}, devices=devices), dist.ddp_shardings
    else:
        mesh, rule = dist.make_mesh({"fsdp": n}, devices=devices), dist.fsdp_shardings
    params = dist.init_sharded(init(n_layer), lambda s: rule(s, mesh))

    B = n                       # one sequence per chip
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, SEQ_LEN)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, SEQ_LEN)), jnp.int32)
    cos, sin = llama.build_rope_cache(cfg, SEQ_LEN)

    def loss_fn(p, i, t, c, s):
        return llama.gpt_loss(p, i, t, c, s, cfg)

    step = dist.make_train_step(loss_fn, optax.adamw(1e-3), mesh)
    opt_state = step.init_optimizer_state(params)
    claimed_before = dict(pallasex.stats)
    res = train_loop(step, params, opt_state, lambda s: (idx, tgt, cos, sin),
                     steps=TRAIN_STEPS)
    losses = [float(x) for x in res.losses]
    claimed = {k: pallasex.stats[k] - claimed_before.get(k, 0) for k in pallasex.stats}
    emit(device, "train", t0, config=CONFIG, n_layer=n_layer, depth_cut_from=full.n_layer,
         mesh=dict(mesh.shape), batch=B, seq_len=SEQ_LEN, dtype="bfloat16",
         params=total, state_bytes=6 * total,
         losses=losses, flash_claims=claimed, restarts=res.restarts,
         retries=res.retries, faults=res.faults, memory=memory(devices))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert (res.restarts, res.retries, res.faults) == (0, 0, []), res.faults
    assert sum(claimed.values()) > 0, "the flash kernel did not claim"
    assert "pallas_sdpa" in trace_symbols(step.fw_trace)
    assert "pallas_sdpa_backward" in trace_symbols(step.bw_trace)


def serve_phase(device: dict, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu.models import generate as gen
    from thunder_tpu.models import llama
    from thunder_tpu.serving.quant import arena_block_bytes

    t0 = time.perf_counter()
    cfg = llama.Config.from_name(CONFIG, n_layer=SERVE_LAYERS)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    block_size = 16
    num_blocks = int(KV_ARENA_SHARE * bytes_limit(dev)
                     // arena_block_bytes(cfg, block_size, jnp.bfloat16))
    eng = tt.serve(None, params, cfg, block_size=block_size, num_blocks=num_blocks,
                   max_batch=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in PROMPT_LENS]
    handles = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    eng.drain()
    results = [h.result(drive=False) for h in handles]
    stats = eng.stats()
    t_served = time.perf_counter() - t0

    rows = []
    for p, r in zip(prompts, results):
        solo = np.asarray(gen.generate(params, p[None], cfg, NEW_TOKENS))[0, len(p):]
        got = np.asarray(r.new_tokens)
        rows.append({"prompt_len": len(p), "finish": r.finish_reason,
                     "error": r.error, "new_tokens": len(got),
                     "first_token_matches_solo": bool(len(got) and got[0] == solo[0]),
                     "tokens_matching_solo": int(np.sum(got == solo[:len(got)]))})
    decode = {k: stats["attn"][k] for k in ("path", "fallback_steps", "kv_chunk_tokens")}
    emit(device, "serve", t0, config=CONFIG, n_layer=cfg.n_layer,
         depth_cut_from=llama.Config.from_name(CONFIG).n_layer,
         dtype="bfloat16", num_blocks=num_blocks, block_size=block_size,
         arena_bytes=stats["arena_bytes"], served_s=round(t_served, 1),
         requests=rows, decode=decode, recoveries=stats["recoveries"],
         compile_counts=stats["compile_counts"],
         matching_share=sum(r["tokens_matching_solo"] for r in rows)
         / (len(rows) * NEW_TOKENS), memory=memory([dev]))
    assert all(r["finish"] == "length" and r["error"] is None
               and r["new_tokens"] == NEW_TOKENS for r in rows), rows
    assert decode["path"] == "walk" and decode["fallback_steps"] == 0 and stats["recoveries"] == 0, stats["attn"]
    # later tokens may part ways: with random weights the top two logits are
    # close, and one rounding flips the argmax and everything after it
    assert all(r["first_token_matches_solo"] for r in rows), rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of %(default)s; a partial run "
                         "prints no final result line")
    phases = ap.parse_args().phases.split(",")
    assert set(phases) <= set(PHASES), phases

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found {devices[0].platform!r} "
                 f"({devices[0].device_kind}); nothing was run")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    from thunder_tpu.core import compile_cache
    from thunder_tpu.models import llama

    t0 = time.perf_counter()
    emit(device, "start", t0, jax=jax.__version__, cache_dir=compile_cache.enable())
    if "kernels" in phases:
        kernels_phase(device, llama.Config.from_name(CONFIG))
    if "train" in phases:
        train_phase(device, devices)
        jax.clear_caches()      # the train step's executable and its buffers
    if "serve" in phases:
        serve_phase(device, devices[0])
    emit(device, "done", t0, phases=phases, compile_cache=compile_cache.stats())
    if set(phases) == set(PHASES):
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
