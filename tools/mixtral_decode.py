"""Milestone E headline: Mixtral-8x7B-architecture int8 decode.

BASELINE.md config E is Mixtral-8x7B MoE inference on the quantized path.
A full 32-layer 8x7B does not fit one v5e chip (46.7B params; ~1.4 GB/layer
even at int8), so — like the 7B training headline — this measures the REAL
architecture (8 experts, top-2 routing, GQA, vocab 32000, d_model 4096)
depth-truncated, fits decode ms/token against depth (per-token cost is
linear in layers), and reports the 32-layer prediction with the fit
residual as its error bound.

Writes BENCH_MIXTRAL.json.  Needs a TPU (one chip-tool call); a depth that
fails fails the run.  ``--smoke`` runs a tiny-geometry CPU plumbing check (no
artifacts, no rates printed) so CI can police the tool.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv

if SMOKE:
    from thunder_tpu._platform import force_cpu

    force_cpu()

import jax
import jax.numpy as jnp
import numpy as np

import bench
from thunder_tpu.models import llama
from thunder_tpu.models import generate as gen

# decode geometry (TPU): 8 streams, short prompt, long-ish generation so the
# scan body dominates the prefill
B, T_PROMPT, N_NEW = 8, 64, 192


def measure_depth(cfg_name: str, n_layer: int, *, quantized: bool, B=B,
                  T_prompt=T_PROMPT, n_new=N_NEW, dtype=jnp.bfloat16) -> dict:
    """Decode tokens/s at ``n_layer`` layers: the first call compiles, then
    the best of three timed calls (the depth FIT amplifies any one disturbed
    sample into the 32-layer prediction)."""
    cfg = llama.Config.from_name(cfg_name, n_layer=n_layer)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T_prompt), 0, cfg.vocab_size)

    t0 = time.perf_counter()
    jax.block_until_ready(gen.generate(params, prompt, cfg, n_new, quantized=quantized))
    first_s = time.perf_counter() - t0
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = gen.generate(params, prompt, cfg, n_new, quantized=quantized)
        jax.block_until_ready(out)
        dt = min(dt, time.perf_counter() - t0)
    row = {
        "n_layer": n_layer,
        "tokens_per_sec": round(B * n_new / dt, 1),
        "ms_per_token_batch": round(dt / n_new * 1e3, 3),
        "first_call_s": round(first_s, 1),
    }
    del params, out
    jax.clear_caches()  # free weights + compiled programs before next depth
    return row


def run(cfg_name: str, depths, quantized: bool, **kw) -> list[dict]:
    rows = []
    for n in depths:
        rows.append(measure_depth(cfg_name, n, quantized=quantized, **kw))
        print(f"depth {n} q={quantized}: {rows[-1]}", file=sys.stderr)
    return rows


def fit_32(rows: list[dict], batch: int = B) -> dict:
    """ms/token = a·L + b over the measured depths → 32-layer prediction.
    ``batch`` must be the B the rows were measured with (tokens/s = B/ms)."""
    L = np.array([r["n_layer"] for r in rows], dtype=np.float64)
    t = np.array([r["ms_per_token_batch"] for r in rows], dtype=np.float64)
    a, b = np.polyfit(L, t, 1)
    pred = {}
    pred["fit_ms_per_layer"] = round(float(a), 4)
    pred["fit_overhead_ms"] = round(float(b), 4)
    if len(rows) >= 3:
        pred["fit_max_residual_pct"] = round(
            float(np.max(np.abs((a * L + b) - t) / t) * 100), 2)
    t32 = a * 32 + b
    pred["predicted_8x7b_tokens_per_sec"] = round(batch * 1e3 / t32, 1)
    pred["predicted_8x7b_ms_per_token"] = round(float(t32), 3)
    return pred


def main() -> int:
    if SMOKE:
        # plumbing check on the tiny MoE architecture: same code path
        # (routing, int8 decode, depth fit), toy sizes, no artifacts
        rows_q = run("mixtral-like", [1, 2], quantized=True,
                     B=2, T_prompt=8, n_new=16, dtype=jnp.float32)
        fit = fit_32(rows_q, batch=2)
        assert fit["predicted_8x7b_ms_per_token"] > 0, fit
        # counts only: a CPU timing is not printed under a device metric's name
        print(json.dumps({"smoke": True, "depths": [r["n_layer"] for r in rows_q],
                          "fit_keys": sorted(fit)}))
        return 0

    device = bench.require_tpu("mixtral_decode")

    # int8 is the headline (milestone E's quantized path); depth 3 holds
    # ~4.2 GB of int8 expert weights + the bf16 originals during
    # quantization.  bf16 rows give the quantization speedup ratio.
    out = {
        "config": "Mixtral-8x7B-like (8 experts, top-2, GQA8, d4096, V32000)",
        "geometry": {"B": B, "T_prompt": T_PROMPT, "n_new": N_NEW},
        "device": device,
        "int8": run("Mixtral-8x7B-like", [1, 2, 3], quantized=True),
        "bf16": run("Mixtral-8x7B-like", [1, 2], quantized=False),
    }
    out["int8_fit"] = fit_32(out["int8"])
    out["bf16_fit"] = fit_32(out["bf16"])

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_MIXTRAL.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
