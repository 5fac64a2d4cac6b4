"""What the two files of looped serving tests share: the tiny configuration (3
blocks run 2 times, so that ``t * L + l`` and ``l * passes + t`` differ; 4 heads over
4 KV heads, a norm on both sides of each sublayer), its seeded float32 weights,
the token streams and the comparisons' helpers."""
from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import common  # noqa: E402
from conftest import compiled_forward  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402

arch = common.load_module("models", "looped_dense_decoder")

BS, L, PASSES = 8, 3, 2
HF = dict(model_name="tiny-looped", hidden_size=64, intermediate_size=176, hidden_act="silu", rms_norm_eps=1e-6,
          max_position_embeddings=512, num_attention_heads=4, num_hidden_layers=L, num_key_value_heads=4,
          head_dim=16, rope_theta=1000000.0, vocab_size=256, initializer_range=0.2, tie_word_embeddings=False,
          sliding_window=None, use_sliding_window=False, total_ut_steps=PASSES, early_exit_threshold=1.0)
ENGINE = dict(block_size=BS, num_blocks=64, max_batch=4, prefill_buckets=[32, 64, 96], cache_dtype=jnp.float32)


@functools.cache
def model(dtype=jnp.float32, **hf):
    """``(cfg, params, hf)`` of the tiny model with the keys ``hf`` changed."""
    hf = {**HF, **hf}
    cfg = llama.Config(**arch.program_config(hf))
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(hf, common.seed_words(5), dtype=dtype)
    return cfg, params, hf


def gate_bias(params, bias: float, w_scale: float = 0.0):
    """``params`` with the exit gate's bias planted (and its weight scaled): ``lambda`` is
    ``sigmoid(bias)`` for every token of every pass where the weight is 0."""
    gate = {"w": params["exit_gate"]["w"] * w_scale, "b": jnp.asarray(bias, params["exit_gate"]["b"].dtype)}
    return {**params, "exit_gate": gate}


def prompt(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], (n,)).astype(np.int32)


def dense_forward(cfg, params, toks, T_max=128, dtype=jnp.float32, **kw):
    """The whole prompt through the dense cache, compiled (one callable a config)."""
    cos, sin = llama.build_rope_cache(cfg, T_max)
    cache = G.init_cache(cfg, 1, T_max, dtype)
    return compiled_forward(cfg, **kw)(params, jnp.asarray(toks)[None], cache, cos, sin)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


def ref_logits(hf, params, toks, positions, pad: int = 128):
    with jax.default_matmul_precision("highest"):
        return arch.ref_logits(hf, params, jnp.asarray(np.pad(toks, (0, pad - len(toks)))), jnp.asarray(positions))


def ref_caches(hf, params, toks, n_real, slabs=None, pad: int = 128):
    with jax.default_matmul_precision("highest"):
        return arch.ref_caches(hf, params, jnp.asarray(np.pad(toks, (0, pad - len(toks)))), n_real, slabs)
