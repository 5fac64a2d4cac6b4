"""What the two files of pre-routed serving tests share: the tiny configuration in
the published pattern (a global layer first, then three window layers; 7 query
heads over 1 KV head; 8 gated-ReLU experts, top 2, routed by softmax on the block's
input), its seeded float32 weights, the token streams and the comparisons' helpers."""
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

arch = common.load_module("models", "prerouted_moe_decoder")

W, BS = 16, 8                # the window; the pool's block: a window of 16 is two blocks, a ring three
KINDS = ("full_attention",) + ("sliding_attention",) * 3       # one period, the global layer first
HF = dict(model_name="tiny-prerouted", hidden_size=64, moe_ffn_hidden_size=32, rms_norm_eps=1e-6,
          max_position_embeddings=512, num_attention_heads=7, num_hidden_layers=4, num_key_value_heads=1,
          head_dim=32, sliding_window_size=W, sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
          rope_theta=1500000, rope_scaling=None, vocab_size=256, initializer_range=0.2,
          moe_num_primary_experts=8, moe_num_active_primary_experts=2, moe_primary_router_apply_softmax=True,
          norm_topk_prob=True, tie_word_embeddings=False)
ENGINE = dict(block_size=BS, num_blocks=64, max_batch=4, prefill_buckets=[32, 64, 96], cache_dtype=jnp.float32)


@functools.cache
def model():
    cfg = llama.Config(**arch.program_config(HF))
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(HF, common.seed_words(5), dtype=jnp.float32)
    return cfg, params


def prompt(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], (n,)).astype(np.int32)


def dense_forward(cfg, params, toks, T_max=128, **kw):
    """The whole prompt through the dense cache, compiled (one callable a config)."""
    cos, sin = llama.build_rope_cache(cfg, T_max)
    cache = G.init_cache(cfg, 1, T_max, jnp.float32)
    return compiled_forward(cfg, **kw)(params, jnp.asarray(toks)[None], cache, cos, sin)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


# the reference as it is, a layer's own compiled calls inside one compiled call: one program a count of positions
_ref_logits = jax.jit(functools.partial(arch.ref_logits, HF))


def ref_logits(params, toks, positions):
    with jax.default_matmul_precision("highest"):
        return _ref_logits(params, jnp.asarray(np.pad(toks, (0, 128 - len(toks)))), jnp.asarray(positions))
