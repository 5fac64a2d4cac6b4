"""What the two files of window/global serving tests share: the tiny
configuration in the published pattern (three window layers to one global,
two leading dense layers, then the expert share), its seeded float32 weights,
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

arch = common.load_module("models", "window_global_moe_decoder")

W, BS = 16, 8                # the window; the pool's block: a window of 16 is two blocks, a ring three
KINDS = ("sliding_attention",) * 3 + ("full_attention",)      # one period: two dense layers, then two of experts
HF = dict(model_name="tiny-window-global", hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
          rms_norm_eps=1e-5, max_position_embeddings=512, num_attention_heads=4, num_hidden_layers=4,
          num_key_value_heads=2, head_dim=32, sliding_window=W, vocab_size=256, initializer_range=0.2,
          layer_types=list(KINDS), num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
          num_dense_layers=2, route_scale=2.826, route_norm=True, score_func="sigmoid", rope_theta=10000,
          mup_enabled=True, tie_word_embeddings=False)
ENGINE = dict(block_size=BS, num_blocks=64, max_batch=4, prefill_buckets=[32, 64, 96], cache_dtype=jnp.float32)


@functools.cache
def model():
    cfg = llama.Config(**arch.program_config(HF))
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(HF, common.seed_words(5), dtype=jnp.float32)
    return cfg, params


def prompt(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], (n,)).astype(np.int32)


def dense_forward(cfg, params, toks, T_max=128, anew=False, **kw):
    """The whole prompt through the dense cache, compiled; traced ``anew``, the weights the
    arrays they are, where a test has planted a fault in the program's functions (some by
    the identity of a weight): a callable kept from before would not hold it."""
    cos, sin = llama.build_rope_cache(cfg, T_max)
    cache = G.init_cache(cfg, 1, T_max, jnp.float32)
    if anew:
        return jax.jit(lambda t, c: G.forward_with_cache(params, t, 0, c, cos, sin, cfg, **kw))(jnp.asarray(toks)[None], cache)
    return compiled_forward(cfg, **kw)(params, jnp.asarray(toks)[None], cache, cos, sin)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


# the reference as it is, a layer's own compiled calls inside one compiled call: one program a count of positions
_ref_logits = jax.jit(functools.partial(arch.ref_logits, HF))


def ref_logits(params, toks, positions, hf=HF):
    ref = _ref_logits if hf is HF else functools.partial(arch.ref_logits, hf)
    with jax.default_matmul_precision("highest"):
        return ref(params, jnp.asarray(np.pad(toks, (0, 128 - len(toks)))), jnp.asarray(positions))
