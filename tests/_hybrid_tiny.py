"""What the two files of hybrid serving tests share: the tiny configuration in
the published ratio, its seeded float32 weights and the token streams."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import common  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402

arch = common.load_module("models", "hybrid_dense_decoder")

TINY = {
    "model_name": "tiny-olmo-hybrid", "hidden_size": 48, "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 128, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2, "linear_key_head_dim": 12,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "initializer_range": 0.2,
}


def tiny_model():
    cfg = llama.Config(**arch.program_config(TINY))
    params = arch.make_params(TINY, common.seed_words(5), dtype=jnp.float32)
    # norms, A_log and dt_bias off their initial values: a dropped weight shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)
