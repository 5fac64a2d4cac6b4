"""A latent-attention model with a whole expert layer under manifold-constrained
hyper-connections (a residual stream ``hc_mult`` wide, ``Config.hc_mult``), served:
the program against the benchmark's plain reference
(``chipbench/models/latent_hc_moe_decoder.py``) at tiny widths in float32, seeded.

What is held: ``generate.hc_open`` / ``hc_close`` against the reference's lines on
random streams, ``H_res`` doubly stochastic, a planted identity the plain pre-norm
block; ``forward_with_cache`` (a whole prompt, then a token at a time through the
dense cache) and the engine (whole-prompt prefill, then decode through the paged
latents on the walk's XLA form, two requests of different lengths together) against
the reference's logits; what the engine holds of a request; three planted faults of
the hyper-connection failing those comparisons; what is refused, with its reason.
Logits and not tokens: with random weights the largest logit changes on rounding.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from chipbench import common  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving.paged_attention import forward_paged  # noqa: E402

arch = common.load_module("models", "latent_hc_moe_decoder")

TINY = {
    "model_name": "tiny-latent-hc-moe", "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
    "vocab_size": 256, "max_position_embeddings": 512, "q_lora_rank": 32, "kv_lora_rank": 128,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.0, "first_k_dense_replace": 1, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "rms_norm_eps": 1e-6, "initializer_range": 0.2,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "hc_alpha_init": 0.4, "hc_res_bias_diag": 2.0,
}
# float32 on both sides at the highest precision: what is left is the order of the sums (the
# reference mixes by einsum, the program a stream at a time; 20 Sinkhorn iterations each), a few
# 1e-5 of logits near 7 on this seed (5e-5 the largest seen); a planted fault reads 1e-2 and more
LOGIT_ATOL = 5e-4
LENGTHS = (40, 17)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


@pytest.fixture(scope="module")
def model():
    cfg = llama.Config(**arch.program_config(TINY))
    params = arch.make_params(TINY, common.seed_words(5), dtype=jnp.float32)
    # norms and biases off their initial value: a dropped weight shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _stream(seed, T=7, dtype=jnp.float32):
    """A random stream as the reference carries it ``(T, n, C)`` and as the program does ``(1, n, T, C)``."""
    X = jax.random.normal(jax.random.PRNGKey(seed), (T, TINY["hc_mult"], TINY["hidden_size"]), jnp.float32).astype(dtype)
    return X, X.transpose(1, 0, 2)[None]


def _dense_logits(cfg, params, seq, n_prompt):
    """Prefill of the first ``n_prompt`` tokens, then a token at a time through the dense cache: ``(len(seq), V)``."""
    cos, sin = llama.build_rope_cache(cfg, 64)

    @jax.jit        # traced anew a call: a test's planted ``hc_maps`` is in it
    def run(params, seq):
        lg, cache = G.forward_with_cache(params, seq[None, :n_prompt], 0, G.init_cache(cfg, 1, 64, dtype=jnp.float32),
                                         cos, sin, cfg)

        def step(cache, t):
            tok = jax.lax.dynamic_slice_in_dim(seq, t, 1)[None]
            lg, cache = G.forward_with_cache(params, tok, t, cache, cos, sin, cfg)
            return cache, lg[0, 0]

        _, rest = jax.lax.scan(step, cache, jnp.arange(n_prompt, len(seq)))
        return jnp.concatenate([lg[0], rest])

    return np.asarray(run(params, jnp.asarray(seq)))


# --------------------------------------------------------------------------
# the configuration
# --------------------------------------------------------------------------

def test_init_params_builds_the_layout_the_reference_builds(model):
    cfg, params = model
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)) == shapes(params)
    assert cfg.hc_mult == 4 and cfg.hc_res_clamp == (-30.0, 30.0) and cfg.moe_router == "sigmoid_bias"
    assert cfg.latent and cfg.mlp_dense(0) and not cfg.mlp_dense(1) and cfg.expert_held == cfg.n_expert == 8
    assert params["blocks"][1]["mlp"]["expert_bias"].dtype == jnp.float32
    assert arch.param_count(TINY) == sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert cfg.training_only is None


@pytest.mark.parametrize("layout", ["parallel_residual", "post_sublayer_norm", "sandwich_norm", "shared_attention_norm"])
def test_a_hyper_connection_is_refused_with_any_other_block_layout(layout):
    extra = {"parallel_residual": True} if layout == "shared_attention_norm" else {}
    with pytest.raises(AssertionError, match="hc_mult > 1: the plain pre-norm block alone"):
        llama.Config(n_layer=2, n_head=2, n_embd=32, hc_mult=4, **{layout: True}, **extra)


def test_the_trainer_refuses_a_hyper_connection_with_its_reason():
    cfg = llama.Config(n_layer=1, n_head=2, n_embd=32, hc_mult=2)
    assert "hc_mult > 1" in llama.serving_only(cfg) and "block_forward carries one stream" in llama.serving_only(cfg)
    assert llama.serving_only(dataclasses.replace(cfg, hc_mult=1)) is None
    with pytest.raises(NotImplementedError, match="hc_mult > 1"):
        llama.block_forward({}, None, None, None, cfg)


# --------------------------------------------------------------------------
# the hyper-connection alone
# --------------------------------------------------------------------------

def test_hc_open_and_close_are_the_references_lines(model):
    cfg, params = model
    s = arch.sizes(TINY)
    for seed, name in ((1, "hc_1"), (2, "hc_2")):
        hp = params["blocks"][1][name]
        X, x = _stream(seed)
        h_pre, h_post, h_res = arch.hc_maps(X, hp, s)
        u, maps = G.hc_open(hp, x, cfg)
        np.testing.assert_allclose(np.asarray(u[0]), np.asarray(arch.hc_read(X, h_pre)), atol=2e-6)
        f = jax.random.normal(jax.random.PRNGKey(9 + seed), u.shape)
        want = arch.hc_write(X, f[0], h_post, h_res)                                    # (T, n, C)
        np.testing.assert_allclose(np.asarray(G.hc_close(x, f, maps)[0].transpose(1, 0, 2)), np.asarray(want), atol=5e-6)


def test_h_res_is_doubly_stochastic_and_a_tokens_own(model):
    cfg, params = model
    _, x = _stream(3, T=64)
    h_pre, h_post, h_res = (np.asarray(a) for a in G.hc_maps(params["blocks"][0]["hc_1"], x, cfg))
    assert h_res.shape == (4, 4, 1, 64) and h_pre.shape == h_post.shape == (4, 1, 64)
    # rows are normalised last: exact to float32's rounding.  Columns: what 20 iterations leave of the
    # first one's error, which shrinks by the matrix's own rate (its second singular value squared an
    # iteration): 1e-6 for most tokens, 1e-2 for the few whose exp(N(0, 1.3)) entries lie near a
    # permutation; after one iteration the same columns read 0.3 (the first planted control)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=5e-6)
    cols = np.abs(h_res.sum(axis=0) - 1.0)
    assert cols.max() < 3e-2 and np.median(cols) < 1e-4, (cols.max(), np.median(cols))
    once = np.asarray(G.hc_maps(params["blocks"][0]["hc_1"], x, dataclasses.replace(cfg, hc_sinkhorn_iters=1))[2])
    assert np.abs(once.sum(axis=0) - 1.0).max() > 10 * cols.max()
    assert (h_res > 0).all() and (0 < h_pre).all() and (h_pre < 1).all() and (0 < h_post).all() and (h_post < 2).all()
    # neither the identity nor uniform, and not one matrix for every token
    diag = np.einsum("iit->t", h_res[:, :, 0]) / 4
    assert 0.35 < diag.mean() < 0.85 and diag.std() > 0.05 and h_pre.std(axis=-1).min() > 0.05


def test_a_bfloat16_streams_maps_are_float32s_on_the_same_numbers(model):
    """The products of a bfloat16 stream (``phi`` split into three bfloat16 pieces, float32 sums)
    against the float32 products of the very same values."""
    cfg, params = model
    _, x = _stream(4, T=16, dtype=jnp.bfloat16)
    hp = params["blocks"][1]["hc_2"]
    for got, want in zip(G.hc_maps(hp, x, cfg), G.hc_maps(hp, x.astype(jnp.float32), cfg)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_planted_identity_maps_are_the_plain_pre_norm_block(model, monkeypatch):
    """``H_pre = e_1``, ``H_post = 1``, ``H_res = I``: stream 0 is the plain block's stream."""
    cfg, params = model
    plain = dataclasses.replace(cfg, hc_mult=1)

    def planted(hp, x, cfg):
        B, n, T, _ = x.shape
        one = jnp.ones((B, T), jnp.float32)
        return (jnp.stack([one] + [0 * one] * (n - 1)), jnp.stack([one] * n),
                jnp.eye(n, dtype=jnp.float32)[:, :, None, None] * one)

    monkeypatch.setattr(G, "hc_maps", planted)
    seq = tokens(24, 11)
    cos, sin = llama.build_rope_cache(cfg, 64)
    run = lambda c: jax.jit(lambda p, t: G.forward_with_cache(  # noqa: E731
        p, t, 0, G.init_cache(c, 1, 64, dtype=jnp.float32), cos, sin, c)[0])(params, jnp.asarray(seq[None]))
    got, want = run(cfg), run(plain)
    # the head sums the n streams: stream 0 is the plain one, the others stay the embedding plus what every sublayer gave
    # (H_post 1 writes to all), so each is the plain stream too and the sum is n of it; the last norm takes the n out
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# --------------------------------------------------------------------------
# the forward against the reference
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ref40(model):
    _, params = model
    seq = tokens(48, 21)
    with jax.default_matmul_precision("highest"):
        return seq, np.asarray(arch.ref_logits(TINY, params, jnp.asarray(seq), jnp.arange(48)))


def test_forward_with_cache_gives_the_references_logits(model, ref40):
    cfg, params = model
    seq, want = ref40
    np.testing.assert_allclose(_dense_logits(cfg, params, seq, 40), want, atol=LOGIT_ATOL)


@pytest.mark.parametrize("which", ["sinkhorn1", "static", "bfloat16"])
def test_a_planted_fault_of_the_hyper_connection_fails_the_comparison(model, ref40, monkeypatch, which):
    """The benchmark's three controls (``chipbench/drivers/serve_latent_hc.py`` ``plant_hc_control``: one
    Sinkhorn iteration for 20; the token's own part dropped; the maps in bfloat16), planted as it plants them."""
    cfg, params = model
    seq, want = ref40
    monkeypatch.setattr(G, "hc_maps", G.hc_maps)            # restored after the plant
    common.load_module("drivers", "serve_latent_hc").plant_hc_control(which)
    err = np.max(np.abs(_dense_logits(cfg, params, seq, 40) - want))
    assert err > 10 * LOGIT_ATOL, err


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def running(model):
    """Two requests of different lengths served together and stopped mid-way, both still running."""
    cfg, params = model
    prompts = [tokens(n, 30 + i) for i, n in enumerate(LENGTHS)]
    with jax.default_matmul_precision("highest"):
        eng = tt.serve(None, params, cfg, num_blocks=40, block_size=16, max_batch=4, prefill_buckets=(16, 32, 48))
        handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
        while min(len(h.tokens_so_far()) for h in handles) < 5:
            eng.step()
        held = [jax.device_get(eng.held(h)) for h in handles]
    return eng, prompts, handles, held


def test_the_engines_arena_and_paged_step_give_the_references_logits(model, running):
    """The rows the engine's whole-prompt prefill and decode steps wrote, read back by one more paged
    step of both requests together (the walk's XLA form): its logits against the full forward pass."""
    cfg, params = model
    eng, prompts, handles, held = running
    counts = eng.stats()["compile_counts"]
    assert counts["prefill_fresh"] >= 1 and counts["decode_paged"] >= 1 and eng.stats()["attn"]["path"] == "xla"
    seqs = [np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)]) for p, h in zip(prompts, handles)]
    pos = [h["tokens"] for h in held]
    width = max(len(h._req.block_table) for h in handles)
    tables = jnp.asarray([list(h._req.block_table) + [0] * (width - len(h._req.block_table)) for h in handles], jnp.int32)
    cos, sin = llama.build_rope_cache(cfg, 64)
    idx = jnp.asarray([[s[p]] for s, p in zip(seqs, pos)], jnp.int32)
    lg = jax.jit(lambda p, a, i, at, t: forward_paged(p, i, at, a, t, cos, sin, cfg, cdtype=jnp.float32)[0])(
        params, eng.pool.arenas, idx, jnp.asarray(pos, jnp.int32), tables)
    for i, (s, p) in enumerate(zip(seqs, pos)):
        want = arch.ref_logits(TINY, params, jnp.asarray(s[:p + 1]), jnp.asarray([p]))
        np.testing.assert_allclose(np.asarray(lg[i, 0]), np.asarray(want[0]), atol=LOGIT_ATOL)


def test_held_is_the_references_latents(model, running):
    cfg, params = model
    _, prompts, handles, held = running
    for p, h, got in zip(prompts, handles, held):
        n = got["tokens"]
        assert set(got) == {"tokens", "latent"} and got["latent"].shape == (cfg.n_layer, n, cfg.latent_width)
        fed = np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)])[:n]
        for layer, want in enumerate(arch.ref_latents(TINY, params, jnp.asarray(fed), n)):
            # rows of unit scale after their norm; float32 on both sides, as the logits
            np.testing.assert_allclose(got["latent"][layer], np.asarray(want), atol=2e-4)


def test_served_is_solo_generate_and_counts_its_experts_rows(model, running):
    cfg, params = model
    eng, prompts, handles, _ = running
    while not all(h.done() for h in handles):
        eng.step()
    for p, h in zip(prompts, handles):
        want = np.asarray(G.generate(params, p[None], cfg, 12))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(h.result(drive=False).new_tokens), want)
    moe = eng.stats()["moe"]
    assert moe["router"] == "sigmoid_bias" and moe["experts_held"] == moe["experts_published"] == 8
    assert moe["row_sums"][0] > 0 and 0 < moe["experts_hit_share"] <= 1
    assert eng.stats()["pool_occupancy"]["kind"] == "latent"
