"""A model of gated short convolutions, narrow-head attention and a whole
expert layer, served: the program against the benchmark's plain reference
(``chipbench/models/shortconv_moe_decoder.py``) at tiny widths in float32,
seeded.

What is held: ``forward_with_cache`` (a whole prompt, a prompt in two pieces, a
token) and the engine (whole-prompt prefill, then decode through the paged K/V
and the conv tails, requests of different lengths together, a slot reused after
a longer request) against the reference's logits; the biased router against a
closed form; the expert shares adding up to the uncut layer; per-head q/k norm
against the reference; the state pool with conv tails alone; what is refused,
with its reason.  The lane-packed arena's own cases are
``tests/test_lane_packed_arena.py``.
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
from conftest import compiled_forward  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving import engine as engine_mod  # noqa: E402
from thunder_tpu.serving import kv_pool  # noqa: E402

arch = common.load_module("models", "shortconv_moe_decoder")

TINY = {
    "model_name": "tiny-shortconv-moe", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 64, "num_hidden_layers": 5, "vocab_size": 256, "max_position_embeddings": 512,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention"], "conv_L_cache": 3,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "num_dense_layers": 2, "routed_scaling_factor": 1, "rope_theta": 1000000, "norm_eps": 1e-5,
    "initializer_range": 0.2,
}
NEW = 10
LENGTHS = (40, 17, 33, 5)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (n,)).astype(np.int32)


def tiny_model(hf=TINY):
    cfg = llama.Config(**arch.program_config(hf))
    params = arch.make_params(hf, common.seed_words(5), dtype=jnp.float32)
    # norms off their initial value: a dropped weight shows (the router's bias is drawn, not 1)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(cfg, params, **kw):
    opts = dict(num_blocks=40, block_size=16, max_batch=4, prefill_buckets=(16, 32, 48))
    return tt.serve(None, params, cfg, **{**opts, **kw})


def _serve(eng, prompts, new=NEW):
    handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).new_tokens) for h in handles]


# --------------------------------------------------------------------------
# the configuration
# --------------------------------------------------------------------------

def test_init_params_builds_the_layout_the_reference_builds(model):
    cfg, params = model
    own = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes(own) == shapes(params)
    assert own["blocks"][2]["mlp"]["expert_bias"].dtype == jnp.float32
    assert cfg.conv_layers == (0, 1, 3) and cfg.kv_layers == (2, 4) and cfg.linear_layers == ()
    assert cfg.state_layers == cfg.conv_layers and cfg.tie_embeddings and "lm_head" not in params
    assert cfg.mlp_dense(1) and not cfg.mlp_dense(2) and cfg.training_only is None
    G.require_servable(cfg)
    assert G.state_shapes(cfg, 3) == {"conv": (3, 3, 2, 64)}
    assert G.cache_shape(cfg, 2, 64) == (2, 2, 2, 64, 64)
    assert G.kv_lane_pack(cfg) == 2
    assert G.kv_block_shape(cfg, 16) == (2, 2, 16, 64) and G.kv_block_shape(cfg, 16, 2) == (2, 1, 16, 128)
    assert arch.param_count(TINY) == sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert arch.kv_bytes_per_token(TINY) == 2 * 2 * 2 * 64 * 2 and arch.tail_bytes_per_slot(TINY) == 3 * 2 * 64 * 2


def test_the_cells_configuration_counts_what_the_issue_counts():
    _, config, _ = common.open_cell("lfm2moe-serve-1chip.offline-wide")
    assert arch.param_count(config) == 3_928_728_256
    assert arch.param_count({**config, "num_hidden_layers": 24}) == 8_339_930_560
    assert arch.kv_bytes_per_token(config) == 6144 and arch.tail_bytes_per_slot(config) == 73_728
    cfg = llama.Config(**arch.program_config(config))
    assert (cfg.head_size, G.kv_lane_pack(cfg), len(cfg.conv_layers), len(cfg.kv_layers)) == (64, 2, 9, 3)
    assert G.kv_block_shape(cfg, 16, 2) == (3, 4, 16, 128) and cfg.expert_held == cfg.n_expert == 32


@pytest.mark.parametrize("hs,ng,pack", [(128, 8, 1), (256, 2, 1), (64, 8, 2), (64, 3, 1), (32, 4, 4), (96, 4, 1), (16, 2, 1)])
def test_heads_that_divide_a_lane_tile_are_packed_in_whole_rows(hs, ng, pack):
    cfg = llama.Config(name="h", n_layer=1, n_head=ng * 2, n_query_groups=ng, n_embd=64, head_size=hs)
    assert G.kv_lane_pack(cfg) == pack
    assert G.kv_block_shape(cfg, 16, pack) == (1, ng // pack, 16, hs * pack)


# --------------------------------------------------------------------------
# the layers
# --------------------------------------------------------------------------

def test_the_biased_router_is_the_closed_form():
    cfg = llama.Config(name="r", n_layer=1, n_head=2, n_embd=32, mlp_class="SparseMoE", n_expert=6,
                       n_expert_per_token=2, intermediate_size=16, moe_router="sigmoid_bias", routed_scaling_factor=2.0)
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2, 0.3, 0.4], [0.5, 0.6, 0.7, 0.1, 0.2, 0.3]], jnp.float32)
    flat = jnp.zeros((6,), jnp.float32)
    w0, i0 = G.route_sigmoid_bias(scores, flat, cfg)
    assert sorted(np.asarray(i0[0])) == [0, 1] and sorted(np.asarray(i0[1])) == [1, 2]
    np.testing.assert_allclose(np.sort(np.asarray(w0[0])), np.array([0.8, 0.9]) / (1.7 + 1e-6) * 2.0, rtol=1e-6)
    # a bias that changes the choice: expert 3 rises over experts 0 and 1 in row 0
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.75, 0.0, 0.0], jnp.float32)
    w1, i1 = G.route_sigmoid_bias(scores, bias, cfg)
    assert sorted(np.asarray(i1[0])) == [0, 3] and sorted(np.asarray(i1[1])) == [2, 3]
    # the weights are the chosen scores, the bias nowhere in them
    by_expert = dict(zip(np.asarray(i1[0]).tolist(), np.asarray(w1[0]).tolist()))
    np.testing.assert_allclose([by_expert[0], by_expert[3]], np.array([0.9, 0.2]) / (1.1 + 1e-6) * 2.0, rtol=1e-6)
    # and the reference's router makes the same choice
    s = {"k": 2, "route_scale": 2.0}
    h, gate = jnp.asarray(np.random.default_rng(0).normal(size=(9, 32)), jnp.float32), jnp.asarray(
        np.random.default_rng(1).normal(size=(6, 32)), jnp.float32)
    b = jnp.asarray(np.random.default_rng(2).normal(size=(6,)), jnp.float32)
    want_w, want_i = arch.route(h, gate, b, s)
    got_w, got_i = G.route_sigmoid_bias(jax.nn.sigmoid(h @ gate.T), b, cfg)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 8 of 32 experts under the biased router: their parts of
    an expert layer sum to the layer with all experts held, in the program and
    in the reference (no shared expert: nothing is counted twice)."""
    whole_hf = {**TINY, "num_experts": 32, "num_experts_per_tok": 4}
    whole = arch.make_params(whole_hf, common.seed_words(5), dtype=jnp.float32)["blocks"][2]["mlp"]
    whole_cfg = llama.Config(**arch.program_config(whole_hf))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 37, 64)), jnp.float32)
    uncut = G.moe_share_mlp(whole, x, whole_cfg)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(arch._expert_share(x[0], whole, arch.sizes(whole_hf))),
                               atol=2e-5)
    total = jnp.zeros_like(uncut)
    for first in range(0, 32, 8):
        hf = {**whole_hf, "num_experts": 8, "published_num_experts": 32, "expert_first": first}
        part = arch.make_params(hf, common.seed_words(5), dtype=jnp.float32)["blocks"][2]["mlp"]
        # an expert's weights follow from its number, whatever the share; the router and its bias are whole
        np.testing.assert_array_equal(np.asarray(part["fc_1"]), np.asarray(whole["fc_1"][first * 64:(first + 8) * 64]))
        np.testing.assert_array_equal(np.asarray(part["expert_bias"]), np.asarray(whole["expert_bias"]))
        cfg = llama.Config(**arch.program_config(hf))
        assert (cfg.expert_first, cfg.expert_held, cfg.n_expert) == (first, 8, 32)
        share = G.moe_share_mlp(part, x, cfg)
        np.testing.assert_allclose(np.asarray(share[0]), np.asarray(arch._expert_share(x[0], part, arch.sizes(hf))),
                                   atol=2e-5)
        total = total + share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-5)
    assert float(jnp.max(jnp.abs(uncut))) > 0.01


@pytest.mark.parametrize("rows,tile", [(2.67, 16), (8, 16), (9, 32), (32, 64), (33, 128), (64, 128), (256, 128)])
def test_a_row_tile_holds_about_twice_an_experts_even_share(rows, tile):
    """64 slots over 12 of 192 experts stay at the narrowest tile, 256 slots over
    32 of 32 (32 rows an expert) take 64: one tile an expert, its weights read
    once a product; a prompt's rows keep the trainer's tile."""
    assert G.moe_row_tile(rows) == tile


def test_q_and_k_are_normed_a_head_before_the_rotation(model):
    cfg, params = model
    ap, s = params["blocks"][2]["attn"], arch.sizes(TINY)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 12, 64)), jnp.float32)
    cos, sin = llama.build_rope_cache(cfg, 12)
    q, k, v = G._project_qkv(ap, x, cos, sin, cfg)
    want_k, want_v = arch.keys_values(x[0], ap, *arch.rope_tables(s, 12), s)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(want_k.swapaxes(0, 1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(v[0]), np.asarray(want_v.swapaxes(0, 1)), atol=2e-5)
    # the norm is a head's: every head of q has the weight's root mean square before the rotation moves pairs about
    plain = dataclasses.replace(cfg, qk_norm=False)
    assert float(jnp.max(jnp.abs(G._project_qkv(ap, x, cos, sin, plain)[0] - q))) > 0.1
    rms = jnp.sqrt(jnp.mean(jnp.square(q / 1.0), axis=-1))
    assert float(jnp.max(jnp.abs(rms - jnp.sqrt(jnp.mean(jnp.square(ap["q_norm"])))))) < 0.2


def test_the_short_conv_is_the_references(model):
    cfg, params = model
    cp, s = params["blocks"][0]["conv"], arch.sizes(TINY)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 9, 64)), jnp.float32)
    y, tail = G.shortconv_mixer(cp, x, jnp.zeros((2, 2, 64)), cfg)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(arch._short_conv(x[b], cp, s)), atol=2e-5)
        v, _ = arch.gated_inputs(x[b], cp)
        np.testing.assert_allclose(np.asarray(tail[b]), np.asarray(v[-2:]), atol=1e-6)
    # padded to a bucket: the tail ends at the last real token
    _, short = G.shortconv_mixer(cp, x, jnp.zeros((2, 2, 64)), cfg, n_real=4)
    np.testing.assert_allclose(np.asarray(short[0]), np.asarray(arch.gated_inputs(x[0], cp)[0][2:4]), atol=1e-6)
    _, first = G.shortconv_mixer(cp, x, jnp.zeros((2, 2, 64)), cfg, n_real=1)
    assert float(jnp.max(jnp.abs(first[:, 0]))) == 0.0              # zeros before a sequence's first token


def test_forward_with_cache_gives_the_references_logits(model):
    cfg, params = model
    seq = tokens(48)
    cos, sin = llama.build_rope_cache(cfg, 64)
    cache = G.init_cache(cfg, 1, 64, dtype=jnp.float32)
    assert set(cache) == {"k", "v", "conv"}
    lg, cache = compiled_forward(cfg)(params, jnp.asarray(seq[None]), cache, cos, sin)
    want = arch.ref_logits(TINY, params, jnp.asarray(seq), jnp.arange(48))
    assert float(jnp.max(jnp.abs(lg[0, :, :256] - want))) < 2e-4
    # then a token through the cache
    nxt = np.asarray(jnp.argmax(lg[0, -1]))[None].astype(np.int32)
    lg1, _ = compiled_forward(cfg, decode=True)(params, jnp.asarray(nxt[None]), 48, cache, cos, sin)
    want1 = arch.ref_logits(TINY, params, jnp.asarray(np.concatenate([seq, nxt])), jnp.arange(48, 49))
    assert float(jnp.max(jnp.abs(lg1[0, :, :256] - want1))) < 2e-4


def test_a_prompt_in_two_pieces_is_the_prompt_in_one(model):
    cfg, params = model
    seq = tokens(48, 1)
    cos, sin = llama.build_rope_cache(cfg, 64)
    whole, held = compiled_forward(cfg)(params, jnp.asarray(seq[None]), G.init_cache(cfg, 1, 64, dtype=jnp.float32), cos, sin)
    cache = G.init_cache(cfg, 1, 64, dtype=jnp.float32)
    # the first piece padded to 24 with 20 real tokens, as a bucket pads it
    a, cache = compiled_forward(cfg, n_real=20)(params, jnp.asarray(np.concatenate([seq[:20], seq[:4]])[None]), cache, cos, sin)
    b, cache = compiled_forward(cfg, decode=True)(params, jnp.asarray(seq[None, 20:]), 20, cache, cos, sin)
    np.testing.assert_allclose(np.asarray(cache["conv"]), np.asarray(held["conv"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a[:, :20], b], axis=1)), np.asarray(whole), atol=2e-4)
    for n, (kind, want) in zip(range(5), arch.ref_caches(TINY, params, jnp.asarray(seq), 48)):
        if kind == "conv":
            layer = cfg.conv_layers.index(n)
            np.testing.assert_allclose(np.asarray(cache["conv"][layer, 0]), np.asarray(want), atol=2e-5)
        else:
            layer = cfg.kv_layers.index(n)
            np.testing.assert_allclose(np.asarray(cache["k"][layer, 0, :, :48]), np.asarray(want[0]), atol=2e-5)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prompts():
    return [tokens(n, 10 + i) for i, n in enumerate(LENGTHS)]


@pytest.fixture(scope="module")
def solo(model, prompts):
    cfg, params = model
    with jax.default_matmul_precision("highest"):
        return [np.asarray(G.generate(params, p[None], cfg, NEW))[0, len(p):] for p in prompts]


@pytest.fixture(scope="module")
def served(model, prompts):
    cfg, params = model
    with jax.default_matmul_precision("highest"):
        eng = _engine(cfg, params)
        out = _serve(eng, prompts)
    return eng, out


def test_served_together_is_the_references_greedy_choice(model, prompts, served):
    """Whole-prompt prefill, then decode through the paged K/V and the tails,
    four requests of different lengths in one batch: every served token is the
    reference's best at its position, the served sequence teacher-forced."""
    _, params = model
    eng, out = served
    for p, got in zip(prompts, out):
        seq = np.concatenate([p, got])
        lg = arch.ref_logits(TINY, params, jnp.asarray(seq), jnp.arange(len(p) - 1, len(seq) - 1))
        best = np.asarray(jnp.max(lg, axis=-1))
        took = np.asarray(lg)[np.arange(NEW), got]
        assert float(np.max(best - took)) < 1e-3
    st = eng.stats()
    assert st["prefill_fresh_runs"] == len(prompts) and st["state"]["free_low_water"] == 0


def test_served_is_solo_generate(served, solo):
    for got, want in zip(served[1], solo):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("options", [{"prefill_chunk": 16}, {"async_step": False}, {"max_batch": 2},
                                     {"interpreted": True, "batch_buckets": (4,), "block_buckets": (4,)}],
                         ids=["chunked", "sync", "two-slots", "kernels"])
def test_the_other_program_kinds_serve_the_same_tokens(model, prompts, solo, options, monkeypatch):
    """A prompt in chunks (the gather chunk program: a lane-packed arena has no
    multi-query kernel), the synchronous loop, two slots for four requests (a
    slot, its tail and its blocks reused after a longer request), and the Pallas
    kernels interpreted (the lane-packed walk and the token writer)."""
    options = dict(options)
    interpreted = options.pop("interpreted", False)
    if interpreted:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = model
    eng = _engine(cfg, params, **options)
    for got, want in zip(_serve(eng, prompts), solo):
        np.testing.assert_array_equal(got, want)
    st = eng.stats()
    if "prefill_chunk" in options:
        assert st["chunk_runs"] > 0 and st["attn"]["chunk"] == "gather"
    if interpreted:
        assert st["attn"]["path"] == "walk" and st["compile_counts"]["decode_paged"] >= 1
        assert "lane-packed" in st["attn"]["chunk_why"]
    eng.shutdown()


def test_a_recovery_rebuilds_the_tails(model, prompts, solo):
    cfg, params = model
    eng = _engine(cfg, params)
    handles = [eng.submit(p, max_new_tokens=NEW) for p in prompts[:3]]
    for _ in range(4):
        eng.step()
    eng.recover()                                                  # the arenas zeroed, then replayed
    while not all(h.done() for h in handles):
        eng.step()
    for h, want in zip(handles, solo):
        np.testing.assert_array_equal(np.asarray(h.result(drive=False).new_tokens), want)
    assert eng.stats()["recoveries"] == 1


def test_held_is_the_references_caches(model, prompts):
    cfg, params = model
    eng = _engine(cfg, params)
    handles = [eng.submit(p, max_new_tokens=NEW) for p in prompts[:3]]
    while min(len(h.tokens_so_far()) for h in handles) < 4:
        eng.step()
    for p, h in zip(prompts, handles):
        held = eng.held(h)
        n = held["tokens"]
        assert set(held) == {"tokens", "k", "v", "conv"}
        assert held["k"].shape == (2, 2, n, 64) and held["conv"].shape == (3, 2, 64)
        fed = np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)])[:n]
        seen = {"conv": 0, "kv": 0}
        for kind, want in arch.ref_caches(TINY, params, jnp.asarray(fed), n):
            layer = seen[kind]
            seen[kind] += 1
            if kind == "conv":
                np.testing.assert_allclose(np.asarray(held["conv"][layer]), np.asarray(want), atol=2e-4)
            else:
                np.testing.assert_allclose(np.asarray(held["k"][layer]), np.asarray(want[0]), atol=2e-4)
                np.testing.assert_allclose(np.asarray(held["v"][layer]), np.asarray(want[1]), atol=2e-4)
        assert seen == {"conv": 3, "kv": 2}


def test_the_engine_says_what_its_arenas_hold(served):
    eng, _ = served
    st = eng.stats()
    occ = st["pool_occupancy"]
    assert occ["kind"] == "kv" and occ["lane_pack"] == 2
    assert occ["token_bytes_counted"] == occ["token_bytes_laid_out"] == 2 * 2 * 2 * 64 * 4       # float32 here
    assert eng.pool.k_arena.shape == (40, 2, 1, 16, 128)
    assert st["state"]["arenas"] == ["conv"] and st["state"]["layers"] == 3
    assert st["state"]["slot_bytes"] == 3 * 2 * 64 * 4 and eng.pool.state.state is None
    assert st["attn"]["lane_pack"] == 2 and st["attn"]["path"] in ("xla", "walk")
    assert {k: st["moe"][k] for k in ("experts_held", "expert_first", "experts_published", "router")} == {
        "experts_held": 8, "expert_first": 0, "experts_published": 8, "router": "sigmoid_bias"}
    assert set(st["moe"]) >= {"expert_rows_per_step", "experts_hit_share", "row_sums"}


# --------------------------------------------------------------------------
# the state pool
# --------------------------------------------------------------------------

def test_the_state_pool_holds_the_tails_alone(model):
    cfg, _ = model
    pool = kv_pool.PagedKVPool(cfg, num_blocks=4, block_size=16, dtype=jnp.bfloat16, state_slots=3)
    st = pool.state
    assert set(st.arenas) == {"conv"} and st.conv.shape == (4, 3, 2, 64) and st.conv.dtype == jnp.bfloat16
    assert st.state is None and st.slot_bytes() == 3 * 2 * 64 * 2 and set(pool.arenas) == {"k", "v", "conv"}
    slot = st.lease()
    pool.set_arenas({**pool.arenas, "conv": pool.arenas["conv"].at[slot].set(1.0)})
    assert float(pool.state.conv[slot, 0, 0, 0]) == 1.0
    with pytest.raises(kv_pool.ArenaMismatchError):
        pool.set_arenas({**pool.arenas, "conv": jnp.zeros((4, 3, 2, 32), jnp.bfloat16)})
    pool.rebuild_arenas()
    assert float(jnp.max(jnp.abs(pool.state.conv.astype(jnp.float32)))) == 0.0 and st.leased == 1
    with pytest.raises(ValueError, match="state_slots"):
        kv_pool.PagedKVPool(cfg, num_blocks=4, block_size=16)


def test_the_delta_rules_slot_costs_what_it_cost():
    _, config, _ = common.open_cell("olmo-hybrid-serve-1chip.offline-longgen")
    olmo = common.load_module("models", "hybrid_dense_decoder")
    cfg = llama.Config(**olmo.program_config(config))
    st = kv_pool.StatePool(cfg, 1, jnp.bfloat16)
    assert set(st.arenas) == {"conv", "state"} and st.slot_bytes() == 27_371_520 == olmo.state_bytes_per_slot(config)
    assert st.state.dtype == jnp.float32 and st.conv.dtype == jnp.bfloat16
    assert st.snapshot()["dtype"] == "float32" and st.snapshot()["arenas"] == ["conv", "state"]


# --------------------------------------------------------------------------
# what is refused, and why
# --------------------------------------------------------------------------

@pytest.mark.parametrize("feature,reason", [
    ("prefix_sharing", "recurrent state or conv tail cannot"), ("sessions", "no state snapshot"),
    ("speculative", "no rollback"), ("lora", "in_proj"), ("mesh", "no layout under a tp axis"),
    ("model_fn", "llama.Config.*custom model_fn")])
def test_each_refused_feature_raises_with_its_reason(model, feature, reason):
    cfg, params = model
    if feature == "model_fn":       # refused for every model: a model is a Config
        with pytest.raises(NotImplementedError, match=reason):
            tt.serve(lambda *a, **k: None, params, cfg, num_blocks=8, max_batch=1)
        return
    value = True if feature == "prefix_sharing" else object()
    assert reason in engine_mod.hybrid_unsupported(cfg, **{feature: value})
    assert engine_mod.hybrid_unsupported(cfg) is None
    if feature == "prefix_sharing":
        with pytest.raises(NotImplementedError, match="conv tail a request"):
            tt.serve(None, params, cfg, num_blocks=8, max_batch=1, **{feature: value})


def test_training_refuses_the_config_with_its_reason(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="cannot be trained through tt.jit.*gated short convolution"):
        llama.block_forward(params["blocks"][0], jnp.zeros((1, 4, 64)), None, None, cfg, "conv")
    biased = llama.Config(name="biased", n_layer=2, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                          n_expert_per_token=2, intermediate_size=32, moe_router="sigmoid_bias")
    assert "sigmoid_bias" in llama.serving_only(biased) and biased.training_only is None
    assert dataclasses.replace(biased, moe_router="softmax").training_only is None       # the softmax router is served (PR 59)
    normed = llama.Config(name="normed", n_layer=1, n_head=2, n_embd=32, qk_norm=True)
    assert normed.training_only is None and llama.serving_only(normed) is None      # served and trained


def test_a_model_of_both_state_kinds_is_refused():
    with pytest.raises(AssertionError, match="one kind's arenas"):
        llama.Config(name="both", n_layer=2, n_head=4, n_embd=64, layer_types=("conv", "linear_attention"),
                     linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8)
    with pytest.raises(AssertionError, match="conv_kernel"):
        llama.Config(name="one-tap", n_layer=1, n_head=4, n_embd=64, layer_types=("conv",), conv_kernel=1)
