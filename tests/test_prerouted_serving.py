"""An ordinary decoder whose expert layers route on the block's input
(SmallThinker's kinds: a global layer that rotates nothing then three window
layers that rotate, 7 query heads a KV head, no bias and no q/k norm, a float32
softmax router over all the experts that reads the stream *before* the first norm
and attention, whole gated-ReLU experts that read the second norm, no shared
expert) in ``models.generate``, at tiny widths in float32 on seeded weights,
against the benchmark's plain reference (``chipbench/models/prerouted_moe_decoder.py``,
which imports nothing of the program).  ``tests/test_prerouted_engine.py`` has the
same model through ``tt.serve``.

Tolerances: the program and the reference compute the same float32 sums in
different orders (a masked softmax on both sides, the experts by sorted rows
against a mask), so logits agree to 1e-4 of a spread of ~8 and held arrays to 1e-4
relative; a piece of the block changed (the router's input, the experts' gate, the
window, a global layer's missing rotation) reads hundreds of times that, and the
test that changes each says so.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from chipbench import common
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _prerouted_tiny import BS, HF, KINDS, W, arch, dense_forward, model, prompt, ref_logits, rel  # noqa: E402
from conftest import compiled_forward  # noqa: E402

# --------------------------------------------------------------------------
# the config and the layout
# --------------------------------------------------------------------------

def test_the_config_names_the_kinds_the_router_and_what_each_keeps():
    cfg, _ = model()
    assert cfg.layer_types == KINDS and not cfg.hybrid_decoder and cfg.keeps_slot
    assert cfg.ring_layers == (1, 2, 3) and cfg.paged_kv_layers == (0,) and cfg.kv_layers == tuple(range(4))
    assert [cfg.rotates(i) for i in range(4)] == [False, True, True, True]
    assert cfg.n_head // cfg.n_query_groups == 7
    assert G.ring_blocks(cfg, BS) == 3 and G.ring_block_shape(cfg, BS) == (3, 1, BS, 32)
    assert (cfg.moe_router, cfg.moe_activation, cfg.moe_route_block_input) == ("softmax", "reglu", True)
    assert cfg.expert_held == cfg.n_expert == 8 and cfg.training_only is None
    G.require_servable(cfg)


def test_the_softmax_router_is_served_and_the_trainer_names_the_two_forms_it_lacks():
    cfg, params = model()
    assert "sliding_attention" in llama.serving_only(cfg)          # the window kind is named first
    cos, sin = llama.build_rope_cache(cfg, 16)
    with pytest.raises(NotImplementedError, match="cannot be trained through tt.jit"):
        llama.block_forward(params["blocks"][0], jnp.zeros((1, 16, 64)), cos, sin, cfg)
    plain = dict(name="softmax", n_layer=2, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                 n_expert_per_token=2, intermediate_size=32)
    softmax = llama.Config(**plain)
    assert softmax.training_only is None and llama.serving_only(softmax) is None          # served and trained
    G.require_servable(softmax)
    assert "'reglu'" in llama.serving_only(llama.Config(**plain, moe_activation="reglu"))
    assert "moe_route_block_input" in llama.serving_only(llama.Config(**plain, moe_route_block_input=True))


@pytest.mark.parametrize("bad", [
    dict(parallel_residual=True), dict(post_sublayer_norm=True), dict(first_k_dense=1),
    dict(mlp_class="LLaMAMLP", n_expert=0, moe_router="softmax", moe_activation="swiglu", expert_held=None),
], ids=["parallel_residual", "post_sublayer_norm", "first_k_dense", "no_expert_layer"])
def test_a_router_on_the_blocks_input_needs_a_pre_norm_sequential_expert_block(bad):
    with pytest.raises(AssertionError, match="moe_route_block_input"):
        llama.Config(**{**arch.program_config(HF), **bad})


@pytest.mark.parametrize("field", [True, False], ids=["no_input_where_the_field_asks", "an_input_where_it_does_not"])
def test_a_caller_that_goes_round_the_block_fails_by_name(field):
    """``_close_block`` alone hands the block's input through; ``distributed/sp.py`` calls ``generate._mlp`` itself, and
    must not route on the experts' input in silence: that is the planted fault the comparison fails."""
    cfg, params = model()
    cfg = dataclasses.replace(cfg, moe_route_block_input=field)
    h = jnp.zeros((1, 8, 64), jnp.float32)
    with pytest.raises(AssertionError, match="moe_route_block_input"):
        G._mlp(params["blocks"][0]["mlp"], h, cfg, route_x=None if field else h)


def test_init_params_has_the_layout_the_reference_states():
    cfg, params = model()
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)) == shapes(params)
    assert llama.param_count(params) == arch.param_count(HF)
    assert arch.param_count(HF, held=8, vocab=HF["vocab_size"]) == arch.param_count(HF)


def test_the_published_count_closes():
    """21.51 B in all and 3.33 B a token at the published sizes, as the model's name says (21B-A3B)."""
    with open(os.path.join(common.ROOT, "chipbench", "configs", "smallthinker-serve-1chip.json")) as f:
        hf = json.load(f)
    s = arch.sizes(hf)
    assert (s["L"], s["L_swa"], s["L_walk_full"], s["W"], s["ng"], s["hs"], s["nh"], s["held"], s["E"], s["k"], s["dense"]) == (
        8, 6, 2, 4096, 4, 128, 28, 64, 64, 6, 0)
    assert s["kinds"] == (("full_attention",) + ("sliding_attention",) * 3) * 2 and s["rope_kinds"] == ("sliding_attention",)
    assert arch.expert_params(hf) == 5_898_240 and arch.layer_params(hf, 0) == 398_627_840
    whole = arch.param_count({**hf, "num_hidden_layers": 52})
    assert abs(whole - 21.51e9) < 0.01e9
    active = whole - 52 * (64 - 6) * arch.expert_params(hf) - 151936 * 2560      # 6 experts a token, the embedding a row
    assert abs(active - 3.33e9) < 0.01e9
    assert abs(arch.param_count(hf) * 2 - 7.93e9) < 0.01e9                      # this chip's stage, bfloat16
    assert arch.ring_bytes_per_slot(hf) == 6 * 257 * 16 * 2048 and arch.kv_bytes_per_token(hf) == 4096
    assert hf["reduced"] == ["num_hidden_layers"] and hf["published_num_hidden_layers"] == 52


# --------------------------------------------------------------------------
# program = reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [W - 1, W, W + 1, W + BS + 1, 77], ids=lambda n: f"tokens{n}")
def test_every_kind_agrees_with_the_reference_around_the_windows_edge(n):
    """The full forward's logits on 100 tokens, and what each layer holds after ``n``
    (one short of the window, the window, one past it, a block of slack past it,
    several windows): layer by layer and kind by kind, 7 query heads on the one KV head."""
    cfg, params = model()
    toks = prompt(100, n)
    logits, _ = dense_forward(cfg, params, toks)
    want = ref_logits(params, toks, np.arange(100))
    assert float(jnp.abs(logits[0] - want).max()) < 1e-4 * float(jnp.abs(want).max())
    _, cache = dense_forward(cfg, params, toks[:n])
    with jax.default_matmul_precision("highest"):
        held = arch.ref_caches(HF, params, jnp.asarray(np.pad(toks, (0, 28))), n)
    assert [k for k, _ in held] == list(KINDS)
    for j, (kind, ref) in enumerate(held):
        lo = n - ref[0].shape[1]
        assert lo == (max(0, n - W) if kind == "sliding_attention" else 0)
        assert rel(cache["k"][j, 0][:, lo:n], ref[0]) < 1e-4 and rel(cache["v"][j, 0][:, lo:n], ref[1]) < 1e-4


def test_prefill_then_decode_through_the_dense_cache_is_the_references_full_forward():
    cfg, params = model()
    toks = prompt(48, 5)
    want = ref_logits(params, toks, np.arange(48))
    tol = 1e-4 * float(jnp.abs(want).max())
    cos, sin = llama.build_rope_cache(cfg, 128)
    lg, cache = dense_forward(cfg, params, toks[:12])       # inside the window: decode carries it across the edge
    errs = [float(jnp.abs(lg[0] - want[:12]).max())]
    step = compiled_forward(cfg, decode=True)
    for t in range(12, 48):
        lg, cache = step(params, jnp.asarray(toks[t:t + 1])[None], jnp.int32(t), cache, cos, sin)
        errs.append(float(jnp.abs(lg[0, 0] - want[t]).max()))
    assert max(errs) < tol


CHANGED = {
    "router_reads_the_experts_input": dict(moe_route_block_input=False),
    "swiglu_experts": dict(moe_activation="swiglu"),
    "no_window": dict(layer_window=1 << 20),
    "global_layers_rotated": dict(rope_kinds=None),
    "sigmoid_router": dict(moe_router="sigmoid_group"),
}


@pytest.mark.parametrize("piece", CHANGED)
def test_each_piece_of_the_block_fails_the_comparison_when_changed(piece):
    """The planted controls: the router fed ``norm_2(x')`` in the block input's place
    (what every other expert layer of the benchmark reads), SwiGLU where the gated
    ReLU stands, and the attention kinds' own."""
    cfg, params = model()
    toks = prompt(60, 13)
    want = ref_logits(params, toks, np.arange(60))
    spread = float(jnp.abs(want).max())
    assert float(jnp.abs(dense_forward(cfg, params, toks)[0][0] - want).max()) < 1e-4 * spread
    got = dense_forward(dataclasses.replace(cfg, **CHANGED[piece]), params, toks)[0][0]
    assert float(jnp.abs(got - want).max()) > 100 * 1e-4 * spread, piece


# --------------------------------------------------------------------------
# the routing and the shares
# --------------------------------------------------------------------------

def test_route_softmax_is_the_trainers_routing_on_the_same_logits():
    """The server's softmax expert layer (``route_softmax`` into the sorted-rows share)
    against the trainer's own traced layer (``llama.sparse_moe_mlp`` through ``tt.jit``)
    on the same weights and tokens, SwiGLU experts on the tensor they read: one
    equation, two modules.  And the choice itself, against the trainer's lines."""
    cfg = llama.Config(name="softmax", n_layer=1, n_head=4, n_embd=64, mlp_class="SparseMoE", n_expert=8,
                       n_expert_per_token=2, intermediate_size=32, shared_expert_size=32)
    mp = llama.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)["blocks"][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        trained = tt.jit(lambda mp_, x_: llama.sparse_moe_mlp(mp_, x_, cfg))(mp, x)
        served = jax.jit(lambda mp_, x_: G.moe_share_mlp(mp_, x_, cfg))(mp, x)
        assert rel(served, trained) < 1e-5
        logits = x.reshape(48, 64) @ mp["gate"].T
        w, idx = G.route_softmax(logits, cfg)
        probs = jax.nn.softmax(logits, -1)
        top_w, top_idx = jax.lax.top_k(probs, 2)
        np.testing.assert_array_equal(idx, top_idx)
        np.testing.assert_allclose(w, top_w / jnp.sum(top_w, -1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-6)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """Four shares of two experts each of eight: every chip routes over all eight *on
    the block's input*, computes its own experts' part on the experts' input; the
    parts are the uncut reference's layer (no shared expert to count once)."""
    with jax.default_matmul_precision("highest"):
        whole = arch.make_params(HF, common.seed_words(9), dtype=jnp.float32)["blocks"][2]["mlp"]
        h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)       # what the experts read
        r = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 64), jnp.float32)       # what the router reads
        want = arch.routed_share(h[0], r[0], whole, arch.sizes(HF))
        total, hit = jnp.zeros_like(want), 0
        for chip in range(4):
            hf = {**HF, "expert_held": 2, "expert_first": 2 * chip}
            cfg = llama.Config(**arch.program_config(hf))
            assert (cfg.expert_first, cfg.expert_held, cfg.n_expert) == (2 * chip, 2, 8)
            mp = arch.make_params(hf, common.seed_words(9), dtype=jnp.float32)["blocks"][2]["mlp"]
            np.testing.assert_array_equal(mp["gate"], whole["gate"])
            np.testing.assert_array_equal(mp["fc_1"], whole["fc_1"][2 * chip * 64:(2 * chip + 2) * 64])

            def share(mp_, h_, r_):
                rows: list = []
                return G.moe_share_mlp(mp_, h_, cfg, moe_rows=rows, route_x=r_)[0], rows[0]

            part, rows = jax.jit(share)(mp, h, r)
            assert rel(part, arch.routed_share(h[0], r[0], mp, arch.sizes(hf))) < 1e-4
            total, hit = total + part, hit + int(rows[0])
        assert hit == 48 * 2                                  # every routed row landed on exactly one chip
        assert rel(total, want) < 1e-5
        # routed on the experts' own input the sum is another layer's
        assert rel(arch.routed_share(h[0], h[0], whole, arch.sizes(HF)), want) > 0.5


def test_the_blocks_scopes_are_the_ones_the_readers_split_by():
    """The router's products keep the scope ``mlp/router`` though they read the block's
    input: the shares by scope count them with the expert sublayer."""
    cfg, params = model()
    cos, sin = llama.build_rope_cache(cfg, 64)
    cache = G.init_cache(cfg, 1, 64, jnp.float32)
    fwd = jax.jit(lambda p, t, c: G.forward_with_cache(p, t, 0, c, cos, sin, cfg))
    text = fwd.lower(params, jnp.asarray(prompt(24, 1))[None], cache).as_text(debug_info=True)
    for name in ("norm", "qkv", "attn", "out"):
        assert f"blk0/mixer/{name}/" in text, name
    assert "blk0/mixer/rope/" not in text and "blk1/mixer/rope/" in text and "blk1/mixer/swa/" in text
    for name in ("residual", "norm", "router", "experts"):
        assert f"blk2/mlp/{name}/" in text, name
    assert "/mixer/router/" not in text and "mlp/shared/" not in text
