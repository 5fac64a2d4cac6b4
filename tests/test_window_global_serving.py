"""An ordinary decoder with window and global softmax layers side by side
(Trinity-Mini's kinds: grouped-query attention with a norm a head on q and k and a
sigmoid gate on its output, a rotation in the window layers alone, a norm before
and after each sublayer, leading dense layers then a sigmoid-bias expert share
beside a shared expert, a scaled embedding) in ``models.generate`` and through
``tt.serve``, at tiny widths in float32 on seeded weights, against the benchmark's
plain reference (``chipbench/models/window_global_moe_decoder.py``, which imports
nothing of the program) and against solo ``generate()``.

Tolerances: the program and the reference compute the same float32 sums in
different orders (a masked softmax on both sides, the experts by sorted rows
against a mask), so logits agree to 1e-4 of a spread of ~8 and held arrays to 1e-4
relative; a piece of the block left out (the gate, any of the four norms, the
embedding's scale, a global layer's missing rotation) reads hundreds of times
that, and the test that leaves each out says so.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _window_global_tiny import (  # noqa: E402
    BS, HF, KINDS, W, arch, dense_forward, model, prompt, ref_logits, rel)
from conftest import compiled_forward  # noqa: E402

# --------------------------------------------------------------------------
# the config and the layout
# --------------------------------------------------------------------------

def test_the_config_names_the_kinds_and_what_each_keeps():
    cfg, _ = model()
    assert cfg.layer_types == KINDS and not cfg.hybrid_decoder and cfg.keeps_slot
    assert cfg.ring_layers == (0, 1, 2) and cfg.paged_kv_layers == (3,) and cfg.kv_layers == tuple(range(4))
    assert cfg.state_layers == () and G.state_shapes(cfg, 2) == {}
    assert [cfg.rotates(i) for i in range(4)] == [True, True, True, False]
    assert G.ring_blocks(cfg, BS) == 3 and G.ring_block_shape(cfg, BS) == (3, 2, BS, 32)
    assert G.kv_block_shape(cfg, BS) == (1, 2, BS, 32) and G.cache_shape(cfg, 1, 64) == (4, 1, 2, 64, 32)
    assert cfg.training_only is None and "sliding_attention" in llama.serving_only(cfg)
    G.require_servable(cfg)
    plain = llama.Config(n_layer=2, n_head=2, n_embd=32)
    assert not plain.keeps_slot and plain.rotates(0)


@pytest.mark.parametrize("bad,word", [
    (dict(layer_window=None), "layer_window"),
    (dict(sliding_window=16), "sliding_window"),
    (dict(layer_types=("sliding_attention", "linear_attention") * 2, linear_num_key_heads=1, linear_num_value_heads=1,
          linear_key_head_dim=8, linear_value_head_dim=8), "full_attention alone"),
    (dict(rope_kinds=("conv",)), "conv"),
    (dict(parallel_residual=True), "sandwich_norm"),
    (dict(post_sublayer_norm=True), "sandwich_norm"),
])
def test_what_the_kinds_need_of_each_other_is_asserted_by_name(bad, word):
    with pytest.raises(AssertionError, match=word):
        llama.Config(**{**arch.program_config(HF), **bad})


def test_init_params_has_the_layout_the_reference_states():
    cfg, params = model()
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)) == shapes(params)
    assert llama.param_count(params) == arch.param_count(HF)
    assert arch.param_count(HF, held=HF["num_experts"], vocab=HF["vocab_size"]) == arch.param_count(HF)


def test_the_published_count_closes():
    """26.12 B in all and 3.07 B a token at the published sizes, as the model's name says (26B-A3B)."""
    import json
    import os

    with open(os.path.join(common.ROOT, "chipbench", "configs", "trinity-mini-serve-1chip.json")) as f:
        hf = json.load(f)
    s = arch.sizes(hf)
    assert (s["L_swa"], s["L_walk_full"], s["W"], s["ng"], s["hs"], s["nh"], s["held"], s["E"], s["k"]) == (
        24, 8, 2048, 4, 128, 32, 16, 128, 8)
    whole = arch.param_count(hf, held=128, vocab=200192)
    assert abs(whole - 26.12e9) < 0.01e9
    active = whole - 30 * (128 - 8) * arch.expert_params(hf) - 200192 * 2048     # 8 routed experts a token, the embedding a row
    assert abs(active - 3.07e9) < 0.45e9        # the head counted in full: "A3B"
    assert abs(arch.param_count(hf) * 2 - 8.53e9) < 0.01e9                     # this chip's share, bfloat16
    assert arch.ring_bytes_per_slot(hf) == 24 * 129 * 16 * 2048 and arch.kv_bytes_per_token(hf) == 16384
    assert hf["reduced"] == ["num_experts", "vocab_size"] and hf["published_num_experts"] == 128


# --------------------------------------------------------------------------
# program = reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [W - 1, W, W + 1, W + BS + 1, 77], ids=lambda n: f"tokens{n}")
def test_every_kind_agrees_with_the_reference_around_the_windows_edge(n):
    """The full forward's logits on 100 tokens, and what each layer holds after ``n``
    (one short of the window, the window, one past it, a block of slack past it,
    several windows): layer by layer and kind by kind."""
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


def test_a_global_layer_is_untouched_by_the_positions_rotation():
    """A global layer's keys are the same at any position (what its cache holds of a
    token does not depend on where the token stands); a window layer's are not."""
    cfg, params = model()
    toks = prompt(40, 11)
    _, cache = dense_forward(cfg, params, toks)
    cos, sin = llama.build_rope_cache(cfg, 128)
    shifted = compiled_forward(cfg, decode=True)(params, jnp.asarray(toks)[None], jnp.int32(7),
                                                 G.init_cache(cfg, 1, 128, jnp.float32), cos, sin)[1]
    # layer 0 is a window layer whose input is the embedding (the same at both offsets): rotated by position
    assert rel(shifted["k"][0, 0][:, 7:47], cache["k"][0, 0][:, :40]) > 0.3
    np.testing.assert_allclose(shifted["v"][0, 0][:, 7:47], cache["v"][0, 0][:, :40], atol=1e-6)
    # a model of global layers alone: its first layer's keys (the embedding's) are the same at any offset
    hf = {**HF, "layer_types": ["full_attention"] * 4}
    gcfg = llama.Config(**arch.program_config(hf))
    a, ca = compiled_forward(gcfg)(params, jnp.asarray(toks)[None], G.init_cache(gcfg, 1, 128, jnp.float32), cos, sin)
    _, cb = compiled_forward(gcfg, decode=True)(params, jnp.asarray(toks)[None], jnp.int32(9),
                                                G.init_cache(gcfg, 1, 128, jnp.float32), cos, sin)
    np.testing.assert_allclose(cb["k"][0, 0][:, 9:49], ca["k"][0, 0][:, :40], atol=1e-6)
    want = ref_logits(params, toks, np.arange(40), hf)
    assert float(jnp.abs(a[0] - want).max()) < 1e-4 * float(jnp.abs(want).max())
    # and the reference that rotated them would not be this model's
    assert float(jnp.abs(dense_forward(dataclasses.replace(cfg, rope_kinds=None), params, toks)[0][0, -1]
                         - ref_logits(params, toks, [39])[0]).max()) > 1e-2


LEFT_OUT = ["gate", "norm_1", "norm_1_post", "norm_2", "norm_2_post", "mup", "qk_norm", "window", "shared_expert"]


@pytest.mark.parametrize("piece", LEFT_OUT)
def test_each_piece_of_the_block_fails_the_comparison_when_left_out(piece, monkeypatch):
    cfg, params = model()
    toks = prompt(60, 13)
    want = ref_logits(params, toks, np.arange(60))
    spread = float(jnp.abs(want).max())
    assert float(jnp.abs(dense_forward(cfg, params, toks)[0][0] - want).max()) < 1e-4 * spread
    if piece == "gate":
        monkeypatch.setattr(G, "gated_out", lambda y, gate: y)
    elif piece.startswith("norm_"):
        mine = {id(bp[piece]) for bp in params["blocks"]}
        whole = G._norm
        monkeypatch.setattr(G, "_norm", lambda x, w, c, b=None: x if id(w) in mine else whole(x, w, c, b))
    elif piece == "mup":
        cfg = dataclasses.replace(cfg, scale_embedding=False)
    elif piece == "qk_norm":
        whole = G._rms
        heads = {id(bp["attn"][n]) for bp in params["blocks"] for n in ("q_norm", "k_norm")}
        monkeypatch.setattr(G, "_rms", lambda x, w, eps: x if id(w) in heads else whole(x, w, eps))
    elif piece == "window":
        cfg = dataclasses.replace(cfg, layer_window=1 << 20)
    elif piece == "shared_expert":
        params = {**params, "blocks": [
            {**bp, "mlp": {**bp["mlp"], "shared": jax.tree_util.tree_map(jnp.zeros_like, bp["mlp"]["shared"])}}
            if "shared" in bp["mlp"] else bp for bp in params["blocks"]]}
    got = dense_forward(cfg, params, toks, anew=True)[0][0]        # traced anew: the planted fault is in it
    assert float(jnp.abs(got - want).max()) > 100 * 1e-4 * spread, piece


def test_the_shares_of_eight_chips_add_up_to_the_uncut_layer():
    """Eight shares of two experts each of sixteen: every chip routes over all
    sixteen, computes its own experts' part and the shared expert; the parts, with
    the shared expert counted once, are the uncut reference's layer."""
    base = {**HF, "num_hidden_layers": 3, "num_experts": 16, "published_num_experts": 16, "num_experts_per_tok": 4}
    with jax.default_matmul_precision("highest"):
        whole = arch.make_params(base, common.seed_words(9), dtype=jnp.float32)["blocks"][2]["mlp"]
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)
        s = arch.sizes(base)
        shared = arch.shared_expert(x[0], whole)
        want = arch.routed_share(x[0], whole, s) + shared
        total, hit = jnp.zeros_like(want), 0
        for chip in range(8):
            hf = {**base, "num_experts": 2, "expert_first": 2 * chip}
            cfg = llama.Config(**arch.program_config(hf))
            mp = arch.make_params(hf, common.seed_words(9), dtype=jnp.float32)["blocks"][2]["mlp"]
            np.testing.assert_array_equal(mp["gate"], whole["gate"])
            np.testing.assert_array_equal(mp["fc_1"], whole["fc_1"][2 * chip * 64:(2 * chip + 2) * 64])
            rows: list = []
            part = G.moe_share_mlp(mp, x, cfg, moe_rows=rows)[0] - shared
            assert rel(part, arch.routed_share(x[0], mp, arch.sizes(hf))) < 1e-4
            total, hit = total + part, hit + int(rows[0][0])
        assert hit == 48 * 4                                  # every routed row landed on exactly one chip
        assert rel(total + shared, want) < 1e-5
        assert rel(total, want) > 0.05                        # the shared expert left out is seen
