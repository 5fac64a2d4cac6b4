"""A looped model (``llama.Config.n_pass`` > 1: the stack of blocks run several times
over one set of weights, a K/V slab a layer a pass, the last norm closing every
pass, the exit gate) in ``models.generate``: the dense cache's forward against the
plain reference (``chipbench/models/looped_dense_decoder.py``: two Python loops) on
seeded weights, every slab of the cache, the exit rule under a planted gate, the
controls that must fail, and the size of the traced program, which does not grow
with the pass count.  The tiny model is ``tests/_looped_tiny.py``'s: 3 blocks run 2
times, so that ``t * L + l`` and ``l * passes + t`` differ."""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu.models import generate as G
from thunder_tpu.models import llama
from thunder_tpu.serving.kv_pool import PagedKVPool

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _looped_tiny import L, PASSES, arch, dense_forward, gate_bias, model, prompt, ref_caches, ref_logits, rel  # noqa: E402
from conftest import prim_names  # noqa: E402

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 6e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_dense_forward_gives_the_references_logits_at_every_position(dtype):
    cfg, params, hf = model(dtype)
    toks = prompt(40)
    with jax.default_matmul_precision("highest"):
        logits, cache = dense_forward(cfg, params, toks, dtype=dtype)
    assert logits.shape == (1, 40, 256) and cache["k"].shape[0] == cfg.kv_slabs == L * PASSES
    assert rel(logits[0], ref_logits(hf, params, toks, np.arange(40))) < TOL[dtype]


@pytest.mark.parametrize("slab", [(t, l) for t in range(PASSES) for l in range(L)], ids=lambda s: f"pass{s[0]}_layer{s[1]}")
def test_the_cache_keeps_a_slab_a_layer_a_pass_where_the_one_map_says(slab):
    """Slab ``kv_slab(t, l)`` of the dense cache holds what the reference's layer ``l``
    projects in pass ``t``; the slab of ``s = l`` (one a layer for all passes) or of
    ``l * passes + t`` (a layer's passes side by side) holds something else."""
    cfg, params, hf = model()
    toks, (t, l) = prompt(40), slab
    with jax.default_matmul_precision("highest"):
        _, cache = dense_forward(cfg, params, toks)
    (_, (k, v)), = ref_caches(hf, params, toks, 40, [slab])
    at = cfg.kv_slab(t, l)
    assert at == t * L + l == arch.slab(hf, t, l)
    assert rel(cache["k"][at, 0, :, :40], k) < 1e-5 and rel(cache["v"][at, 0, :, :40], v) < 1e-5
    for wrong in {l, l * PASSES + t} - {at}:
        assert rel(cache["k"][wrong, 0, :, :40], k) > 0.1


def test_a_decode_step_reads_its_passes_own_slabs():
    """A prompt, then one token at a traced position against the cache: the token's
    logits are the reference's at that position, and a cache whose passes are
    swapped (every layer reads the other pass's slab) gives others."""
    cfg, params, hf = model()
    toks = prompt(33)
    cos, sin = llama.build_rope_cache(cfg, 128)
    from conftest import compiled_forward

    with jax.default_matmul_precision("highest"):
        _, cache = dense_forward(cfg, params, toks[:32])
        step = compiled_forward(cfg, decode=True)
        logits, new = step(params, jnp.asarray(toks[None, 32:]), jnp.int32(32), cache, cos, sin)
        want = ref_logits(hf, params, toks, np.asarray([32]))
        assert rel(logits[0], want) < 2e-5
        swapped = {n: jnp.concatenate([a[L:], a[:L]]) for n, a in cache.items()}
        assert rel(step(params, jnp.asarray(toks[None, 32:]), jnp.int32(32), swapped, cos, sin)[0][0], want) > 1e-3
    assert new["k"].shape == cache["k"].shape


@pytest.mark.parametrize("threshold,bias,picks", [(0.5, 2.1972246, 0), (1.0, 2.1972246, PASSES - 1), (0.5, -4.0, PASSES - 1)],
                         ids=["lambda_0.9_under_0.5_exits_at_once", "threshold_1_reads_the_last_pass", "a_shut_gate_reads_the_last_pass"])
def test_the_exit_rule_picks_the_pass_the_head_reads(threshold, bias, picks):
    """A planted gate bias makes ``lambda`` 0.9 at every pass: under a threshold of 0.5
    the first pass's cumulative exit probability reaches it and the head reads that
    pass's closed state, as the reference's does; at the published threshold of 1 no
    pass but the last reaches it."""
    cfg, params, hf = model(early_exit_threshold=threshold)
    params = gate_bias(params, bias)
    toks, exits = prompt(24), []
    cos, sin = llama.build_rope_cache(cfg, 128)
    with jax.default_matmul_precision("highest"):
        logits, _ = G.forward_with_cache(params, jnp.asarray(toks)[None], 0, G.init_cache(cfg, 1, 128, jnp.float32),
                                         cos, sin, cfg, exits=exits)
        chosen, p = exits[0]
        e_ref, p_ref = arch.ref_exit(hf, params, jnp.asarray(np.pad(toks, (0, 104))))
    assert np.all(np.asarray(chosen) == picks) and np.all(np.asarray(e_ref)[:24] == picks)
    np.testing.assert_allclose(np.asarray(p)[:, 0], np.asarray(p_ref)[:, :24], atol=1e-6)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, atol=1e-6)
    assert rel(logits[0], ref_logits(hf, params, toks, np.arange(24))) < 2e-5


def test_tokens_exit_at_passes_of_their_own():
    """The gate's weight at work (scaled up so that tokens part): some tokens exit
    early and some late, each where the reference's does, and the logits follow."""
    cfg, params, hf = model(early_exit_threshold=0.5)
    params = gate_bias(params, 0.0, w_scale=40.0)
    toks, exits = prompt(48, seed=3), []
    cos, sin = llama.build_rope_cache(cfg, 128)
    with jax.default_matmul_precision("highest"):
        logits, _ = G.forward_with_cache(params, jnp.asarray(toks)[None], 0, G.init_cache(cfg, 1, 128, jnp.float32),
                                         cos, sin, cfg, exits=exits)
        e_ref, _ = arch.ref_exit(hf, params, jnp.asarray(np.pad(toks, (0, 80))))
    chosen = np.asarray(exits[0][0])[0]
    assert set(chosen.tolist()) == {0, 1}, "the planted gate should part the tokens"
    np.testing.assert_array_equal(chosen, np.asarray(e_ref)[:48])
    assert rel(logits[0], ref_logits(hf, params, toks, np.arange(48))) < 2e-5


@pytest.mark.parametrize("control", ["norm_once_after_the_last_pass", "one_pass_fewer", "one_slab_a_layer"])
def test_a_planted_fault_in_the_loop_fails_the_reference(control, monkeypatch):
    """What the benchmark's controls plant (``chipbench/drivers/serve_looped.py``): the last
    norm outside the loop, a pass fewer, every pass on its layer's one slab.  The first
    two move a prompt's logits; the third moves what a decode step reads."""
    cfg, params, hf = model()
    toks = prompt(33)
    cos, sin = llama.build_rope_cache(cfg, 128)
    if control == "norm_once_after_the_last_pass":
        close = G.close_pass
        monkeypatch.setattr(G, "close_pass", lambda p, u, c, t: jnp.where(t == c.n_pass - 1, close(p, u, c, t), u))
    elif control == "one_pass_fewer":
        cfg = dataclasses.replace(cfg, n_pass=PASSES - 1)
    else:
        monkeypatch.setattr(llama.Config, "kv_slab", lambda self, t, l: l + 0 * t)
    with jax.default_matmul_precision("highest"):
        cache = G.init_cache(cfg, 1, 128, jnp.float32)
        logits, cache = G.forward_with_cache(params, jnp.asarray(toks[None, :32]), 0, cache, cos, sin, cfg)
        if control == "one_slab_a_layer":       # a prompt attends its own keys: the cache shows in the next token
            logits, _ = G.forward_with_cache(params, jnp.asarray(toks[None, 32:]), jnp.int32(32), cache, cos, sin, cfg)
            want = ref_logits(hf, params, toks, np.asarray([32]))
        else:
            want = ref_logits(hf, params, toks[:32], np.arange(32))
    assert rel(logits[0], want) > 1e-2


def _traced(cfg, params):
    """The jaxprs of a decode step through the paged arenas (4 rows of 8 blocks) and of a 16-token prompt through the dense cache."""
    from thunder_tpu.serving.paged_attention import forward_paged

    pool = PagedKVPool(cfg, num_blocks=8, block_size=8, dtype=jnp.float32)
    cos, sin = llama.build_rope_cache(cfg, 64)
    shape = lambda s, d=jnp.int32: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    arenas = {n: shape(a.shape, a.dtype) for n, a in pool.arenas.items()}
    decode = jax.make_jaxpr(lambda p, i, pos, a, t: forward_paged(p, i, pos, a, t, cos, sin, cfg, cdtype=jnp.float32))(
        params, shape((4, 1)), shape((4,)), arenas, shape((4, 8)))
    cache = jax.eval_shape(lambda: G.init_cache(cfg, 1, 32, jnp.float32))
    prefill = jax.make_jaxpr(lambda p, i, c: G.forward_with_cache(p, i, 0, c, cos, sin, cfg))(params, shape((1, 16)), cache)
    return decode, prefill


def _decode_equations(n_pass: int, n_layer: int = L) -> int:
    cfg, _, hf = model(total_ut_steps=n_pass, num_hidden_layers=n_layer)
    params = jax.eval_shape(lambda w: arch.make_params(hf, w, dtype=jnp.float32), jnp.zeros((2,), jnp.uint32))
    if n_pass == 1:
        cfg = dataclasses.replace(cfg, n_pass=1)
    return len(prim_names(_traced(cfg, params)[0].jaxpr))


def test_the_traced_decode_program_does_not_grow_with_the_pass_count():
    """The passes are one ``lax.scan`` whose body is the blocks: four passes trace to
    the equations of two, and to those of one pass plus less than one block's (the
    loop, the gate, the exit rule, the slabs' reshapes, and a layer's few for the
    slab's arithmetic and its dynamic index where the one-pass program slices)."""
    two, four, one = _decode_equations(2), _decode_equations(4), _decode_equations(1)
    a_block = (_decode_equations(1, L + 2) - one) // 2
    assert two == four and 0 < four - one < a_block, (one, two, four, a_block)


def test_a_one_pass_models_programs_hold_no_loop_and_index_no_slab_dynamically():
    """Every other model is the one-pass case of the same code: its slab is its layer, a
    Python integer, so its decode and prefill programs hold no ``scan`` and no dynamic
    index into the cache's layer axis (they lower to what they lowered to before;
    ``tools/lowered_same.py`` compares the texts)."""
    cfg = llama.Config.from_name("tiny-mistral-debug")
    params = jax.eval_shape(lambda: llama.init_params(cfg, dtype=jnp.float32))
    assert cfg.kv_slabs == cfg.paged_kv_slabs == cfg.n_layer and [cfg.kv_slab(0, l) for l in range(2)] == [0, 1]
    decode, prefill = _traced(cfg, params)
    for jaxpr in (decode, prefill):
        names = [n for n, _ in prim_names(jaxpr.jaxpr)]
        assert "scan" not in names and "while" not in names


def test_the_published_widths_keep_a_mebibyte_and_a_half_a_token():
    """``Ouro-2.6B`` in the registry: 192 slabs of 2 x 16 heads x 128 in bfloat16 are
    1,572,864 B a token, from shapes alone (a pool of one block and the sink)."""
    cfg = llama.Config.from_name("Ouro-2.6B")
    assert (cfg.n_layer, cfg.n_pass, cfg.kv_slabs, cfg.exit_threshold) == (48, 4, 192, 1.0)
    assert [cfg.kv_slab(t, l) for t, l in ((0, 0), (1, 0), (3, 47))] == [0, 48, 191]
    assert G.kv_block_shape(cfg, 16) == (192, 16, 16, 128) and G.cache_shape(cfg, 1, 384) == (192, 1, 16, 384, 128)
    snap = PagedKVPool(cfg, num_blocks=2, block_size=16, dtype=jnp.bfloat16).kind_snapshot()
    assert snap["token_bytes_counted"] == snap["token_bytes_laid_out"] == 1_572_864 and snap["slabs"] == 192
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(jax.eval_shape(lambda: llama.init_params(cfg))))
    assert n == 48 * 51_388_416 + 2 * 49_152 * 2048 + 2048 + 2049 == 2_667_974_657


def test_the_trainer_names_the_loop_it_does_not_take():
    cfg, params, _ = model()
    assert "n_pass > 1" in llama.serving_only(cfg)
    with pytest.raises(NotImplementedError, match="n_pass > 1"):
        llama.block_forward(params["blocks"][0], jnp.zeros((1, 8, 64)), None, None, cfg)
    with pytest.raises(AssertionError, match="n_pass > 1"):
        dataclasses.replace(cfg, sliding_window=32)
