"""The looped model of ``tests/test_looped_serving.py`` through ``tt.serve`` on the
normal path (the same engine, scheduler, pool, whole-prompt ``prefill_fresh``
programs and one ``decode_paged`` program as every dense decoder): served tokens
against solo ``generate()`` and the reference's logits, what ``engine.held`` keeps of
every slab against the reference, the exit rule's counts harvested with the tokens,
the pieces of a prompt and a shared prefix through the loop, the storage options, and
each engine option that is not carried through the loop refused by name."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _looped_tiny import BS, ENGINE, L, PASSES, arch, gate_bias, model, prompt, ref_caches, ref_logits, rel  # noqa: E402


def served(eng, prompts, new):
    handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).tokens)[len(p):] for p, h in zip(prompts, handles)]


def shortfall(hf, params, p, toks):
    """How far below the reference's best logit the served token's lies, at every position."""
    seq = np.concatenate([p, toks])
    lg = ref_logits(hf, params, seq, np.arange(len(p) - 1, len(seq) - 1))
    return np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0])


@pytest.mark.parametrize("kernels", [False, True], ids=["xla_form", "interpreted_kernels"])
def test_served_tokens_are_solo_generates_and_the_references_best(kernels, monkeypatch):
    """Three requests through the pool together, whole-prompt prefills and then decode
    through the paged arenas' 6 slabs, the slab a traced operand of the walk (or of its
    XLA form's dynamic slice): bit for bit solo ``generate()``, and at every position
    the reference's best logit; the exit rule's counts sum to the served tokens."""
    if kernels:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params, hf = model()
    with jax.default_matmul_precision("highest"):
        eng = tt.serve(None, params, cfg, **ENGINE)
        st = eng.stats()["attn"]
        assert st["path"] == ("walk" if kernels else "xla")
        prompts, new = [prompt(5, 6), prompt(21, 7), prompt(64, 8)], [8, 20, 12]
        got = served(eng, prompts, new)
        stats = eng.stats()
        built = {kind for kind, n in stats["compile_counts"].items() if n}
        assert built == {"prefill_fresh", "decode_paged"}
        for p, n, toks in zip(prompts, new, got):
            solo = np.asarray(G.generate(params, p[None], cfg, n, T_max=128))[0, len(p):]
            np.testing.assert_array_equal(toks, solo)
            assert float(np.max(shortfall(hf, params, p, toks))) == 0.0
    passes = stats["passes"]
    assert passes["steps"] == stats["decode_steps"] and passes["layer_passes"] == stats["decode_steps"] * PASSES * L
    assert passes["exit"] == [0, sum(new)] and sum(passes["exit"]) == stats["tokens_generated"]
    assert abs(sum(passes["exit_mass"]) - sum(new)) < 1e-3
    seen = stats["attn"]["attended_tokens"]
    assert seen["steps"] == stats["decode_steps"] and seen["slab_walks"] == seen["full_attention"] * PASSES * L
    assert seen["full_attention"] == sum(len(p) + k for p, n in zip(prompts, new) for k in range(1, n))
    snap = eng.pool.kind_snapshot()
    assert snap["slabs"] == PASSES * L and snap["token_bytes_counted"] == PASSES * L * 2 * 4 * 16 * 4


def test_a_head_of_128_walks_its_slabs_with_the_layer_a_traced_operand(monkeypatch):
    """At the published head size the decode program's attention is the walk, one body
    for every layer of every pass; the served tokens are the XLA form's."""
    prompts, new = [prompt(9, 1), prompt(30, 2)], [6, 6]
    got = {}
    for kernels in (False, True):
        with pytest.MonkeyPatch.context() as env:
            if kernels:
                env.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
            cfg, params, hf = model(head_dim=128)
            eng = tt.serve(None, params, cfg, **ENGINE)
            assert eng.stats()["attn"]["path"] == ("walk" if kernels else "xla")
            got[kernels] = served(eng, prompts, new)
            assert eng.stats()["attn"]["fallback_steps"] == (0 if kernels else eng.stats()["decode_steps"])
    for a, b in zip(got[False], got[True]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.05)], ids=["float32", "bfloat16"])
def test_what_the_engine_holds_of_every_slab_is_the_references(dtype, tol):
    """Two running requests, one past a block's edge: ``engine.held`` keeps, of slab
    ``(t, l)``, what the reference's layer ``l`` projects in pass ``t`` of the same tokens,
    for every ``t`` and ``l``; read under ``s = l`` or ``l * passes + t`` it does not."""
    cfg, params, hf = model(dtype)
    with jax.default_matmul_precision("highest"):
        eng = tt.serve(None, params, cfg, **{**ENGINE, "cache_dtype": dtype})
        prompts = [prompt(13, 3), prompt(40, 4)]
        handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
        while min(len(h.tokens_so_far()) for h in handles) < 6:
            eng.step()
        for p, h in zip(prompts, handles):
            held = eng.held(h)
            n = held["tokens"]
            fed = np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)])[:n]
            assert held["k"].shape == (PASSES * L, 4, n, 16)
            for (t, l), (k, v) in ref_caches(hf, params, fed, n):
                at = arch.slab(hf, t, l)
                assert rel(held["k"][at], k) < tol and rel(held["v"][at], v) < tol, (t, l)
                for wrong in {l, l * PASSES + t} - {at}:
                    assert rel(held["k"][wrong], k) > 0.1, (t, l, wrong)
            some = eng.held(h, layers=[4, 0])       # the slabs asked for, in the order asked
            assert some["k"].shape == (2, 4, n, 16)
            np.testing.assert_array_equal(some["k"], held["k"][jnp.asarray([4, 0])])
            np.testing.assert_array_equal(some["v"], held["v"][jnp.asarray([4, 0])])
        eng.shutdown(drain=False)


def test_a_planted_gate_sends_the_served_tokens_out_early():
    """``lambda`` 0.9 at every pass under a threshold of 0.5: every served token, the
    prompts' first among them, leaves at pass 0, and is the reference's best under
    the same gate; the later passes ran all the same (their slabs are the reference's)."""
    cfg, params, hf = model(early_exit_threshold=0.5)
    params = gate_bias(params, 2.1972246)
    with jax.default_matmul_precision("highest"):
        eng = tt.serve(None, params, cfg, **ENGINE)
        p = prompt(18, 5)
        h = eng.submit(p, max_new_tokens=10)
        while len(h.tokens_so_far()) < 6:
            eng.step()
        held = eng.held(h)
        fed = np.concatenate([p, np.asarray(h.tokens_so_far(), np.int32)])[:held["tokens"]]
        (_, (k, _)), = ref_caches(hf, params, fed, held["tokens"], [(PASSES - 1, L - 1)])
        assert rel(held["k"][-1], k) < 1e-5
        toks = np.asarray(h.result().tokens)[18:]
        assert float(np.max(shortfall(hf, params, p, toks))) == 0.0
    stats = eng.stats()["passes"]
    assert stats["exit"] == [10, 0] and abs(stats["exit_mass"][0] - 9.0) < 1e-3


def test_pieces_of_a_prompt_and_a_shared_prefix_go_through_the_loop():
    """``prefill_chunk`` takes the gather chunk (a piece attends its gathered keys of its
    pass's slabs through the dense forward) and a shared prefix its owner's blocks of
    every slab: the served tokens are the whole prompt's."""
    cfg, params, hf = model()
    base, tail_a, tail_b = prompt(48, 11), prompt(9, 12), prompt(14, 13)
    prompts = [np.concatenate([base, tail_a]), np.concatenate([base, tail_b])]
    with jax.default_matmul_precision("highest"):
        whole = served(tt.serve(None, params, cfg, **ENGINE, prefix_sharing=False), prompts, [8, 8])
        eng = tt.serve(None, params, cfg, **ENGINE, prefill_chunk=32)
        assert eng.stats()["attn"]["chunk"] == "gather" and "looped" in eng.stats()["attn"]["chunk_why"]
        handles = [eng.submit(prompts[0], max_new_tokens=8)]
        while not handles[0].tokens_so_far():
            eng.step()
        handles.append(eng.submit(prompts[1], max_new_tokens=8))    # its first 48 tokens are the first's blocks
        got = [np.asarray(h.result().tokens)[len(p):] for p, h in zip(prompts, handles)]
        st = eng.stats()
    assert st["chunk_runs"] > 0 and st["prefix_hits"] >= 1
    for a, b in zip(whole, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("option", [{"kv_dtype": "fp8"}, {"kv_dtype": "int8"}, {"quantized": True}],
                         ids=["fp8_arena", "int8_arena", "int8_weight_products"])
def test_the_storage_options_are_carried_through_the_loop(option):
    """The quantised arenas (a scale row a slab, indexed dynamically) and int8 weight
    products serve a looped model: near the reference's tokens, not on them (they are
    the benchmark's storage controls)."""
    cfg, params, hf = model()
    with jax.default_matmul_precision("highest"):
        eng = tt.serve(None, params, cfg, **ENGINE, **option)
        p = prompt(30, 9)
        toks, = served(eng, [p], [12])
        gap = shortfall(hf, params, p, toks)
    assert len(toks) == 12 and float(np.mean(gap)) < 0.5
    assert sum(eng.stats()["passes"]["exit"]) == 12


def _lora(cfg):
    from thunder_tpu.serving.lora import AdapterRegistry

    return AdapterRegistry(cfg, rank=2, max_adapters=2)


def _spec(cfg, params):
    from thunder_tpu.serving.speculative import SpecConfig

    return SpecConfig(draft_params=params, draft_cfg=cfg, K=2)


def _mesh(cfg):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("tp",))


@pytest.mark.parametrize("name,option", [("speculative", lambda cfg, params: _spec(cfg, params)),
                                          ("lora", lambda cfg, params: _lora(cfg)),
                                          ("mesh", lambda cfg, params: _mesh(cfg)),
                                          ("sessions", lambda cfg, params: True)],
                         ids=["speculative", "lora", "mesh", "sessions"])
def test_an_option_not_carried_through_the_loop_is_refused_by_name(name, option):
    cfg, params, _ = model()
    with pytest.raises(NotImplementedError, match=rf"n_pass = {PASSES} times.*{name}= is unsupported"):
        tt.serve(None, params, cfg, **ENGINE, **{name: option(cfg, params)})


def test_a_verify_or_a_piece_through_the_paged_forward_is_refused_by_name():
    from thunder_tpu.serving.kv_pool import PagedKVPool
    from thunder_tpu.serving.paged_attention import forward_paged

    cfg, params, _ = model()
    pool = PagedKVPool(cfg, num_blocks=8, block_size=BS, dtype=jnp.float32)
    cos, sin = llama.build_rope_cache(cfg, 64)
    with pytest.raises(NotImplementedError, match="looped model's arenas are walked one token a row"):
        forward_paged(params, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32), pool.arenas,
                      jnp.zeros((1, 8), jnp.int32), cos, sin, cfg, cdtype=jnp.float32)
