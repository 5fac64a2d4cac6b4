"""The pre-routed decoder of ``tests/test_prerouted_serving.py`` through ``tt.serve``:
served tokens against solo ``generate()`` and the reference's logits with requests
inside and past the window in one batch, 7 query heads on the one KV head through
both forms of the decode walk, what is held (the global layer first, then the
rings) against the reference around the window's edge, the planted router input
failing the served tokens, and the counters.  The tiny model and the tolerances are
``tests/_prerouted_tiny.py``'s and that file's."""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as G

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _prerouted_tiny import BS, ENGINE, HF, W, arch, model, prompt, ref_logits, rel  # noqa: E402


def served(eng, prompts, new):
    handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).tokens)[len(p):] for p, h in zip(prompts, handles)]


def shortfall(params, p, toks):
    """How far below the reference's best logit the served token's lies, at every position."""
    seq = np.concatenate([p, toks])
    lg = ref_logits(params, seq, np.arange(len(p) - 1, len(seq) - 1))
    return np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0])


@pytest.mark.parametrize("kernels", [False, True], ids=["xla_form", "interpreted_kernels"])
def test_served_tokens_are_solo_generates_and_the_references_best(kernels, monkeypatch):
    """Three requests through the pools together: one that never leaves the window
    (5 + 8 of 16), one that crosses it while it decodes (11 + 40: its ring wraps
    beside one that has not), one four windows deep from its first token; whole-prompt
    prefills, then decode through the rings and the global layer's blocks; bit for
    bit solo ``generate()``, and at every position the reference's best logit."""
    if kernels:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    st = eng.stats()["attn"]
    assert st["path"] == ("walk" if kernels else "xla") and st["lane_pack"] == 1
    prompts, new = [prompt(5, 6), prompt(11, 7), prompt(64, 8)], [8, 40, 24]
    got = served(eng, prompts, new)
    stats = eng.stats()
    assert stats["compile_counts"]["prefill_fresh"] >= 1 and stats["compile_counts"]["decode_paged"] >= 1
    assert stats["attn"]["fallback_steps"] == (0 if kernels else stats["decode_steps"])
    for p, n, toks in zip(prompts, new, got):
        solo = np.asarray(G.generate(params, p[None], cfg, n, T_max=128))[0, len(p):]
        np.testing.assert_array_equal(toks, solo)
        assert float(shortfall(params, p, toks).max()) < 1e-3
    # the keys attended, by kind: request r's k-th decode step (k >= 0) feeds position len(p) + k
    seen = [len(p) + k + 1 for p, n in zip(prompts, new) for k in range(n - 1)]
    att = stats["attn"]["attended_tokens"]
    assert att["full_attention"] == sum(seen) and att["sliding_attention"] == sum(min(s, W) for s in seen)
    assert att["sliding_attention"] < att["full_attention"] and min(seen) < W < max(seen)
    assert att["steps"] == stats["decode_steps"]
    moe = stats["moe"]
    assert (moe["router"], moe["experts_held"], moe["experts_published"]) == ("softmax", 8, 8)
    assert 0.0 < moe["experts_hit_share"] <= 1.0 and moe["row_sums"][0] == stats["decode_steps"]
    eng.shutdown(drain=False)


def test_a_router_fed_the_experts_input_fails_the_served_tokens():
    """The check's planted control at this size: the engine serves the model with
    ``moe_route_block_input`` off, and the reference ranks its tokens clearly lower."""
    cfg, params = model()
    p = prompt(40, 21)
    eng = tt.serve(None, params, cfg, **ENGINE)
    sound = shortfall(params, p, served(eng, [p], [24])[0])
    eng.shutdown(drain=False)
    eng = tt.serve(None, params, dataclasses.replace(cfg, moe_route_block_input=False), **ENGINE)
    planted = shortfall(params, p, served(eng, [p], [24])[0])
    eng.shutdown(drain=False)
    assert float(sound.max()) < 1e-3 and float(planted.mean()) > 0.05, (sound.max(), planted.mean())


def test_the_allocator_keeps_a_ring_and_a_whole_length_a_request_with_the_global_layer_first():
    """Layer 0 is the global kind: its blocks hold the request's whole length, reserved
    at admission; layers 1-3 keep a ring of ``ceil(W / bs) + 1`` blocks a request
    whatever its length.  What is held is the reference's at lengths around the
    window's edge, for a request inside its window beside one far past it."""
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    pool, state = eng.pool, eng.pool.state
    assert state.ring_blocks == 3 == -(-W // BS) + 1 and sorted(state.shapes) == ["k_ring", "v_ring"]
    assert state.shapes["k_ring"] == ((4 + 1) * 3, 3, 1, BS, 32) and pool.k_arena.shape == (64, 1, 1, BS, 32)
    free0 = pool.num_free
    a = eng.submit(prompt(10, 9), max_new_tokens=60)
    b = eng.submit(prompt(90, 10), max_new_tokens=20)
    for target in (W - 1, W, W + 1, W + BS + 1):            # a's length as it grows across the window's edge
        while a._req.pos < target:
            eng.step()
        for h in (a, b):
            held, r = jax.device_get(eng.held(h)), h._req
            n = held["tokens"]
            seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])[:n]
            with jax.default_matmul_precision("highest"):
                want = arch.ref_caches(HF, params, jnp.asarray(np.pad(seq, (0, 128 - n))), n)
            assert held["k"].shape == (1, 1, n, 32) and held["k_ring"].shape == (3, 1, min(n, W), 32)
            assert [kind for kind, _ in want] == ["full_attention"] + ["sliding_attention"] * 3
            seen = {"sliding_attention": 0, "full_attention": 0}
            for kind, ref in want:
                j = seen[kind]
                seen[kind] += 1
                names = ("k_ring", "v_ring") if kind == "sliding_attention" else ("k", "v")
                assert rel(held[names[0]][j], ref[0]) < 1e-4 and rel(held[names[1]][j], ref[1]) < 1e-4, (target, kind, j)
    ra, rb = a._req, b._req
    assert len(ra.block_table) == -(-70 // BS) and len(rb.block_table) == -(-110 // BS)         # the whole length
    assert free0 - pool.num_free == len(ra.block_table) + len(rb.block_table)
    assert state.leased == 2 and ra.state_slot != rb.state_slot
    occ = eng.stats()["pool_occupancy"]["state"]
    assert occ["ring_blocks"] == 3 and occ["ring_fill_frac"] == 0.5      # a ring whole for a request inside its window
    while not (a.done() and b.done()):
        eng.step()
    assert pool.num_free == free0 and state.leased == 0
    eng.shutdown(drain=False)
