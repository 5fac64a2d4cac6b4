"""The window/global decoder of ``tests/test_window_global_serving.py`` through
``tt.serve``: served tokens against solo ``generate()`` and the reference's logits,
the allocator's ring and whole-length reservation a request, what is held against
the reference around the window's edge, the refusals by name, the scopes and the
counters.  The tiny model and the tolerances are ``tests/_window_global_tiny.py``'s
and that file's."""
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
from thunder_tpu.models import llama
from thunder_tpu.serving import kv_pool
from thunder_tpu.serving.engine import hybrid_unsupported

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _window_global_tiny import (  # noqa: E402
    BS, ENGINE, HF, W, arch, dense_forward, model, prompt, ref_logits, rel)


def served(eng, prompts, new):
    handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).tokens)[len(p):] for p, h in zip(prompts, handles)]


@pytest.mark.parametrize("kernels", [False, True], ids=["xla_form", "interpreted_kernels"])
def test_served_tokens_are_solo_generates_and_the_references_best(kernels, monkeypatch):
    """Three requests through the pools together: whole-prompt prefills (one shorter
    than the window, one past it, one four windows deep), then decode through the
    rings (a sequence of 88 tokens overwrites its ring's three blocks three times)
    and the global layers' blocks; bit for bit solo ``generate()``, and at every
    position the reference's best logit (the comparison is of logits: a served token
    is the engine's argmax, held to the reference's own row)."""
    if kernels:
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    st = eng.stats()["attn"]
    assert st["path"] == ("walk" if kernels else "xla") and st["lane_pack"] == 1
    prompts, new = [prompt(40, 6), prompt(11, 7), prompt(64, 8)], [30, 40, 24]
    got = served(eng, prompts, new)
    stats = eng.stats()
    assert stats["compile_counts"]["prefill_fresh"] >= 1 and stats["compile_counts"]["decode_paged"] >= 1
    assert stats["attn"]["fallback_steps"] == (0 if kernels else stats["decode_steps"])
    for p, n, toks in zip(prompts, new, got):
        solo = np.asarray(G.generate(params, p[None], cfg, n, T_max=128))[0, len(p):]
        np.testing.assert_array_equal(toks, solo)
        seq = np.concatenate([p, toks])
        lg = ref_logits(params, seq, np.arange(len(p) - 1, len(seq) - 1))
        short = np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0])
        assert float(short.max()) < 1e-3
    # the keys attended, by kind: request r's k-th decode step (k >= 0) feeds position len(p) + k
    seen = [len(p) + k + 1 for p, n in zip(prompts, new) for k in range(n - 1)]
    att = stats["attn"]["attended_tokens"]
    assert att["full_attention"] == sum(seen) and att["sliding_attention"] == sum(min(s, W) for s in seen)
    assert att["steps"] == stats["decode_steps"]
    moe = stats["moe"]
    assert moe["experts_held"] == 8 and 0.0 < moe["experts_hit_share"] <= 1.0 and moe["row_sums"][0] == stats["decode_steps"]
    eng.shutdown(drain=False)


def test_the_allocator_keeps_a_ring_and_a_whole_length_a_request_and_frees_both():
    """The window kind holds a ring of ``ceil(W / bs) + 1`` blocks a request whatever
    its length, leased with a slot of a pool that holds rings alone; the global kind
    its whole length, reserved at admission; both go back at finish; what is held
    is the reference's at lengths around the window's edge, and not another request's."""
    cfg, params = model()
    eng = tt.serve(None, params, cfg, **ENGINE)
    pool, state = eng.pool, eng.pool.state
    assert state.ring_blocks == 3 == -(-W // BS) + 1 and sorted(state.shapes) == ["k_ring", "v_ring"]
    assert state.shapes["k_ring"] == ((4 + 1) * 3, 3, 2, BS, 32) and pool.k_arena.shape == (64, 1, 2, BS, 32)
    free0 = pool.num_free
    a = eng.submit(prompt(10, 9), max_new_tokens=60)
    b = eng.submit(prompt(90, 10), max_new_tokens=20)
    helds = {}
    for target in (W - 1, W, W + 1, W + BS + 1):            # a's length as it grows across the window's edge
        while a._req.pos < target:
            eng.step()
        for h in (a, b):
            held, r = jax.device_get(eng.held(h)), h._req
            n = held["tokens"]
            seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])[:n]
            with jax.default_matmul_precision("highest"):
                want = arch.ref_caches(HF, params, jnp.asarray(np.pad(seq, (0, 128 - n))), n)
            helds[h] = (held, want)
            assert held["k"].shape == (1, 2, n, 32) and held["k_ring"].shape == (3, 2, min(n, W), 32)
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
    slot_bytes = 2 * 3 * 3 * 2 * BS * 32 * 4
    assert occ["ring_blocks"] == 3 and occ["ring_fill_frac"] == 0.5 and occ["ring_arena_bytes"] == 5 * slot_bytes
    assert occ["ring_slot_bytes"] == slot_bytes and occ["ring_leased_bytes"] == 2 * slot_bytes
    assert occ["arenas"] == ["k_ring", "v_ring"] and occ["dtype"] == "float32" and occ["layers"] == 0
    tabs = np.asarray(kv_pool.ring_tables(jnp.asarray([ra.state_slot, rb.state_slot]), 3, 14))
    assert set(tabs[0]) == {3 * ra.state_slot + i for i in range(3)} and not set(tabs[0]) & set(tabs[1])
    (held_a, _), (_, want_b) = helds[a], helds[b]
    other = {kind: ref for kind, ref in reversed(want_b)}
    assert rel(held_a["k_ring"][0], other["sliding_attention"][0][:, -W:]) > 0.5
    assert rel(held_a["k"][0], other["full_attention"][0][:, :held_a["tokens"]]) > 0.5
    while not (a.done() and b.done()):
        eng.step()
    assert pool.num_free == free0 and state.leased == 0
    eng.shutdown(drain=False)


REFUSALS = [(dict(prefix_sharing=True), "prefix_sharing"), (dict(sessions=True), "sessions"),
            (dict(speculative=object()), "speculative"), (dict(mesh=object()), "mesh"),
            (dict(kv_dtype="fp8"), "kv_dtype"),
            (dict(prefill_chunk=32), "prefill_chunk"), (dict(priorities=True), "priorities"),
            (dict(fault_plan=object()), "fault_plan"), (dict(lora=object()), "lora")]


@pytest.mark.parametrize("option,word", REFUSALS, ids=[w for _, w in REFUSALS])
def test_what_the_ring_cannot_do_yet_is_refused_by_name(option, word):
    cfg, _ = model()
    why = hybrid_unsupported(cfg, **option)
    assert why.startswith(word) and "unsupported" in why
    if word in ("prefix_sharing", "sessions", "speculative", "mesh", "decode_steps", "kv_dtype", "prefill_chunk"):
        assert "ring" in why                                   # by its own mechanism, not a recurrent state's
    assert not any(w in why for w in ("differential", "scan", "state", "untested"))   # nor SambaY's
    if word == "lora":
        assert "gate" in why                                   # the adapter's wq target has no room for the gate's rows


def test_the_engine_refuses_by_name_and_builds_two_program_kinds():
    cfg, params = model()
    assert hybrid_unsupported(cfg) is None
    with pytest.raises(NotImplementedError, match="sliding_attention layers.*kv_dtype"):
        tt.serve(None, params, cfg, kv_dtype="int8", **ENGINE)
    with pytest.raises(NotImplementedError, match="sliding_attention layers.*prefill_chunk"):
        tt.serve(None, params, cfg, prefill_chunk=32, **ENGINE)
    with pytest.raises(ValueError, match="state_slots"):
        kv_pool.PagedKVPool(cfg, 16, BS, jnp.float32)
    eng = tt.serve(None, params, cfg, **ENGINE)
    assert not eng.prefix_sharing
    with pytest.raises(NotImplementedError, match="per-kind caches"):
        eng._program("prefill_chunk", 32, 8)
    eng.shutdown(drain=False)
    # the trainer names what it lacks
    cos, sin = llama.build_rope_cache(cfg, 16)
    with pytest.raises(NotImplementedError, match="sandwich_norm"):
        llama.block_forward(params["blocks"][0], jnp.zeros((1, 16, 64)), cos, sin, cfg, "sliding_attention")


def test_a_gated_model_without_rings_is_served_through_the_ordinary_table():
    """``attn_output_gate`` alone (no window kind): the paged table, any program
    kind, bit for bit solo ``generate()``; the gate is seen."""
    cfg = llama.Config(name="gated", n_layer=2, n_head=4, n_embd=64, n_query_groups=2, vocab_size=256,
                       intermediate_size=96, attn_output_gate=True, qk_norm=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    assert params["blocks"][0]["attn"]["wq"].shape == (2 * 64, 64) and not cfg.keeps_slot
    eng = tt.serve(None, params, cfg, block_size=BS, num_blocks=32, max_batch=2, cache_dtype=jnp.float32)
    p = prompt(20, 3)
    got = served(eng, [p], [12])[0]
    np.testing.assert_array_equal(got, np.asarray(G.generate(params, p[None], cfg, 12, T_max=64))[0, 20:])
    eng.shutdown(drain=False)
    lg = dense_forward(cfg, params, p, T_max=64)[0]
    flat = dataclasses.replace(cfg, attn_output_gate=False)
    ungated = jax.tree_util.tree_map(lambda a: a, params)
    for bp in ungated["blocks"]:
        bp["attn"]["wq"] = bp["attn"]["wq"].reshape(4, 2, 16, 64)[:, 0].reshape(64, 64)
    assert float(jnp.abs(dense_forward(flat, ungated, p, T_max=64)[0] - lg).max()) > 1e-2


def test_the_blocks_scopes_are_the_ones_the_readers_split_by():
    cfg, params = model()
    cos, sin = llama.build_rope_cache(cfg, 64)
    cache = G.init_cache(cfg, 1, 64, jnp.float32)
    fwd = jax.jit(lambda p, t, c: G.forward_with_cache(p, t, 0, c, cos, sin, cfg))
    text = fwd.lower(params, jnp.asarray(prompt(24, 1))[None], cache).as_text(debug_info=True)
    for name in ("norm", "qkv", "rope", "swa", "gate", "out", "post_norm"):
        assert f"blk0/mixer/{name}/" in text, name
    assert "blk3/mixer/attn/" in text and "blk3/mixer/rope/" not in text and "blk3/mixer/swa/" not in text
    for name in ("norm", "router", "experts", "shared", "post_norm", "residual"):
        assert f"blk2/mlp/{name}/" in text, name
    assert "blk0/mlp/up/" in text and "blk0/mlp/router/" not in text        # a leading dense layer
