"""The ``prefill_fresh`` program kind: a whole prompt at position 0 attends its
own keys and projects one row onto the vocabulary.

The engine knows on the host that a piece is a whole prompt at position 0 (no
shared block, no earlier piece) and gives it a program that reads no arena;
every other last piece (behind a shared prefix, after chunks, a session's
next turn) keeps the ``prefill`` kind.  Both stay bit-identical to solo
``generate()``, which takes the same path through ``forward_with_cache``.
Tiny float32 models; the prompts are shorter than their buckets.
"""
from __future__ import annotations

import dataclasses

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import thunder_tpu as tt  # noqa: E402
from thunder_tpu import distributed as dist  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as gen  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.observability.events import clear_events, events  # noqa: E402
from thunder_tpu.observability.metrics import registry  # noqa: E402
from thunder_tpu.serving import AdapterRegistry, TokenSetConstraint, make_lora_factors  # noqa: E402

from _hybrid_tiny import tiny_model  # noqa: E402

MICRO = dict(n_layer=2, n_head=4, n_embd=32, intermediate_size=64, vocab_size=48, block_size=64)
BUCKETS = dict(batch_buckets=(2,), block_buckets=(8,), prefill_buckets=(8, 16))


def _model(**over):
    cfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, **over})
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _engine(cfg, params, **kw):
    opts = dict(block_size=4, num_blocks=40, max_batch=2, cache_dtype=jnp.float32, **BUCKETS)
    return tt.serve(None, params, cfg, **{**opts, **kw})


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(1, cfg.vocab_size, (n,)).astype(np.int32)


def _solo(cfg, params, prompt, n, **kw):
    return np.asarray(gen.generate(params, prompt[None], cfg, n, cache_dtype=jnp.float32, **kw))[0, len(prompt):]


def _kinds(eng):
    """The prefill kinds this engine dispatched (its own programs, whoever compiled them)."""
    return {kind for kind, _, _ in eng._programs if kind.startswith("prefill")}


# --------------------------------------------------------------------------
# a whole prompt: the fresh kind, bit-identical to solo
# --------------------------------------------------------------------------

WHOLE = {
    "plain_mha": dict(cfg=dict(n_query_groups=4)),
    "grouped_kv": dict(cfg=dict(n_query_groups=2)),
    "window_wider_than_prompt": dict(cfg=dict(sliding_window=40)),
    "window_narrower_than_prompt": dict(cfg=dict(sliding_window=6)),
    "window_as_wide_as_the_bucket": dict(cfg=dict(sliding_window=16)),
    "hybrid": dict(hybrid=True),
    "int8_kv_pool": dict(engine=dict(kv_dtype="int8")),
    "constrained": dict(constrained=True),
    "lora_slot": dict(lora=True),
    "paged_decode_kernels": dict(interpret=True),
}


@pytest.mark.parametrize("case", WHOLE)
def test_a_whole_prompt_takes_prefill_fresh_and_is_bit_identical_to_solo(case, monkeypatch):
    spec = WHOLE[case]
    if spec.get("interpret"):
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    if spec.get("hybrid"):
        cfg, params = tiny_model()
        eng_kw = dict(block_size=16, prefill_buckets=(32, 64), block_buckets=(8,), batch_buckets=(2,))
        n_prompt, T_max = 23, 128
    else:
        cfg, params = _model(**spec.get("cfg", {}))
        eng_kw, n_prompt, T_max = dict(spec.get("engine", {})), 13, None     # 13 of a bucket of 16
    prompt = _prompt(cfg, n_prompt, seed=3)
    submit = {}
    if spec.get("constrained"):
        eng_kw["constraints"] = True
        submit["constraint"] = TokenSetConstraint(cfg.padded_vocab_size, range(cfg.padded_vocab_size))  # solo's draw
    if spec.get("lora"):
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2)
        reg.register("tenant", make_lora_factors(cfg, 2, jax.random.PRNGKey(4), std=0.5))
        eng_kw["lora"] = reg
    eng = _engine(cfg, params, **eng_kw)
    got = eng.submit(prompt, max_new_tokens=6, **submit).result()
    st = eng.stats()
    assert st["prefill_fresh_runs"] == st["prefill_runs"] == 1 and st["chunk_runs"] == 0
    assert _kinds(eng) == {"prefill_fresh"}
    if spec.get("interpret"):          # a fresh prefill is no attention fallback step
        assert st["attn"]["path"] == "walk" and st["attn"]["fallback_steps"] == 0 and st["decode_steps"] == 5
    solo_kw = {} if T_max is None else {"T_max": T_max}
    assert np.array_equal(np.asarray(got.new_tokens), _solo(cfg, params, prompt, 6, **solo_kw))

    if spec.get("constrained"):        # a set that binds: token 0 is drawn under it in the fresh program too
        allowed = [5, 9, 11]
        r = eng.submit(prompt, max_new_tokens=4, constraint=TokenSetConstraint(cfg.padded_vocab_size, allowed)).result()
        assert set(r.new_tokens) <= set(allowed) and eng.stats()["prefill_fresh_runs"] == 2
    if spec.get("lora"):               # an adapter's request: the fresh program against the general one
        whole = eng.submit(prompt, max_new_tokens=6, adapter_id="tenant").result()
        chunked = _engine(cfg, params, lora=reg, prefill_chunk=8)
        pieces = chunked.submit(prompt, max_new_tokens=6, adapter_id="tenant").result()
        assert chunked.stats()["prefill_fresh_runs"] == 0 and chunked.stats()["chunk_runs"] == 1
        assert whole.new_tokens == pieces.new_tokens != got.new_tokens
        chunked.shutdown()
    eng.shutdown()


# --------------------------------------------------------------------------
# every other last piece: the general kinds, never the fresh one
# --------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["chunked", "behind_a_shared_prefix", "a_sessions_second_turn"])
def test_a_piece_with_something_before_it_takes_the_general_kinds(how):
    cfg, params = _model()
    if how == "chunked":
        eng = _engine(cfg, params, prefill_chunk=8)
        prompt = _prompt(cfg, 13, seed=5)
        got = eng.submit(prompt, max_new_tokens=5).result()
        want_kinds, fresh_runs = {"prefill", eng._chunk_kind()}, 0
    elif how == "behind_a_shared_prefix":
        eng = _engine(cfg, params)
        first = _prompt(cfg, 12, seed=6)
        eng.submit(first, max_new_tokens=8)
        eng.step()                                                # whole, and its blocks registered
        prompt = np.concatenate([first[:8], _prompt(cfg, 5, seed=7)])
        got = eng.submit(prompt, max_new_tokens=5).result()
        assert got.shared_prefix_blocks == 2
        want_kinds, fresh_runs = {"prefill", "prefill_fresh"}, 1
    else:
        eng = _engine(cfg, params, sessions=True)
        p1 = _prompt(cfg, 7, seed=8)
        r1 = eng.submit(p1, max_new_tokens=4, session_id="chat").result()
        prompt = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32), _prompt(cfg, 3, seed=9)])
        got = eng.submit(prompt, max_new_tokens=5, session_id="chat").result()
        assert got.shared_prefix_blocks > 0
        want_kinds, fresh_runs = {"prefill", "prefill_fresh"}, 1
    st = eng.stats()
    assert _kinds(eng) == want_kinds and st["prefill_fresh_runs"] == fresh_runs
    assert st["prefill_runs"] == fresh_runs + 1
    assert np.array_equal(np.asarray(got.new_tokens), _solo(cfg, params, prompt, 5))
    eng.shutdown()


# --------------------------------------------------------------------------
# the head on one row
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["llama", "hybrid"])
@pytest.mark.parametrize("pos", ["static_zero", "traced"])
def test_logits_at_returns_the_row_the_full_head_returns(model, pos):
    cfg, params = tiny_model() if model == "hybrid" else _model(n_query_groups=2)
    toks = jnp.asarray(_prompt(cfg, 24, seed=11)[None])
    cos, sin = llama.build_rope_cache(cfg, 32)
    p0 = 0 if pos == "static_zero" else jnp.int32(0)

    @jax.jit
    def both(row):
        cache = gen.init_cache(cfg, 1, 32, dtype=jnp.float32)
        full, c1 = gen.forward_with_cache(params, toks, p0, cache, cos, sin, cfg, n_real=19)
        one, c2 = gen.forward_with_cache(params, toks, p0, cache, cos, sin, cfg, n_real=19, logits_at=row)
        return full, one, c1, c2

    full, one, c1, c2 = both(jnp.int32(18))
    assert one.shape == (1, 1, cfg.padded_vocab_size) and full.shape == (1, 24, cfg.padded_vocab_size)
    assert jnp.array_equal(one[:, 0], full[:, 18])
    assert all(jnp.array_equal(c1[k], c2[k]) for k in c1)


def test_the_static_and_the_traced_position_zero_agree():
    """The triangle over the fresh keys against the same prompt scored over
    the cache's every slot: the same values (another order of summation)."""
    cfg, params = _model(n_query_groups=2, sliding_window=9)
    toks = jnp.asarray(_prompt(cfg, 24, seed=12)[None])
    cos, sin = llama.build_rope_cache(cfg, 32)
    plain = {name: jnp.zeros((2, 1, 2, 32, 8), jnp.float32) for name in "kv"}   # wider than the window: no ring
    run = lambda p0: gen.forward_with_cache(params, toks, p0, plain, cos, sin, cfg)  # noqa: E731
    (la, ca), (lb, cb) = run(0), run(jnp.int32(0))
    np.testing.assert_allclose(la, lb, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ca["k"], cb["k"], rtol=2e-5, atol=2e-5)
    assert ca["k"].shape == cb["k"].shape == (2, 1, 2, 32, 8)


# --------------------------------------------------------------------------
# the counters the kind brings
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def counted():
    # a configuration of its own, by name: nobody's programs in the module's cache, in any order of files
    cfg, params = _model(vocab_size=40)
    cfg = dataclasses.replace(cfg, name="prefill-fresh-counted")
    reg = registry()
    before = {n: reg.counter(n).value for n in ("serving.steps.prefill_fresh", "serving.steps.prefill",
                                                "serving.compiles.prefill_fresh")}
    clear_events()
    eng = _engine(cfg, params, trace=True, prefill_chunk=8)
    for n, seed in ((5, 1), (7, 2), (13, 3)):                     # two whole prompts, one in two pieces
        eng.submit(_prompt(cfg, n, seed), max_new_tokens=3)
    eng.drain()
    after = {n: reg.counter(n).value - v for n, v in before.items()}
    pieces = [e["args"] for e in events() if e["name"] == "serve.prefill_dispatch" and e["ph"] == "E"]
    out = eng.stats(), dict(eng.compile_counts), after, pieces, eng._chunk_kind()
    eng.shutdown()
    return out


@pytest.mark.parametrize("what", ["stats", "compile_counts", "registry_counters", "span_piece"])
def test_the_fresh_kind_is_counted(counted, what):
    stats, compiles, counters, pieces, chunk_kind = counted
    if what == "stats":
        assert stats["prefill_runs"] == 3 and stats["prefill_fresh_runs"] == 2 and stats["chunk_runs"] == 1
        assert stats["compile_counts"]["prefill_fresh"] == 1
    elif what == "compile_counts":
        assert compiles["prefill_fresh"] == 1 and compiles["prefill"] == 1 and compiles[chunk_kind] == 1
    elif what == "registry_counters":
        assert counters == {"serving.steps.prefill_fresh": 2, "serving.steps.prefill": 3,
                            "serving.compiles.prefill_fresh": 1}
    else:
        assert sorted(p["piece"] for p in pieces) == sorted(["prefill_fresh"] * 2 + [chunk_kind, "prefill"])
        fresh = [p for p in pieces if p["piece"] == "prefill_fresh"]
        assert sorted(p["tokens"] for p in fresh) == [5, 7] and {p["bucket"] for p in fresh} == {"8x2"}


# --------------------------------------------------------------------------
# where the kernel takes the shapes: the flash call, interpreted
# --------------------------------------------------------------------------

@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


FLASH = dict(n_layer=1, n_head=4, n_query_groups=2, n_embd=32, intermediate_size=64, vocab_size=48, block_size=256)


@pytest.mark.parametrize("window", [None, 96], ids=["no_window", "a_window_that_binds"])
def test_a_prompt_of_whole_lane_tiles_goes_through_the_flash_kernel(interpreted, window):
    cfg, params = _model(**FLASH, sliding_window=window)
    toks = jnp.asarray(_prompt(cfg, 128, seed=13)[None])
    cos, sin = llama.build_rope_cache(cfg, 160)
    plain = {name: jnp.zeros((1, 1, 2, 160, 8), jnp.float32) for name in "kv"}  # wider than the window: no ring
    run = lambda p0: gen.forward_with_cache(params, toks, p0, plain, cos, sin, cfg, logits_at=127)  # noqa: E731
    claims = px.stats["direct"]
    (la, ca), (lb, cb) = run(0), run(jnp.int32(0))
    assert px.stats["direct"] == claims + 1                       # one layer, the static position alone
    assert px.flash_schedule["grid_steps"] == 1 and px.flash_schedule["running_blocks"] == 1
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-4)
    assert jnp.array_equal(ca["k"], cb["k"]) and jnp.array_equal(ca["v"], cb["v"])


def test_the_engine_and_solo_take_the_kernel_together(interpreted):
    cfg, params = _model(**FLASH)
    prompt = _prompt(cfg, 128, seed=14)
    eng = _engine(cfg, params, block_size=16, num_blocks=24, prefill_buckets=(128,), block_buckets=(12,))
    claims = px.stats["direct"]
    got = eng.submit(prompt, max_new_tokens=4).result()
    assert px.stats["direct"] > claims and eng.stats()["prefill_fresh_runs"] == 1
    assert np.array_equal(np.asarray(got.new_tokens), _solo(cfg, params, prompt, 4))
    eng.shutdown()


def test_a_ring_cache_the_prompt_fills_takes_the_kernel_and_holds_the_same_slots(interpreted):
    """A cache exactly as wide as the window reads as a ring.  A prompt at 0
    that fills it attends its own keys like any other, and slot = position
    % window is slot = position: what the plain layout holds."""
    cfg, params = _model(**FLASH, sliding_window=128)
    toks = jnp.asarray(_prompt(cfg, 128, seed=16)[None])
    cos, sin = llama.build_rope_cache(cfg, 160)
    cache = lambda Tc: {name: jnp.zeros((1, 1, 2, Tc, 8), jnp.float32) for name in "kv"}  # noqa: E731
    claims = px.stats["direct"]
    ring, cr = gen.forward_with_cache(params, toks, 0, cache(128), cos, sin, cfg)
    plain, cp = gen.forward_with_cache(params, toks, 0, cache(160), cos, sin, cfg)
    assert px.stats["direct"] == claims + 2
    assert jnp.array_equal(ring, plain)
    assert jnp.array_equal(cr["k"], cp["k"][..., :128, :]) and jnp.array_equal(cr["v"], cp["v"][..., :128, :])


def test_under_a_tp_mesh_the_prompt_keeps_the_einsum_form(interpreted):
    """A bare ``pallas_call`` has no partitioning rule: on placed operands it
    would run replicated.  The engine and solo ``generate(mesh=)`` both say so
    to the forward, which scores the (T, T) triangle as XLA ops."""
    cfg, params = _model(**FLASH)
    mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    placed = dist.tp_fsdp(params, mesh)
    prompt = _prompt(cfg, 128, seed=15)
    eng = _engine(cfg, placed, mesh=mesh, block_size=16, num_blocks=24, prefill_buckets=(128,), block_buckets=(12,))
    prompt_claims = lambda: {k: n for k, n in px.stats.items() if not k.startswith("paged_")}   # noqa: E731 - the decode
    claims = prompt_claims()                                                   # steps' walks are counted a call site (PR 47)
    got = eng.submit(prompt, max_new_tokens=4).result()
    solo = np.asarray(gen.generate(placed, prompt[None], cfg, 4, cache_dtype=jnp.float32, mesh=mesh))[0, 128:]
    assert prompt_claims() == claims and eng.stats()["prefill_fresh_runs"] == 1
    assert np.array_equal(np.asarray(got.new_tokens), solo)
    assert np.array_equal(solo, _solo(cfg, params, prompt, 4))    # and the kernel's tokens, off the mesh
    eng.shutdown()


# --------------------------------------------------------------------------
# the forward is the in-tree one: a model is a llama.Config
# --------------------------------------------------------------------------

@pytest.mark.parametrize("piece", ["a_whole_prompt", "behind_a_shared_prefix"])
def test_the_in_tree_forward_serves_both_pieces_and_a_model_fn_is_refused(piece, monkeypatch):
    """The two pieces a custom ``model_fn`` used to be called for, served by the
    in-tree forward, which projects row ``n_real - 1`` alone: static position 0
    for a whole prompt, a traced one behind a shared prefix.  ``tt.serve(fn,
    ...)`` itself refuses, with what to do instead."""
    cfg, params = _model(n_query_groups=2)
    seen = []
    real = gen.forward_with_cache

    def spy(params, idx, pos, cache, cos_all, sin_all, cfg, **kw):
        logits, cache = real(params, idx, pos, cache, cos_all, sin_all, cfg, **kw)
        seen.append((idx.shape[1], logits.shape[1], isinstance(pos, int)))
        return logits, cache

    with pytest.raises(NotImplementedError, match="llama.Config"):
        tt.serve(spy, params, cfg, block_size=4, num_blocks=40, max_batch=2, cache_dtype=jnp.float32, **BUCKETS)
    import thunder_tpu.serving.engine as engine_mod

    monkeypatch.setattr(engine_mod, "forward_with_cache", spy)
    monkeypatch.setattr(engine_mod, "_program_cache", {})       # the programs are traced here, through the spy
    eng = tt.serve(None, params, cfg, block_size=4, num_blocks=40, max_batch=2, cache_dtype=jnp.float32, **BUCKETS)
    prompt = _prompt(cfg, 13, seed=17)
    if piece == "behind_a_shared_prefix":
        eng.submit(prompt, max_new_tokens=8)
        eng.step()
        prompt = np.concatenate([prompt[:8], _prompt(cfg, 5, seed=18)])
    got = eng.submit(prompt, max_new_tokens=5).result()
    assert np.array_equal(np.asarray(got.new_tokens), _solo(cfg, params, prompt, 5))
    assert all(T > 1 and rows == 1 for T, rows, _ in seen)         # a prompt's pieces alone, one row projected
    assert [static for _, _, static in seen] == ([True] if piece == "a_whole_prompt" else [True, False])
    eng.shutdown()
