"""A hybrid dense decoder through ``tt.serve``: the recurrent state beside the
paged KV, on both decode paths (the config, the kernels and the pool are in
``tests/test_hybrid_serving.py``).

Float32 weights at tiny widths in the published ratio (``dk`` 12, ``dv`` 24,
four layers); the served tokens are compared with solo ``generate()`` bit for
bit: six requests through three slots, padded and exact prompts, a recovery,
an injected fault, an eviction, chunked prompts, and what refuses.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import thunder_tpu as tt  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving import ServingEngine, faults  # noqa: E402

from _hybrid_tiny import tiny_model, tokens as _tokens  # noqa: E402
from conftest import ATTN_FORMS, set_attn_form  # noqa: E402

@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def model():
    return tiny_model()


# --------------------------------------------------------------------------
# served = solo, bit for bit
# --------------------------------------------------------------------------

REQUESTS = [(40, 12), (23, 9), (64, 6), (7, 14), (33, 8), (50, 10)]   # prompt, new tokens: padded, exact and tiny prompts


@pytest.fixture(scope="module")
def solo(model):
    cfg, params = model
    return [np.asarray(G.generate(params, _tokens(p, seed=p)[None], cfg, n, T_max=128))[0, p:] for p, n in REQUESTS]


def _engine(model, **kw):
    cfg, params = model
    opts = dict(max_batch=3, num_blocks=40, block_size=16, prefill_buckets=[32, 64],
                batch_buckets=[4], block_buckets=[8])
    return tt.serve(None, params, cfg, **{**opts, **kw})


def _served(eng, which=range(len(REQUESTS))):
    handles = [eng.submit(_tokens(REQUESTS[i][0], seed=REQUESTS[i][0]), max_new_tokens=REQUESTS[i][1]) for i in which]
    eng.drain()
    return [np.asarray(h.result(drive=False).tokens)[-REQUESTS[i][1]:] for h, i in zip(handles, which)]


@pytest.fixture(scope="module")
def served(model):
    """Six requests through three slots, so slots and state slots turn over, in
    both forms of the decode program's attention; the engine's counts afterwards."""
    out = {}
    for form in ATTN_FORMS:
        with pytest.MonkeyPatch.context() as env:   # the worker's next test file gets its environment back
            set_attn_form(env, form)
            claims = px.stats.get("gdn_decode", 0)
            eng = _engine(model)
            out[form] = (_served(eng), eng.stats(), eng._flight_state(), px.stats.get("gdn_decode", 0) - claims)
            eng.shutdown()
    return out


@pytest.mark.parametrize("i", range(len(REQUESTS)))
@pytest.mark.parametrize("form", ATTN_FORMS)
def test_a_served_request_is_bit_identical_to_solo_generate(served, solo, form, i):
    assert np.array_equal(served[form][0][i], solo[i])


@pytest.mark.parametrize("form", ATTN_FORMS)
def test_the_engine_reports_its_state_pool(served, form):
    _, stats, flight, claims = served[form]
    st = stats["state"]
    assert st["slots"] == 3 and st["leased"] == 0 and st["free_low_water"] == 0 and st["layers"] == 3
    assert st["dtype"] == "float32" and st["arena_bytes"] == 4 * st["slot_bytes"]
    # as counted, and as the chip's tiles hold them (a tiny row of 12 by 48 fills a tenth of its tiles)
    assert st["arena_laid_out_bytes"] == 4 * st["slot_laid_out_bytes"] > st["arena_bytes"]
    assert stats["pool_occupancy"]["state"]["fill_frac"] == 0.0 and flight["pool"]["state"]["slots"] == 3
    assert stats["attn"]["path"] == ("xla" if form == "xla" else "walk") and stats["recoveries"] == 0
    assert stats["attn"]["fallback_steps"] == (stats["decode_steps"] if form == "xla" else 0)
    assert "decode_paged" in stats["compile_counts"] and "decode" not in stats["compile_counts"]


def test_state_gauges_are_published(model):
    from thunder_tpu.observability.metrics import registry

    eng = _engine(model)
    eng.submit(_tokens(20), max_new_tokens=3)
    eng.step()
    reg = registry()
    assert reg.gauge("serving.state.slots").value == 3 and reg.gauge("serving.state.leased").value == 1
    assert reg.gauge("serving.state.arena_bytes").value == eng.stats()["state"]["arena_bytes"]
    assert reg.gauge("serving.state.arena_laid_out_bytes").value == eng.stats()["state"]["arena_laid_out_bytes"]
    eng.shutdown(drain=False)


def test_a_recovery_rebuilds_the_state_through_the_prefill_programs(model, solo, attn_form):
    eng = _engine(model)
    handles = [eng.submit(_tokens(REQUESTS[i][0], seed=REQUESTS[i][0]), max_new_tokens=REQUESTS[i][1]) for i in (0, 1, 2)]
    for _ in range(6):
        eng.step()
    assert all(len(h.tokens_so_far()) >= 2 for h in handles)
    eng.recover()                                          # arenas and state zeroed, then replayed
    eng.drain()
    for h, i in zip(handles, (0, 1, 2)):
        assert np.array_equal(np.asarray(h.result(drive=False).tokens)[-REQUESTS[i][1]:], solo[i]), i
    assert eng.stats()["recoveries"] == 1 and eng.stats()["chunk_runs"] >= 3
    eng.shutdown()


def test_an_injected_fault_recovers_to_the_same_tokens(model, solo):
    plan = faults.FaultPlan(specs=[faults.FaultSpec(point=faults.FP_DECODE, kind="oom", at=3)])
    eng = _engine(model, fault_plan=plan)
    got = _served(eng, which=(0, 3))
    assert np.array_equal(got[0], solo[0]) and np.array_equal(got[1], solo[3])
    assert eng.stats()["recoveries"] >= 1
    eng.shutdown()


def test_an_evicted_request_frees_both_kinds_and_the_others_are_untouched(model, solo):
    eng = _engine(model)
    handles = [eng.submit(_tokens(REQUESTS[i][0], seed=REQUESTS[i][0]), max_new_tokens=REQUESTS[i][1]) for i in (0, 1, 4)]
    for _ in range(4):
        eng.step()
    assert eng.stats()["state"]["leased"] == 3
    eng.evict(handles[1])
    assert eng.stats()["state"]["leased"] == 2
    eng.drain()
    assert np.array_equal(np.asarray(handles[0].result(drive=False).tokens)[-12:], solo[0])
    assert np.array_equal(np.asarray(handles[2].result(drive=False).tokens)[-8:], solo[4])
    assert eng.stats()["state"]["leased"] == 0 and eng.pool.num_free == eng.pool.num_usable
    eng.shutdown()


def test_a_chunked_prompt_carries_its_state_from_piece_to_piece(model, solo, attn_form):
    eng = _engine(model, prefill_chunk=32)
    got = _served(eng, which=(2, 5, 0))                    # 64 = two whole pieces, 50 and 40 = a piece and a padded rest
    for tokens, i in zip(got, (2, 5, 0)):
        assert np.array_equal(tokens, solo[i]), i
    assert eng.stats()["chunk_runs"] >= 3
    eng.shutdown()


def test_the_state_arena_is_float32_and_a_narrower_one_is_the_controls_to_plant(model, monkeypatch):
    from thunder_tpu.serving.kv_pool import StatePool

    eng = _engine(model)
    assert eng.stats()["state"]["dtype"] == "float32" and eng.pool.state.state.dtype == jnp.float32
    eng.shutdown(drain=False)
    # no option of the engine's: the benchmark's control sets the pool's constant
    # (chipbench/drivers/serve_held.py --state-arena), and the programs follow the arena they are handed
    monkeypatch.setattr(StatePool, "STATE_DTYPE", jnp.bfloat16)
    eng = _engine(model)
    assert eng.stats()["state"]["dtype"] == "bfloat16" and eng.pool.state.state.dtype == jnp.bfloat16
    assert len(_served(eng, which=(1,))[0]) == 9
    eng.shutdown()


def test_fp8_kv_beside_a_float32_state(model):
    eng = _engine(model, kv_dtype="fp8")
    assert set(eng.pool.arenas) == {"k", "v", "k_scale", "v_scale", "state", "conv"}
    assert len(_served(eng, which=(0, 1))[1]) == 9
    eng.shutdown()


# --------------------------------------------------------------------------
# what a recurrent state cannot serve yet refuses at construction
# --------------------------------------------------------------------------

def _refusals(model):
    from thunder_tpu.serving.lora import AdapterRegistry
    from thunder_tpu.serving.speculative import SpecConfig

    cfg, params = model
    return {
        "prefix_sharing": (dict(prefix_sharing=True), "prefix_sharing=True.*no snapshot"),
        "sessions": (dict(sessions=True), "sessions=.*no state snapshot"),
        "speculative": (dict(speculative=object.__new__(SpecConfig)), "speculative=.*no rollback"),
        "lora": (dict(lora=object.__new__(AdapterRegistry)), "lora=.*mixer's projections"),
        "mesh": (dict(mesh=object()), "mesh=.*tp axis"),
    }


@pytest.mark.parametrize("feature", ["prefix_sharing", "sessions", "speculative", "lora", "mesh", "model_fn"])
def test_each_refused_feature_raises_with_its_reason(model, feature):
    cfg, params = model
    if feature == "model_fn":       # refused for every model: a model is a Config
        with pytest.raises(NotImplementedError, match="llama.Config.*custom model_fn"):
            tt.serve(lambda *a, **k: None, params, cfg, num_blocks=8, max_batch=1)
        return
    kw, why = _refusals(model)[feature]
    with pytest.raises(NotImplementedError, match="linear_attention layers.*" + why):
        ServingEngine(params, cfg, num_blocks=8, max_batch=1, **kw)


def test_prefix_sharing_defaults_off_for_a_state_and_on_for_a_dense_model(model):
    cfg, params = model
    eng = _engine(model)
    assert eng.prefix_sharing is False
    eng.shutdown(drain=False)
    dense_cfg = llama.Config.from_name("tiny-llama-debug")
    dense = tt.serve(None, llama.init_params(dense_cfg, jax.random.PRNGKey(0), dtype=jnp.float32), dense_cfg,
                     num_blocks=8, max_batch=1)
    assert dense.prefix_sharing is True and "state" not in dense.stats()
    dense.shutdown(drain=False)


@pytest.mark.parametrize("kv_dtype", [None, "fp8"])
def test_held_is_what_solo_generation_holds(model, kv_dtype):
    """``held`` on a running request: the state slot and the K/V of its
    blocks against solo ``generate()``'s own cache after the same tokens (a
    full-width arena holds solo's keys bit for bit; an fp8 one a rounding away)."""
    cfg, params = model
    eng = _engine(model, **({"kv_dtype": kv_dtype} if kv_dtype else {}))
    prompt = _tokens(19, seed=7)
    h = eng.submit(prompt, max_new_tokens=8)
    other = eng.submit(_tokens(11, seed=8), max_new_tokens=8)      # a second row beside it
    while len(h.tokens_so_far()) < 5:
        eng.step()
    held = eng.held(h)
    fed = np.concatenate([prompt, np.asarray(h.tokens_so_far(), np.int32)])[:held["tokens"]]
    assert held["tokens"] == len(prompt) + len(h.tokens_so_far()) - 1
    cos, sin = G.build_rope_cache(cfg, 64)
    _, cache = G.forward_with_cache(params, jnp.asarray(fed)[None], 0, G.init_cache(cfg, 1, 64, jnp.float32),
                                    cos, sin, cfg)
    n = held["tokens"]
    assert held["state"].dtype == jnp.float32 and held["state"].shape == cache["state"][:, 0].shape
    np.testing.assert_allclose(held["state"], cache["state"][:, 0], rtol=2e-2, atol=2e-3)
    for name in ("k", "v"):
        want = np.asarray(cache[name][:, 0, :, :n], np.float32)
        got = np.asarray(held[name], np.float32)
        assert got.shape == want.shape == (len(cfg.kv_layers), cfg.n_query_groups, n, cfg.head_size)
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert (err < 2e-2) if kv_dtype is None else (5e-3 < err < 8e-2), (name, err)
    eng.drain()
    assert h.done() and other.done()
    with pytest.raises(RuntimeError, match="running request only"):
        eng.held(h)
