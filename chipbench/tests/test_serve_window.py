"""`drivers/serve_window.py` and `models/window_global_moe_decoder.py` at the rehearsal
size, on the CPU: the program as configured passes every number of the comparison,
the program in float32 reads what the reference reads, a request held against
another request's ring and blocks (`swap_requests`) fails, keys and values rounded
to fp8 before they are kept (`plant_kv_store`, which reaches a plain attention
layer where `serve_kinds.py`'s does not) read above the program, and the window's
counts ride on the result.  The limits of the cell's own size, and the controls'
readings there, are in PERF.md section 2."""
import argparse
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import calibrate, common

CELL = "trinity-mini-serve-1chip.offline-docqa"
NUMBERS = ("ring_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False, swap=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    driver = common.load_module("drivers", "serve_window")
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    if swap:
        ctx["arch"] = driver.swap_requests(ctx["arch"])
    return ctx, driver, driver.build(ctx)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    ctx, driver, st = built(seed)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert "state_rel_err" not in sound                     # no kind of this model keeps a state
    assert {k: len(v) for k, v in sound["held_rel_err_by_layer"].items()} == {
        "sliding_attention.k_ring": 6, "sliding_attention.v_ring": 6, "full_attention.k": 2, "full_attention.v": 2}
    assert (sound["decode_path"], sound["lane_pack"], sound["state_arena"]) == ("walk", 1, "bfloat16")
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["kv_rel_err_max"] < 1e-3, exact


def test_another_requests_ring_and_blocks_fail():
    ctx, driver, st = built(12, swap=True)
    faulty = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert not faulty["ok"] and faulty["ring_rel_err"] > 0.5 and faulty["kv_rel_err"] > 0.5, faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


def test_keys_and_values_rounded_to_fp8_read_above_the_program(monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine, paged_attention

    ctx, driver, st = built(13)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    monkeypatch.setattr(generate, "_project_qkv", generate._project_qkv)              # restored after the plant
    monkeypatch.setattr(paged_attention, "_project_qkv", paged_attention._project_qkv)
    driver.plant_kv_store("float8_e4m3fn")
    ctx, driver, st = built(13)
    planted = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert planted["ring_rel_err"] > 3 * sound["ring_rel_err"] and planted["kv_rel_err"] > 1.5 * sound["kv_rel_err"], (
        sound, planted)


def test_a_run_keeps_the_windows_counts(monkeypatch):
    ctx, driver, st = built(14)
    ctx.update(seconds=0.5, trace_dir=None, trace_s=0.0)
    out = driver.measure(ctx, st, {"ok": True})              # the accepted measure drops them
    assert "moe" not in out["counters"]["stats1"]
    st["engine"].shutdown(drain=False)
    ctx, driver, st = built(14)
    ctx.update(seconds=0.5, trace_dir=None, trace_s=0.0)
    monkeypatch.setattr(driver, "check", lambda c, s: {"ok": True})
    monkeypatch.setattr(driver, "build", lambda c: st)
    out = driver.run(ctx)
    s0, s1 = out["counters"]["stats0"], out["counters"]["stats1"]
    assert s1["moe"]["row_sums"][0] > s0["moe"]["row_sums"][0] and 0 < s1["moe"]["experts_hit_share"] <= 1
    assert s1["attended_tokens"]["steps"] > s0["attended_tokens"]["steps"]
    assert s1["attended_tokens"]["full_attention"] >= s1["attended_tokens"]["sliding_attention"] > 0
    reader = common.load_reader("experts_hit_share.nemoserve")
    assert 0 < reader.read({"counters": out["counters"]}) <= 1


def test_the_reference_rotates_the_window_layers_alone_and_masks_their_window():
    arch = common.load_module("models", "window_global_moe_decoder")
    _, hf, _ = common.open_cell(CELL, rehearse=True)
    s = arch.sizes(hf)
    assert s["kinds"][:4] == ("sliding_attention",) * 3 + ("full_attention",) and (s["W"], s["held"], s["E"]) == (32, 4, 8)
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(hf, common.seed_words(3), dtype=jnp.float32)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, hf["vocab_size"], (128,)), jnp.int32)
        held = arch.ref_caches(hf, params, toks, 100)
        assert [k for k, _ in held] == list(s["kinds"]) and held[0][1][0].shape == (2, 32, 128) and held[3][1][0].shape == (2, 100, 128)
        # moved 7 places on, a global layer's own keys are the same and a window layer's are turned
        a = jnp.ones((40, s["C"]), jnp.float32) * jnp.linspace(0.5, 1.5, s["C"])
        ap = params["blocks"][0]["attn"]
        k_full, _ = arch.keys_values(a, ap, s, "full_attention")
        k_swa, _ = arch.keys_values(a, ap, s, "sliding_attention")
        np.testing.assert_allclose(k_full[7], k_full[0], atol=1e-6)
        assert float(jnp.abs(k_swa[7] - k_swa[0]).max()) > 0.1
        # a token 40 back moves a global layer's output and not a window layer's (W = 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, s["C"]), jnp.float32)
        y = x.at[3].add(1.0)
        d_swa = arch._attention(x, ap, s, "sliding_attention")[50] - arch._attention(y, ap, s, "sliding_attention")[50]
        d_full = arch._attention(x, ap, s, "full_attention")[50] - arch._attention(y, ap, s, "full_attention")[50]
        assert float(jnp.abs(d_swa).max()) == 0.0 and float(jnp.abs(d_full).max()) > 1e-4
    wq, wg = arch.query_gate_weights(ap, s)
    assert wq.shape == wg.shape == (s["nh"] * s["hs"], s["C"])
    np.testing.assert_array_equal(wq[:s["hs"]], ap["wq"][:s["hs"]])
    np.testing.assert_array_equal(wg[:s["hs"]], ap["wq"][s["hs"]:2 * s["hs"]])


def test_the_unlisted_reader_reads_nothing_for_another_architecture():
    reader = common.load_reader("moe_grouped_mm_roofline_share.winserve")
    other = common.load_module("models", "sambay_decoder")
    _, hf, _ = common.open_cell("phi4flash-serve-1chip.offline-reason", rehearse=True)
    trace = types.SimpleNamespace(devices=[types.SimpleNamespace(ops=[], modules=[])])
    assert reader.SHARE_OF_PEAK and reader.read({"trace": trace, "arch": other, "config": hf, "peaks": {}}) is None
    assert reader.read({"trace": trace, "arch": other, "config": hf, "peaks": None}) is None
