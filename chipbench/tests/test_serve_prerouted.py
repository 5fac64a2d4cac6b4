"""`drivers/serve_prerouted.py` and `models/prerouted_moe_decoder.py` at the rehearsal
size (7 query heads over 1 KV head, window 32, 2 of 8 experts a token), on the CPU:
the command end to end, the program as configured passing every number of the
comparison with requests inside and past the window, the program in float32
reading what the reference reads, and each planted control failing or reading
above the program: a router that reads what its experts read (`route_on_mlp_input`),
another request's ring and blocks (`swap_requests`), keys and values rounded to fp8
before they are kept (`plant_kv_store`), a router's logits rounded to bfloat16
(`plant_router_dtype`: the plant alone, the rehearsal is too small for it).  The new reader reads `min(context, W)` from
the engine's count on a synthetic trace and `None` for another architecture.  The
limits of the cell's own size, and the controls' readings there, are in PERF.md
section 2."""
import argparse
import functools
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import calibrate, common

CELL = "smallthinker-serve-1chip.offline-mixedlen"
NUMBERS = ("ring_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False, swap=False, router_on_mlp_input=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    driver = common.load_module("drivers", "serve_prerouted")
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    if router_on_mlp_input:
        ctx["arch"] = driver.route_on_mlp_input(ctx["arch"])
    if swap:
        ctx["arch"] = driver.swap_requests(ctx["arch"])
    return ctx, driver, driver.build(ctx)


def checked(*args, **kw):
    ctx, driver, st = built(*args, **kw)
    out = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    return out


def test_the_rehearsal_runs_end_to_end_and_reports_the_joined_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "THUNDER_TPU_PALLAS_INTERPRET": "1"}
    run = subprocess.run([sys.executable, os.path.join(common.HERE, "run.py"), "--workload", CELL, "--seed", str(2**31 + 59),
                          "--seconds", "2", "--trace", "1", "--rehearse"], env=env, capture_output=True, text=True, timeout=1200)
    assert run.returncode == 0, run.stderr[-2000:]
    check, result = (json.loads(line) for line in run.stdout.strip().splitlines()[-2:])
    assert result["correct"] and result["failed"] == 0 and check["check"]["window_compiles"] == 0
    assert check["check"]["decode_path"] == "walk" and check["check"]["attn_fallback_steps"] == 0
    s1 = check["counters"]["stats1"]
    assert s1["moe"]["router"] == "softmax" and 0 < s1["moe"]["experts_hit_share"] <= 1
    assert s1["attended_tokens"]["full_attention"] > s1["attended_tokens"]["sliding_attention"] > 0
    for name in ("window_pool_fill_share.flashserve", "state_pool_fill_share.serve", "experts_hit_share.nemoserve",
                 "expert_rows_per_step.nemoserve", "kv_pool_fill_share.serve", "setup_decode_programs_s"):
        assert name in result["metrics"], name
    assert "paged_attn_decode_roofline_share.flashserve" not in result["metrics"]      # the cell is not on its list


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    sound = checked(seed)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert {k: len(v) for k, v in sound["held_rel_err_by_layer"].items()} == {
        "sliding_attention.k_ring": 6, "sliding_attention.v_ring": 6, "full_attention.k": 2, "full_attention.v": 2}
    assert (sound["decode_path"], sound["lane_pack"]) == ("walk", 1)
    assert sound["held_tokens"] == [22, 30, 92]             # inside the window of 32 twice, past it once
    exact = checked(seed, float32=True)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["kv_rel_err_max"] < 1e-3, exact


def test_a_router_that_reads_what_its_experts_read_fails_the_tokens():
    sound, planted = checked(12), checked(12, router_on_mlp_input=True)
    assert sound["ok"] and not planted["ok"], (sound, planted)
    assert planted["mean_logit_shortfall"] > planted["mean_logit_shortfall_limit"] > sound["mean_logit_shortfall"]
    # layer 0's K/V is projected from the embedding before any router ran: the plant leaves it alone
    assert planted["kv_rel_err"] <= planted["kv_rel_err_limit"] and planted["ring_rel_err"] > 10 * sound["ring_rel_err"]


def test_another_requests_ring_and_blocks_fail():
    faulty = checked(12, swap=True)
    assert not faulty["ok"] and faulty["ring_rel_err"] > 0.5 and faulty["kv_rel_err"] > 0.5, faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


def test_keys_and_values_rounded_to_fp8_read_above_the_program(monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine, paged_attention

    sound = checked(13)
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    monkeypatch.setattr(generate, "_project_qkv", generate._project_qkv)              # restored after the plant
    monkeypatch.setattr(paged_attention, "_project_qkv", paged_attention._project_qkv)
    common.load_module("drivers", "serve_prerouted").plant_kv_store("float8_e4m3fn")
    planted = checked(13)
    assert planted["kv_rel_err"] > 3 * sound["kv_rel_err"] and planted["ring_rel_err"] > 1.5 * sound["ring_rel_err"], (
        sound, planted)


def test_a_router_kept_at_bfloat16_chooses_by_the_rounded_logits(monkeypatch):
    """At the rehearsal size (8 experts, some 150 tokens) a rounding of 2**-9 moves no choice, so the comparison reads what
    the program reads; the cell's size, 64 experts on logits of std 0.02, is PERF.md section 2's.  Here: the plant itself."""
    from thunder_tpu.models import generate, llama

    cfg = llama.Config(name="r", n_layer=1, n_head=2, n_embd=32, mlp_class="SparseMoE", n_expert=4, n_expert_per_token=1,
                       intermediate_size=16)
    monkeypatch.setattr(generate, "route_softmax", generate.route_softmax)            # restored after the plant
    logits = jnp.asarray([[1.0, 1.001, 0.0, 0.0], [1.001, 1.0, 0.0, 0.0], [0.5, 0.0, 1.5, 0.0]], jnp.float32)
    exact = np.asarray(generate.route_softmax(logits, cfg)[1])[:, 0]
    common.load_module("drivers", "serve_prerouted").plant_router_dtype("bfloat16")
    rounded = np.asarray(generate.route_softmax(logits, cfg)[1])[:, 0]
    assert exact.tolist() == [1, 0, 2] and rounded.tolist() == [0, 0, 2]        # a tie at bfloat16 goes to the first


def test_the_reference_routes_on_the_blocks_input_and_rotates_the_window_layers_alone():
    arch = common.load_module("models", "prerouted_moe_decoder")
    _, hf, _ = common.open_cell(CELL, rehearse=True)
    s = arch.sizes(hf)
    assert s["kinds"][:4] == ("full_attention",) + ("sliding_attention",) * 3 and s["rope_kinds"] == ("sliding_attention",)
    assert (s["W"], s["held"], s["E"], s["k"], s["nh"] // s["ng"], s["dense"]) == (32, 8, 8, 2, 7, 0)
    assert arch.program_config(hf)["moe_route_block_input"] is True
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(hf, common.seed_words(3), dtype=jnp.float32)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, hf["vocab_size"], (128,)), jnp.int32)
        held = arch.ref_caches(hf, params, toks, 100)
        assert [k for k, _ in held] == list(s["kinds"]) and held[0][1][0].shape == (1, 100, 128) and held[1][1][0].shape == (1, 32, 128)
        # moved 7 places on, a global layer's own keys are the same and a window layer's are turned
        a = jnp.ones((40, s["C"]), jnp.float32) * jnp.linspace(0.5, 1.5, s["C"])
        ap, mp = params["blocks"][0]["attn"], params["blocks"][0]["mlp"]
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
        # the choice follows what the router reads, the experts' products what they read
        w_x, idx_x = arch.route(x, mp["gate"], s)
        w_y, idx_y = arch.route(-x, mp["gate"], s)
        assert float(jnp.abs(jnp.sum(w_x, -1) - 1).max()) < 1e-6 and not np.array_equal(idx_x, idx_y)
        np.testing.assert_allclose(arch.routed_share(2 * x, x, mp, s), 4 * arch.routed_share(x, x, mp, s), rtol=1e-4, atol=1e-6)


def synthetic(walks: int, seconds: float):
    ops = [types.SimpleNamespace(name="paged_attn_decode.3", dur=seconds / walks) for _ in range(walks)]
    ops.append(types.SimpleNamespace(name="fusion.9", dur=1.0))
    return types.SimpleNamespace(op_seconds=lambda m: sum(o.dur for o in ops if m(o)), op_count=lambda m: sum(1 for o in ops if m(o)))


def test_the_new_reader_counts_the_keys_the_engine_says_were_attended():
    """Ten decode steps of 8 layers (2 global, 6 window: 80 walks): the engine's count
    over the window says a step's rows attended 6,000 keys in a global layer and
    3,000 in a window layer (`min(context, W)`); the bytes are those keys' K and V."""
    reader = common.load_reader("paged_attn_decode_roofline_share.mixedserve")
    arch = common.load_module("models", "prerouted_moe_decoder")
    _, hf, _ = common.open_cell(CELL)
    counters = {"stats0": {"attended_tokens": {"steps": 5, "full_attention": 100, "sliding_attention": 50}},
                "stats1": {"attended_tokens": {"steps": 105, "full_attention": 600_100, "sliding_attention": 300_050}}}
    peaks = {"hbm_bytes_per_sec": 819e9, "bf16_flops_per_sec": 197e12}
    ctx = {"arch": arch, "config": hf, "counters": counters, "peaks": peaks, "trace": synthetic(80, 0.5)}
    nbytes = 10 * (2 * 6000 + 6 * 3000) * 2 * 4 * 128 * 2
    assert reader.SHARE_OF_PEAK and reader.read(ctx) == pytest.approx(nbytes / 819e9 / 0.5)
    assert reader.window_keys_a_step(counters) == (6000.0, 3000.0)
    # nothing to read: no walk in the trace, no count (a parent without it), no peaks (a rehearsal), another architecture
    assert reader.read({**ctx, "trace": synthetic(80, 0.0)}) is None
    assert reader.read({**ctx, "counters": {"stats0": {}, "stats1": {}}}) is None
    assert reader.read({**ctx, "peaks": None}) is None
    other = common.load_module("models", "dense_decoder")
    _, dense_hf, _ = common.open_cell("mistral7b-serve-1chip.offline-batch")
    assert reader.read({**ctx, "arch": other, "config": dense_hf}) is None
