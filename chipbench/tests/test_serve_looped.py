"""`drivers/serve_looped.py` and `models/looped_dense_decoder.py` at the rehearsal size
(3 blocks run 2 times, 4 heads of 128 over 4 KV heads), on the CPU: the command end
to end, the program as configured passing every number of the comparison, the
program in float32 reading what the reference reads, and each of the five planted
controls reading `ok: false`: one slab a layer for all passes (`plant_slab_per_layer`),
a pass fewer (`fewer_passes`), the last norm once after the last pass
(`plant_norm_once`), the engine's fp8 K/V arena and int8 weight products.  The joined
roofline reader counts `L_full` = every layer of every pass.  The limits of the
cell's own size, and the controls' readings there, are in PERF.md section 2."""
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

CELL = "ouro-serve-1chip.offline-shortqa"
NUMBERS = ("kv_rel_err", "pass_rel_err", "kv_rel_err_max", "mean_logit_shortfall")
# at the rehearsal size the mix's limits are wide (they find wrong paths); the controls are held to these,
# between the program's readings over the seeds below and each control's own
TIGHT = {"kv_rel_err_limit": 0.007, "pass_rel_err_limit": 0.016, "kv_rel_err_max_limit": 0.024, "mean_logit_shortfall_limit": 0.05}


def built(seed, float32=False, passes=0, **engine):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    ctx["mix"]["check"].update(TIGHT)
    driver = common.load_module("drivers", "serve_looped")
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    if passes:
        ctx["arch"] = driver.fewer_passes(ctx["arch"], passes)
    return ctx, driver, driver.build(ctx, **engine)


def checked(*args, **kw):
    ctx, driver, st = built(*args, **kw)
    out = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    return out


def test_the_rehearsal_runs_end_to_end_and_reports_the_joined_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "THUNDER_TPU_PALLAS_INTERPRET": "1"}
    run = subprocess.run([sys.executable, os.path.join(common.HERE, "run.py"), "--workload", CELL, "--seed", str(2**31 + 61),
                          "--seconds", "2", "--trace", "1", "--rehearse"], env=env, capture_output=True, text=True, timeout=1200)
    assert run.returncode == 0, run.stderr[-2000:]
    check, result = (json.loads(line) for line in run.stdout.strip().splitlines()[-2:])
    assert result["correct"] and result["failed"] == 0 and check["check"]["window_compiles"] == 0
    assert check["check"]["decode_path"] == "walk" and check["check"]["attn_fallback_steps"] == 0
    assert check["check"]["pool"]["slabs"] == 6 and sorted(check["check"]["held_rel_err_by_slab"]) == ["0.0", "1.0", "1.1", "1.2"]
    s1 = check["counters"]["stats1"]
    assert sum(s1["passes"]["exit"]) == s1["tokens_generated"] and s1["passes"]["exit"][0] == 0
    assert s1["passes"]["layer_passes"] == s1["passes"]["steps"] * 6
    assert s1["attended_tokens"]["slab_walks"] == 6 * s1["attended_tokens"]["full_attention"] > 0
    for name in ("kv_pool_fill_share.serve", "slot_goodput_share.serve", "setup_decode_programs_s", "setup_prefill_programs_s"):
        assert name in result["metrics"], name
    for name in ("paged_attn_decode_roofline_share.offline", "state_pool_fill_share.serve", "moe_share_of_busy.serve"):
        assert name not in result["metrics"], name                                      # the cell is on none of their lists


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    sound = checked(seed)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert sound["held_tokens"] == [28, 58, 98] and sound["decode_path"] == "walk"
    exact = checked(seed, float32=True)
    assert exact["ok"] and all(exact[n] < 1e-3 for n in NUMBERS), exact


def _fresh_programs(monkeypatch):
    """A plant is made before a process's first engine: no program built yet, and the plant taken back after the test."""
    from thunder_tpu.models import generate, llama
    from thunder_tpu.serving import engine

    monkeypatch.setattr(engine, "_program_cache", {})
    monkeypatch.setattr(generate, "close_pass", generate.close_pass)
    monkeypatch.setattr(llama.Config, "kv_slab", llama.Config.kv_slab)


def test_one_slab_a_layer_for_all_passes_fails(monkeypatch):
    _fresh_programs(monkeypatch)
    common.load_module("drivers", "serve_looped").plant_slab_per_layer()
    planted = checked(12)
    # every pass's walk reads its layer's pass-0 slab; the fresh K/V leave the loop in the slabs' own order and are
    # written where they belong, so what is held still reads right and the served tokens do not
    assert not planted["ok"] and planted["mean_logit_shortfall"] > 4 * planted["mean_logit_shortfall_limit"], planted
    assert planted["argmax_agree_share"] < 0.5 and planted["kv_rel_err"] <= planted["kv_rel_err_limit"]


def test_a_pass_fewer_fails():
    planted = checked(12, passes=1)
    assert not planted["ok"] and planted["pass_rel_err"] == float("inf")       # the engine has no slab of pass 1
    assert planted["kv_rel_err"] <= planted["kv_rel_err_limit"]                # pass 0 of layer 0 is the same
    assert planted["mean_logit_shortfall"] > planted["mean_logit_shortfall_limit"]


def test_the_last_norm_once_after_the_last_pass_fails(monkeypatch):
    _fresh_programs(monkeypatch)
    common.load_module("drivers", "serve_looped").plant_norm_once()
    planted = checked(12)
    assert not planted["ok"] and planted["kv_rel_err"] <= planted["kv_rel_err_limit"]     # pass 0 runs before any close
    # pass 1 opens on a state no norm closed.  Layer 0 of pass 1 does not see it: its keys are projected from
    # RMSNorm_1 of that state, which no scale moves (the norms' weights are 1); layer 1's input is the state plus
    # a normed sublayer's output, and does
    rel = planted["held_rel_err_by_slab"]
    assert rel["1.0"] <= planted["pass_rel_err_limit"] and rel["1.1"] > 0.1 and planted["kv_rel_err_max"] > planted["kv_rel_err_max_limit"]
    assert planted["mean_logit_shortfall"] > planted["mean_logit_shortfall_limit"]


@pytest.mark.parametrize("engine,number", [({"kv_dtype": "fp8"}, "kv_rel_err"), ({"quantized": True}, "pass_rel_err")],
                         ids=["fp8_arena", "int8_weight_products"])
def test_a_storage_one_precision_lower_fails(engine, number):
    sound, planted = checked(13), checked(13, **engine)
    assert sound["ok"] and not planted["ok"], (sound, planted)
    assert planted[number] > planted[number + "_limit"] > sound[number]


def test_the_reference_loops_in_python_and_keeps_a_slab_a_pass():
    arch = common.load_module("models", "looped_dense_decoder")
    _, hf, _ = common.open_cell(CELL)
    s = arch.sizes(hf)
    assert (s["L"], s["passes"], s["L_full"], s["nh"], s["ng"], s["hs"], s["threshold"]) == (48, 4, 192, 16, 16, 128, 1.0)
    assert arch.param_count(hf) == 2_667_974_657 and arch.kv_bytes_per_token(hf) == 1_572_864
    assert [arch.slab(hf, t, l) for t, l in ((0, 0), (1, 0), (3, 47))] == [0, 48, 191]
    assert arch.program_config(hf)["n_pass"] == 4 and arch.program_config(hf)["sandwich_norm"] is True
    _, hf, _ = common.open_cell(CELL, rehearse=True)
    with jax.default_matmul_precision("highest"):
        params = arch.make_params(hf, common.seed_words(3), dtype=jnp.float32)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, hf["vocab_size"], (128,)), jnp.int32)
        held = arch.ref_caches(hf, params, toks, 100)
        assert [s for s, _ in held] == [(t, l) for t in range(2) for l in range(3)] and held[0][1][0].shape == (4, 100, 128)
        assert [s for s, _ in arch.ref_caches(hf, params, toks, 100, [[1, 0], [0, 0]])] == [(0, 0), (1, 0)]
        # the same weights every pass, and yet a layer's keys of the two passes differ: their inputs do
        assert float(jnp.abs(held[0][1][0] - held[3][1][0]).max()) > 0.1
        # the exit rule: a shut gate reads the last pass, an open one under a low threshold the first
        e, p = arch.exit_pass(jnp.full((2, 5), -20.0), 1.0)
        assert e.tolist() == [1] * 5 and np.allclose(p.sum(0), 1.0)
        e, p = arch.exit_pass(jnp.full((2, 5), 2.1972246), 0.5)
        assert e.tolist() == [0] * 5 and np.allclose(p[0], 0.9, atol=1e-6)
        # a token's logits come from the pass its own gate picks
        gated = {**params, "exit_gate": {"w": params["exit_gate"]["w"] * 0, "b": jnp.asarray(2.1972246)}}
        early = arch.ref_logits({**hf, "early_exit_threshold": 0.5}, gated, toks, jnp.arange(4))
        late = arch.ref_logits(hf, gated, toks, jnp.arange(4))
        assert float(jnp.abs(early - late).max()) > 1e-3


def test_the_joined_roofline_reader_counts_every_layer_of_every_pass():
    reader = common.load_reader("paged_attn_decode_roofline_share.hybserve")
    arch = common.load_module("models", "looped_dense_decoder")
    _, hf, _ = common.open_cell(CELL)
    ops = [types.SimpleNamespace(meta='bf16[12,16,1,128]{3,2,1,0} custom-call(s32[12,28]{1,0} %t), custom_call_target="tpu_custom_call"',
                                 dur=0.001) for _ in range(192)]
    trace = types.SimpleNamespace(op_seconds=lambda m: sum(o.dur for o in ops if m(o)))
    ctx = {"arch": arch, "config": hf, "peaks": {"hbm_bytes_per_sec": 819e9, "bf16_flops_per_sec": 197e12},
           "trace": trace, "host": {"traced_decode_context_tokens": 3000}}
    assert reader.read(ctx) == pytest.approx(192 * 3000 * 2 * 16 * 128 * 2 / 819e9 / 0.192)
