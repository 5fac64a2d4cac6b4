"""`drivers/serve_kinds.py` at the rehearsal size, on the CPU: the program as configured
passes every number of the comparison, the program in float32 reads what the reference
reads, a request handed another request's state, ring or blocks fails, a head pair's
lanes swapped in a 128-lane row read over 1, and the planted storage controls (a
bfloat16 state arena, `plant_kv_store`) read above the program.  The limits of the
cell's own size, and the controls' readings there, are in PERF.md section 2."""
import argparse
import functools
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "phi4flash-serve-1chip.offline-reason"
NUMBERS = ("state_rel_err", "ring_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    driver = common.load_module("drivers", "serve_kinds")
    return ctx, driver, driver.build(ctx)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    ctx, driver, st = built(seed)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    by_layer = sound["held_rel_err_by_layer"]
    assert {k: len(v) for k, v in by_layer.items()} == {
        "ssm.state": 3, "ssm.conv": 3, "sliding_attention.k_ring": 2, "sliding_attention.v_ring": 2,
        "full_attention.k": 1, "full_attention.v": 1}
    assert (sound["decode_path"], sound["lane_pack"], sound["state_arena"]) == ("walk", 2, "float32")
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["kv_rel_err_max"] < 1e-3, exact
    assert max(exact["held_rel_err_by_layer"]["ssm.state"]) < 1e-3


@pytest.mark.parametrize("fault", ["state", "ring", "blocks", "lanes"])
def test_what_another_request_or_another_head_pair_holds_fails(fault, monkeypatch):
    ctx, driver, st = built(12)
    eng = st["engine"]
    held, seen = eng.held, []

    def swapped(handle):
        seen.append(held(handle))
        got = dict(seen[-1])
        if fault == "lanes":                               # the two heads of a 128-lane row, each in the other's lanes
            got["k_ring"] = got["k_ring"][:, ::-1]
        elif len(seen) > 1 and fault == "state":           # every request after the first is handed the first's
            got["state"] = seen[0]["state"]
        elif len(seen) > 1 and fault == "ring":
            got["k_ring"] = seen[0]["k_ring"]
        elif len(seen) > 1:
            n = min(got["k"].shape[2], seen[0]["k"].shape[2])
            got["k"] = got["k"].at[:, :, :n].set(seen[0]["k"][:, :, :n])
        return got

    monkeypatch.setattr(eng, "held", swapped)
    faulty = driver.check(ctx, st)
    eng.shutdown(drain=False)
    number = {"state": "state_rel_err", "ring": "ring_rel_err", "blocks": "kv_rel_err", "lanes": "ring_rel_err"}[fault]
    assert not faulty["ok"] and faulty[number] > 5 * faulty[number + "_limit"], faulty
    if fault == "lanes":
        assert faulty["ring_rel_err"] > 1.0 and faulty["kv_rel_err_max"] > 1.0
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


@pytest.mark.parametrize("control", ["state_arena", "kv_store"])
def test_the_planted_stores_read_above_the_program(control, monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine, kv_pool, paged_attention

    ctx, driver, st = built(13)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    if control == "state_arena":
        monkeypatch.setattr(kv_pool.StatePool, "STATE_DTYPE", jnp.dtype("bfloat16"))
        number = "state_rel_err"
    else:
        monkeypatch.setattr(generate, "diff_attention", generate.diff_attention)      # restored after the plant
        monkeypatch.setattr(paged_attention, "diff_attention", paged_attention.diff_attention)
        driver.plant_kv_store("float8_e4m3fn")
        number = "ring_rel_err"
    ctx, driver, st = built(13)
    planted = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    # at the rehearsal's widths the bfloat16 weights' own rounding is most of the state's error: the arena adds half again
    assert planted[number] > (1.4 if control == "state_arena" else 3) * sound[number], (sound, planted)
