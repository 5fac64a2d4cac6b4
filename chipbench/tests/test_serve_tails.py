"""`drivers/serve_tails.py` at the rehearsal size, on the CPU: the program as configured
passes every number of the comparison, the program in float32 reads what the reference
reads, a request handed another request's blocks or tails fails, a head's lanes swapped
with its neighbour's in the lane-packed rows fail, and the planted storage control
(`plant_tail_store`) reads above the program.  The limits of the cell's own size, and the
controls' readings there, are in PERF.md section 2."""
import argparse
import functools
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "lfm2moe-serve-1chip.offline-wide"
NUMBERS = ("tail_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    driver = common.load_module("drivers", "serve_tails")
    return ctx, driver, driver.build(ctx)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    ctx, driver, st = built(seed)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert len(sound["held_rel_err_by_layer"]["conv"]) == 3 and (sound["decode_path"], sound["lane_pack"]) == ("walk", 2)
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["kv_rel_err_max"] < 1e-3, exact
    assert max(exact["held_rel_err_by_layer"]["conv"]) < 1e-3


@pytest.mark.parametrize("fault", ["blocks", "tails", "lanes"])
def test_what_another_request_or_another_head_holds_fails(fault, monkeypatch):
    ctx, driver, st = built(12)
    eng = st["engine"]
    held, seen = eng.held, []

    def swapped(handle):
        seen.append(held(handle))
        got = dict(seen[-1])
        if fault == "lanes":                               # the two heads of a 128-lane row, each in the other's lanes
            got["k"] = got["k"][:, ::-1]
        elif len(seen) > 1 and fault == "tails":           # every request after the first is handed the first's
            got["conv"] = seen[0]["conv"]
        elif len(seen) > 1:
            n = min(got["k"].shape[2], seen[0]["k"].shape[2])
            got["k"] = got["k"].at[:, :, :n].set(seen[0]["k"][:, :, :n])
        return got

    monkeypatch.setattr(eng, "held", swapped)
    faulty = driver.check(ctx, st)
    eng.shutdown(drain=False)
    number = "tail_rel_err" if fault == "tails" else "kv_rel_err"
    assert not faulty["ok"] and faulty[number] > 5 * faulty[number + "_limit"], faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


def test_the_planted_store_reads_above_the_program(monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine, paged_attention

    ctx, driver, st = built(13)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    monkeypatch.setattr(generate, "shortconv_mixer", generate.shortconv_mixer)      # restored after the plant
    monkeypatch.setattr(paged_attention, "shortconv_mixer", paged_attention.shortconv_mixer)
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    driver.plant_tail_store("float8_e4m3fn")
    ctx, driver, st = built(13)
    control = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert control["tail_rel_err"] > 3 * sound["tail_rel_err"], (sound, control)
