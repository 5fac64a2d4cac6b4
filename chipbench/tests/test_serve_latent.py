"""`drivers/serve_latent.py` at the rehearsal size, on the CPU: the program as configured
passes every number of the comparison, the program in float32 reads what the reference
reads, a request handed another request's rows fails, and the planted storage control
(`plant_latent_store`) reads above the program.  The limits of the cell's own size, and
the control's readings there, are in PERF.md section 2."""
import argparse
import functools
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "axk1-serve-1chip.offline-longctx"
NUMBERS = ("latent_rel_err", "latent_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    driver = common.load_module("drivers", ctx["config"]["driver"])
    return ctx, driver, driver.build(ctx)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    ctx, driver, st = built(seed)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert len(sound["latent_rel_err_by_layer"]) == 3 and sound["arena_kind"] == "latent"
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["latent_rel_err_max"] < 1e-3, exact


def test_another_requests_rows_fail(monkeypatch):
    ctx, driver, st = built(12)
    eng = st["engine"]
    held, seen = eng.held, []

    def swapped(handle):
        seen.append(held(handle))
        got = dict(seen[-1])
        if len(seen) > 1:                                  # every request after the first is handed the first's
            n = min(got["latent"].shape[1], seen[0]["latent"].shape[1])
            got["latent"] = got["latent"].at[:, :n].set(seen[0]["latent"][:, :n])
        return got

    monkeypatch.setattr(eng, "held", swapped)
    faulty = driver.check(ctx, st)
    eng.shutdown(drain=False)
    assert not faulty["ok"] and faulty["latent_rel_err"] > 10 * faulty["latent_rel_err_limit"], faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


def test_the_planted_store_reads_above_the_program(monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine

    ctx, driver, st = built(13)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    monkeypatch.setattr(generate, "mla_latent", generate.mla_latent)      # restored after the plant
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    driver.plant_latent_store("float8_e4m3fn")
    ctx, driver, st = built(13)
    control = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert control["latent_rel_err"] > 3 * sound["latent_rel_err"], (sound, control)
