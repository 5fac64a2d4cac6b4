"""`drivers/serve_held.py` at the rehearsal size, on the CPU: the program as configured
passes every number of the comparison, the program in float32 reads what the reference
reads, and a request handed another request's slot or blocks fails.  The two storage
controls (an fp8 K/V arena, a bfloat16 state arena) separate only at the cell's own size,
where a layer is 3,840 wide and a request runs 150 decode steps: their readings on the
chip are in `traffic/offline-longgen.json` (`check.why`) and PERF.md section 2."""
import argparse
import functools
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "olmo-hybrid-serve-1chip.offline-longgen"
NUMBERS = ("state_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


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
    assert len(sound["held_rel_err_by_layer"]["state"]) == 3 and len(sound["held_rel_err_by_layer"]["k"]) == 1
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-4, exact
    assert max(max(v) for v in exact["held_rel_err_by_layer"].values()) < 1e-4, exact


@pytest.mark.parametrize("what", ["state", "k"])
def test_another_requests_slot_or_blocks_fail(monkeypatch, what):
    ctx, driver, st = built(12)
    eng = st["engine"]
    held, seen = eng.held, []

    def swapped(handle):
        seen.append(held(handle))
        got = dict(seen[-1])
        if len(seen) > 1:                                  # every request after the first is handed the first's
            n = min(got[what].shape[-2], seen[0][what].shape[-2])
            got[what] = got[what].at[..., :n, :].set(seen[0][what][..., :n, :])
        return got

    monkeypatch.setattr(eng, "held", swapped)
    faulty = driver.check(ctx, st)
    eng.shutdown(drain=False)
    number = "state_rel_err" if what == "state" else "kv_rel_err"
    assert not faulty["ok"] and faulty[number] > 10 * faulty[number + "_limit"], faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it
