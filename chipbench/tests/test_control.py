"""The comparison that decides `correct`, shown to fail: at the rehearsal size, on the
CPU, the program as configured passes each cell's comparison with the reference and
the control does not.  The control is what the contract names: for a serving cell the
engine's own lower-precision path (`kv_dtype="fp8"` under a bfloat16 configuration),
for a training cell the reference computed in float8, forward and backward, put in
the program's place; there every number compared has to tell the two apart.
The readings at the cells' own sizes, on the chip, are in PERF.md section 2."""
import argparse

import pytest

from chipbench import calibrate, common

CELLS = [w["name"] for w in common.manifest()["workloads"]]
SEEDS = (11, 12, 2**31 + 13)


def context(cell, seed):
    args = argparse.Namespace(workload=cell, rehearse=True)
    ctx = calibrate.context(args, seed)
    return ctx, common.load_module("drivers", ctx["config"]["driver"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_configured_precision_passes_and_the_control_fails(cell):
    for seed in SEEDS:
        ctx, driver = context(cell, seed)
        if ctx["config"]["driver"] == "serve":
            readings = []
            for over in ({}, {"kv_dtype": "fp8"}):
                st = driver.build(ctx, **over)
                readings.append(driver.check(ctx, st))
                st["engine"].shutdown(drain=False)
            sound, control = readings
            numbers = ("mean_logit_shortfall",)
        else:
            st = driver.build(ctx)
            control = driver.check(ctx, st, control=True)
            sound = driver.check(ctx, st)
            numbers = ("layer_grad_rel_err", "grad_rel_err")
            # the gradient compared runs through every block, down to the embedding
            assert sound["leaves_compared"] == 9 * ctx["config"]["num_hidden_layers"] + 3
        assert sound["ok"], (cell, seed, sound)
        assert not control["ok"], (cell, seed, control)
        for number in numbers:
            assert control[number] > 3 * sound[number], (cell, seed, sound[number], control[number])
            assert sound[number] <= sound[number + "_limit"] < control[number]


def test_a_broken_backward_pass_fails_the_train_comparison(monkeypatch):
    """What the head's rows alone let through: a program whose gradient is
    right in the head and wrong below it (here: the blocks' and the
    embedding's gradient scaled by 0.8, as a wrong softmax scale in a backward
    kernel would leave it)."""
    import jax

    cell = next(c for c in CELLS if common.config_of(common.cell(c))["driver"] == "train")
    ctx, driver = context(cell, SEEDS[0])
    st = driver.build(ctx)
    sampled = driver._sampled

    def broken(tree, scale, **kw):
        out = sampled(tree, scale, **kw)
        if scale != 1.0:                                  # the program's moments, not the reference
            out = {k: v if "lm_head" in k or "ln_f" in k else 0.8 * v for k, v in out.items()}
        return out

    monkeypatch.setattr(driver, "_sampled", broken)
    got = driver.check(ctx, st)
    assert got["grad_rel_err"] <= got["grad_rel_err_limit"]          # the head sees nothing
    assert got["layer_grad_rel_err"] > got["layer_grad_rel_err_limit"] and not got["ok"]
