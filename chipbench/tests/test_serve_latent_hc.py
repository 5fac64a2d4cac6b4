"""`drivers/serve_latent_hc.py` and `models/latent_hc_moe_decoder.py`: the new cell's
`--rehearse` run ends `correct` and reports the expert layers' counts; the reference's
parameter count at the published keys is the published "29B-A4B"; each planted control of the
hyper-connection (`plant_hc_control`) reads above the program at the rehearsal size (the
maps in bfloat16 above the float32 program: a bfloat16 program's own rounding hides them).
The limits of the cell's own size, and the controls' readings there, are in PERF.md
section 2 and the mix's `check.why`."""
import argparse
import functools
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "xing4-serve-1chip.offline-digest"


def test_the_cells_rehearsal_ends_correct_and_counts_its_experts_rows():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "THUNDER_TPU_PALLAS_INTERPRET": "1"}
    run = subprocess.run([sys.executable, os.path.join(common.HERE, "run.py"), "--workload", CELL, "--seed", str(2**31 + 17),
                          "--seconds", "3", "--trace", "1", "--rehearse"], capture_output=True, text=True, env=env, cwd=common.ROOT)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.splitlines()[-1])
    assert result["correct"] and result["failed"] == 0 and result["device"]["platform"] == "cpu"
    got = result["metrics"]
    assert got["expert_rows_per_step.nemoserve"]["value"] == 8.0          # 4 rows x 2 a token, every expert held
    assert 0 < got["experts_hit_share.nemoserve"]["value"] <= 1 and "serve_out_tok_per_s" not in got   # a traced run's line


def test_the_count_at_the_published_keys_is_29b_a4b():
    arch = common.load_module("models", "latent_hc_moe_decoder")
    hf = common.config_of(common.cell(CELL))
    published = {**hf, **{k: hf["published_" + k] for k in hf["reduced"]}}
    assert (published["num_hidden_layers"], published["first_k_dense_replace"]) == (40, 2)
    assert arch.attn_params(hf) == 28_411_136 and arch.expert_params(hf) == 11_010_048 and arch.hc_params(hf) == 716_854
    assert arch.layer_params(published, 0) == 128_225_590 and arch.layer_params(published, 2) == 745_017_718
    whole = arch.param_count(published) + published["num_nextn_predict_layers"] * arch.module_params(published)
    assert round(whole / 1e9, 1) == 30.3 and round(arch.active_params(published) / 1e9, 2) == 3.93
    assert round(arch.param_count(hf) / 1e9, 3) == 4.793 and arch.latent_bytes_per_token(hf) == 6912     # the cut


def _reading(float32=False):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), 21)
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    driver = common.load_module("drivers", ctx["config"]["driver"])
    st = driver.build(ctx)
    out = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    return driver, out


# The maps in bfloat16 are told from the program where the program's own rounding is out of the way, in
# float32: beside a bfloat16 program they are not (0.055 for 0.056 on the deepest rows here; 0.101-0.109 for
# 0.102-0.109 at the cell's sizes on the chip, the mix's `check.why`).
@pytest.mark.parametrize("which,float32", [("sinkhorn1", False), ("static", False), ("bfloat16", True)])
def test_a_planted_control_reads_above_the_program(which, float32, monkeypatch):
    from thunder_tpu.models import generate
    from thunder_tpu.serving import engine

    driver, sound = _reading(float32)
    assert sound["ok"], sound
    if float32:         # the program in float32 reads what the reference reads
        assert sound["latent_rel_err_max"] < 1e-3 and sound["mean_logit_shortfall"] < 1e-3, sound
    monkeypatch.setattr(generate, "hc_maps", generate.hc_maps)            # restored after the plant
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    driver.plant_hc_control(which)
    _, control = _reading(float32)
    assert control["latent_rel_err_max"] > (10 if float32 else 2) * sound["latent_rel_err_max"], (sound, control)
