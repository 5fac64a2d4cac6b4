"""`drivers/serve_rows.py` (the check is `serve_kinds.py`'s) on the new cell at the
rehearsal size, on the CPU: the program as configured passes every number of the
comparison, the program in float32 reads what the reference reads, a request
handed another request's state or blocks fails, the storage controls (a bfloat16
state arena planted in the pool, the engine's own fp8 K/V arena) read above the
program, and the window's counts of the expert share's rows reach the readers.
The operations and bytes of the two new kernel files against a hand count at the
cell's shapes.  The limits of the cell's own size, and the controls' readings
there, are in PERF.md section 2."""
import argparse
import functools
import types

import jax.numpy as jnp
import pytest

from chipbench import calibrate, common

CELL = "nemotron3super-serve-1chip.offline-rollouts"
NUMBERS = ("state_rel_err", "kv_rel_err", "kv_rel_err_max", "mean_logit_shortfall")


def built(seed, float32=False, **engine):
    ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
    if float32:
        ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
            ctx["arch"].make_params, dtype=jnp.float32)})
    driver = common.load_module("drivers", "serve_rows")
    return ctx, driver, driver.build(ctx, **engine)


@pytest.mark.parametrize("seed", [11, 2**31 + 13])
def test_the_program_as_configured_passes_and_in_float32_reads_nothing(seed):
    ctx, driver, st = built(seed)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert sound["ok"] and all(sound[n] <= sound[n + "_limit"] for n in NUMBERS), sound
    assert {k: len(v) for k, v in sound["held_rel_err_by_layer"].items()} == {
        "mamba2.state": 2, "mamba2.conv": 2, "full_attention.k": 1, "full_attention.v": 1}
    assert (sound["decode_path"], sound["lane_pack"], sound["state_arena"]) == ("walk", 1, "float32")
    ctx, driver, st = built(seed, float32=True)
    exact = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert exact["ok"] and exact["mean_logit_shortfall"] < 1e-3 and exact["kv_rel_err_max"] < 1e-3, exact
    assert max(exact["held_rel_err_by_layer"]["mamba2.state"]) < 1e-3


@pytest.mark.parametrize("fault", ["state", "blocks"])
def test_what_another_request_holds_fails(fault, monkeypatch):
    ctx, driver, st = built(12)
    eng = st["engine"]
    held, seen = eng.held, []

    def swapped(handle):
        seen.append(held(handle))
        got = dict(seen[-1])
        if len(seen) > 1 and fault == "state":             # every request after the first is handed the first's
            got["state"] = seen[0]["state"]
        elif len(seen) > 1:
            n = min(got["k"].shape[2], seen[0]["k"].shape[2])
            got["k"] = got["k"].at[:, :, :n].set(seen[0]["k"][:, :, :n])
        return got

    monkeypatch.setattr(eng, "held", swapped)
    faulty = driver.check(ctx, st)
    eng.shutdown(drain=False)
    number = {"state": "state_rel_err", "blocks": "kv_rel_err"}[fault]
    assert not faulty["ok"] and faulty[number] > 5 * faulty[number + "_limit"], faulty
    assert faulty["mean_logit_shortfall"] <= faulty["mean_logit_shortfall_limit"]      # the tokens see nothing of it


@pytest.mark.parametrize("control", ["state_arena", "kv_dtype"])
def test_the_storage_controls_read_above_the_program(control, monkeypatch):
    from thunder_tpu.serving import engine, kv_pool

    ctx, driver, st = built(13)
    sound = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    monkeypatch.setattr(engine, "_program_cache", {})     # the plant is made before a process's first engine: no program yet
    if control == "state_arena":
        monkeypatch.setattr(kv_pool.StatePool, "STATE_DTYPE", jnp.dtype("bfloat16"))
        ctx, driver, st = built(13)
        number, factor = "state_rel_err", 1.2   # 24-40 steps at these widths: the bfloat16 weights' own rounding is most of the error
    else:
        ctx, driver, st = built(13, kv_dtype="fp8")       # the engine's own arena: no plant
        number, factor = "kv_rel_err", 1.5      # three layers' bfloat16 rounding lies under the attention layer's keys at these widths
    planted = driver.check(ctx, st)
    st["engine"].shutdown(drain=False)
    assert planted[number] > factor * sound[number], (sound, planted)


def test_the_windows_expert_rows_reach_the_readers():
    ctx, driver, st = built(14)
    ctx.update(seconds=1.0, trace_s=0.0, trace_dir=None)
    out = driver.measure(ctx, st, {"ok": True})            # serve.measure as it stands: the counts are dropped
    assert "moe" not in out["counters"]["stats1"]
    st["engine"].shutdown(drain=False)
    ctx, driver, st = built(14)
    ctx.update(seconds=1.0, trace_s=0.0, trace_dir=None)
    ctx["t_process"] = 0.0
    driver.check = lambda ctx, st: {"ok": True}
    out = driver.run(ctx)
    sums0, sums1 = (out["counters"][k]["moe"]["row_sums"] for k in ("stats0", "stats1"))
    assert sums1[0] > sums0[0] > 0
    reader = common.load_module("layer_metrics", "expert_rows_per_step.nemoserve")
    hit = common.load_module("layer_metrics", "experts_hit_share.nemoserve")
    rows = reader.read({"counters": out["counters"]})
    # four rows a step, four choices of sixteen each, four experts held: four rows land on average
    assert 1.0 < rows < 12.0 and 0.2 < hit.read({"counters": out["counters"]}) <= 1.0
    assert reader.read({"counters": {"stats0": {}, "stats1": {}}}) is None          # a parent without the counter


def test_the_new_kernel_files_count_what_a_hand_count_gives():
    _, config, _ = common.open_cell(CELL)
    s = common.load_module("models", config["arch"]).sizes(config)
    assert (s["H"], s["P"], s["G"], s["N"], s["d"]) == (128, 64, 8, 128, 8192)
    peaks = {"bf16_flops_per_sec": 197e12, "hbm_bytes_per_sec": 819e9}
    step = common.load_module("kernels", "ssd_decode_step")
    w = step.call_work(s, 128)
    assert w["bytes"] == 128 * 2 * 128 * 64 * 128 * 4 == 1_073_741_824          # 8.39 MB a row: read and written
    assert step.least_seconds(s, 128, peaks) == pytest.approx(1_073_741_824 / 819e9)    # memory bound: 1.31 ms a layer-step
    assert w["vector_ops"] / (197e12 / 16) < 0.5 * w["bytes"] / 819e9
    chunk = common.load_module("kernels", "ssd_chunk")
    w = chunk.call_work(s, 5120, 128)
    a_token = 2 * (8 * 128 * 128 + 128 * 8192 + 2 * 128 * 8192)                 # C B^T a group; the scores' product; read-out and update
    assert w["flops"] == 5120 * a_token and a_token == 6_553_600
    assert w["bytes"] == 5120 * (8192 * 6 + 2 * 8 * 128 * 2 + 4 * 128 * 4) + 2 * 8192 * 128 * 4
    # 33.6 GFLOP against 291 MB: 0.17 ms of products under 0.36 ms of bytes (y leaves in float32): bound by memory
    assert chunk.least_seconds(s, 5120, 128, peaks) == pytest.approx(w["bytes"] / 819e9)
    assert w["bytes"] / 819e9 > w["flops"] / 197e12
    assert step.matches(types.SimpleNamespace(name="ssd_decode_step.3")) and chunk.matches(
        types.SimpleNamespace(name="ssd_chunk_fwd")) and not chunk.matches(types.SimpleNamespace(name="ssm_scan_fwd"))
