"""Benchmarks as tests (reference benchmarks/targets.py:402-700 pytest
targets, SURVEY §4 "Benchmarks as tests").

Runs every bench.py harness mode at CPU smoke shapes so the benchmark code
itself is CI-policed — the reference keeps its benchmark classes importable
and pytest-runnable the same way.  Also unit-tests the timing helpers (every
timed loop ends in ``jax.block_until_ready``) and the rule that a mode which
times a device fails without a TPU instead of falling back to the CPU.

Harness-mode runs that cost more than a few seconds are ``slow``-marked per
the ROADMAP tier-1 budget policy (the 870 s window must fit the whole
suite); the committed-artifact and regression gates below stay in the fast
lane, so every BENCH_*.json target is still policed on every run."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench


class TestTimingHelpers:
    def test_time_fn_is_one_warmup_plus_iters_calls(self):
        calls = []
        fn = lambda x: (calls.append(1), x * 2.0)[1]
        dt = bench._time_fn(fn, jnp.ones((8, 8)), iters=3)
        assert dt > 0 and len(calls) == 4

    def test_time_steps_threads_state_and_blocks(self):
        step = jax.jit(lambda p, o, k: (p + k, o * 2.0, p.sum()))
        dt, state = bench.time_steps(lambda p, o: step(p, o, 1.0), 3, jnp.zeros(4), jnp.ones(4))
        assert dt > 0
        # three chained steps: the (params, opt) pair of each feeds the next
        assert float(state[0][0]) == 3.0 and float(state[1][0]) == 8.0

    def test_require_tpu_exits_without_a_chip(self):
        """A mode that times a device has failed when jax found no TPU: no
        probe, no retry, no fall back to the CPU."""
        with pytest.raises(SystemExit) as e:
            bench.require_tpu("sweep")
        assert e.value.code not in (0, None) and "needs a TPU" in str(e.value.code)
        assert bench.device_info()["platform"] == "cpu"

    def test_time_fn_positive(self):
        fn = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((64, 64))
        assert bench._time_fn(fn, x, iters=3) > 0

    def test_best_ms_is_the_least_disturbed_rep(self, monkeypatch):
        vals = iter([0.003, 0.002, 0.001])
        monkeypatch.setattr(bench, "_time_fn", lambda fn, *a: next(vals))
        assert bench._best_ms(None, reps=3) == pytest.approx(1.0)

    def test_mfu_reads_one_table_and_refuses_an_unknown_device(self):
        """Peaks live in examine.DEVICE_PEAKS keyed by device_kind, each with
        its source; a device that is not there is an error, not a default."""
        from thunder_tpu.examine import DEVICE_PEAKS, device_peaks

        assert all(row["source"] for row in DEVICE_PEAKS.values())
        cfg = bench.llama.Config.from_name("tiny-llama-debug")
        peak = device_peaks("TPU v5 lite")["bf16_flops_per_sec"]
        want = 1e6 * bench.model_flops_per_token(cfg, 64) / peak
        assert bench.mfu(1e6, cfg, 64, "TPU v5 lite") == pytest.approx(want)
        for kind in ("cpu", "TPU v9 imaginary"):
            with pytest.raises(ValueError, match="no published peaks"):
                bench.mfu(1e6, cfg, 64, kind)


class TestHarnessTargets:
    @pytest.mark.slow
    def test_micro_benchmarks_cpu(self):
        results = bench.micro_benchmarks(on_tpu=False)
        for name in ("sdpa_ms", "sdpa_nokernel_ms", "cross_entropy_ms",
                     "rms_norm_ms", "block_fwd_ms"):
            assert results[name] > 0, (name, results)

    @pytest.mark.slow
    def test_sweep_benchmarks_cpu(self, tmp_path):
        out = tmp_path / "sweep.json"
        results = bench.sweep_benchmarks(on_tpu=False, out_path=str(out))
        artifact = json.loads(out.read_text())
        assert artifact["backend"] == "cpu"
        assert set(results) == {"gelu", "cross_entropy", "rms_norm", "sdpa_causal",
                                "swiglu_mlp", "sdpa_grad", "ce_grad",
                                "sdpa_decode", "ce_decode", "cross_entropy_halfp"}
        measured = [r for r in results.values() if "error" not in r]
        # every case must measure on CPU — an {'error': ...} entry here means
        # the harness regressed
        assert len(measured) == len(results), results
        for name, r in results.items():
            assert r["thunder_ms"] > 0 and r["jax_ms"] > 0, (name, r)

    def test_dispatch_overhead_bench_cpu(self):
        """The dispatch-overhead microbench (µs/call vs cached
        specializations) must run and report — no perf gate, but the
        counters must show the timed loop dispatching through the keyed
        tier (key hits, no scan blowup)."""
        from thunder_tpu.benchmarks.dispatch import dispatch_overhead_bench

        # CI-affordable sizes: the suite is wall-clock-budgeted, so the full
        # 1/8/64 curve is the `bench.py dispatch` artifact's job, not CI's
        r = dispatch_overhead_bench(spec_counts=(1, 8), iters=20)
        assert set(r) == {"1", "8"}
        for n, row in r.items():
            assert row["us_per_call"] > 0, (n, row)
            assert row["cached_specializations"] == int(n), (n, row)
            assert row["key_hits"] >= 20, (n, row)  # the timed loop itself
            assert row["scan_hits"] == 0 and row["guard_evictions"] == 0, (n, row)

    def test_profile_overhead_bench_cpu(self):
        """The profiling-transform overhead bench (`bench.py profile`) must
        measure all three variants on the llama block target and report the
        profiler's own accounting — no perf gate (host timing jitters), but
        every number must be real."""
        from thunder_tpu.benchmarks.profile_overhead import profile_overhead_bench

        out = profile_overhead_bench(on_tpu=False, iters=10)
        assert out["shapes"]["cfg"] == "tiny-llama-debug"
        r = out["results"]
        for k in ("block_fwd_plain_us", "block_fwd_profiled_us",
                  "block_fwd_profiled_barrier_us"):
            assert r[k] > 0, (k, r)
        assert r["overhead_x"] > 0 and r["barrier_overhead_x"] > 0
        assert r["instrumented_symbols"] >= 1
        # warmup + timed loop all flowed through the instrumented program
        assert r["instrumented_calls"] > r["instrumented_symbols"], r
        assert r["profiled_total_ms"] > 0

    @pytest.mark.slow
    def test_dist_throughput_smoke(self):
        results = bench.dist_throughput_smoke()
        assert results and all(v > 0 for v in results.values())

    @pytest.mark.slow
    def test_benchmark_classes_cpu(self, tmp_path):
        """Every class in the benchmark library (per-op, per-block,
        per-model tiers — reference benchmarks/__init__.py:50-460) must
        measure at toy dims; an {'error': ...} row means the harness
        regressed."""
        out = tmp_path / "blocks.json"
        rows = bench.blocks_benchmarks(on_tpu=False, out_path=str(out))
        artifact = json.loads(out.read_text())
        assert artifact["backend"] == "cpu"
        tiers = {r["tier"] for r in rows}
        assert tiers == {"op", "block", "model", "ablation"}, rows
        # the model tier must span the zoo: every family benches loss+grad
        model_names = {r["name"] for r in rows if r["tier"] == "model"}
        for fam in ("llama2", "gpt2", "mistral_sw", "gemma", "falcon", "pythia", "moe"):
            assert f"{fam}_loss" in model_names and f"{fam}_grad" in model_names, model_names
        for r in rows:
            assert "error" not in r, r
            assert r["thunder_ms"] > 0, r

    @pytest.mark.slow
    def test_scaling_table_cpu(self, tmp_path):
        """The distributed scaling + training-knob table must produce a
        tokens/s number for every mode × mesh size (reference's distributed
        benchmark runner analog) plus the deterministic knob sweeps the
        scaling TargetSpec gates."""
        out = tmp_path / "scaling.json"
        art = bench.scaling_table(out_path=str(out))
        table = art["results"]["modes"]
        assert set(table) == {"ddp", "fsdp", "tp"}
        for mode, row in table.items():
            assert set(row) == {"1", "2", "4", "8"}, (mode, row)
            assert all(v > 0 for v in row.values()), (mode, row)
        assert art["results"]["restart_loss_bitident"] is True
        assert json.loads(out.read_text())["results"]["modes"] == table

    @pytest.mark.slow
    def test_decode_benchmark_cpu(self):
        results = bench.decode_benchmark(on_tpu=False)
        assert results["fp"] > 0 and results["int8"] > 0
        assert results["speculative"] > 0

    @pytest.mark.slow
    def test_headline_runs_at_toy_dims(self):
        """compiled_run/baseline_run (the headline's two timed runs) work and
        agree on loss at toy dims.  The full driver path incl. report assembly
        is driven by test_headline_preflight_subprocess below."""
        import optax

        cfg = bench.llama.Config.from_name(
            "Llama-2-7b-hf", n_layer=2, n_embd=128, n_head=4,
            intermediate_size=344, vocab_size=256,
        )
        tps = bench.compiled_run(cfg, 2, 64, optax.adamw(1e-4), 2)
        base = bench.baseline_run(cfg, 2, 64, optax.adamw(1e-4), 2)
        assert tps > 0 and base > 0

    @pytest.mark.slow
    def test_headline_without_a_tpu_fails_and_prints_no_result(self):
        """``python bench.py`` end to end on this CPU-only machine: non-zero
        exit, nothing on stdout — a CPU timing is never printed under the
        headline's name."""
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, str(Path(bench.__file__))],
            capture_output=True, text=True, timeout=600, env=dict(os.environ),
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == "" and "needs a TPU" in proc.stderr

    @pytest.mark.slow
    def test_mixtral_decode_smoke_subprocess(self):
        """Milestone E tool (tools/mixtral_decode.py): the --smoke path runs
        the same routing/int8-decode/depth-fit code on toy sizes, so a
        broken tool does not waste a chip-tool call.  It prints counts, not
        CPU rates."""
        import os
        import subprocess

        tool = Path(bench.__file__).parent / "tools" / "mixtral_decode.py"
        proc = subprocess.run(
            [sys.executable, str(tool), "--smoke"],
            capture_output=True, text=True, timeout=900, env=dict(os.environ),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["smoke"] is True and out["depths"] == [1, 2]
        assert "predicted_8x7b_tokens_per_sec" in out["fit_keys"]

    @pytest.mark.slow
    def test_cost_mode_subprocess(self):
        """`bench.py cost`: the analytic roofline companion must emit one
        JSON line with a finite compute-bound tokens/s at headline shapes
        (shape-only lowering — runs in seconds on CPU)."""
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, str(Path(bench.__file__)), "cost"],
            capture_output=True, text=True, timeout=600, env=dict(os.environ),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["metric"] == "compute_roofline_tokens_per_sec"
        assert out["value"] > 0 and out["fwd_bwd"]["flops"] > out["fwd"]["flops"] > 0

    def test_kernel_tune_smoke_subprocess(self):
        """tools/kernel_tune.py --smoke: the CE geometry sweep + decision
        format at toy dims on CPU, WITHOUT touching the committed tuning
        file — a tool that crashes would waste a chip-tool call."""
        import os
        import subprocess

        tool = Path(bench.__file__).parent / "tools" / "kernel_tune.py"
        tuning = Path(bench.__file__).parent / "thunder_tpu" / "executors" / "pallas_tuning.json"
        before = tuning.read_bytes() if tuning.exists() else None
        proc = subprocess.run(
            [sys.executable, str(tool), "--smoke"],
            capture_output=True, text=True, timeout=900, env=dict(os.environ),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["smoke"] is True and out["ce_rows"] >= 1
        after = tuning.read_bytes() if tuning.exists() else None
        assert after == before, "smoke must not write/alter the tuning file"

    def test_all_tools_compile(self):
        """Every tool must at least byte-compile: the TPU-only ones
        (flash_tune, ...) need the chip and cannot EXECUTE in CI, but a
        syntax error must not lurk until a chip-tool call is spent on it."""
        import py_compile

        tools_dir = Path(bench.__file__).parent / "tools"
        tools = sorted(tools_dir.glob("*.py"))
        assert len(tools) >= 4, tools
        for t in tools:
            py_compile.compile(str(t), doraise=True)

    def test_device_modes_fail_without_a_tpu(self, monkeypatch, capsys):
        """Every bench mode that times a device exits non-zero on this
        CPU-only machine, before measuring and without printing a result
        line — no subprocess probe, no re-exec onto the CPU, no stale chip
        record embedded in its place."""
        for argv in (["bench.py"], ["bench.py", "blocks"], ["bench.py", "micro"],
                     ["bench.py", "sweep"], ["bench.py", "decode"]):
            monkeypatch.setattr(sys, "argv", argv)
            with pytest.raises(SystemExit) as e:
                bench.main()
            assert e.value.code not in (0, None), argv
            assert capsys.readouterr().out == "", argv
        src = Path(bench.__file__).read_text()
        for gone in ("last_tpu", "execve", "BENCH_MAX_WAIT", "subprocess"):
            assert gone not in src, gone

    def test_donation_bench_cpu(self):
        """The buffer-donation microbench (`bench.py donation`) must show a
        real peak-bytes reduction on the llama-block train step (the del-aware
        estimate is exact about what XLA may reuse) and pass the donate=False
        overhead gate: the donation pass must never touch the donate=False
        path."""
        from thunder_tpu.benchmarks.donation import donation_bench
        from tools.bench_targets import check_donation_off_overhead

        out = donation_bench(on_tpu=False, iters=8)
        assert out["shapes"]["cfg"] == "tiny-llama-debug"
        r = out["results"]
        # the tentpole's headline: donation lowers the peak (optimizer update
        # writes into the donated dead params/grads instead of a third copy)
        assert r["update_peak_bytes_on"] < r["update_peak_bytes_off"], r
        assert r["peak_bytes_saved"] > 0 and r["peak_reduction_pct"] > 0
        assert r["buffers_donated"] > 0 and r["bytes_donated"] > 0
        assert r["aliased_outputs"] > 0
        for k in ("steps_per_sec_donate_on", "steps_per_sec_donate_off",
                  "steps_per_sec_plain"):
            assert r[k] > 0, (k, r)
        # CI gate: live measurement AND the committed artifact
        assert check_donation_off_overhead(r) > 0

    def test_bench_target_gates_on_committed_artifacts(self):
        """tools/bench_targets.py must hold against what is committed: the
        BENCH_DONATION.json overhead ratio and the BENCH_MICRO.json schema
        the sweep/tuning tools parse.  A regression recorded into either
        artifact fails CI here, not in a wasted chip-tool call."""
        from tools.bench_targets import (
            check_donation_off_overhead,
            check_micro_baseline_schema,
            load_artifact,
        )

        donation = load_artifact("BENCH_DONATION.json")
        assert donation["results"]["peak_bytes_saved"] > 0
        assert check_donation_off_overhead(donation["results"]) > 0
        micro = check_micro_baseline_schema()
        assert micro["backend"] in ("cpu", "tpu")

    def test_anomaly_overhead_bench_cpu(self):
        """The anomaly-detection overhead bench (`bench.py anomaly`) must
        measure plain vs anomaly-mode dispatch on the llama block target —
        no perf gate (host timing jitters), but every number must be real
        and a healthy input must detect nothing."""
        from thunder_tpu.benchmarks.anomaly_overhead import anomaly_overhead_bench

        out = anomaly_overhead_bench(on_tpu=False, iters=10)
        assert out["shapes"]["cfg"] == "tiny-llama-debug"
        r = out["results"]
        for k in ("block_fwd_plain_us", "block_fwd_anomaly_us"):
            assert r[k] > 0, (k, r)
        assert r["overhead_x"] > 0
        assert r["checked_symbols"] >= 1
        assert r["anomalies_detected"] == 0, r



#
# Committed-artifact target gates (tools/bench_targets.py), one spec per
# BENCH_*.json target.  Every target runs the same trio — gate the committed
# artifact, reject hand-mutated regressions, live-smoke the harness — so the
# trio is a parametrized helper, not a copy-pasted class per target.  The
# spec fields carry everything target-specific:
#
# - ``committed``: extra assertions on the committed artifact beyond the
#   check function itself (each target's headline number restated, so a
#   silently-relaxed check function still fails CI here).
# - ``regressions``: (mutator, match) pairs — the mutator corrupts a deep
#   copy of the committed ``results`` and the check must raise an
#   ``AssertionError`` matching ``match`` (``None`` = any message, used for
#   schema/key deletions).
# - ``smoke``/``smoke_check_kwargs``/``smoke_extra``: the live harness run
#   at CI-affordable shapes, checked with jitter-sensitive gates relaxed
#   (deterministic gates — parity, purity, conservation, blocks ratios —
#   stay on); marked slow.
#


class TargetSpec(NamedTuple):
    name: str
    artifact: str
    check: str                      # attribute of tools.bench_targets
    committed: "Callable[[dict], None] | None" = None
    regressions: tuple = ()
    smoke: "Callable[[], dict] | None" = None
    smoke_check_kwargs: dict = {}
    smoke_extra: "Callable[[dict], None] | None" = None


def _set(key, value):
    return lambda r: r.__setitem__(key, value)


def _del(key):
    return lambda r: r.pop(key)


# -- per-target extras that need more than a lambda ------------------------

def _serving_committed(art):
    assert art["results"]["throughput_ratio"] >= 1.0


def _async_committed(art):
    assert art["results"]["ttft_p95_improvement_x"] >= 2.0


def _capacity_committed(art):
    assert art["results"]["admitted_ratio"] >= 3.0
    assert art["results"]["adapter_mix_new_programs_after_register"] == 0


def _mesh_committed(art):
    assert art["results"]["throughput_ratio"] >= 1.0
    assert art["results"]["mesh_axes"]["tp"] >= 2


def _tracing_committed(art):
    assert art["results"]["off_overhead_x"] <= 1.05


def _recovery_committed(art):
    r = art["results"]
    assert r["faults_off_overhead_x"] <= 1.05
    assert r["injected_fault_token_parity"] is True
    assert r["speedup_x"] >= 1.0


def _paged_attn_committed(art):
    assert art["results"]["parity_ok"] is True
    assert art["results"]["paged_arena_gathers"] == 0


def _spec_committed(art):
    assert art["results"]["speedup_x"] >= 1.2
    assert art["results"]["acceptance_rate"] >= 0.5


def _dp_committed(art):
    r = art["results"]
    assert r["throughput_ratio"] >= 1.6
    assert r["affinity_hits"] >= 1
    assert r["imbalance"] == 0


def _multistep_committed(art):
    r = art["results"]
    assert r["horizons"][0] == 1 and len(r["horizons"]) >= 2
    top = str(max(r["horizons"]))
    assert (r["per_horizon"][top]["tokens_per_host_visit"]
            > r["per_horizon"]["1"]["tokens_per_host_visit"])


def _sessions_committed(art):
    r = art["results"]
    assert r["ttft_resident_ms"] < r["ttft_cold_ms"]
    assert r["preempt_p95_ms"] < r["fifo_p95_ms"]


def _goodput_committed(art):
    r = art["results"]
    assert r["spec_draft_tokens"] >= r["spec_accepted_tokens"] > 0
    assert r["off_ms"] > 0 and r["on_ms"] > 0


def _ragged_committed(art):
    r = art["results"]
    assert r["blocks_ratio_x"] >= 2.0
    assert r["warm_engine_new_programs"] == 0
    assert r["chunk_attn_mode"] == "paged"


def _scaling_committed(art):
    r = art["results"]
    assert r["remat_peak_reduction_frac"] >= 0.15
    assert r["overlap_grad_parity"] is True
    assert r["restart_loss_bitident"] is True
    assert r["restart_restarts"] >= 1


def _scaling_flatten_remat(r):
    r["remat"]["full_block"]["peak_bytes"] = r["remat"]["none"]["peak_bytes"] + 1


def _scaling_grow_accum(r):
    ks = sorted(r["accum"], key=int)
    r["accum"][ks[-1]]["peak_bytes"] = r["accum"][ks[0]]["peak_bytes"] + 1


def _scaling_shrink_buckets(r):
    finest = min(r["overlap"], key=float)
    r["overlap"][finest]["n_buckets"] = 1


def _compiles_over_bound(key="decode_compiles"):
    return lambda r: r.__setitem__(key, r["bucket_bound"] + 1)


def _multistep_flatten_top(r):
    top = str(max(r["horizons"]))
    r["per_horizon"][top]["host_visits_per_token"] = (
        r["per_horizon"]["1"]["host_visits_per_token"])


def _multistep_compiles_over_bound(r):
    top = str(max(r["horizons"]))
    r["per_horizon"][top]["decode_compiles"] = (
        r["per_horizon"][top]["bucket_bound"] + 1)


# -- live smoke runners (lazy imports: slow-marked tests only) -------------

def _smoke_serving():
    from thunder_tpu.benchmarks.serving import serving_bench
    return serving_bench(on_tpu=False, smoke=True)


def _smoke_serving_async():
    from thunder_tpu.benchmarks.serving_async import serving_async_bench
    return serving_async_bench(on_tpu=False, smoke=True)


def _smoke_capacity():
    from thunder_tpu.benchmarks.capacity import capacity_bench
    return capacity_bench(on_tpu=False, smoke=True)


def _smoke_serving_mesh():
    from thunder_tpu.benchmarks.serving_mesh import serving_mesh_bench
    return serving_mesh_bench(on_tpu=False, smoke=True)


def _smoke_tracing():
    from thunder_tpu.benchmarks.tracing_overhead import tracing_overhead_bench
    return tracing_overhead_bench(on_tpu=False, reps=2, n_requests=3, max_new=4)


def _smoke_recovery():
    from thunder_tpu.benchmarks.recovery import recovery_bench
    return recovery_bench(on_tpu=False, smoke=True)


def _smoke_paged_attn():
    from thunder_tpu.benchmarks.paged_attention import paged_attention_bench
    return paged_attention_bench(on_tpu=False, reps=1, n_requests=2, max_new=4)


def _smoke_serving_spec():
    from thunder_tpu.benchmarks.serving_spec import serving_spec_bench
    return serving_spec_bench(on_tpu=False, smoke=True)


def _smoke_serving_dp():
    from thunder_tpu.benchmarks.serving_dp import serving_dp_bench
    return serving_dp_bench(on_tpu=False, smoke=True)


def _smoke_multistep():
    from thunder_tpu.benchmarks.multistep import multistep_bench
    return multistep_bench(on_tpu=False, smoke=True)


def _smoke_sessions():
    from thunder_tpu.benchmarks.sessions import sessions_bench
    return sessions_bench(on_tpu=False, smoke=True)


def _smoke_goodput():
    from thunder_tpu.benchmarks.goodput import goodput_bench
    return goodput_bench(on_tpu=False, smoke=True)


def _smoke_ragged():
    from thunder_tpu.benchmarks.ragged import ragged_bench
    return ragged_bench(on_tpu=False, smoke=True)


def _smoke_scaling():
    # scaling_table writes its artifact — the smoke must land in a temp
    # path, never over the committed BENCH_SCALING.json
    import os
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        return bench.scaling_table(out_path=os.path.join(d, "scaling.json"), smoke=True)



# -- live-smoke extra assertions (deterministic facts the relaxed check
#    kwargs turned off must still hold at smoke shapes) ---------------------

def _smoke_extra_smoke_flag(r):
    assert r["smoke"] is True, r


def _smoke_extra_parity_exact(r):
    assert r["smoke"] is True, r
    assert r["token_parity_exact"] is True, r


def _smoke_extra_serving(r):
    assert r["smoke"] is True, r
    assert r["mean_batch_occupancy"] > 1.0, r


def _smoke_extra_serving_async(r):
    assert r["smoke"] is True, r
    assert r["token_parity_exact"] is True, r
    assert r["chunk_runs"] > 0, r


def _smoke_extra_serving_mesh(r):
    assert r["smoke"] is True, r
    assert r["token_parity"] is True, r


def _smoke_extra_tracing(r):
    assert r["async_spans"] > 0, r
    assert r["slo_dimensions"] == 4, r


def _smoke_extra_recovery(r):
    assert r["smoke"] is True, r
    assert r["injected_fault_recoveries"] >= 1, r


def _smoke_extra_paged_attn(r):
    assert r["parity_ok"] is True, r


def _smoke_extra_serving_spec(r):
    assert r["smoke"] is True, r
    assert r["token_parity_exact"] is True, r
    assert r["acceptance_rate"] == 1.0, r


def _smoke_extra_goodput(r):
    assert r["smoke"] is True, r
    assert r["conservation_exact"] is True, r


def _smoke_extra_ragged(r):
    assert r["smoke"] is True, r
    assert r["parity_ok"] is True and r["chunk_parity_ok"] is True, r


def _smoke_extra_scaling(r):
    assert r["overlap_grad_parity"] is True, r
    assert r["restart_loss_bitident"] is True, r
    assert r["remat_loss_max_delta"] == 0.0, r


TARGETS = [
    TargetSpec(
        # continuous batching >= sequential generate() in tokens/sec, real
        # occupancy, compiles inside the bucket bound
        name="serving", artifact="BENCH_SERVING.json",
        check="check_serving_targets", committed=_serving_committed,
        regressions=(
            (_set("mean_batch_occupancy", 1.0), "occupancy"),
            (_set("throughput_ratio", 0.8), "lost to sequential"),
            (_compiles_over_bound(), "bucket bound"),
            (_set("cold_compile_prefills_measured", 2), "cold starts"),
            (_del("serving_tokens_per_sec"), None),
        ),
        smoke=_smoke_serving, smoke_check_kwargs={"min_ratio": 0.0},
        smoke_extra=_smoke_extra_serving,
    ),
    TargetSpec(
        # short-cohort TTFT p95 >= 2x better under long-prompt contention,
        # exact parity, real chunking/overlap, chunk-extended bucket bound
        name="serving_async", artifact="BENCH_SERVING_ASYNC.json",
        check="check_serving_async_targets", committed=_async_committed,
        regressions=(
            (_set("ttft_p95_improvement_x", 1.5), "not protecting TTFT"),
            (_set("token_parity_exact", False), "diverged"),
            (_set("chunk_runs", 0), "not actually chunked"),
            (_set("overlap_frac_mean", 0.0), "not overlapping"),
            (_compiles_over_bound(), "bucket"),
            (_set("cold_compile_prefills_measured", 1), "cold"),
            (_del("async_short_ttft_p95_s"), None),
        ),
        smoke=_smoke_serving_async,
        smoke_check_kwargs={"min_improvement": 0.0},
        smoke_extra=_smoke_extra_serving_async,
    ),
    TargetSpec(
        # int8 pool admits >= 3x at equal arena bytes with exact parity and
        # the zero-recompile adapter contract (bytes properties: the full
        # gate applies even at smoke shapes)
        name="capacity", artifact="BENCH_CAPACITY.json",
        check="check_capacity_targets", committed=_capacity_committed,
        regressions=(
            (_set("admitted_ratio", 2.5), "capacity multiple"),
            (_set("token_parity_exact", False), "diverged"),
            (_set("kv_quant_rel_err", 0.5), "tolerance"),
            (_set("kv_quant_rel_err", 0.0), "tolerance"),
            (lambda r: r.__setitem__(
                "int8_admitted_peak", r["baseline_admitted_peak"]),
             "no capacity"),
            (_set("adapter_mix_new_programs_after_register", 1),
             "leaked into the program cache"),
            (_set("adapter_mix_max_distinct", 2), "multi-tenant"),
            (_compiles_over_bound(), "bucket bound"),
            (_del("admitted_ratio"), None),
        ),
        smoke=_smoke_capacity,
        smoke_extra=_smoke_extra_smoke_flag,
    ),
    TargetSpec(
        # SPMD engine >= single-device at equal total batch, parity vs solo
        # sharded generate(), per-(mesh, bucket) bound, arena actually sharded
        name="serving_mesh", artifact="BENCH_SERVING_MESH.json",
        check="check_serving_mesh_targets", committed=_mesh_committed,
        regressions=(
            (_set("throughput_ratio", 0.8), "lost to the single-device"),
            (_set("token_parity", False), "diverged"),
            (_compiles_over_bound(), "bucket bound"),
            (lambda r: r.__setitem__(
                "arena_shard_bytes", r["arena_total_bytes"]), "not sharded"),
            (_set("collectives_decode", {"total": 0}), "no collectives"),
            (_set("mesh_devices", 1), "one device"),
            (_del("mesh_tokens_per_sec"), None),
        ),
        smoke=_smoke_serving_mesh, smoke_check_kwargs={"min_ratio": 0.0},
        smoke_extra=_smoke_extra_serving_mesh,
    ),
    TargetSpec(
        # serving observability costs nothing when off; the armed run
        # actually recorded spans/SLO/flight data
        name="tracing", artifact="BENCH_TRACING.json",
        check="check_tracing_targets", committed=_tracing_committed,
        regressions=(
            (_set("off_overhead_x", 1.2), "cost nothing when off"),
            (_set("async_spans", 0), "not actually on"),
            (_del("flight_events"), None),
        ),
        smoke=_smoke_tracing, smoke_check_kwargs={"max_off_ratio": 100.0},
        smoke_extra=_smoke_extra_tracing,
    ),
    TargetSpec(
        # armed-but-silent FaultPlan is free and program-identical; injected
        # faults drain bit-identical; re-prefill recovery beats cold restart
        name="recovery", artifact="BENCH_RECOVERY.json",
        check="check_recovery_targets", committed=_recovery_committed,
        regressions=(
            (_set("faults_off_overhead_x", 1.2), "unfaulted hot path"),
            (_set("programs_added_when_armed", 1), "byte-identical"),
            (_set("injected_fault_token_parity", False), "recovery guarantee"),
            (_set("injected_fault_recoveries", 0), "never recovered"),
            (_set("pool_clean_after_faulted_drain", False), "leaking blocks"),
            (_set("recovered_token_parity", False), "re-prefill replay"),
            (_set("speedup_x", 0.5), "reason to exist"),
            (_del("recovery_s"), None),
        ),
        smoke=_smoke_recovery,
        smoke_check_kwargs={"max_off_ratio": 100.0, "min_speedup": 0.0},
        smoke_extra=_smoke_extra_recovery,
    ),
    TargetSpec(
        # paged decode: token parity, gather/scatter-free program (gather
        # program as live positive control), arena-traffic ratio > 1
        name="paged_attn", artifact="BENCH_PAGED_ATTN.json",
        check="check_paged_attn_targets", committed=_paged_attn_committed,
        regressions=(
            (_set("parity_ok", False), "bit-exactness contract"),
            (_set("paged_scatters", 3), "leaked into the paged"),
            (_set("gather_arena_gathers", 0), "positive control went blind"),
            (_set("arena_traffic_ratio_x", 0.9), "fewer arena bytes"),
            (_del("kernel_steps"), None),
        ),
        smoke=_smoke_paged_attn,
        smoke_extra=_smoke_extra_paged_attn,
    ),
    TargetSpec(
        # speculative lane: >= 1.2x at occupancy 8 with exact parity, live
        # acceptance histogram, compile-free measured window
        name="serving_spec", artifact="BENCH_SERVING_SPEC.json",
        check="check_serving_spec_targets", committed=_spec_committed,
        regressions=(
            (_set("speedup_x", 1.1), "not\\s+amortizing"),
            (_set("token_parity_exact", False), "diverged"),
            (_set("spec_rounds", 0), "never engaged"),
            (_set("acceptance_rate", 0.1), "not proposing"),
            (_compiles_over_bound("draft_decode_compiles"), "bucket"),
            (_set("cold_compile_prefills_measured", 2), "cold"),
            (_del("accept_len_hist"), None),
        ),
        smoke=_smoke_serving_spec, smoke_check_kwargs={"min_ratio": 0.0},
        smoke_extra=_smoke_extra_serving_spec,
    ),
    TargetSpec(
        # routed 2-replica fleet: shape-segregation win >= 1.6x, exact
        # parity, both lanes live with affinity hits
        name="serving_dp", artifact="BENCH_SERVING_DP.json",
        check="check_serving_dp_targets", committed=_dp_committed,
        regressions=(
            (_set("throughput_ratio", 1.2), "not paying for the router"),
            (_set("token_parity_exact", False), "diverged"),
            (_set("affinity_hits", 0), "affinity"),
            (_set("routed_by_replica", [16, 0]), "collapsed"),
            (lambda r: r.__setitem__("routed", r["routed"] - 1), "never left"),
            (_compiles_over_bound(), "bucket"),
            (_set("cold_compile_prefills_measured", 2), "cold"),
            (_del("routed_by_replica"), None),
        ),
        smoke=_smoke_serving_dp, smoke_check_kwargs={"min_ratio": 0.0},
        smoke_extra=_smoke_extra_parity_exact,
    ),
    TargetSpec(
        # multi-step decode: visits/token at horizon N within 1.1x of 1/N,
        # exact parity (visit counts are deterministic: full gate at smoke)
        name="multistep", artifact="BENCH_MULTISTEP.json",
        check="check_multistep_targets", committed=_multistep_committed,
        regressions=(
            (_set("token_parity_exact", False), "diverged"),
            (_multistep_flatten_top, "not amortizing"),
            (_multistep_compiles_over_bound, "bucket"),
            (_set("cold_compile_prefills_measured", 2), "cold"),
            (lambda r: r["per_horizon"].pop("1"), None),
        ),
        smoke=_smoke_multistep,
        smoke_extra=_smoke_extra_parity_exact,
    ),
    TargetSpec(
        # stateful serving: resident turn-2 TTFT >= 2x cold with identical
        # tokens, preemption beats FIFO starvation, constraint schemas
        # compile nothing (the skipped prefill dominates even at smoke
        # shapes, so the full gate applies)
        name="sessions", artifact="BENCH_SESSIONS.json",
        check="check_sessions_targets", committed=_sessions_committed,
        regressions=(
            (_set("session_token_parity_exact", False), "diverged"),
            (_set("ttft_speedup_x", 1.2), "re-attach is not"),
            (_set("reattach_hits", 0), "re-attach"),
            (_set("preempt_token_parity_exact", False), "undisturbed"),
            (_set("preemptions", 0), "preemption"),
            (_set("constrained_new_programs", 3), "mask ARGUMENTS"),
            (_set("cold_compile_prefills_measured", 2), "cold"),
            (_del("ttft_speedup_x"), None),
        ),
        smoke=_smoke_sessions,
        smoke_extra=_smoke_extra_smoke_flag,
    ),
    TargetSpec(
        # goodput ledger: exact conservation, <= 1.05x observation overhead,
        # ledger integers equal to spec acceptance counters, zero programs
        name="goodput", artifact="BENCH_GOODPUT.json",
        check="check_goodput_targets", committed=_goodput_committed,
        regressions=(
            (_set("conservation_exact", False), "conservation"),
            (_set("overhead_ratio_x", 1.5), "overhead"),
            (_set("spec_acceptance_exact", False), "acceptance"),
            (_set("new_programs_with_goodput", 2), "programs"),
            (_del("overhead_ratio_x"), None),
        ),
        smoke=_smoke_goodput,
        smoke_check_kwargs={"max_overhead": math.inf},
        smoke_extra=_smoke_extra_goodput,
    ),
    TargetSpec(
        # ragged paged decode + paged chunk prefill: blocks walked >= 2x the
        # real blocks streamed on the mixed cohort (deterministic position
        # math), exact parity for both drives, analytic chunk-traffic ratio,
        # zero new programs on a warm engine (the smoke cohort is smaller,
        # so its blocks gate relaxes to 1.2x; everything else stays on)
        name="ragged", artifact="BENCH_RAGGED.json",
        check="check_ragged_targets", committed=_ragged_committed,
        regressions=(
            (_set("parity_ok", False), "bit-exactness"),
            (_set("chunk_parity_ok", False), "bit-exactness"),
            (_set("blocks_ratio_x", 1.5), "bucket tax"),
            (lambda r: r.__setitem__("blocks_real", r["blocks_walked"]),
             "bucket slack"),
            (_set("chunk_attn_mode", "gather"), "never actually ran"),
            (_set("warm_engine_new_programs", 2), "program identity"),
            (_compiles_over_bound("compiles_total"),
             "leaking program shapes"),
            (_set("chunk_traffic_ratio_x", 0.9), "fewer arena bytes"),
            (_del("blocks_walked"), None),
        ),
        smoke=_smoke_ragged, smoke_check_kwargs={"min_blocks_ratio": 1.2},
        smoke_extra=_smoke_extra_ragged,
    ),
    TargetSpec(
        # production-training knob table: remat peak curve monotone with a
        # >= 15% full_block reduction at bit-stable loss, accum peak curve
        # nonincreasing over k, overlap bucket monotonicity + grad parity
        # vs plain SPMD, and the mid-run-kill elastic restart bit-identical
        # (all deterministic facts — the full gate applies at smoke shapes)
        name="scaling", artifact="BENCH_SCALING.json",
        check="check_scaling_targets", committed=_scaling_committed,
        regressions=(
            (_set("remat_peak_reduction_frac", 0.05), "pruning residuals"),
            (_scaling_flatten_remat, "monotone"),
            (_set("remat_loss_max_delta", 1.0), "math transform"),
            (_scaling_grow_accum, "trade steps for memory"),
            (_set("accum_loss_max_delta", 1.0), "reassociation"),
            (_scaling_shrink_buckets, "smaller buckets"),
            (_set("overlap_grad_parity", False), "ordering optimization"),
            (_set("restart_loss_bitident", False), "bit-identical"),
            (_del("remat"), None),
        ),
        smoke=_smoke_scaling,
        smoke_extra=_smoke_extra_scaling,
    ),
]

_IDS = [s.name for s in TARGETS]


def _check_fn(spec):
    import tools.bench_targets as bench_targets
    return getattr(bench_targets, spec.check)


class TestTargetGates:
    @pytest.mark.parametrize("spec", TARGETS, ids=_IDS)
    def test_gate_on_committed_artifact(self, spec):
        """The committed BENCH_*.json must keep showing its subsystem's
        reason to exist — a regression recorded into the artifact fails CI
        here, not in a wasted chip-tool call."""
        art = _check_fn(spec)()
        assert art["backend"] in ("cpu", "tpu")
        if spec.committed is not None:
            spec.committed(art)

    @pytest.mark.parametrize("spec", TARGETS, ids=_IDS)
    def test_gate_rejects_regressions(self, spec):
        """Every mutation a regression could write into the artifact must
        be rejected with its own diagnosable message — a check function
        that silently stopped looking would pass the committed artifact
        forever."""
        from tools.bench_targets import load_artifact

        good = load_artifact(spec.artifact)
        assert spec.regressions, spec.name
        for mutate, match in spec.regressions:
            bad = json.loads(json.dumps(good))
            mutate(bad["results"])
            with pytest.raises(AssertionError, match=match):
                _check_fn(spec)(bad)

    @pytest.mark.slow
    @pytest.mark.parametrize("spec", TARGETS, ids=_IDS)
    def test_bench_live_smoke(self, spec):
        """The bench harness itself at CI-affordable shapes: deterministic
        gates (parity, purity, conservation, block/byte ratios) hold live;
        jitter-sensitive throughput/overhead gates are relaxed via
        ``smoke_check_kwargs`` — the committed full-shape artifact carries
        those."""
        out = spec.smoke()
        art = {"backend": jax.default_backend(), **out}
        _check_fn(spec)(art, **spec.smoke_check_kwargs)
        if spec.smoke_extra is not None:
            spec.smoke_extra(out["results"])
