"""Observability subsystem: per-symbol runtime profiling, compile-pipeline
event tracing, and the unified metrics registry + hooks (ISSUE 2), plus the
numerics-and-memory layer (ISSUE 3): debug hooks, anomaly detection with
source provenance, per-symbol memory accounting, and step telemetry.

Covers: per-symbol stats on a small jitted model (counts match the
instrumented trace, times monotone), Chrome-trace export validity (matched
B/E events, metadata rows, file-like sinks, ring wraparound), metrics
snapshot/reset, hook callbacks on cache miss vs key hit (errors counted in
``hooks.errors``), the zero-overhead assertions (profiling/debugging
disabled ⇒ byte-identical generated program), the dynamic env gates,
the unguardable-dict-keys sharp edge, pre/post debug hooks with provenance,
AnomalyError on forward and backward NaN/Inf (incl. a NaN injected via a
custom grad rule), provenance surviving fusion, live/peak-bytes columns,
StepLogger JSONL + registry mirror, and ``tt.reset_observability``."""
from __future__ import annotations

import json
import re
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
import thunder_tpu.torch as ltorch
from thunder_tpu import observability as obs

rng = np.random.default_rng(7)


def _xw():
    return (
        rng.standard_normal((8, 16)).astype(np.float32),
        rng.standard_normal((4, 16)).astype(np.float32),
    )


def _mlp(a, b):
    return ltorch.relu(a @ b.T).sum()


class TestRuntimeProfiling:
    def test_per_symbol_stats_on_llama_block(self):
        from thunder_tpu.models import llama

        cfg = llama.Config.from_name("tiny-llama-debug")
        B, T = 2, 16
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        jfn = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg), profile=True)
        jfn(params, idx, cos, sin)
        jfn(params, idx, cos, sin)

        report = tt.profile_stats(jfn)
        assert len(report) >= 1
        # counts match the instrumented trace's wrapped symbols exactly
        instr = tt.last_traces(jfn)[-1]
        wrapped = [b for b in instr.bound_symbols if b.sym.name.startswith("_prof")]
        assert len(wrapped) == len(report)
        for label, st in report.items():
            assert st["calls"] == 2, (label, st)
            # times monotone/consistent: 0 < min <= mean <= max <= total
            assert 0 < st["min_ns"] <= st["mean_ns"] <= st["max_ns"] <= st["total_ns"]
        # the sorted table prints every symbol
        table = str(report)
        for label in report:
            assert label[:40] in table

    def test_flops_bytes_from_xla_cost_model(self):
        x, w = _xw()
        jfn = tt.jit(_mlp, profile=True)
        jfn(x, w)
        report = tt.profile_stats(jfn)
        # the fused region carries XLA cost_analysis estimates (matmul ⇒
        # nonzero flops); keys are optional per-record but must appear here
        assert any(st.get("flops", 0) and st.get("flops") > 0 for st in report.values()), dict(report)
        assert any(st.get("bytes", 0) and st.get("bytes") > 0 for st in report.values())

    def test_backward_trace_instrumented_under_grad(self):
        x = rng.standard_normal((8, 16)).astype(np.float32)
        g = tt.grad(lambda a: ltorch.relu(a).sum(), profile=True)
        g(x)
        report = tt.profile_stats(g)
        assert any(k.startswith("backward:") for k in report), list(report)
        assert any(not k.startswith("backward:") for k in report)

    def test_zero_overhead_when_disabled(self):
        x, w = _xw()
        plain = tt.jit(_mlp)
        plain(x, w)
        src_plain = tt.last_traces(plain)[-1].python()
        assert "_prof" not in src_plain

        prof = tt.jit(_mlp, profile=True)
        prof(x, w)
        traces = tt.last_traces(prof)
        src_prof = traces[-1].python()
        assert "_prof" in src_prof
        # byte-identical contract: the profiled jit's PRE-instrumentation
        # execution trace prints the same program a plain jit generates —
        # instrumentation is purely additive, as a final pass
        assert traces[-2].python() == src_plain

        with pytest.raises(RuntimeError, match="no profiling data"):
            tt.profile_stats(plain)

    def test_profiled_results_match_unprofiled(self):
        x, w = _xw()
        expected = tt.jit(_mlp)(x, w)
        got = tt.jit(_mlp, profile=True)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-6)

    def test_env_var_enables_profiling(self, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_PROFILE", "1")
        x, w = _xw()
        jfn = tt.jit(_mlp)
        jfn(x, w)
        assert len(tt.profile_stats(jfn)) >= 1


class TestCompileEvents:
    def test_chrome_trace_export_is_valid_and_matched(self, tmp_path):
        obs.clear_events()
        x, w = _xw()
        tt.jit(_mlp)(x, w)

        path = str(tmp_path / "compile_trace.json")
        assert tt.export_chrome_trace(path) == path
        data = json.loads(open(path).read())
        evs = data["traceEvents"]
        assert evs, "no events recorded"
        names = {e["name"] for e in evs}
        # at least the interpret/transform/lower/compile pipeline phases
        assert {"compile", "interpret", "lower", "codegen"} <= names, names
        assert any(n.startswith("transform:") for n in names), names
        # Perfetto metadata rows (satellite: process/thread labels)
        assert "process_name" in names and "thread_name" in names, names
        for e in evs:
            assert e["ph"] in ("B", "E", "M")
            if e["ph"] != "M":
                assert isinstance(e["ts"], float)
            assert "pid" in e and "tid" in e
        for name in names:
            b = sum(1 for e in evs if e["name"] == name and e["ph"] == "B")
            en = sum(1 for e in evs if e["name"] == name and e["ph"] == "E")
            assert b == en, (name, b, en)

    def test_xla_compile_event_recorded(self):
        obs.clear_events()
        x, w = _xw()
        tt.jit(_mlp)(x, w)
        names = [e["name"] for e in obs.events()]
        assert "xla_compile" in names

    def test_ring_buffer_bounded(self):
        obs.clear_events()
        cap = obs.event_buffer_capacity()
        for i in range(cap + 50):
            obs.record_event("i", f"e{i}")
        assert len(obs.events()) == cap
        obs.clear_events()


class TestMetricsRegistry:
    def test_counter_gauge_histogram_snapshot_reset(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(2)
        reg.gauge("g").set(1.5)
        h = reg.histogram("h")
        h.observe(2.0)
        h.observe(4.0)

        snap = reg.snapshot()
        assert snap["c"] == 3
        assert snap["g"] == 1.5
        assert snap["h"] == {
            "count": 2, "sum": 6.0, "mean": 3.0, "min": 2.0, "max": 4.0,
            "p50": 2.0, "p95": 4.0, "p99": 4.0,
            "window": obs.Histogram.WINDOW,
        }

        # get-or-create returns the same object; a type collision raises
        assert reg.counter("c") is c
        with pytest.raises(TypeError):
            reg.gauge("c")

        reg.reset()
        snap = reg.snapshot()
        assert snap["c"] == 0 and snap["g"] is None and snap["h"]["count"] == 0
        c.inc()  # held references survive reset
        assert reg.snapshot()["c"] == 1

    def test_histogram_percentiles(self):
        """p50/p95/p99 are nearest-rank over the bounded recent window, so
        latency histograms (serving TTFT/TPOT, train.step_s) report as the
        percentiles dashboards scrape."""
        h = obs.Histogram("lat")
        assert h.percentile(50) is None and h.snapshot()["p99"] is None
        for v in range(1, 101):                      # 1..100
            h.observe(float(v))
        snap = h.snapshot()
        assert snap["p50"] == 50.0
        assert snap["p95"] == 95.0
        assert snap["p99"] == 99.0
        assert snap["min"] == 1.0 and snap["max"] == 100.0
        # window-bounded: a burst of large values shifts the percentiles
        # even though min/mean stay exact over the full stream
        for _ in range(obs.Histogram.WINDOW):
            h.observe(1000.0)
        snap = h.snapshot()
        assert snap["p50"] == 1000.0 and snap["min"] == 1.0
        h.reset()
        assert h.snapshot()["p50"] is None and h.count == 0

    def test_dispatch_and_compile_mirror_into_global_registry(self):
        reg = obs.registry()
        base = {
            k: reg.counter(k).value
            for k in ("dispatch.calls", "dispatch.cache_hits", "dispatch.cache_misses", "compile.count")
        }
        x, w = _xw()
        jfn = tt.jit(_mlp)
        jfn(x, w)  # miss (compiles)
        jfn(x, w)  # key hit
        assert reg.counter("dispatch.calls").value >= base["dispatch.calls"] + 2
        assert reg.counter("dispatch.cache_misses").value >= base["dispatch.cache_misses"] + 1
        assert reg.counter("dispatch.cache_hits").value >= base["dispatch.cache_hits"] + 1
        assert reg.counter("compile.count").value >= base["compile.count"] + 1
        assert reg.histogram("dispatch.ns").snapshot()["count"] > 0


class TestHooks:
    def test_hooks_fire_on_miss_vs_hit(self):
        seen = []
        hooks = {
            "on_cache_miss": lambda p: seen.append(("miss", p["fn"])),
            "on_cache_hit": lambda p: seen.append(("hit", p["fn"])),
            "on_dispatch": lambda p: seen.append(("dispatch", p["ns"], p["cache_hit"])),
            "on_compile_start": lambda p: seen.append(("compile_start", p["fn"])),
            "on_compile_end": lambda p: seen.append(("compile_end", p["ns"])),
        }
        for ev, fn in hooks.items():
            obs.register_hook(ev, fn)
        try:
            x, w = _xw()
            jfn = tt.jit(_mlp)
            jfn(x, w)  # miss → compile
            jfn(x, w)  # key hit
        finally:
            for ev, fn in hooks.items():
                obs.unregister_hook(ev, fn)

        kinds = [s[0] for s in seen]
        assert ("miss", "_mlp") in seen
        assert ("hit", "_mlp") in seen
        assert kinds.index("compile_start") < kinds.index("compile_end")
        dispatches = [s for s in seen if s[0] == "dispatch"]
        assert len(dispatches) == 2
        assert dispatches[0][2] is False and dispatches[1][2] is True
        assert all(d[1] > 0 for d in dispatches)
        # unregistered hooks stay silent
        n = len(seen)
        jfn(x, w)
        assert len(seen) == n

    def test_unknown_event_raises_and_hook_errors_are_swallowed(self):
        with pytest.raises(ValueError):
            obs.register_hook("on_nonsense", lambda p: None)

        def broken(p):
            raise RuntimeError("boom")

        obs.register_hook("on_cache_miss", broken)
        try:
            x, w = _xw()
            with warnings.catch_warnings(record=True) as ws:
                warnings.simplefilter("always")
                out = tt.jit(_mlp)(x, w)  # must not raise
            assert np.isfinite(float(np.asarray(out)))
            assert any("boom" in str(w.message) for w in ws)
        finally:
            obs.unregister_hook("on_cache_miss", broken)


class TestNoAnnotationGate:
    """The profiler session is the only switch of an annotation: no
    environment variable, no module flag, no shim."""

    def test_span_annotates_whatever_the_old_env_var_says(self, monkeypatch):
        import sys

        from jax.profiler import TraceAnnotation

        ev = sys.modules["thunder_tpu.observability.events"]   # the module, not the accessor
        opened = []
        monkeypatch.setattr(ev, "TraceAnnotation",
                            lambda name, **kw: opened.append((name, kw)) or TraceAnnotation(name, **kw))
        for value in (None, "0", "1"):
            if value is None:
                monkeypatch.delenv("THUNDER_TPU_ANNOTATE_TRACES", raising=False)
            else:
                monkeypatch.setenv("THUNDER_TPU_ANNOTATE_TRACES", value)
            with obs.span("region", k=value or "unset"):
                pass
        assert opened == [("thunder_tpu.region", {"k": k}) for k in ("unset", "0", "1")]

    def test_the_gate_and_the_shim_are_gone(self):
        import importlib

        for gone in ("add_markers", "profiling_enabled", "annotations_enabled"):
            assert not hasattr(obs, gone), gone
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module("thunder_tpu.core.profile")
        # the per-symbol annotation stays, under profile=True alone
        cfn = tt.jit(_mlp, profile=True)
        cfn(*_xw())
        assert len(tt.profile_stats(cfn)) > 0


class TestUnguardableKeySharpEdge:
    """interpreter.py _read_keys: iterating a
    tracked dict with unguardable keys under-guards (LEN only while keys and
    values bake) — it must surface through the sharp-edges policy."""

    class _Obj:
        pass

    def _ctx_and_dict(self):
        from thunder_tpu.core.interpreter import (
            InterpreterCompileCtx,
            ProvenanceRecord,
            PseudoInst,
        )

        d = {self._Obj(): 1.0, "lr": 0.5}
        ctx = InterpreterCompileCtx(fn=lambda: None)
        ctx.track(d, ProvenanceRecord(PseudoInst.LOAD_GLOBAL, key="CFG"))
        return ctx, d

    def test_allow_policy_keeps_len_guard_silently(self):
        from thunder_tpu.core.interpreter import PseudoInst, _read_keys

        ctx, d = self._ctx_and_dict()
        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            keys = _read_keys(ctx, d)  # no compile data → allow
        assert keys is not None and len(keys) == 2
        assert any(r.inst is PseudoInst.LEN for r, _ in ctx.reads)
        assert not any("unguardable" in str(w.message) for w in ws)

    def test_error_policy_raises(self):
        from thunder_tpu.core.compile_data import compile_data_and_stats
        from thunder_tpu.core.interpreter import _read_keys
        from thunder_tpu.core.options import SHARP_EDGES_OPTIONS
        from thunder_tpu.core.sharp_edges import SharpEdgeError

        ctx, d = self._ctx_and_dict()
        cd = types.SimpleNamespace(sharp_edges=SHARP_EDGES_OPTIONS.ERROR)
        with compile_data_and_stats(cd, None):
            with pytest.raises(SharpEdgeError, match="unguardable keys"):
                _read_keys(ctx, d)

    def test_warn_policy_warns_and_names_key_type(self):
        from thunder_tpu.core.compile_data import compile_data_and_stats
        from thunder_tpu.core.interpreter import _read_keys
        from thunder_tpu.core.options import SHARP_EDGES_OPTIONS

        ctx, d = self._ctx_and_dict()
        cd = types.SimpleNamespace(sharp_edges=SHARP_EDGES_OPTIONS.WARN)
        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            with compile_data_and_stats(cd, None):
                keys = _read_keys(ctx, d)
        assert keys is not None and len(keys) == 2
        msgs = [str(w.message) for w in ws]
        assert any("unguardable keys" in m and "_Obj" in m for m in msgs), msgs

    def test_guardable_keys_unaffected(self):
        from thunder_tpu.core.compile_data import compile_data_and_stats
        from thunder_tpu.core.interpreter import (
            InterpreterCompileCtx,
            ProvenanceRecord,
            PseudoInst,
            _read_keys,
        )
        from thunder_tpu.core.options import SHARP_EDGES_OPTIONS

        d = {"a": 1, ("b", 0): 2}
        ctx = InterpreterCompileCtx(fn=lambda: None)
        ctx.track(d, ProvenanceRecord(PseudoInst.LOAD_GLOBAL, key="CFG"))
        cd = types.SimpleNamespace(sharp_edges=SHARP_EDGES_OPTIONS.ERROR)
        with compile_data_and_stats(cd, None):
            keys = _read_keys(ctx, d)  # fully guardable: no sharp edge
        assert keys == ["a", ("b", 0)]
        assert any(r.inst is PseudoInst.KEYS for r, _ in ctx.reads)


#
# ISSUE 3: numerics observability — debug hooks, anomaly detection with
# provenance, memory accounting, telemetry, and the one-call reset
#


def _nan_mid(a):
    z = a - a
    return (z / z).sum()  # 0/0 -> NaN mid-trace


def _inf_mid(a):
    z = a - a
    return (1.0 / z).sum()  # 1/0 -> Inf mid-trace


class TestDebugHooks:
    def test_pre_post_fire_with_symbol_info_and_provenance(self):
        calls = []

        def pre(info, args, kwargs):
            calls.append(("pre", info.name, info.trace))

        def post(info, out):
            calls.append(("post", info.name, info.trace))
            assert any(f.endswith("test_observability.py") for f, _ in info.provenance), info

        x, w = _xw()
        jfn = tt.jit(_mlp, debug_hooks=(pre, post))
        out = jfn(x, w)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(tt.jit(_mlp)(x, w)), rtol=1e-6
        )
        kinds = {c[0] for c in calls}
        assert kinds == {"pre", "post"}, calls
        assert all(c[2] == "computation" for c in calls)

    def test_single_callable_and_dict_forms(self):
        seen = []
        jfn = tt.jit(_mlp, debug_hooks=lambda info, out: seen.append(info.name))
        jfn(*_xw())
        assert seen  # single callable == post hook

        seen2 = []
        jfn2 = tt.jit(_mlp, debug_hooks={"pre": lambda i, a, k: seen2.append(i.name)})
        jfn2(*_xw())
        assert seen2

    def test_hook_exceptions_propagate(self):
        # debug hooks exist to STOP the program — unlike metrics hooks,
        # their exceptions are not swallowed
        def post(info, out):
            raise ValueError("stop here")

        jfn = tt.jit(_mlp, debug_hooks={"post": post})
        with pytest.raises(ValueError, match="stop here"):
            jfn(*_xw())

    def test_backward_trace_hooks_under_grad(self):
        traces = set()
        g = tt.grad(
            lambda a: ltorch.relu(a).sum(),
            debug_hooks={"post": lambda i, o: traces.add(i.trace)},
        )
        g(rng.standard_normal((4, 4)).astype(np.float32))
        assert traces == {"computation", "backward"}, traces

    def test_byte_identical_program_when_disabled(self):
        x, w = _xw()
        plain = tt.jit(_mlp)
        plain(x, w)
        src = tt.last_traces(plain)[-1].python()
        assert "_dbg" not in src

        off = tt.jit(_mlp, detect_anomalies=False)
        off(x, w)
        # the header names the last pass and the wall time it took: not the program's
        untimed = lambda text: re.sub(r"\(took \d+ milliseconds\)", "", text)
        assert untimed(tt.last_traces(off)[-1].python()) == untimed(src)

        on = tt.jit(_mlp, detect_anomalies=True)
        on(x, w)
        traces = tt.last_traces(on)
        assert "_dbg" in traces[-1].python()
        # instrumentation is purely additive, as a final pass
        assert traces[-2].python() == src


class TestAnomalyDetection:
    def test_forward_nan_names_symbol_and_user_line(self):
        x = rng.standard_normal((8,)).astype(np.float32)
        jfn = tt.jit(_nan_mid, detect_anomalies=True)
        with pytest.raises(tt.AnomalyError) as ei:
            jfn(x)
        e = ei.value
        assert e.kind == "nan" and e.trace == "computation"
        assert e.nan_count >= 1
        assert e.symbol  # names the executed symbol (fusion region or op)
        assert any(f.endswith("test_observability.py") for f, _ in e.provenance), e.provenance
        assert "test_observability.py" in str(e) and "repro" in str(e)

    def test_forward_inf_detected(self):
        x = rng.standard_normal((8,)).astype(np.float32)
        jfn = tt.jit(_inf_mid, detect_anomalies=True)
        with pytest.raises(tt.AnomalyError) as ei:
            jfn(x)
        assert ei.value.kind == "inf" and ei.value.inf_count >= 1

    def test_no_false_positive_and_results_match(self):
        x, w = _xw()
        expected = tt.jit(_mlp)(x, w)
        got = tt.jit(_mlp, detect_anomalies=True)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-6)

    def test_env_var_enables_anomaly_mode(self, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_DETECT_ANOMALIES", "1")
        x = rng.standard_normal((8,)).astype(np.float32)
        with pytest.raises(tt.AnomalyError):
            tt.jit(_nan_mid)(x)

    def test_backward_nan_via_custom_grad(self, monkeypatch):
        # satellite: a custom grad rule injects NaN into the backward trace;
        # the forward stays finite, so the raise must come from the backward
        # instrumentation and still name the user's source line
        from thunder_tpu import clang
        from thunder_tpu.core import transforms as T
        from thunder_tpu.core.prims import PrimIDs

        def nan_rule(bsym, g):
            a = bsym.args[0]
            return [(a, clang.full_like(a, float("nan")))]

        monkeypatch.setitem(T.backward_rules, PrimIDs.SIN, nan_rule)
        g = tt.grad(lambda a: ltorch.sin(a).sum(), detect_anomalies=True)
        with pytest.raises(tt.AnomalyError) as ei:
            g(rng.standard_normal((4,)).astype(np.float32))
        e = ei.value
        assert e.kind == "nan" and e.trace == "backward"
        assert any(f.endswith("test_observability.py") for f, _ in e.provenance), e.provenance

    def test_anomaly_counter_incremented(self):
        base = obs.registry().counter("anomaly.detected").value
        x = rng.standard_normal((8,)).astype(np.float32)
        with pytest.raises(tt.AnomalyError):
            tt.jit(_nan_mid, detect_anomalies=True)(x)
        assert obs.registry().counter("anomaly.detected").value == base + 1


class TestProvenance:
    def test_recorded_at_trace_time(self):
        import inspect

        x, w = _xw()
        jfn = tt.jit(_mlp)
        jfn(x, w)
        acquisition = tt.last_traces(jfn)[0]
        lines, start = inspect.getsourcelines(_mlp)
        body = range(start, start + len(lines))
        hits = [
            b
            for b in acquisition.bound_symbols
            if b.source_filename is not None
            and b.source_filename.endswith("test_observability.py")
            and b.source_positions in body
        ]
        assert hits, [
            (b.sym.name, b.source_filename, b.source_positions)
            for b in acquisition.bound_symbols
        ]

    def test_provenance_survives_fusion(self):
        from thunder_tpu.core.symbol import gather_provenance

        x, w = _xw()
        jfn = tt.jit(_mlp)
        jfn(x, w)
        extrace = tt.last_traces(jfn)[-1]
        fusions = [b for b in extrace.bound_symbols if b.sym.is_fusion]
        assert fusions, extrace.python()
        fused = fusions[0]
        # the fused region carries the provenance LIST of the ops it absorbed
        assert isinstance(fused.source_positions, list) and fused.source_positions
        prov = gather_provenance(fused)
        assert any(f.endswith("test_observability.py") for f, _ in prov), prov

    def test_backward_symbols_inherit_forward_provenance(self):
        g = tt.grad(lambda a: ltorch.relu(a).sum())
        g(rng.standard_normal((4, 4)).astype(np.float32))
        from thunder_tpu.core.symbol import gather_provenance

        bw = tt.last_backward_traces(g)[-1]
        prov = [p for b in bw.bound_symbols for p in gather_provenance(b)]
        assert any(f.endswith("test_observability.py") for f, _ in prov), prov


class TestMemoryAccounting:
    def test_timeline_matches_estimate_and_alignment(self):
        from thunder_tpu.examine import memory_estimate, memory_timeline

        x, w = _xw()
        jfn = tt.jit(_mlp)
        jfn(x, w)
        trc = tt.last_traces(jfn)[-1]
        t = memory_timeline(trc)
        m = memory_estimate(trc)
        assert len(t["rows"]) == len(trc.bound_symbols)
        assert t["peak_bytes_estimate"] == m["peak_bytes_estimate"]
        assert m["peak_bytes_estimate"] >= m["input_bytes"] > 0
        peaks = [r["peak_bytes"] for r in t["rows"]]
        assert peaks == sorted(peaks)  # running peak is monotone
        assert peaks[-1] == t["peak_bytes_estimate"]
        assert all(0 <= r["live_bytes"] <= r["peak_bytes"] for r in t["rows"])
        # del placement must actually free: some row's live drops below peak
        assert any(r["live_bytes"] < r["peak_bytes"] for r in t["rows"])

    def test_profile_stats_has_memory_columns_and_gauges(self):
        x, w = _xw()
        jfn = tt.jit(_mlp, profile=True)
        jfn(x, w)
        report = tt.profile_stats(jfn)
        stats = dict(report)
        assert any("live_bytes" in st and "peak_bytes" in st for st in stats.values()), stats
        for st in stats.values():
            if "live_bytes" in st:
                assert 0 <= st["live_bytes"] <= st["peak_bytes"]
        assert "live_mb" in str(report) and "peak_mb" in str(report)
        gauge = obs.registry().gauge("memory.computation.peak_bytes_estimate")
        assert gauge.value is not None and gauge.value > 0


class TestStepLogger:
    def test_jsonl_and_registry_mirror(self):
        import io

        from thunder_tpu.observability.telemetry import StepLogger

        reg = obs.registry()
        base_steps = reg.counter("train.steps").value
        buf = io.StringIO()
        with StepLogger(buf, meta={"config": "tiny", "mode": "none"}) as sl:
            sl.log_step(0, loss=1.5, step_time_s=0.5, tokens=100, peak_bytes=1000)
            sl.log_step(1, loss=1.25, grad_norm=0.7, step_time_s=0.25, tokens=100)
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert len(lines) == 3
        assert lines[0]["event"] == "run_start" and lines[0]["config"] == "tiny"
        assert lines[1]["event"] == "step" and lines[1]["peak_bytes"] == 1000
        assert lines[1]["tokens_per_sec"] == pytest.approx(200.0)
        assert lines[2]["grad_norm"] == 0.7 and "peak_bytes" not in lines[2]
        assert reg.counter("train.steps").value == base_steps + 2
        assert reg.gauge("train.loss").value == 1.25
        assert reg.gauge("train.grad_norm").value == 0.7
        assert reg.histogram("train.step_s").snapshot()["count"] >= 2

    def test_path_sink_appends_and_closes(self, tmp_path):
        from thunder_tpu.observability.telemetry import StepLogger

        path = tmp_path / "steps.jsonl"
        sl = StepLogger(str(path))
        sl.log_step(0, loss=2.0)
        sl.close()
        sl2 = StepLogger(str(path))
        sl2.log_step(1, loss=1.0)
        sl2.close()
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [l["step"] for l in lines] == [0, 1]

    def test_request_records(self):
        """Per-request serving records share the step-JSONL sink: one
        ``{"event": "request", ...}`` line per completed request, None
        fields omitted (the serving engine drives this)."""
        import io

        from thunder_tpu.observability.telemetry import StepLogger

        buf = io.StringIO()
        with StepLogger(buf, meta={"kind": "serving"}) as sl:
            rec = sl.log_request(
                rid=3, prompt_tokens=7, new_tokens=5, finish_reason="length",
                ttft_s=0.01, tpot_s=0.002, tokens_per_sec=450.0, queue_s=None,
            )
        assert rec["event"] == "request" and "queue_s" not in rec
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert lines[1]["rid"] == 3
        assert lines[1]["finish_reason"] == "length"
        assert lines[1]["ttft_s"] == 0.01 and lines[1]["tokens_per_sec"] == 450.0


class TestResetObservability:
    def test_one_call_clears_metrics_events_and_reports(self):
        x, w = _xw()
        obs.registry().counter("reset.probe").inc()
        obs.record_event("i", "reset-marker")
        jfn = tt.jit(_mlp, profile=True)
        jfn(x, w)
        report = tt.profile_stats(jfn)
        assert len(report) >= 1
        assert obs.events()

        tt.reset_observability()
        assert obs.registry().counter("reset.probe").value == 0
        assert obs.events() == []
        assert len(report) == 0  # live reports cleared in place


class TestEventExportSatellites:
    def test_export_accepts_file_like_and_emits_metadata(self):
        import io

        obs.clear_events()
        with obs.span("satellite-phase"):
            pass
        buf = io.StringIO()
        assert obs.export_chrome_trace(buf) is buf
        data = json.loads(buf.getvalue())
        names = [e["name"] for e in data["traceEvents"]]
        assert "process_name" in names and "thread_name" in names
        assert "satellite-phase" in names

    def test_ring_wraparound_drops_oldest_and_export_stays_valid(self):
        import io

        obs.clear_events()
        cap = obs.event_buffer_capacity()
        for i in range(cap + 50):
            obs.record_event("i", f"e{i}")
        evs = obs.events()
        assert len(evs) == cap
        names = {e["name"] for e in evs}
        assert "e0" not in names and f"e{cap + 49}" in names  # oldest dropped
        buf = io.StringIO()
        obs.export_chrome_trace(buf)
        data = json.loads(buf.getvalue())  # still valid JSON
        assert len(data["traceEvents"]) >= cap
        obs.clear_events()


class TestHookErrorCounter:
    def test_swallowed_hook_exceptions_are_counted(self):
        reg = obs.registry()
        base = reg.counter("hooks.errors").value

        def broken(p):
            raise RuntimeError("boom")

        obs.register_hook("on_cache_hit", broken)
        try:
            with warnings.catch_warnings(record=True) as ws:
                warnings.simplefilter("always")
                obs.emit("on_cache_hit", {"fn": "f"})
            assert any("boom" in str(w.message) for w in ws)
        finally:
            obs.unregister_hook("on_cache_hit", broken)
        assert reg.counter("hooks.errors").value == base + 1
