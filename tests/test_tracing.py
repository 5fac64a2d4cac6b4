"""Serving-plane observability: request-lifecycle tracing, SLO burn rates,
flight recorder, and the events-ring fixes that back them.

The load-bearing guarantees mirror PR 2-5's off-by-default discipline:
tokens served with tracing+SLO+flight armed are bit-identical to the
untraced engine, the default engine records nothing, and a crashing
``step()`` leaves a usable flight-record JSON behind.  Everything runs on
the micro model (one layer, 16-wide) so the file stays CPU-fast.
"""
from __future__ import annotations

import io
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import llama

# the module, not the same-named events() accessor the package re-exports
import sys as _sys
import thunder_tpu.observability.events  # noqa: F401

ev = _sys.modules["thunder_tpu.observability.events"]
from thunder_tpu.observability.flight import FlightRecorder
from thunder_tpu.observability.slo import SLOConfig, SLOMonitor, resolve_slo

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32, block_size=64,
)


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_dtype", jnp.float32)
    return tt.serve(None, params, cfg, **kw)


def _reqs(cfg, n=3, max_new=4):
    rng = np.random.default_rng(7)
    return [
        {"prompt": rng.integers(0, cfg.vocab_size, (2 + 3 * i,)).astype(np.int32),
         "max_new_tokens": max_new}
        for i in range(n)
    ]


def _export() -> list[dict]:
    buf = io.StringIO()
    tt.export_chrome_trace(buf)
    return json.loads(buf.getvalue())["traceEvents"]


#
# events ring: dynamic capacity + category-derived track names
#


class TestEventsRing:
    def test_capacity_reapplied_after_env_change(self, monkeypatch):
        """The ring bound must follow THUNDER_TPU_EVENT_BUFFER changes made
        AFTER import (the old deque(maxlen=...) froze it)."""
        monkeypatch.setenv("THUNDER_TPU_EVENT_BUFFER", "16")
        for i in range(40):
            ev.record_event("i", f"e{i}")
        assert len(ev.events()) == 16
        assert ev.events()[-1]["name"] == "e39"  # oldest dropped, newest kept
        monkeypatch.setenv("THUNDER_TPU_EVENT_BUFFER", "32")
        ev.record_event("i", "grow")
        # the surviving 16 + the new event fit the regrown ring
        assert len(ev.events()) == 17
        for i in range(40):
            ev.record_event("i", f"f{i}")
        assert len(ev.events()) == 32

    def test_capacity_floor_and_bad_values(self, monkeypatch):
        from thunder_tpu.observability.config import event_buffer_capacity

        monkeypatch.setenv("THUNDER_TPU_EVENT_BUFFER", "1")
        assert event_buffer_capacity() == 16
        monkeypatch.setenv("THUNDER_TPU_EVENT_BUFFER", "junk")
        assert event_buffer_capacity() == 4096

    def test_process_names_derived_from_category(self):
        """Serving-category events must NOT be labeled as compile-pipeline
        work; compile events keep the legacy label."""
        ev.clear_events()
        ev.record_event("B", "compile")                       # default cat, real pid
        ev.record_event("b", "queued", cat="serving.request",
                        pid=999_001, tid=3, id=1)
        evs = _export()
        names = {e["pid"]: e["args"]["name"]
                 for e in evs if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names[999_001] == "thunder_tpu serving"
        import os

        assert names[os.getpid()] == "thunder_tpu compile pipeline"

    def test_registered_track_names_win(self):
        ev.clear_events()
        ev.register_process_name(999_002, "my engine")
        ev.register_thread_name(999_002, 5, "req 5")
        ev.record_event("b", "x", cat="serving.request", pid=999_002, tid=5, id=5)
        evs = _export()
        metas = [e for e in evs if e.get("ph") == "M"]
        assert any(m["name"] == "process_name" and m["args"]["name"] == "my engine"
                   for m in metas)
        assert any(m["name"] == "thread_name" and m["args"]["name"] == "req 5"
                   for m in metas)


#
# request-lifecycle tracing
#


@pytest.fixture(scope="module")
def traced(micro):
    """One fully-instrumented drive (trace + SLO + flight) next to an
    untraced control drive of the same requests.  The export and the metric
    snapshot are captured eagerly: the autouse observability reset clears
    the event ring and the registry between the tests sharing this
    fixture."""
    cfg, params = micro
    reqs = _reqs(cfg)
    plain = _engine(cfg, params)
    plain_results = plain.run([dict(r) for r in reqs])
    ev.clear_events()
    eng = _engine(cfg, params, trace=True,
                  slo={"ttft_s": 30.0, "tpot_s": 30.0, "queue_s": 30.0},
                  flight_recorder=True)
    results = eng.run([dict(r) for r in reqs])
    full = _export()
    serving = [e for e in full if e.get("cat", "").startswith("serving")]
    snap = tt.metrics_snapshot()
    return {"plain_results": plain_results, "eng": eng, "results": results,
            "serving": serving, "full": full, "snap": snap}


class TestRequestTracing:
    def test_tokens_bit_identical_to_untraced(self, traced):
        """Acceptance: spans+SLO+flight armed change no served token."""
        for a, b in zip(traced["plain_results"], traced["results"]):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            assert a.finish_reason == b.finish_reason

    def test_every_request_has_lifecycle_spans(self, traced):
        per_rid = {}
        for e in traced["serving"]:
            if e["cat"] == "serving.request":
                per_rid.setdefault(e["id"], []).append(e)
        assert set(per_rid) == {r.rid for r in traced["results"]}
        for rid, evs in per_rid.items():
            names = {e["name"] for e in evs}
            assert {"queued", "prefill", "prefill.host", "decode", "finish"} <= names
            # async span pairs balance per phase name
            for phase in ("queued", "prefill", "decode"):
                b = sum(1 for e in evs if e["ph"] == "b" and e["name"] == phase)
                e_ = sum(1 for e in evs if e["ph"] == "e" and e["name"] == phase)
                assert b == e_ > 0, (rid, phase)

    def test_prefill_spans_carry_compile_tag(self, traced):
        serving, results = traced["serving"], traced["results"]
        begins = [e for e in serving
                  if e["ph"] == "b" and e["name"] == "prefill"]
        assert len(begins) == len(results)
        for e in begins:
            assert isinstance(e["args"]["compile"], bool)
        # the dispatch-phase child span is named by its dominant cost
        assert all(
            any(c["name"] in ("prefill.compile", "prefill.dispatch")
                for c in serving if c["ph"] == "b" and c.get("id") == e["id"])
            for e in begins
        )

    def test_engine_step_spans_on_engine_track(self, traced):
        steps = [e for e in traced["serving"] if e["name"] == "serve.step"]
        assert sum(1 for e in steps if e["ph"] == "B") == \
               sum(1 for e in steps if e["ph"] == "E") > 0
        assert all(e["cat"] == "serving.engine" for e in steps)

    def test_request_tracks_are_rid_named(self, traced):
        tnames = {e["args"]["name"] for e in traced["full"]
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
        for r in traced["results"]:
            assert f"req {r.rid}" in tnames

    def test_serving_process_separate_from_compile(self, traced):
        pnames = {e["args"]["name"] for e in traced["full"]
                  if e.get("ph") == "M" and e["name"] == "process_name"}
        assert "thunder_tpu serving" in pnames
        srv_pids = {e["pid"] for e in traced["serving"]}
        import os

        assert os.getpid() not in srv_pids  # distinct display process

    def test_prefill_compile_counter_and_result_tag(self, traced):
        results = traced["results"]
        tagged = sum(1 for r in results if r.prefill_compiled)
        # the traced engine ran after an identical plain engine, so its
        # prefills reuse warmed programs unless a new bucket appeared; either
        # way the counter agrees with the per-result tags
        assert traced["snap"].get("serving.prefill.compiles", 0) >= tagged
        assert all(isinstance(r.prefill_compiled, bool) for r in results)

    def test_default_engine_records_no_serving_events(self, micro):
        cfg, params = micro
        ev.clear_events()
        eng = _engine(cfg, params)
        eng.run(_reqs(cfg, n=1))
        assert not [e for e in ev.events()
                    if e.get("cat", "").startswith("serving")]

    def test_e2e_s_in_result_and_jsonl(self, micro):
        from thunder_tpu.observability.telemetry import StepLogger

        cfg, params = micro
        sink = io.StringIO()
        eng = _engine(cfg, params, telemetry=StepLogger(sink))
        r = eng.run(_reqs(cfg, n=1))[0]
        assert r.e2e_s is not None and r.e2e_s >= (r.ttft_s or 0.0)
        rec = [json.loads(l) for l in sink.getvalue().splitlines()
               if json.loads(l)["event"] == "request"][0]
        assert rec["e2e_s"] == pytest.approx(r.e2e_s)
        assert rec["prefill_compiled"] == r.prefill_compiled


#
# SLO monitor
#


def _fake(ttft=0.01, tpot=0.01, queue=0.0, reason="length"):
    return types.SimpleNamespace(ttft_s=ttft, tpot_s=tpot, queue_s=queue,
                                 finish_reason=reason)


class TestSLOMonitor:
    def test_burn_rate_math(self):
        mon = SLOMonitor(SLOConfig(ttft_s=0.1, objective=0.9, window=10))
        for _ in range(8):
            mon.observe(_fake(ttft=0.05))
        for _ in range(2):
            mon.observe(_fake(ttft=0.5))
        # 2/10 bad against a 10% budget: burning 2x
        assert mon.window_bad_fraction("ttft_s") == pytest.approx(0.2)
        assert mon.burn_rate("ttft_s") == pytest.approx(2.0)
        rep = mon.report()
        assert rep["dimensions"]["ttft_s"]["on_budget"] is False
        assert rep["dimensions"]["ttft_s"]["good"] == 8
        assert rep["dimensions"]["ttft_s"]["bad"] == 2

    def test_window_slides(self):
        mon = SLOMonitor(SLOConfig(ttft_s=0.1, objective=0.5, window=4))
        for _ in range(4):
            mon.observe(_fake(ttft=1.0))            # all bad
        assert mon.burn_rate("ttft_s") == pytest.approx(2.0)
        for _ in range(4):
            mon.observe(_fake(ttft=0.01))           # window turns over: clean
        assert mon.burn_rate("ttft_s") == 0.0

    def test_missing_latency_counts_bad(self):
        mon = SLOMonitor(SLOConfig(ttft_s=10.0, objective=0.5, window=8))
        mon.observe(_fake(ttft=None))               # died before first token
        assert mon.report()["dimensions"]["ttft_s"]["bad"] == 1

    def test_deadline_dimension(self):
        mon = SLOMonitor(SLOConfig(objective=0.5, window=8))
        mon.observe(_fake())
        mon.observe(_fake(reason="deadline"))
        d = mon.report()["dimensions"]["deadline"]
        assert d["good"] == 1 and d["bad"] == 1
        assert d["burn_rate"] == pytest.approx(1.0)

    def test_registry_mirror(self):
        mon = SLOMonitor(SLOConfig(ttft_s=0.1, window=8))
        mon.observe(_fake(ttft=1.0))
        snap = tt.metrics_snapshot()
        assert snap["serving.slo.ttft_s.bad"] == 1
        assert snap["serving.slo.ttft_s.burn_rate"] > 0

    def test_resolve_and_validation(self):
        assert resolve_slo(None) is None and resolve_slo(False) is None
        assert isinstance(resolve_slo(True), SLOMonitor)
        assert isinstance(resolve_slo({"ttft_s": 0.2}), SLOMonitor)
        mon = resolve_slo(SLOConfig())
        assert resolve_slo(mon) is mon
        with pytest.raises(ValueError):
            SLOConfig(objective=1.5)
        with pytest.raises(ValueError):
            SLOConfig(window=0)
        with pytest.raises(TypeError):
            resolve_slo(42)

    def test_engine_slo_report(self, micro):
        cfg, params = micro
        assert _engine(cfg, params).slo_report() == {"enabled": False}
        eng = _engine(cfg, params, slo={"ttft_s": 1e-9, "objective": 0.9})
        eng.run(_reqs(cfg, n=2))
        rep = eng.slo_report()
        assert rep["enabled"] is True
        dims = rep["dimensions"]
        assert dims["ttft_s"]["target_s"] == 1e-9
        # a nanosecond TTFT target is unmeetable: every request burns budget
        assert dims["ttft_s"]["bad"] == 2
        assert dims["ttft_s"]["burn_rate"] == pytest.approx(10.0)
        assert dims["ttft_s"]["on_budget"] is False


#
# flight recorder
#


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(capacity=8)
        for i in range(30):
            rec.record("tick", i=i)
        assert len(rec.events()) == 8
        assert rec.events()[-1]["i"] == 29
        assert rec.events_recorded == 30

    def test_state_provider_failure_keeps_ring(self):
        def boom():
            raise ValueError("provider broke")

        rec = FlightRecorder(capacity=8, state_provider=boom)
        rec.record("tick")
        snap = rec.snapshot(reason="manual")
        assert snap["state"] is None and "provider broke" in snap["state_error"]
        assert len(snap["events"]) == 1

    def test_crash_dump_on_step_failure(self, micro, tmp_path, monkeypatch):
        """Acceptance: a forced step() failure writes a usable JSON dump
        and the original exception still propagates."""
        cfg, params = micro
        monkeypatch.setenv("THUNDER_TPU_FLIGHT_DIR", str(tmp_path))
        eng = _engine(cfg, params, flight_recorder=True)
        eng.submit(np.arange(3, dtype=np.int32), max_new_tokens=4)
        eng.step()                                   # healthy prefill first

        from thunder_tpu.observability.debug import SymbolInfo

        err = tt.AnomalyError(
            kind="nan",
            info=SymbolInfo("XLA0", 0, "computation", True, ()),
            output_index=0, nan_count=3, inf_count=0,
            shape=(4,), dtype="float32",
        )

        def boom():
            raise err

        monkeypatch.setattr(eng, "_decode_once", boom)
        with pytest.warns(UserWarning, match="flight record dumped"):
            with pytest.raises(tt.AnomalyError):
                eng.step()
        dumps = list(tmp_path.glob("tt_flight_*.json"))
        assert len(dumps) == 1
        d = json.loads(dumps[0].read_text())
        assert d["reason"] == "crash"
        assert d["error"]["type"] == "AnomalyError"
        kinds = [e["kind"] for e in d["events"]]
        assert "submit" in kinds and "prefill" in kinds
        state = d["state"]
        assert state["scheduler"]["running"] == 1
        assert state["pool"]["num_free"] < state["pool"]["num_blocks"] - 1
        assert state["engine"]["prefill_runs"] == 1
        assert tt.metrics_snapshot()["serving.flight.dumps"] == 1

    def test_manual_flight_record(self, micro, tmp_path):
        cfg, params = micro
        eng = _engine(cfg, params, flight_recorder=True)
        eng.run(_reqs(cfg, n=2))
        path = tt.flight_record(tmp_path / "manual.json")
        d = json.loads((tmp_path / "manual.json").read_text())
        assert str(path) == str(tmp_path / "manual.json")
        assert d["reason"] == "manual" and "error" not in d
        assert {"engine", "scheduler", "pool", "prefix_share_hit_rate",
                "compiles", "slo"} <= set(d["state"])
        assert [e for e in d["events"] if e["kind"] == "finish"]

    def test_flight_record_without_recorder_raises(self, monkeypatch):
        from thunder_tpu.observability import flight

        monkeypatch.setattr(flight, "_last_recorder", None)
        with pytest.raises(RuntimeError, match="no active flight recorder"):
            tt.flight_record("/tmp/nope.json")
