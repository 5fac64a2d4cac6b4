"""Serving goodput ledger + Prometheus export plane (ISSUE 18).

The load-bearing guarantee is the conservation identity: for every program
the engine dispatches, the ledger's ``committed + sum(waste)`` equals
``rows x positions`` as exact integers — across sampling modes, steps sent
ahead through a row's end, speculative rounds, preemption, fault recovery, and session
re-attach.  The ledger runs strict by default, so a violated dispatch
raises :class:`ConservationError` the moment it is accounted; these tests
additionally pin the *aggregate* identity and that ``committed_tokens``
equals the tokens requests actually streamed.

Second pillar: the off-path is byte-identical — a ``goodput=False``
engine adds no module-cache programs, carries no ``goodput`` stats key,
and ``goodput=True`` compiles ZERO additional programs (the ledger never
enters the static program key).

Satellites pinned here: histogram ``window`` field, the pool occupancy
ring, telemetry request-schema v2, and the Prometheus text exposition
(validated by a test-local minimal format checker, round-tripping
registry values).
"""
from __future__ import annotations

import io
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import llama
from thunder_tpu.observability.goodput import (
    WASTE_CAUSES,
    ConservationError,
    GoodputConfig,
    GoodputLedger,
    fleet_goodput,
    resolve_goodput,
)
from thunder_tpu.observability.metrics import Histogram, export_text, registry
from thunder_tpu.serving import FaultPlan, FaultSpec, RetryPolicy, SpecConfig
from thunder_tpu.serving.faults import FP_DECODE

MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(8,), prefill_buckets=(16,))


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def draft():
    dcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "n_layer": 1})
    dp = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    return dcfg, dp


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("retry", RetryPolicy(sleep=lambda s: None))
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, params, cfg, **kw)


def _prompt(seed, n, cfg):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


def _drive(eng, prompts, n=6, keys=None, **submit_kw):
    hs = [eng.submit(p, max_new_tokens=n,
                     key=(keys[i] if keys else None), **submit_kw)
          for i, p in enumerate(prompts)]
    return [h.result() for h in hs]


def _check_conserved(snap):
    """The aggregate conservation identity + snapshot self-consistency."""
    assert snap["violations"] == 0
    assert snap["committed"] + sum(snap["waste"].values()) == snap["positions"]
    assert set(snap["waste"]) <= set(WASTE_CAUSES)
    assert all(n > 0 for n in snap["waste"].values())   # zero causes elided
    assert 0.0 <= snap["token_goodput_frac"] <= snap["goodput_frac"] <= 1.0


def _streamed(results):
    return sum(len(r.new_tokens) for r in results)


#
# ledger unit behavior (pure host: no engine, no device)
#


class TestLedgerUnit:
    def test_account_conserves_and_tags(self):
        led = GoodputLedger()
        tag = led.account("decode", 4, 1, committed=3, pad_row=1)
        assert tag == {"kind": "decode", "rows": 4, "positions": 1,
                       "committed": 3, "pad_row": 1}
        led.account("prefill", 1, 16, committed=10, pad_prefill=6)
        snap = led.snapshot()
        assert snap["positions"] == 20 and snap["committed"] == 13
        assert snap["waste"] == {"pad_row": 1, "pad_prefill": 6}
        _check_conserved(snap)

    def test_strict_violation_raises(self):
        led = GoodputLedger()
        with pytest.raises(ConservationError, match="4x1"):
            led.account("decode", 4, 1, committed=3)     # 1 slot unaccounted

    def test_lenient_counts_violations(self):
        led = GoodputLedger(GoodputConfig(strict=False))
        led.account("decode", 4, 1, committed=3)
        assert led.snapshot()["violations"] == 1

    def test_unknown_cause_and_negative_rejected(self):
        led = GoodputLedger()
        with pytest.raises(KeyError, match="unknown waste cause"):
            led.account("decode", 1, 1, nonsense=1)
        with pytest.raises(ValueError, match="negative"):
            led.account("decode", 1, 1, committed=2, pad_row=-1)

    def test_report_per_kind_and_device_time(self):
        led = GoodputLedger()
        led.account("decode", 4, 1, committed=2, pad_row=2)
        led.note_device_s("decode", 2.0)
        row = led.report()["per_kind"]["decode"]
        assert row["goodput_frac"] == 0.5
        assert row["device_s"] == 2.0 and row["wasted_device_s"] == 1.0

    def test_device_time_off(self):
        led = GoodputLedger(GoodputConfig(device_time=False))
        led.account("decode", 1, 1, committed=1)
        led.note_device_s("decode", 2.0)
        assert "device_s" not in led.report()

    def test_resolve_forms(self):
        assert resolve_goodput(None) is None
        assert resolve_goodput(False) is None
        assert isinstance(resolve_goodput(True), GoodputLedger)
        assert resolve_goodput({"strict": False}).config.strict is False
        led = GoodputLedger()
        assert resolve_goodput(led) is led
        with pytest.raises(TypeError, match="goodput"):
            resolve_goodput(42)

    def test_fleet_aggregate_and_imbalance(self):
        a, b = GoodputLedger(), GoodputLedger()
        a.account("decode", 4, 1, committed=3, pad_row=1)
        b.account("decode", 4, 1, committed=1, pad_row=3)
        fleet = fleet_goodput([a.snapshot(), b.snapshot()])
        assert fleet["lanes"] == 2 and fleet["positions"] == 8
        assert fleet["committed"] == 4 and fleet["waste"] == {"pad_row": 4}
        assert fleet["committed_per_lane"] == [3, 1]
        assert fleet["committed_imbalance"] == pytest.approx(1.0)  # (3-1)/2


#
# conservation across the serving matrix (the acceptance bar)
#


class TestConservationMatrix:
    @pytest.mark.parametrize("temperature", [0.0, 0.7])
    @pytest.mark.parametrize("ends", ["together", "apart"])
    def test_decode_matrix(self, micro, temperature, ends):
        cfg, params = micro
        eng = _engine(cfg, params, temperature=temperature, goodput=True)
        keys = ([jax.random.PRNGKey(i) for i in range(3)]
                if temperature else [None] * 3)
        prompts = [_prompt(40 + i, 5 + i, cfg) for i in range(3)]
        new = (6, 6, 6) if ends == "together" else (4, 6, 8)
        hs = [eng.submit(p, max_new_tokens=n, key=k) for p, n, k in zip(prompts, new, keys)]
        eng.drain()
        snap = eng.stats()["goodput"]
        _check_conserved(snap)
        assert snap["committed_tokens"] == _streamed([h.result(drive=False) for h in hs]) == 18
        # rows that end apart: the step sent ahead through each end but the
        # last runs the ended row once more, and that row-step is dead_scan_row
        assert snap["waste"].get("dead_scan_row", 0) == (0 if ends == "together" else 2)
        eng.shutdown()

    def test_every_dispatch_classified(self, micro):
        """dispatches covers every program the engine ran (prefill +
        decode lanes), and per-kind positions sum to the total."""
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        _drive(eng, [_prompt(50 + i, 5, cfg) for i in range(2)], n=4)
        rep = eng.goodput_report()
        assert rep.get("enabled", True) is not False
        assert set(rep["per_kind"]) <= {
            "prefill", "prefill_fresh", "prefill_chunk", "prefill_chunk_paged", "decode_paged"}
        assert "prefill_fresh" in rep["per_kind"]      # whole prompts at position 0
        assert sum(k["positions"] for k in rep["per_kind"].values()) \
            == rep["positions"]
        assert sum(k["dispatches"] for k in rep["per_kind"].values()) \
            == rep["dispatches"]
        eng.shutdown()

    def test_speculative_acceptance_exact(self, micro, draft):
        """Draft-kind committed reproduces the engine's acceptance
        integers exactly, and conservation spans both spec programs."""
        cfg, params = micro
        dcfg, dp = draft
        eng = _engine(cfg, params, num_blocks=64,
                      speculative=SpecConfig(dp, dcfg, K=2), goodput=True)
        res = _drive(eng, [_prompt(60 + i, 5 + i, cfg) for i in range(3)], n=6)
        snap = eng.stats()["goodput"]
        _check_conserved(snap)
        assert snap["committed_tokens"] == _streamed(res)
        per = eng.goodput_report()["per_kind"]
        assert per["draft_decode"]["committed"] == eng.spec_accepted_tokens
        live_rows = eng.spec_draft_tokens // eng.spec.K
        draft_live = per["draft_decode"]["positions"] \
            - per["draft_decode"]["waste"].get("pad_row", 0) \
            - per["draft_decode"]["waste"].get("dead_scan_row", 0)
        assert draft_live == eng.spec_draft_tokens == live_rows * eng.spec.K
        assert snap["waste"].get("draft_rejected", 0) > 0
        eng.shutdown()

    def test_preemption_replay_attributed(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, priorities=True, goodput=True,
                      num_blocks=10, max_batch=1, max_queue=8)
        h_low = eng.submit(_prompt(70, 8, cfg), max_new_tokens=8,
                           priority="low")
        for _ in range(5):
            eng.step()                                   # low is mid-decode
        eng.submit(_prompt(71, 8, cfg), max_new_tokens=4,
                   priority="high").result()
        r_low = h_low.result()
        assert eng.preempted == 1
        snap = eng.stats()["goodput"]
        _check_conserved(snap)
        assert snap["waste"].get("replay_preemption", 0) > 0
        assert r_low.tokens_recomputed > 0
        assert "replay_preemption" in r_low.recompute_causes
        eng.shutdown()

    def test_recovery_replay_attributed(self, micro):
        cfg, params = micro
        eng = _engine(
            cfg, params, goodput=True,
            fault_plan=FaultPlan(
                specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]))
        r = eng.submit(_prompt(72, 6, cfg), max_new_tokens=8).result()
        assert eng.recoveries == 1 and r.finish_reason == "length"
        snap = eng.stats()["goodput"]
        _check_conserved(snap)
        assert snap["waste"].get("replay_recovery", 0) > 0
        assert r.tokens_recomputed > 0
        assert "replay_recovery" in r.recompute_causes
        assert snap["committed_tokens"] == len(r.new_tokens)
        eng.shutdown()

    def test_session_tail_replay_attributed(self, micro):
        """A re-attached turn recomputes the parked turn's block-unaligned
        tail: those positions are replay_session_tail, not committed."""
        cfg, params = micro
        eng = _engine(cfg, params, sessions=True, goodput=True)
        p1 = _prompt(73, 6, cfg)                         # 6+5=11: unaligned
        r1 = eng.submit(p1, max_new_tokens=5, session_id="s").result()
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32),
                             _prompt(74, 3, cfg)])
        r2 = eng.submit(p2, max_new_tokens=4, session_id="s").result()
        assert r2.shared_prefix_blocks > 0
        snap = eng.stats()["goodput"]
        _check_conserved(snap)
        assert snap["waste"].get("replay_session_tail", 0) > 0
        assert r2.tokens_recomputed > 0
        assert "replay_session_tail" in r2.recompute_causes
        eng.shutdown()

    def test_clean_run_has_no_recompute(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        (r,) = _drive(eng, [_prompt(75, 5, cfg)], n=4)
        assert r.tokens_recomputed == 0 and r.recompute_causes == ()
        eng.shutdown()


#
# off-path byte-identity + zero new programs (the structural bar)
#


class TestOffPath:
    def test_off_engine_has_no_goodput_surface(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        _drive(eng, [_prompt(80, 5, cfg)], n=3)
        assert "goodput" not in eng.stats()
        assert eng.goodput_report() == {"enabled": False}
        eng.shutdown()

    def test_goodput_compiles_zero_new_programs(self, micro):
        """The ledger never enters the static program key: after an OFF
        engine warms the module cache, an ON engine of identical geometry
        adds no cache entries and compiles nothing itself."""
        from thunder_tpu.serving.engine import _program_cache

        cfg, params = micro
        prompts = [_prompt(81 + i, 5 + i, cfg) for i in range(2)]
        off = _engine(cfg, params)
        _drive(off, prompts, n=4)
        off.shutdown()
        keys_before = set(_program_cache)
        on = _engine(cfg, params, goodput=True)
        _drive(on, prompts, n=4)
        assert set(_program_cache) == keys_before
        assert all(v == 0 for v in on.compile_counts.values())
        _check_conserved(on.stats()["goodput"])
        on.shutdown()

    def test_bad_spec_rejected_at_build(self, micro):
        cfg, params = micro
        with pytest.raises(TypeError, match="goodput"):
            _engine(cfg, params, goodput=42)


#
# Prometheus text exposition (satellite: metrics export plane)
#


_PROM_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$')


def _prom_parse(text):
    """Minimal Prometheus text-format (0.0.4) checker: every sample line
    parses, names are legal, HELP/TYPE precede their family's samples,
    TYPE is a known kind.  Returns {family: {"type": t, "samples": {...}}}."""
    fams, cur = {}, None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert _PROM_NAME.match(name), name
            cur = fams.setdefault(name, {"type": None, "samples": {}})
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name in fams, f"TYPE before HELP for {name}"
            assert kind in ("counter", "gauge", "summary", "histogram",
                            "untyped"), kind
            fams[name]["type"] = kind
        else:
            m = _PROM_SAMPLE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            name, labels, value = m.groups()
            base = re.sub(r"_(sum|count)$", "", name)
            assert base in fams or name in fams, f"sample before HELP: {name}"
            float(value)                               # must parse (or raise)
            fams.setdefault(base, {"type": None, "samples": {}})
            fams[base]["samples"][(name, labels or "")] = value
    return fams


class TestPromExport:
    def test_round_trips_counter_and_gauge(self):
        reg = registry()
        reg.counter("promtest.requests").inc(41)
        reg.gauge("promtest.depth").set(2.5)
        fams = _prom_parse(export_text())
        assert fams["promtest_requests"]["type"] == "counter"
        assert fams["promtest_requests"]["samples"][
            ("promtest_requests", "")] == "41"
        assert fams["promtest_depth"]["samples"][
            ("promtest_depth", "")] == "2.5"

    def test_histogram_renders_as_summary(self):
        reg = registry()
        h = reg.histogram("promtest.lat_s")
        for v in (0.1, 0.2, 0.3, 0.4):
            h.observe(v)
        text = export_text()
        fams = _prom_parse(text)
        fam = fams["promtest_lat_s"]
        assert fam["type"] == "summary"
        keys = set(fam["samples"])
        assert ("promtest_lat_s", '{quantile="0.5"}') in keys
        assert ("promtest_lat_s_count", "") in keys
        assert float(fam["samples"][("promtest_lat_s_sum", "")]) \
            == pytest.approx(1.0)
        assert fam["samples"][("promtest_lat_s_count", "")] == "4"
        # the windowed-quantile caveat is part of the contract
        assert "window" in text.split("promtest_lat_s")[1].splitlines()[0]

    def test_name_sanitization(self):
        reg = registry()
        reg.counter("promtest.waste.pad-row").inc()
        fams = _prom_parse(export_text())
        assert "promtest_waste_pad_row" in fams

    def test_none_gauge_skipped_and_nonfinite_rendered(self):
        reg = registry()
        reg.gauge("promtest.unset")                      # value None
        reg.gauge("promtest.inf").set(math.inf)
        fams = _prom_parse(export_text())
        assert "promtest_unset" not in fams
        assert fams["promtest_inf"]["samples"][("promtest_inf", "")] == "+Inf"

    def test_tt_alias_covers_serving_metrics(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        _drive(eng, [_prompt(85, 5, cfg)], n=3)
        text = tt.metrics_export_text()
        fams = _prom_parse(text)
        assert "serving_goodput_positions" in fams
        assert "serving_goodput_committed_positions" in fams
        snap = eng.stats()["goodput"]
        assert fams["serving_goodput_positions"]["samples"][
            ("serving_goodput_positions", "")] == str(snap["positions"])
        eng.shutdown()


#
# histogram window + pool occupancy ring (satellites)
#


class TestHistogramWindow:
    def test_snapshot_carries_window(self):
        h = Histogram("t")
        h.observe(1.0)
        snap = h.snapshot()
        assert snap["window"] == Histogram.WINDOW

    def test_count_is_all_time_quantiles_windowed(self):
        h = Histogram("t")
        for _ in range(Histogram.WINDOW):
            h.observe(100.0)
        for _ in range(Histogram.WINDOW):
            h.observe(1.0)                               # evicts the 100s
        snap = h.snapshot()
        assert snap["count"] == 2 * Histogram.WINDOW     # all-time
        assert snap["p99"] == pytest.approx(1.0)         # window-local
        assert snap["max"] == 100.0                      # all-time


class TestOccupancyRing:
    def test_ring_bounded_and_snapshotted(self, micro):
        from thunder_tpu.serving.kv_pool import OCCUPANCY_WINDOW

        cfg, params = micro
        eng = _engine(cfg, params)
        for _ in range(OCCUPANCY_WINDOW + 8):
            eng.pool.sample_occupancy()
        occ = eng.pool.occupancy_snapshot()
        assert occ["window"] == OCCUPANCY_WINDOW
        assert occ["samples"] == OCCUPANCY_WINDOW        # ring, not a log
        assert len(eng.pool.occupancy_timeline()) == OCCUPANCY_WINDOW
        assert occ["last"] == (eng.pool.num_free, 0, 0)
        assert "occupancy_timeline" in eng.pool.state_snapshot()
        eng.shutdown()

    def test_engine_samples_and_exports_gauge(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        _drive(eng, [_prompt(86, 5, cfg)], n=3)
        occ = eng.stats()["pool_occupancy"]
        assert occ["samples"] > 0 and occ["peak_leased"] > 0
        assert "serving.pool.occupancy_frac" in tt.metrics_snapshot()
        eng.shutdown()


#
# telemetry request-schema v2 (satellite: reader-side pin)
#


class TestTelemetryV2:
    def test_run_start_documents_schema(self):
        from thunder_tpu.observability.telemetry import (
            REQUEST_FIELDS_V2, REQUEST_SCHEMA_V, StepLogger)

        sink = io.StringIO()
        StepLogger(sink, meta={"kind": "t"})
        head = json.loads(sink.getvalue().splitlines()[0])
        assert head["request_schema_v"] == REQUEST_SCHEMA_V == 2
        assert head["request_fields"] == list(REQUEST_FIELDS_V2)

    def test_request_records_pin_to_v2_fields(self, micro):
        """Reader-side schema pin: every field a served-request record
        carries is in REQUEST_FIELDS_V2 — growth is a deliberate bump."""
        from thunder_tpu.observability.telemetry import (
            REQUEST_FIELDS_V2, StepLogger)

        cfg, params = micro
        sink = io.StringIO()
        eng = _engine(cfg, params, goodput=True,
                      telemetry=StepLogger(sink, meta={"kind": "t"}),
                      fault_plan=FaultPlan(
                          specs=[FaultSpec(point=FP_DECODE, kind="oom", at=2)]))
        eng.submit(_prompt(87, 5, cfg), max_new_tokens=6).result()
        recs = [json.loads(l) for l in sink.getvalue().splitlines()]
        reqs = [r for r in recs if r.get("event") == "request"]
        assert reqs, "no request record written"
        for rec in reqs:
            assert rec["v"] == 2
            assert set(rec) <= set(REQUEST_FIELDS_V2), \
                set(rec) - set(REQUEST_FIELDS_V2)
        # the recovery in this run surfaces the v2 recompute fields
        assert any(r.get("tokens_recomputed", 0) > 0 for r in reqs)
        assert any("replay_recovery" in (r.get("recompute_causes") or [])
                   for r in reqs)
        eng.shutdown()


#
# fleet aggregation through the router (tentpole wiring)
#


class TestFleet:
    def test_router_aggregates_goodput(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, replicas=2, goodput=True)
        _drive(eng, [_prompt(90 + i, 5 + i, cfg) for i in range(4)], n=4)
        agg = eng.stats()["aggregate"]["goodput"]
        assert agg["lanes"] == 2
        assert agg["committed"] + sum(agg["waste"].values()) \
            == agg["positions"]
        assert len(agg["committed_per_lane"]) == 2
        assert agg["committed_imbalance"] >= 0.0
        rep = eng.goodput_report()
        assert rep["replicas"] == 2 and len(rep["per_replica"]) == 2
        assert rep["positions"] == agg["positions"]
        eng.shutdown()

    def test_router_off_path(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, replicas=2)
        _drive(eng, [_prompt(94, 5, cfg)], n=3)
        assert "goodput" not in eng.stats()["aggregate"]
        assert eng.goodput_report()["enabled"] is False
        eng.shutdown()
