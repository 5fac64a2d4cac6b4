"""Fault-tolerant serving: deterministic injection, quarantine, retry,
re-prefill recovery (ISSUE 12).

The load-bearing guarantee is differential: with any seeded FaultPlan that
eventually allows progress, drained tokens are bit-identical to the
fault-free run — the PRNG key chain only advances at harvest, so the KV
arena is soft state the engine can rebuild by replaying known tokens
through the sampling-free chunked-prefill program.  Fast tests pin one
fault per injection site and assert the expected classification path
(quarantine / retry / recovery); the chaos soak (``slow``) drives a random
seeded plan over a mixed int8+LoRA workload.  ``fault_plan=None`` must keep
the compiled-program set byte-identical (module-cache assertion).
"""
from __future__ import annotations

import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.distributed import make_mesh
from thunder_tpu.models import llama
from thunder_tpu.observability.metrics import registry
from thunder_tpu.serving import (
    AdapterRegistry,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    make_lora_factors,
)
from thunder_tpu.serving.faults import (
    CLASS_ENGINE,
    CLASS_REQUEST,
    CLASS_TRANSIENT,
    FP_DECODE,
    FP_HARVEST,
    FP_PREFILL,
    FP_SCATTER,
    DeviceOOMFault,
    HarvestHangFault,
    RequestAnomalyFault,
    TransientDispatchFault,
    WatchdogTimeout,
    classify_fault,
    fault_cause,
    resolve_fault_plan,
)

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(2, 8), prefill_buckets=(8, 16))


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    # deterministic tests never want a real sleep between retries
    kw.setdefault("retry", RetryPolicy(sleep=lambda s: None))
    return tt.serve(None, params, cfg, **kw)


def _pool_clean(eng):
    return eng.pool.num_free == eng.pool.num_usable and not eng.pool._retired


P0 = np.arange(1, 7, dtype=np.int32)
P1 = np.arange(3, 12, dtype=np.int32)


#
# plan mechanics (pure host: no engine, no device)
#


class TestFaultPlan:
    def test_spec_validation(self):
        with pytest.raises(ValueError, match="unknown fault point"):
            FaultSpec(point="nope")
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSpec(point=FP_DECODE, kind="nope")
        with pytest.raises(ValueError, match="at/count"):
            FaultSpec(point=FP_DECODE, at=0)
        with pytest.raises(ValueError, match="rate"):
            FaultPlan(rate=1.5)

    def test_arrival_counting_and_window(self):
        plan = FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="fail", at=2, count=2)])
        plan.check(FP_DECODE, (0,))                    # arrival 1: no fire
        for _ in range(2):                             # arrivals 2 and 3: window
            with pytest.raises(TransientDispatchFault):
                plan.check(FP_DECODE, (0,))
        plan.check(FP_DECODE, (0,))                    # arrival 4: past the window
        plan.check(FP_PREFILL, (0,))                   # other points never fire
        assert plan.injected == 2
        assert [f["point"] for f in plan.fired] == [FP_DECODE, FP_DECODE]

    def test_rid_pinned_spec_counts_and_blames_only_that_rid(self):
        plan = FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="nan", at=2, rid=7)])
        plan.check(FP_DECODE, (1, 2))                  # rid 7 absent: not an arrival
        plan.check(FP_DECODE, (1, 7))                  # arrival 1
        with pytest.raises(RequestAnomalyFault) as ei:
            plan.check(FP_DECODE, (1, 7, 9))           # arrival 2: fires
        # blast radius is the poison request, not the batch it shared
        assert ei.value.rids == (7,)

    def test_max_faults_bounds_total_injections(self):
        plan = FaultPlan(
            specs=[FaultSpec(point=FP_DECODE, kind="fail", at=1, count=99)],
            max_faults=3,
        )
        for _ in range(3):
            with pytest.raises(TransientDispatchFault):
                plan.check(FP_DECODE, (0,))
        plan.check(FP_DECODE, (0,))                    # exhausted: progress allowed
        assert plan.injected == 3

    def test_seeded_random_mode_is_deterministic(self):
        def run(seed):
            plan = FaultPlan(seed=seed, rate=0.5, max_faults=4)
            fired = []
            for i in range(32):
                try:
                    plan.check(FP_DECODE, (i % 3, (i + 1) % 3))
                except Exception as e:
                    fired.append((i, type(e).__name__, e.rids))
            return fired

        a, b = run(42), run(42)
        assert a == b and len(a) == 4                  # same seed, same schedule
        assert run(43) != a                            # different seed differs
        # a random nan blames exactly one in-flight request
        for _, name, rids in a:
            if name == "RequestAnomalyFault":
                assert len(rids) == 1

    def test_classification_taxonomy(self):
        assert classify_fault(RequestAnomalyFault(FP_DECODE)) == CLASS_REQUEST
        assert classify_fault(TransientDispatchFault(FP_PREFILL)) == CLASS_TRANSIENT
        for exc in (DeviceOOMFault(FP_DECODE), HarvestHangFault(FP_HARVEST),
                    WatchdogTimeout(FP_HARVEST, (1,), age_s=3.0)):
            assert classify_fault(exc) == CLASS_ENGINE
        # real runtime failures classify off the status-code surface
        assert classify_fault(RuntimeError("rpc UNAVAILABLE: socket closed")) == CLASS_TRANSIENT
        assert classify_fault(RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == CLASS_ENGINE
        # anything else stays un-absorbed (crash-dump-and-raise contract)
        assert classify_fault(KeyError("bug")) is None
        assert classify_fault(RuntimeError("plain bug")) is None
        cause = fault_cause(WatchdogTimeout(FP_HARVEST, (1,), age_s=3.0))
        assert cause["kind"] == "hang" and cause["injected"] is False
        assert cause["rids"] == [1] and cause["point"] == FP_HARVEST

    def test_resolve_fault_plan_forms(self, monkeypatch):
        assert resolve_fault_plan(False) is None
        monkeypatch.delenv("THUNDER_TPU_FAULT_PLAN", raising=False)
        assert resolve_fault_plan(None) is None
        spec = FaultSpec(point=FP_DECODE)
        assert resolve_fault_plan(spec).specs == (spec,)
        assert resolve_fault_plan({"point": FP_HARVEST, "kind": "oom"}).specs[0].kind == "oom"
        assert resolve_fault_plan({"seed": 1, "rate": 0.1}).rate == 0.1
        assert resolve_fault_plan([{"point": FP_DECODE}]).specs[0].point == FP_DECODE
        monkeypatch.setenv(
            "THUNDER_TPU_FAULT_PLAN",
            json.dumps({"specs": [{"point": "harvest", "kind": "hang", "at": 2}], "max_faults": 1}),
        )
        env_plan = resolve_fault_plan(None)
        assert env_plan.max_faults == 1 and env_plan.specs[0].point == FP_HARVEST
        with pytest.raises(TypeError):
            resolve_fault_plan(123)

    def test_retry_policy_backoff(self):
        pol = RetryPolicy(max_retries=3, backoff_s=0.1, multiplier=2.0, sleep=lambda s: None)
        assert [pol.backoff(n) for n in (1, 2, 3)] == [0.1, 0.2, 0.4]
        with pytest.raises(ValueError):
            RetryPolicy(max_retries=-1)


#
# per-site classification paths (micro engine, one pinned fault each)
#


class TestFaultPaths:
    def _ref(self, cfg, params, n=8, **kw):
        eng = _engine(cfg, params, **kw)
        return eng.submit(P0, max_new_tokens=n).result().new_tokens

    def test_prefill_transient_fail_retries_with_backoff(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        slept = []
        eng = _engine(
            cfg, params,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_PREFILL, kind="fail", at=1, count=2)]),
            retry=RetryPolicy(backoff_s=0.05, multiplier=2.0, sleep=slept.append),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref and r.finish_reason == "length"
        assert slept == [0.05, 0.1]                    # exponential, injectable
        assert eng.recoveries == 0                     # retry sufficed
        snap = tt.metrics_snapshot()
        assert snap["serving.faults.injected"] == 2
        assert snap["serving.faults.observed"] == 2
        assert snap["serving.faults.retries"] == 2
        assert _pool_clean(eng)

    def test_decode_nan_quarantines_only_the_poison_request(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        ha = eng.submit(P0, max_new_tokens=8, key=jax.random.PRNGKey(7))
        hb = eng.submit(P1, max_new_tokens=8, key=jax.random.PRNGKey(8))
        refa, refb = ha.result().new_tokens, hb.result().new_tokens

        eng = _engine(
            cfg, params, flight_recorder=True,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="nan", at=3, rid=0)]),
        )
        ha = eng.submit(P0, max_new_tokens=8, key=jax.random.PRNGKey(7))
        hb = eng.submit(P1, max_new_tokens=8, key=jax.random.PRNGKey(8))
        eng.drain()
        ra, rb = ha.result(drive=False), hb.result(drive=False)
        # poison request: finished with the structured cause, tokens a prefix
        assert ra.finish_reason == "error"
        assert ra.error["kind"] == "nan" and ra.error["point"] == FP_DECODE
        assert ra.error["rids"] == [0] and ra.error["injected"] is True
        assert ra.new_tokens == refa[: len(ra.new_tokens)]
        # bystander: untouched, bit-identical
        assert rb.finish_reason == "length" and rb.new_tokens == refb
        kinds = [e["kind"] for e in eng._flight.events()]
        assert "fault" in kinds and "quarantine" in kinds
        snap = tt.metrics_snapshot()
        assert snap["serving.faults.quarantined"] == 1
        assert snap["serving.finish.error"] == 1
        assert eng.recoveries == 0
        assert _pool_clean(eng)

    @pytest.mark.parametrize("async_step", [True, False])
    def test_decode_oom_triggers_recovery_bit_identical(self, micro, async_step):
        cfg, params = micro
        ref = self._ref(cfg, params, async_step=async_step)
        eng = _engine(
            cfg, params, async_step=async_step, flight_recorder=True,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref and r.finish_reason == "length"
        assert eng.recoveries == 1
        kinds = [e["kind"] for e in eng._flight.events()]
        assert "fault" in kinds and "recover" in kinds and "recovered" in kinds
        snap = tt.metrics_snapshot()
        assert snap["serving.faults.recoveries"] == 1
        assert snap["serving.recovery.duration_s"]["count"] == 1
        assert _pool_clean(eng)

    def test_scatter_fault_routes_to_recovery_not_stale_retry(self, micro):
        """The donated-arena hazard: a failed dispatch past the donation
        point may have consumed its inputs, so even a *transient* fault at
        the scatter routes through arena rebuild instead of re-submitting
        stale handles."""
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(
            cfg, params,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_SCATTER, kind="fail", at=2)]),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref
        assert eng.recoveries == 1                     # not a plain retry
        assert _pool_clean(eng)

    def test_harvest_hang_fault_recovers(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(
            cfg, params,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_HARVEST, kind="hang", at=2)]),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref and eng.recoveries == 1
        assert _pool_clean(eng)

    def test_retry_exhaustion_escalates_to_recovery(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(
            cfg, params,
            retry=RetryPolicy(max_retries=1, sleep=lambda s: None),
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="fail", at=2, count=2)]),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref
        assert eng.recoveries >= 1                     # streak 2 > max_retries=1
        assert tt.metrics_snapshot()["serving.faults.retries"] >= 1
        assert _pool_clean(eng)

    def test_watchdog_converts_hung_harvest_to_recovery(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        clk = {"t": 0.0}
        eng = _engine(cfg, params, clock=lambda: clk["t"], watchdog_timeout_s=5.0)
        h = eng.submit(P0, max_new_tokens=8)
        steps = 0
        while not h.done():
            eng.step()
            steps += 1
            if steps == 2:
                clk["t"] += 100.0                      # in-flight decode now "hung"
        assert h.result(drive=False).new_tokens == ref
        assert eng.recoveries == 1
        fired = eng.stats()
        assert fired["recoveries"] == 1
        assert _pool_clean(eng)

    def test_manual_recover_midstream(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(cfg, params)
        h = eng.submit(P0, max_new_tokens=8)
        for _ in range(4):
            eng.step()
        eng.recover()                                  # operational rebuild
        assert h.result().new_tokens == ref
        assert eng.recoveries == 1 and _pool_clean(eng)

    def test_unclassified_exception_still_raises(self, micro):
        """A programming error is not a fault: the crash-dump-and-raise
        contract survives the recovery layer."""
        cfg, params = micro
        eng = _engine(cfg, params)
        eng.submit(P0, max_new_tokens=4)
        original = eng._decode_dispatch

        def boom(*a, **k):
            raise KeyError("programming bug")

        eng._decode_dispatch = boom
        with pytest.raises(KeyError):
            eng.drain()
        eng._decode_dispatch = original

    def test_fault_plan_off_keeps_programs_byte_identical(self, micro):
        """Arming a plan (that never fires) adds zero compiled programs and
        changes zero tokens: fault checks are host arithmetic outside the
        program cache key."""
        from thunder_tpu.serving.engine import _program_cache

        cfg, params = micro
        eng = _engine(cfg, params)
        ref = eng.submit(P0, max_new_tokens=4).result().new_tokens
        n_progs = len(_program_cache)
        eng2 = _engine(
            cfg, params,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=10_000)]),
        )
        r = eng2.submit(P0, max_new_tokens=4).result()
        assert len(_program_cache) == n_progs          # same cache keys: cache hit
        assert r.new_tokens == ref
        assert eng2.stats()["faults"]["injected"] == 0
        # unarmed engine reports no plan at all
        assert eng.stats()["faults"] is None


#
# error finish_reason plumbing (SLO, telemetry, tracing)
#


class TestErrorFinishPlumbing:
    def test_slo_counts_error_bad_on_every_dim(self, micro):
        cfg, params = micro
        eng = _engine(
            cfg, params, slo={"ttft_s": 60.0, "tpot_s": 60.0},
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="nan", at=2, rid=0)]),
        )
        eng.submit(P0, max_new_tokens=6).result()
        rep = eng.slo_report()
        for dim in ("ttft_s", "tpot_s"):
            assert rep["dimensions"][dim]["bad"] == 1  # generous targets: only error

    def test_telemetry_and_tracer_carry_error_cause(self, micro):
        import io

        from thunder_tpu.observability.telemetry import StepLogger

        cfg, params = micro
        sink = io.StringIO()
        eng = _engine(
            cfg, params, trace=True, telemetry=StepLogger(sink),
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="nan", at=2, rid=0)]),
        )
        eng.submit(P0, max_new_tokens=6).result()
        recs = [json.loads(l) for l in sink.getvalue().splitlines()]
        req = next(r for r in recs if r.get("event") == "request")
        assert req["finish_reason"] == "error"
        assert req["error"]["kind"] == "nan"
        import sys

        import thunder_tpu.observability.events  # noqa: F401

        ev = sys.modules["thunder_tpu.observability.events"]
        finishes = [e for e in ev.events() if e.get("name") == "finish"]
        assert any((e.get("args") or {}).get("error") == "RequestAnomalyFault"
                   for e in finishes)


#
# recovery parity across serving features
#


class TestRecoveryParity:
    def test_temperature_sampling_recovers_bit_identical(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, temperature=0.8)
        ref = eng.submit(P0, max_new_tokens=8, key=jax.random.PRNGKey(3)).result().new_tokens
        eng = _engine(
            cfg, params, temperature=0.8,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_HARVEST, kind="oom", at=3)]),
        )
        r = eng.submit(P0, max_new_tokens=8, key=jax.random.PRNGKey(3)).result()
        assert r.new_tokens == ref and eng.recoveries == 1

    def test_int8_kv_recovers_bit_identical(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8")
        ref = eng.submit(P0, max_new_tokens=8).result().new_tokens
        eng = _engine(
            cfg, params, kv_dtype="int8",
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]),
        )
        r = eng.submit(P0, max_new_tokens=8).result()
        assert r.new_tokens == ref and eng.recoveries == 1
        assert _pool_clean(eng)

    def test_lora_adapter_recovers_bit_identical(self, micro):
        cfg, params = micro
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2)
        reg.register("a", make_lora_factors(cfg, rank=2, key=jax.random.PRNGKey(5)))
        eng = _engine(cfg, params, lora=reg)
        ref = eng.submit(P0, max_new_tokens=6, adapter_id="a").result().new_tokens
        eng = _engine(
            cfg, params, lora=reg,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_HARVEST, kind="oom", at=2)]),
        )
        r = eng.submit(P0, max_new_tokens=6, adapter_id="a").result()
        assert r.new_tokens == ref and eng.recoveries == 1

    def test_chunked_prefill_recovers_bit_identical(self, micro):
        cfg, params = micro
        plong = np.arange(1, 14, dtype=np.int32)
        eng = _engine(cfg, params, prefill_chunk=8)
        ref = eng.submit(plong, max_new_tokens=6).result().new_tokens
        eng = _engine(
            cfg, params, prefill_chunk=8,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_SCATTER, kind="oom", at=2)]),
        )
        r = eng.submit(plong, max_new_tokens=6).result()
        assert r.new_tokens == ref and eng.recoveries == 1
        assert _pool_clean(eng)

    def test_mesh_engine_recovers_bit_identical(self, micro):
        cfg, params = micro
        # not jax.make_mesh: since jax 0.9 its axes default to Explicit
        # (sharding in types), which the engine's programs are not written for
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        eng = _engine(cfg, params, mesh=mesh)
        ref = eng.submit(P0, max_new_tokens=6).result().new_tokens
        eng = _engine(
            cfg, params, mesh=mesh,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]),
        )
        r = eng.submit(P0, max_new_tokens=6).result()
        assert r.new_tokens == ref and eng.recoveries == 1
        # rebuilt arenas keep the compiled-against sharding
        assert eng.pool.k_arena.sharding == eng.pool.arena_sharding


#
# shutdown hygiene (satellite bugfix)
#


class TestShutdownInflight:
    def test_shutdown_discards_inflight_futures_and_retired_handles(self, micro):
        """Regression: shutdown(drain=False) with an async decode (and a
        chunk prefill) in flight must drop the futures table and the parked
        donated handles — neither may leak past the engine's life."""
        cfg, params = micro
        plong = np.arange(1, 14, dtype=np.int32)
        eng = _engine(cfg, params, prefill_chunk=8)
        eng.submit(P0, max_new_tokens=8)
        eng.submit(plong, max_new_tokens=8)
        for _ in range(3):
            eng.step()                                 # decode + chunk in flight
        assert eng._inflight_decode is not None or eng._inflight_prefill
        eng.shutdown(drain=False)
        assert eng._inflight_decode is None and eng._inflight_prefill == []
        assert eng.pool._retired == []
        assert eng.pool.num_free == eng.pool.num_usable

    def test_shutdown_drain_still_clean(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        h = eng.submit(P0, max_new_tokens=4)
        eng.step()
        eng.shutdown(drain=True)
        assert h.done() and _pool_clean(eng)


#
# a decode record dispatched ahead of the harvest before it
#


def _ahead_in_flight(eng) -> bool:
    rec = eng._inflight_decode
    return rec is not None and "parked" in rec


class TestFaultsWithAStepAhead:
    """The engine dispatches a steady batch's next decode step before it
    harvests the last one.  Faults, the watchdog, eviction and shutdown meet
    that record as they meet any in-flight record: its tokens were never
    promised, its parked handles go with it."""

    def _ref(self, cfg, params, n=10):
        return _engine(cfg, params).submit(P0, max_new_tokens=n).result().new_tokens

    @pytest.mark.parametrize("point,kind,at", [
        (FP_DECODE, "oom", 4),       # the dispatch ahead itself refuses, the step before still in flight
        (FP_SCATTER, "fail", 4),     # it fails past the donation
        (FP_HARVEST, "hang", 4),     # the harvest it overtook fails, the step ahead on the device
    ])
    def test_a_fault_around_a_dispatch_ahead_recovers_bit_identical(self, micro, point, kind, at):
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(cfg, params, fault_plan=FaultPlan(specs=[FaultSpec(point=point, kind=kind, at=at)]))
        seen = []
        recover = eng._recover
        eng._recover = lambda cause: (seen.append((eng.decode_ahead_steps, _ahead_in_flight(eng))), recover(cause))
        r = eng.submit(P0, max_new_tokens=10).result()
        assert r.new_tokens == ref and r.finish_reason == "length"
        assert eng.recoveries == 1 and len(seen) == 1 and seen[0][0] >= 1
        if point == FP_HARVEST:
            assert seen[0][1]                              # the record ahead was in flight, and was discarded
        assert eng.decode_ahead_steps > seen[0][0]         # and the engine ran ahead again afterwards
        assert _pool_clean(eng)

    @pytest.mark.parametrize("point,kind", [
        (FP_DECODE, "oom"),          # the dispatch ahead through the end refuses, the ending step still in flight
        (FP_SCATTER, "fail"),        # it fails past the donation
        (FP_HARVEST, "hang"),        # the ending step's harvest fails, the step past the end on the device
    ])
    def test_a_fault_around_a_dispatch_ahead_through_a_rows_end_loses_neither_record(self, micro, point, kind):
        """The twin at an ending step: P0's last step is in flight and the
        dispatch ahead carries P0 as a dead row beside P1.  A fault inside that
        dispatch leaves the ending step's record where it was, one in the
        harvest finds both; recovery discards what is in flight, P0's last token
        and P1's are derived again, and both streams are the fault-free ones."""
        cfg, params = micro
        lengths = ((P0, 5, 7), (P1, 10, 8))

        def submit(eng):
            return [eng.submit(p, max_new_tokens=n, key=jax.random.PRNGKey(k)) for p, n, k in lengths]

        # the fault-free run, its arrivals at each point counted: where the step ahead through the end falls
        eng = _engine(cfg, params)
        arrivals = collections.Counter()
        eng._fault_point = lambda pt, rids=(): arrivals.update([pt])
        hs, at = submit(eng), None
        while not all(h.done() for h in hs):
            before = dict(arrivals)
            eng.step()
            rec = eng._inflight_decode
            if rec is not None and rec["ending"]:
                assert at is None                              # once: P1 ends alone, nobody outlives it
                at = before[point] + 1                         # the turn's first arrival: the dispatch, or step k's harvest
        ref = [h.result(drive=False).new_tokens for h in hs]
        assert at is not None and eng.stats()["decode_ahead"]["through_end"] == 1

        eng = _engine(cfg, params, fault_plan=FaultPlan(specs=[FaultSpec(point=point, kind=kind, at=at)]))
        seen = []
        recover = eng._recover

        def watched(cause):
            rec = eng._inflight_decode
            seen.append((rec is not None and rec["ending"], [len(r.generated) for r in eng.scheduler.running]))
            return recover(cause)

        eng._recover = watched
        hs = submit(eng)
        eng.drain()
        res = [h.result(drive=False) for h in hs]
        assert [r.new_tokens for r in res] == ref and all(r.finish_reason == "length" for r in res)
        assert eng.recoveries == 1 and len(seen) == 1
        ending, generated = seen[0]
        # P0 stood one token from its end: the record in flight was its last step's
        # (a fault in the dispatch), or the step past it with P0 dead in it (a fault in the harvest)
        assert generated == [4, 4] and ending == (1 if point == FP_HARVEST else 0)
        assert _pool_clean(eng)

    def test_a_poison_row_named_by_the_dispatch_ahead_is_quarantined_alone(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        ha = eng.submit(P0, max_new_tokens=10, key=jax.random.PRNGKey(7))
        hb = eng.submit(P1, max_new_tokens=10, key=jax.random.PRNGKey(8))
        refa, refb = ha.result().new_tokens, hb.result().new_tokens
        eng = _engine(cfg, params, fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="nan", at=4, rid=0)]))
        ha = eng.submit(P0, max_new_tokens=10, key=jax.random.PRNGKey(7))
        hb = eng.submit(P1, max_new_tokens=10, key=jax.random.PRNGKey(8))
        eng.drain()
        ra, rb = ha.result(drive=False), hb.result(drive=False)
        assert ra.finish_reason == "error" and ra.new_tokens == refa[:len(ra.new_tokens)]
        assert rb.finish_reason == "length" and rb.new_tokens == refb
        assert eng.recoveries == 0 and eng.decode_ahead_steps > 0 and _pool_clean(eng)

    def test_the_watchdog_finds_the_overtaken_record(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        clk = {"t": 0.0}
        eng = _engine(cfg, params, clock=lambda: clk["t"], watchdog_timeout_s=5.0)
        h = eng.submit(P0, max_new_tokens=10)
        jumped = False
        while not h.done():
            eng.step()
            if not jumped and _ahead_in_flight(eng):
                clk["t"] += 100.0                          # the record in flight is "hung"; the next step runs ahead of it
                jumped = True
        assert jumped and h.result(drive=False).new_tokens == ref
        assert eng.recoveries == 1 and _pool_clean(eng)

    def test_evict_and_shutdown_with_a_record_ahead(self, micro):
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(cfg, params)
        h = eng.submit(P0, max_new_tokens=10)
        while not _ahead_in_flight(eng):
            eng.step()
        eng.evict(h)                                       # the record on the device names a finished row
        assert h.result(drive=False).finish_reason == "evicted"
        assert eng.pool.num_free == eng.pool.num_usable
        assert eng.submit(P0, max_new_tokens=10).result().new_tokens == ref
        assert _pool_clean(eng)
        h = eng.submit(P0, max_new_tokens=10)
        while not _ahead_in_flight(eng):
            eng.step()
        assert eng.pool.n_retired >= 1
        eng.shutdown(drain=False)
        assert eng._inflight_decode is None and eng._decode_state is None
        assert h.result(drive=False).finish_reason == "evicted" and _pool_clean(eng)

    def test_held_reads_the_caches_behind_a_record_ahead(self, micro):
        """``held()`` harvests what is in flight first: with a record ahead that
        is one record, as ever, and the chain goes on from it."""
        cfg, params = micro
        ref = self._ref(cfg, params)
        eng = _engine(cfg, params)
        h = eng.submit(P0, max_new_tokens=10)
        while not _ahead_in_flight(eng):
            eng.step()
        held = eng.held(h)
        assert eng._inflight_decode is None and held["tokens"] == h._req.pos == len(P0) + len(h.tokens_so_far()) - 1
        assert eng.pool.n_retired == 0
        assert h.result().new_tokens == ref and _pool_clean(eng)


#
# chaos soak (slow): random seeded plan over a mixed int8+LoRA workload
#


@pytest.mark.slow
class TestChaosSoak:
    def test_random_plan_no_divergence_no_leaks(self, micro):
        cfg, params = micro
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2)
        reg.register("a", make_lora_factors(cfg, rank=2, key=jax.random.PRNGKey(5)))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 6, 9, 5, 12, 7)]
        adapters = [None, "a", None, "a", None, "a"]

        def drive(fault_plan=None):
            eng = _engine(cfg, params, kv_dtype="int8", lora=reg,
                          temperature=0.6, fault_plan=fault_plan)
            handles = [
                eng.submit(p, max_new_tokens=8, adapter_id=a,
                           key=jax.random.PRNGKey(100 + i))
                for i, (p, a) in enumerate(zip(prompts, adapters))
            ]
            eng.drain()
            return eng, [h.result(drive=False) for h in handles]

        _, refs = drive()
        for seed in (1, 2, 3):
            eng, results = drive(FaultPlan(seed=seed, rate=0.08, max_faults=6))
            for ref, res in zip(refs, results):
                if res.finish_reason == "error":
                    # quarantined: partial stream is a prefix of the
                    # fault-free stream, cause attached
                    assert res.new_tokens == ref.new_tokens[: len(res.new_tokens)]
                    assert res.error is not None
                else:
                    # survivor: bit-identical to the fault-free run
                    assert res.new_tokens == ref.new_tokens, f"seed={seed}"
            assert _pool_clean(eng), f"seed={seed} leaked blocks"
            assert len(eng.scheduler.queue) == 0 and len(eng.scheduler.running) == 0
