"""Serving subsystem: paged KV pool, continuous-batching scheduler, engine.

The load-bearing guarantee is differential: tokens produced through the
continuously-batched engine must be *identical* to a solo ``generate()``
run with the same seed — greedy AND temperature sampling (each request
carries its own PRNG key chain, split exactly like the solo path).  Policy
behavior (admission, FIFO, deadlines, eviction, prefix sharing, window
expiry) is tested host-side on a micro model so the whole file stays
CPU-fast; the multi-request soak at the end is ``slow``-marked.
"""
from __future__ import annotations

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import (
    AdmissionError,
    PagedKVPool,
    PoolExhaustedError,
    Scheduler,
    pick_bucket,
    pow2_buckets,
)
from thunder_tpu.serving.kv_pool import SINK_BLOCK

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


def _solo(params, prompt, cfg, n, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return np.asarray(gen.generate(params, np.asarray(prompt)[None], cfg, n, **kw))[0]


#
# paged pool (pure allocator)
#


class TestPagedKVPool:
    def _pool(self, cfg, n=8, bs=4):
        return PagedKVPool(cfg, num_blocks=n, block_size=bs, dtype=jnp.float32)

    def test_alloc_free_roundtrip_and_sink(self, micro):
        cfg, _ = micro
        pool = self._pool(cfg)
        assert pool.num_usable == 7 and pool.num_free == 7
        got = pool.alloc(3)
        assert SINK_BLOCK not in got and len(set(got)) == 3
        assert pool.num_free == 4 and pool.utilization() == pytest.approx(3 / 7)
        pool.free(got)
        assert pool.num_free == 7 and pool.utilization() == 0.0

    def test_exhaustion_raises_without_side_effects(self, micro):
        cfg, _ = micro
        pool = self._pool(cfg)
        pool.alloc(5)
        with pytest.raises(PoolExhaustedError):
            pool.alloc(3)
        assert pool.num_free == 2  # the failed alloc leased nothing

    def test_refcount_sharing(self, micro):
        cfg, _ = micro
        pool = self._pool(cfg)
        blocks = pool.alloc(2)
        pool.share(blocks)
        assert all(pool.refcount(b) == 2 for b in blocks)
        assert pool.free(blocks) == 0          # first owner out: still leased
        assert pool.num_free == 5
        assert pool.free(blocks) == 2          # last owner out: blocks return
        assert pool.num_free == 7
        with pytest.raises(ValueError):
            pool.free(blocks)                  # double free
        with pytest.raises(ValueError):
            pool.share(blocks)                 # unleased share

    def test_geometry_helpers(self, micro):
        cfg, _ = micro
        pool = self._pool(cfg, bs=4)
        assert pool.blocks_for_tokens(1) == 1
        assert pool.blocks_for_tokens(4) == 1
        assert pool.blocks_for_tokens(5) == 2
        L, ng, hs = cfg.n_layer, cfg.n_query_groups, cfg.head_size
        assert pool.k_arena.shape == (8, L, ng, 4, hs)
        assert pool.dense_shape(3, 2) == (L, 3, ng, 8, hs)


#
# scheduler policy (host-side, no compiled programs)
#


class TestSchedulerPolicy:
    def _sched(self, cfg, *, num_blocks=8, bs=4, **kw):
        pool = PagedKVPool(cfg, num_blocks=num_blocks, block_size=bs, dtype=jnp.float32)
        return Scheduler(pool, **kw)

    def test_buckets(self):
        assert pow2_buckets(1, 8) == (1, 2, 4, 8)
        assert pow2_buckets(3, 5) == (4, 8)
        assert pick_bucket(3, (1, 2, 4, 8)) == 4
        with pytest.raises(ValueError):
            pick_bucket(9, (1, 2, 4, 8))

    def test_submit_validation(self, micro):
        cfg, _ = micro
        sch = self._sched(cfg)
        key = jax.random.PRNGKey(0)
        with pytest.raises(ValueError):
            sch.submit(np.zeros(0, np.int32), 4, key=key)
        with pytest.raises(ValueError):
            sch.submit([1, 2], 0, key=key)
        with pytest.raises(AdmissionError):
            sch.submit(np.arange(20) % 32, 64, key=key)  # can never fit 7 blocks

    def test_queue_bound_rejects(self, micro):
        cfg, _ = micro
        sch = self._sched(cfg, max_queue=2)
        key = jax.random.PRNGKey(0)
        sch.submit([1, 2, 3], 4, key=key)
        sch.submit([1, 2, 3], 4, key=key)
        with pytest.raises(AdmissionError):
            sch.submit([1, 2, 3], 4, key=key)

    def test_fifo_head_blocks_smaller_requests(self, micro):
        """Strict FIFO: an unadmittable head is never jumped by a smaller
        later request (no starvation of big requests under saturation)."""
        cfg, _ = micro
        sch = self._sched(cfg, num_blocks=8)       # 7 usable
        key = jax.random.PRNGKey(0)
        big = sch.submit(np.arange(16) % 32, 8, key=key)     # 6 blocks
        small = sch.submit([1, 2], 1, key=key)               # 1 block
        sch.pool.alloc(3)                                    # only 4 free now
        assert sch.next_admittable() is None                 # head (6 > 4) blocks...
        assert sch.queue[0] is big and sch.queue[1] is small  # ...and small waits
        assert sch.blocks_needed(big) == 6

    def test_deadline_expiry_with_injected_clock(self, micro):
        cfg, _ = micro
        clk = {"t": 0.0}
        sch = self._sched(cfg, clock=lambda: clk["t"])
        key = jax.random.PRNGKey(0)
        r1 = sch.submit([1, 2], 4, key=key, deadline_s=5.0)
        r2 = sch.submit([1, 2], 4, key=key)                  # no deadline
        assert sch.deadline_expired() == []
        clk["t"] = 6.0
        assert sch.deadline_expired() == [r1]
        assert r2.deadline_t is None

    def test_window_expiry_releases_dead_blocks(self, micro):
        cfg, _ = micro
        sch = self._sched(cfg, sliding_window=6, bs=2, num_blocks=10)
        key = jax.random.PRNGKey(0)
        req = sch.submit([1, 2, 3], 9, key=key)              # capacity 12 -> 6 blocks
        sch.queue.popleft()
        req.block_table = sch.pool.alloc(6)
        req.state = "running"
        sch.running.append(req)
        req.pos = 4
        assert sch.expire_window_blocks(req) == 0            # nothing below pos+1-W
        req.pos = 9                                          # positions 0..3 dead
        free_before = sch.pool.num_free
        assert sch.expire_window_blocks(req) == 2            # blocks 0,1 (4 slots)
        assert sch.pool.num_free == free_before + 2
        assert req.block_table[0] == SINK_BLOCK and req.block_table[1] == SINK_BLOCK
        assert req.block_table[2] != SINK_BLOCK
        assert sch.expire_window_blocks(req) == 0            # idempotent


#
# engine end-to-end (micro model; programs shared via the module cache)
#


@pytest.fixture(scope="module")
def served(micro):
    """One engine drive shared by several assertions: mixed-length greedy
    batch with streaming callbacks and JSONL telemetry attached."""
    from thunder_tpu.observability.telemetry import StepLogger

    cfg, params = micro
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (3, 5, 9, 14)]
    sink = io.StringIO()
    streams: dict[int, list[int]] = {}
    eng = _engine(cfg, params, max_batch=4, num_blocks=32,
                  telemetry=StepLogger(sink, meta={"kind": "serving-test"}))
    handles = []
    for i, p in enumerate(prompts):
        streams[i] = []
        handles.append(eng.submit(p, max_new_tokens=5, stream_cb=streams[i].append))
    eng.drain()
    results = [h.result(drive=False) for h in handles]
    # snapshot eagerly: the autouse observability reset wipes the registry
    # between the tests that share this fixture
    snap = tt.metrics_snapshot()
    return cfg, params, prompts, results, streams, sink, eng, snap


class TestEngine:
    def test_differential_vs_solo_generate(self, served):
        """Acceptance: fixed seed, mixed-length batch — every request's
        tokens are identical to a solo generate() run."""
        cfg, params, prompts, results, *_ = served
        for p, r in zip(prompts, results):
            assert r.finish_reason == "length"
            np.testing.assert_array_equal(r.tokens, _solo(params, p, cfg, 5))

    def test_streaming_callback_ordering(self, served):
        _, _, _, results, streams, _, _, _ = served
        for i, r in enumerate(results):
            assert tuple(streams[i]) == r.new_tokens  # every token, in order

    def test_request_latency_metrics(self, served):
        _, _, _, results, _, _, eng, snap = served
        for r in results:
            assert r.ttft_s is not None and r.ttft_s >= 0
            assert r.tpot_s is not None and r.tpot_s >= 0
            assert r.queue_s is not None
            # submit→finish wall time dominates every partial latency
            assert r.e2e_s is not None and r.e2e_s >= r.ttft_s >= r.queue_s
        assert snap["serving.requests.completed"] >= 4
        assert snap["serving.ttft_s"]["count"] >= 4
        assert "p95" in snap["serving.ttft_s"]
        stats = eng.stats()
        assert stats["mean_batch_occupancy"] > 1.0
        assert stats["tokens_generated"] == sum(len(r.new_tokens) for r in results)

    def test_telemetry_jsonl_request_records(self, served):
        _, _, _, results, _, sink, _, _ = served
        recs = [json.loads(line) for line in sink.getvalue().splitlines()]
        assert recs[0]["event"] == "run_start"
        reqs = [r for r in recs if r["event"] == "request"]
        assert len(reqs) == 4
        for rec in reqs:
            assert rec["finish_reason"] == "length"
            assert rec["new_tokens"] == 5
            assert "ttft_s" in rec and "tokens_per_sec" in rec
            assert rec["e2e_s"] >= rec["ttft_s"]
            assert isinstance(rec["prefill_compiled"], bool)

    def test_pool_drains_clean(self, served):
        *_, eng, _snap = served
        assert eng.pool.num_free == eng.pool.num_usable
        assert len(eng.scheduler.queue) == 0 and len(eng.scheduler.running) == 0

    @pytest.mark.slow
    def test_temperature_parity_with_request_keys(self, micro):
        """Per-request PRNG chains: temperature samples match the solo run
        with the same key, independent of batch composition."""
        cfg, params = micro
        eng = _engine(cfg, params, temperature=0.7, num_blocks=32)
        p1 = (np.arange(6) * 3 + 1).astype(np.int32) % cfg.vocab_size
        p2 = (np.arange(11) * 5 + 2).astype(np.int32) % cfg.vocab_size
        h1 = eng.submit(p1, max_new_tokens=4, key=jax.random.PRNGKey(42))
        h2 = eng.submit(p2, max_new_tokens=6, key=jax.random.PRNGKey(7))
        eng.drain()
        np.testing.assert_array_equal(
            h1.result(drive=False).tokens,
            _solo(params, p1, cfg, 4, temperature=0.7, key=jax.random.PRNGKey(42)),
        )
        np.testing.assert_array_equal(
            h2.result(drive=False).tokens,
            _solo(params, p2, cfg, 6, temperature=0.7, key=jax.random.PRNGKey(7)),
        )

    def test_deadline_expiry_mid_decode(self, micro):
        cfg, params = micro
        clk = {"t": 0.0}
        eng = _engine(cfg, params, max_batch=1, clock=lambda: clk["t"])
        h = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=20, deadline=5.0)
        steps = 0
        while not h.done():
            eng.step()
            clk["t"] += 2.0
            steps += 1
        r = h.result(drive=False)
        assert r.finish_reason == "deadline"
        assert 0 < len(r.new_tokens) < 20                # cut mid-decode
        assert eng.pool.num_free == eng.pool.num_usable  # blocks reclaimed

    def test_pool_exhaustion_queues_then_rejects(self, micro):
        cfg, params = micro
        # 7 usable blocks; each request needs 24/4 = 6 -> only one resident
        eng = _engine(cfg, params, num_blocks=8, max_batch=2, max_queue=1)
        p = np.arange(4, dtype=np.int32)
        h1 = eng.submit(p, max_new_tokens=20)
        eng.step()                                       # h1 running, pool nearly full
        assert h1.state == "running"
        h2 = eng.submit(p, max_new_tokens=20)
        eng.step()
        assert h2.state == "queued"                      # pool full -> waits
        with pytest.raises(AdmissionError):
            eng.submit(p, max_new_tokens=20)             # queue full -> rejected
        eng.drain()
        assert h1.done() and h2.done()
        # FIFO: h2 was admitted only after h1 released its blocks
        assert h2.result(drive=False).queue_s > 0
        np.testing.assert_array_equal(
            h1.result(drive=False).tokens, h2.result(drive=False).tokens
        )

    def test_drain_stall_carries_state_snapshot(self, micro):
        """A stalled drain raises EngineStalledError with the flight-state
        snapshot attached (queued/running rids, pool counts) instead of the
        old bare 'engine stalled during drain' message."""
        from thunder_tpu.serving import EngineStalledError

        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=8, max_batch=2)
        leak = eng.pool.alloc(5)          # blocks held outside the scheduler
        h = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=8)
        with pytest.raises(EngineStalledError) as ei:
            eng.drain()
        assert h.state == "queued"        # head needs 3 blocks, 2 free: stuck
        err = ei.value
        assert err.state["pool"]["num_free"] == 2
        assert [r["rid"] for r in err.state["scheduler"]["requests"]] == [h.rid]
        assert f"queued rids=[{h.rid}]" in str(err)
        assert "free=2/8" in str(err)
        eng.pool.free(leak)
        eng.drain()                       # unstuck: the head admits and runs
        assert h.done()

    def test_fifo_fairness_under_saturation(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=8, max_batch=1)
        p = np.arange(3, dtype=np.int32)
        handles = [eng.submit(p, max_new_tokens=6, key=jax.random.PRNGKey(i)) for i in range(4)]
        eng.drain()
        admits = [h.result(drive=False) for h in handles]
        queue_times = [r.queue_s for r in admits]
        # admission strictly in submission order
        admit_ts = [h._req.admit_t for h in handles]
        assert admit_ts == sorted(admit_ts)
        assert queue_times[0] <= queue_times[-1]

    def test_eviction_and_block_reuse(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=8, max_batch=1)
        p = np.arange(4, dtype=np.int32) + 1
        h1 = eng.submit(p, max_new_tokens=16)
        eng.step()
        assert h1.state == "running"
        old_blocks = set(h1._req.block_table) - {SINK_BLOCK}
        assert old_blocks
        eng.evict(h1)
        assert h1.done() and h1.result(drive=False).finish_reason == "evicted"
        assert eng.pool.num_free == eng.pool.num_usable
        # a new request re-leases the evicted request's physical blocks and
        # still produces exactly the solo-generate tokens
        h2 = eng.submit(p, max_new_tokens=6)
        eng.step()
        assert set(h2._req.block_table) & old_blocks
        eng.drain()
        np.testing.assert_array_equal(
            h2.result(drive=False).tokens, _solo(params, p, cfg, 6)
        )

    def test_prefix_sharing_refcounts_and_correctness(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=32, max_batch=2)
        base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size
        ha = eng.submit(base, max_new_tokens=4)
        eng.step()                                       # prefill A, register prefix
        hb = eng.submit(base.copy(), max_new_tokens=4)
        eng.step()                                       # admit B via shared blocks
        shared = [b for b in hb._req.block_table if eng.pool.refcount(b) > 1]
        assert hb._req.n_shared_blocks == 2 and len(shared) >= 2
        eng.drain()
        ra, rb = ha.result(drive=False), hb.result(drive=False)
        assert rb.shared_prefix_blocks == 2
        solo = _solo(params, base, cfg, 4)
        np.testing.assert_array_equal(ra.tokens, solo)
        np.testing.assert_array_equal(rb.tokens, solo)
        assert eng.pool.num_free == eng.pool.num_usable  # refcounts drained

    def test_evict_scrubs_prefix_index(self, micro):
        """Audit of the PR-5 stale-prefix-index bug class on the evict
        path: evicting a running request must scrub its _prefix_index
        entries exactly like window expiry does (the blocks are freed and
        may be re-leased — a later same-prefix request sharing the stale
        snapshot would lease dead or foreign blocks).  The resubmit gets
        no shared blocks and matches solo."""
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=16, max_batch=2)
        p = (np.arange(9) * 5 + 2).astype(np.int32) % cfg.vocab_size
        ha = eng.submit(p, max_new_tokens=12)
        eng.step()                                       # prefill A registers prefixes
        assert eng._prefix_index
        old_blocks = set(ha._req.block_table) - {SINK_BLOCK}
        eng.evict(ha)
        assert ha.result(drive=False).finish_reason == "evicted"
        assert len(eng._prefix_index) == 0               # evict scrubbed A's entries
        assert eng.pool.num_free == eng.pool.num_usable
        hb = eng.submit(p.copy(), max_new_tokens=4)
        eng.step()                                       # would share stale blocks pre-fix
        assert hb._req.n_shared_blocks == 0
        assert set(hb._req.block_table) & old_blocks     # same physical blocks, re-leased
        eng.drain()
        np.testing.assert_array_equal(
            hb.result(drive=False).tokens, _solo(params, p, cfg, 4)
        )

    def test_free_blocks_low_water_gauge(self, micro):
        """The capacity floor is visible post-mortem: the gauge and the
        flight-recorder pool snapshot carry the fewest free blocks ever
        seen, surviving after the pool drains back to full."""
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=16, max_batch=2)
        p = np.arange(6, dtype=np.int32)
        eng.run([{"prompt": p, "max_new_tokens": 6, "key": jax.random.PRNGKey(i)}
                 for i in range(2)])
        assert eng.pool.num_free == eng.pool.num_usable  # drained clean...
        low = eng.pool.free_blocks_low_water
        assert low < eng.pool.num_usable                 # ...but the floor survives
        assert eng._flight_state()["pool"]["free_blocks_low_water"] == low
        assert eng.stats()["pool_free_blocks_low_water"] == low
        snap = tt.metrics_snapshot()
        assert snap["serving.pool.free_blocks_low_water"] == low

    def test_window_expiry_scrubs_prefix_index(self, micro):
        """Regression: sliding-window expiry frees a running request's
        leading blocks; a later same-prefix request must not share the
        stale snapshot (pre-fix: pool.share raised 'not leased', or leased
        a re-allocated foreign block).  It re-prefills and matches solo."""
        cfg, params = micro
        wcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 6})
        eng = _engine(wcfg, params, block_size=2, num_blocks=16, max_batch=2)
        p = (np.arange(4) * 3 + 1).astype(np.int32) % cfg.vocab_size
        ha = eng.submit(p, max_new_tokens=8)
        eng.step()                                       # prefill A registers prefixes
        assert eng._prefix_index
        free0 = eng.pool.num_free
        while eng.pool.num_free <= free0:                # decode until a block expires
            eng.step()
        assert not ha.done()
        assert len(eng._prefix_index) == 0               # expiry scrubbed A's entries
        hb = eng.submit(p.copy(), max_new_tokens=4)
        eng.step()                                       # would crash on a stale share
        assert hb._req.n_shared_blocks == 0
        eng.drain()
        np.testing.assert_array_equal(
            ha.result(drive=False).tokens, _solo(params, p, wcfg, 8)
        )
        np.testing.assert_array_equal(
            hb.result(drive=False).tokens, _solo(params, p, wcfg, 4)
        )
        assert eng.pool.num_free == eng.pool.num_usable

    def test_nbb_widths_stay_in_bucket_set(self, micro):
        """Every table width _nbb can produce — including the prefill
        overflow past the largest block bucket and the sliding-window
        capacity dodge — is in the precomputed set that bucket_bound
        counts."""
        cfg, params = micro
        eng = _engine(cfg, params, block_buckets=(1, 2), prefill_buckets=(8,))
        assert eng._table_widths == (1, 2, 4)            # overflow extends the set
        for k in range(1, max(eng._table_widths) + 1):
            assert eng._nbb(k) in eng._table_widths
        stats = eng.stats()
        assert stats["bucket_bound"] == (
            (len(eng.scheduler.batch_buckets) + len(eng.scheduler.prefill_buckets))
            * len(eng._table_widths)
            + len(eng.scheduler.prefill_buckets)   # prefill_fresh reads no table: one a bucket
        )
        # window dodge: a width whose gathered capacity equals the window
        # (which forward_with_cache would read as the ring layout) is shifted
        wcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 8})
        weng = _engine(wcfg, params, block_buckets=(1, 2, 4))
        assert 2 not in weng._table_widths               # capacity 2*4 == window
        assert weng._nbb(2) == 3
        for w in weng._table_widths:
            assert weng.pool.capacity_tokens(w) != 8

    def test_run_backpressure_not_counted_as_rejection(self, micro):
        """run() riding out a full wait queue is backpressure, not a
        rejection — serving.requests.rejected must stay zero."""
        cfg, params = micro
        eng = _engine(cfg, params, num_blocks=8, max_batch=1, max_queue=1)
        p = np.arange(3, dtype=np.int32)
        results = eng.run([{"prompt": p, "max_new_tokens": 4} for _ in range(3)])
        assert all(r.finish_reason == "length" for r in results)
        snap = tt.metrics_snapshot()
        assert snap.get("serving.requests.rejected", 0) == 0
        assert snap["serving.requests.submitted"] == 3

    @pytest.mark.slow
    def test_sliding_window_frees_blocks_and_matches_ring_generate(self, micro):
        cfg, params = micro
        wcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 6})
        eng = _engine(wcfg, params, block_size=2, num_blocks=16, max_batch=1)
        p = np.arange(4, dtype=np.int32) + 2
        h = eng.submit(p, max_new_tokens=10)
        frees = []
        while not h.done():
            eng.step()
            frees.append(eng.pool.num_free)
        # blocks released while still decoding, not only at finish
        assert frees[-1] == eng.pool.num_usable
        assert any(f > frees[0] for f in frees[:-1])
        np.testing.assert_array_equal(
            h.result(drive=False).tokens, _solo(params, p, wcfg, 10)
        )

    def test_eos_finish_reason(self, micro):
        cfg, params = micro
        # greedy tokens are deterministic: discover one, then rerun with it as eos
        p = np.arange(5, dtype=np.int32)
        probe = _engine(cfg, params)
        toks = probe.run([{"prompt": p, "max_new_tokens": 3}])[0].new_tokens
        eos = int(toks[1])
        eng = _engine(cfg, params, eos_id=eos)
        r = eng.run([{"prompt": p, "max_new_tokens": 10}])[0]
        assert r.finish_reason == "eos"
        assert r.new_tokens[-1] == eos
        assert len(r.new_tokens) == toks.index(eos) + 1

    def test_shutdown_rejects_new_submits(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        eng.shutdown()
        with pytest.raises(RuntimeError):
            eng.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)


def test_serving_is_strictly_additive(micro):
    """Off-path guarantee (same pattern as PR 2/4): building and running an
    engine leaves other compiled programs byte-identical — including an
    engine with the full serving-observability stack (tracing + SLO +
    flight recorder) armed."""
    cfg, params = micro

    def fn(x):
        return x * 2.0 + 1.0

    def program(jfn):
        # a pass's header says how long it took: the clock's, not the program's (4 ms for 0 on a loaded machine, PR 54)
        import re

        return re.sub(r"\(took \d+ milliseconds\)", "", tt.last_traces(jfn)[-1].python())

    x = np.ones((4, 4), np.float32)
    before = tt.jit(fn)
    before(x)
    ref = program(before)
    eng = _engine(cfg, params)
    eng.run([{"prompt": np.arange(3, dtype=np.int32), "max_new_tokens": 2}])
    after = tt.jit(fn)
    after(x)
    assert program(after) == ref
    instrumented = _engine(cfg, params, trace=True, slo=True, flight_recorder=True)
    instrumented.run([{"prompt": np.arange(3, dtype=np.int32), "max_new_tokens": 2}])
    again = tt.jit(fn)
    again(x)
    assert program(again) == ref


@pytest.mark.slow
def test_many_request_soak(micro):
    """Multi-request soak: saturating queue+batch with mixed shapes keeps
    every differential guarantee."""
    cfg, params = micro
    rng = np.random.default_rng(3)
    eng = _engine(cfg, params, num_blocks=32, max_batch=4, max_queue=64)
    reqs = []
    for i in range(24):
        n = int(rng.integers(2, 14))
        reqs.append({
            "prompt": rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            "max_new_tokens": int(rng.integers(1, 8)),
        })
    results = eng.run(reqs)
    for q, r in zip(reqs, results):
        np.testing.assert_array_equal(
            r.tokens, _solo(params, q["prompt"], cfg, q["max_new_tokens"])
        )
