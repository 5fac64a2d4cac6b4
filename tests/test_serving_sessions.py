"""Session KV persistence (serving/sessions.py, ISSUE 17).

The load-bearing guarantee is differential: a turn-k≥2 request that
re-attaches a parked session's resident KV must serve tokens bit-identical
to a cold engine prefilling the full history — greedy, temperature, int8
KV, LoRA, and the paged-attention kernel path.  Sessions change the
*lifetime* of blocks, never the computation: re-attach rides the existing
shared-prefix path, so there is no new device code to validate, only the
parking/refcount/liveness bookkeeping around it.

Structural pillars: the table is budgeted (LRU count + bytes caps) and a
closed/evicted session's blocks return to the free list immediately —
including fleet-wide on every router lane (the PR's regression fix);
recovery replays resident sessions so re-attach survives a fault.
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
    AdapterRegistry,
    PagedKVPool,
    SessionConfig,
    SessionTable,
    make_lora_factors,
)
from thunder_tpu.serving.kv_pool import SINK_BLOCK, PrefixIndex

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32,
    block_size=64,
)
BUCKETS = dict(batch_buckets=(1, 2), block_buckets=(4, 8), prefill_buckets=(8, 16))


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
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, params, cfg, **kw)


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (n,)).astype(np.int32)


#
# the table itself (pure allocator bookkeeping, no device work)
#


class TestSessionTable:
    def _table(self, cfg, **kw):
        pool = PagedKVPool(cfg, num_blocks=16, block_size=4, dtype=jnp.float32)
        return pool, SessionTable(pool, PrefixIndex(4), SessionConfig(**kw))

    def test_park_shares_and_close_frees(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg)
        blocks = pool.alloc(3)
        tab.park("s", np.arange(12), blocks)
        pool.free(blocks)                      # caller's refs gone
        assert pool.num_free == pool.num_usable - 3   # table still holds them
        assert tab.resident("s") and tab.resident_blocks == 3
        assert tab.close("s") == 3
        assert pool.num_free == pool.num_usable
        assert tab.close("s") == 0             # idempotent

    def test_park_truncates_to_block_aligned_tokens(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg)
        blocks = pool.alloc(3)
        entry = tab.park("s", np.arange(10), blocks)   # 10 tokens -> 2 blocks
        assert len(entry.blocks) == 2 and len(entry.tokens) == 8
        pool.free(blocks)
        assert pool.num_free == pool.num_usable - 2

    def test_park_stops_at_sink_block(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg)
        blocks = pool.alloc(2)
        entry = tab.park("s", np.arange(12), [SINK_BLOCK, *blocks])
        assert entry is None                   # leading sink: nothing parkable
        pool.free(blocks)
        assert pool.num_free == pool.num_usable

    def test_lru_eviction_respects_count_budget(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg, max_sessions=2)
        for i in range(3):
            b = pool.alloc(1)
            tab.park(f"s{i}", np.arange(4), b)
            pool.free(b)
        assert len(tab) == 2 and not tab.resident("s0")
        assert tab.evictions == 1
        assert pool.num_free == pool.num_usable - 2    # evictee's block freed

    def test_bytes_budget_and_oversized_park(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg, max_bytes=2 * PagedKVPool(
            cfg, num_blocks=4, block_size=4, dtype=jnp.float32).block_bytes())
        b = pool.alloc(3)
        assert tab.park("big", np.arange(12), b) is None   # 3 blocks > budget
        pool.free(b)
        assert pool.num_free == pool.num_usable
        b = pool.alloc(2)
        assert tab.park("fits", np.arange(8), b) is not None
        pool.free(b)

    def test_repark_same_session_keeps_overlap_alive(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg)
        b1 = pool.alloc(2)
        tab.park("s", np.arange(8), b1)
        pool.free(b1)
        grown = list(b1) + pool.alloc(1)       # turn 2 grew by one block
        tab.park("s", np.arange(12), grown)
        pool.free(grown[2:])
        assert tab.resident_blocks == 3
        assert tab.close("s") == 3
        assert pool.num_free == pool.num_usable

    def test_alive_tracks_ownership(self, micro):
        cfg, _ = micro
        pool, tab = self._table(cfg)
        b = pool.alloc(2)
        e = tab.park("s", np.arange(8), b)
        pool.free(b)
        assert tab.alive(e.owner_rid, e.blocks)
        assert tab.alive(e.owner_rid, e.blocks[:1])
        assert not tab.alive(e.owner_rid, (99, 98))
        tab.close("s")
        assert not tab.alive(e.owner_rid, e.blocks)


#
# engine end-to-end: turn-2 re-attach parity (the acceptance criterion)
#


class TestSessionServing:
    def _two_turns(self, cfg, params, *, key1, key2, engine_kw=None,
                   submit_kw=None, solo_check=True):
        """Serve turn 1 + turn 2 on a session engine; return turn-2 result
        plus a cold engine's result for the identical full-history prompt."""
        engine_kw = dict(engine_kw or {})
        submit_kw = dict(submit_kw or {})
        p1 = _prompt(11, 7, cfg)
        eng = _engine(cfg, params, sessions=True, **engine_kw)
        r1 = eng.submit(p1, max_new_tokens=5, key=key1,
                        session_id="chat", **submit_kw).result()
        assert eng.stats()["sessions"]["sessions"] == 1
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32),
                             _prompt(12, 3, cfg)])
        r2 = eng.submit(p2, max_new_tokens=4, key=key2,
                        session_id="chat", **submit_kw).result()
        st = eng.stats()["sessions"]
        cold = _engine(cfg, params, **engine_kw)
        rc = cold.submit(p2, max_new_tokens=4, key=key2, **submit_kw).result()
        cold.shutdown()
        eng.shutdown()
        return r2, rc, st

    def test_turn2_reattach_parity_greedy(self, micro):
        cfg, params = micro
        r2, rc, st = self._two_turns(cfg, params, key1=None, key2=None)
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0         # tail-only re-prefill
        assert st["reattach_hits"] == 1

    def test_turn2_reattach_parity_temperature(self, micro):
        cfg, params = micro
        r2, rc, st = self._two_turns(
            cfg, params, key1=jax.random.PRNGKey(7), key2=jax.random.PRNGKey(8),
            engine_kw=dict(temperature=0.8))
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0 and st["reattach_hits"] == 1

    def test_turn2_reattach_parity_int8(self, micro):
        cfg, params = micro
        r2, rc, st = self._two_turns(cfg, params, key1=None, key2=None,
                                     engine_kw=dict(kv_dtype="int8"))
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0 and st["reattach_hits"] == 1

    def test_turn2_reattach_parity_paged(self, micro, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")     # the kernel in the decode program, interpreted
        cfg, params = micro
        r2, rc, st = self._two_turns(cfg, params, key1=None, key2=None, engine_kw={})
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0 and st["reattach_hits"] == 1

    def test_turn2_reattach_parity_lora(self, micro):
        cfg, params = micro
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2)
        reg.register("tenant", make_lora_factors(
            cfg, rank=2, key=jax.random.PRNGKey(3)))
        r2, rc, st = self._two_turns(
            cfg, params, key1=None, key2=None,
            engine_kw=dict(lora=reg), submit_kw=dict(adapter_id="tenant"))
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0 and st["reattach_hits"] == 1

    def test_turn3_keeps_growing(self, micro):
        """k≥2: every later turn re-attaches the grown prefix."""
        cfg, params = micro
        p = _prompt(21, 6, cfg)
        eng = _engine(cfg, params, sessions=True, num_blocks=32)
        cold = _engine(cfg, params, num_blocks=32)
        for turn in range(3):
            r = eng.submit(p, max_new_tokens=3, session_id="s").result()
            rc = cold.submit(p, max_new_tokens=3).result()
            assert r.new_tokens == rc.new_tokens
            if turn:
                assert r.shared_prefix_blocks > 0
            p = np.concatenate([p, np.asarray(r.new_tokens, np.int32),
                                _prompt(30 + turn, 2, cfg)])
        assert eng.stats()["sessions"]["reattach_hits"] == 2
        eng.shutdown()
        cold.shutdown()

    def test_reattach_survives_recovery(self, micro):
        """A fault wipes the arenas; the session replay restores parked KV
        bit-identically, so turn 2 still re-attaches and matches cold."""
        cfg, params = micro
        p1 = _prompt(41, 7, cfg)
        eng = _engine(cfg, params, sessions=True)
        r1 = eng.submit(p1, max_new_tokens=5, session_id="s").result()
        eng._recover_once()
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32),
                             _prompt(42, 3, cfg)])
        r2 = eng.submit(p2, max_new_tokens=4, session_id="s").result()
        cold = _engine(cfg, params)
        rc = cold.submit(p2, max_new_tokens=4).result()
        assert r2.new_tokens == rc.new_tokens
        assert r2.shared_prefix_blocks > 0
        cold.shutdown()
        eng.shutdown()

    def test_close_session_frees_blocks(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, sessions=True)
        eng.submit(_prompt(51, 7, cfg), max_new_tokens=5,
                   session_id="s").result()
        assert eng.pool.num_free < eng.pool.num_usable
        assert eng.close_session("s") > 0
        assert eng.pool.num_free == eng.pool.num_usable
        assert eng.close_session("s") == 0
        eng.shutdown()

    def test_abnormal_finish_kills_session(self, micro):
        """An evicted turn must not leave a half-written prefix parked."""
        cfg, params = micro
        eng = _engine(cfg, params, sessions=True)
        h = eng.submit(_prompt(52, 7, cfg), max_new_tokens=8, session_id="s")
        for _ in range(3):
            eng.step()
        eng.evict(h)
        assert eng.stats()["sessions"]["sessions"] == 0
        assert eng.pool.num_free == eng.pool.num_usable
        eng.shutdown()

    def test_shutdown_clears_table(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, sessions=True)
        eng.submit(_prompt(53, 7, cfg), max_new_tokens=4,
                   session_id="s").result()
        eng.shutdown()
        assert eng.pool.num_free == eng.pool.num_usable

    def test_session_requires_knob_and_prefix_sharing(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        with pytest.raises(ValueError, match="sessions"):
            eng.submit(_prompt(54, 7, cfg), max_new_tokens=2, session_id="s")
        eng.shutdown()
        with pytest.raises(ValueError, match="prefix"):
            _engine(cfg, params, sessions=True, prefix_sharing=False)

    def test_telemetry_and_flight_carry_session_fields(self, micro):
        from thunder_tpu.observability.telemetry import StepLogger

        cfg, params = micro
        sink = io.StringIO()
        eng = _engine(cfg, params, sessions=True, trace=True,
                      telemetry=StepLogger(sink))
        eng.submit(_prompt(55, 7, cfg), max_new_tokens=3,
                   session_id="s").result()
        recs = [json.loads(l) for l in sink.getvalue().splitlines()]
        reqs = [r for r in recs if r.get("event") == "request"]
        assert reqs and reqs[0]["session_id"] == "s"
        st = eng.stats()["sessions"]
        assert st["resident_blocks"] > 0 and st["ids"] == ["s"]
        snap = eng._flight_state()
        assert snap["engine"]["sessions"]["sessions"] == 1
        eng.shutdown()

    def test_session_metrics_registered(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, sessions=True)
        eng.submit(_prompt(56, 7, cfg), max_new_tokens=3,
                   session_id="s").result()
        snap = tt.metrics_snapshot()
        assert snap["serving.session.resident_blocks"] > 0
        assert snap["serving.session.reattach_hits"] == 0
        eng.shutdown()


#
# the dp router: session affinity + the fleet-wide release regression
#


class TestRouterSessions:
    def _router(self, cfg, params, **kw):
        kw.setdefault("block_size", 4)
        kw.setdefault("num_blocks", 16)
        kw.setdefault("max_batch", 2)
        kw.setdefault("cache_dtype", jnp.float32)
        for k, v in BUCKETS.items():
            kw.setdefault(k, v)
        return tt.serve(None, params, cfg, replicas=2, sessions=True, **kw)

    def test_session_affinity_pins_lane(self, micro):
        cfg, params = micro
        r = self._router(cfg, params)
        p1 = _prompt(61, 7, cfg)
        h1 = r.submit(p1, max_new_tokens=4, session_id="sA")
        r1 = h1.result()
        lane = h1.replica
        assert r.engines[lane].session_resident("sA")
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32),
                             _prompt(62, 3, cfg)])
        h2 = r.submit(p2, max_new_tokens=3, session_id="sA")
        h2.result()
        assert h2.replica == lane
        agg = r.stats()["aggregate"]
        assert agg["session_reattach_hits"] == 1
        assert agg["session_resident_blocks"] > 0
        r.shutdown()

    def test_dead_session_blocks_freed_on_every_lane(self, micro):
        """The regression fix: router-side eviction and deadline expiry
        must return a dead session's blocks to the free list on EVERY
        lane, not just wherever affinity last routed it."""
        cfg, params = micro
        r = self._router(cfg, params)
        h = r.submit(_prompt(63, 7, cfg), max_new_tokens=4, session_id="sB")
        h.result()
        h2 = r.submit(_prompt(64, 7, cfg), max_new_tokens=8, session_id="sB")
        for _ in range(3):
            r.step()
        r.evict(h2)                      # routed eviction → fleet-wide close
        for eng in r.engines:
            assert not eng.session_resident("sB")
            assert eng.pool.num_free == eng.pool.num_usable
        # pending-side deadline expiry takes the same sweep
        h3 = r.submit(_prompt(65, 7, cfg), max_new_tokens=4,
                      session_id="sC", deadline=60.0)
        h3.result()
        assert any(e.session_resident("sC") for e in r.engines)
        h4 = r.submit(_prompt(66, 7, cfg), max_new_tokens=4,
                      session_id="sC", deadline=-1.0)
        r.step()
        assert h4.result(drive=False).finish_reason == "deadline"
        for eng in r.engines:
            assert not eng.session_resident("sC")
            assert eng.pool.num_free == eng.pool.num_usable
        r.shutdown()

    def test_aggregate_surfaces_prefix_hit_counters(self, micro):
        """The satellite fix: PrefixIndex hit counters aggregate across
        lanes in ReplicatedEngine.stats()."""
        cfg, params = micro
        r = self._router(cfg, params)
        p1 = _prompt(67, 7, cfg)
        r1 = r.submit(p1, max_new_tokens=4, session_id="sD").result()
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32),
                             _prompt(68, 3, cfg)])
        r.submit(p2, max_new_tokens=3, session_id="sD").result()
        agg = r.stats()["aggregate"]
        assert agg["prefix_lookups"] >= 2
        assert agg["prefix_hits"] >= 1
        assert 0 < agg["prefix_hit_rate"] <= 1
        r.shutdown()
