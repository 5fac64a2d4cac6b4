"""Async serving core: prefill/decode disaggregation, chunked prefill, and
host/device overlap (the event-loop engine).

The load-bearing guarantee is double-differential: tokens served through
the async engine must be *identical* to the synchronous engine
(``async_step=False``) AND to solo ``generate()`` — greedy and temperature,
with chunked prefill, prefix sharing, quantized KV, and LoRA mixes in
play.  Deferred materialization reorders host work, never device math.

Policy coverage: the chunked prefill lane (a long prompt admitted
mid-decode advances running requests one token per step — no TPOT stall
beyond the chunk bound), the hot-spin fix (bounded ``step()`` calls while
draining — the idle backoff is the blocking harvest of the in-flight
futures table, never a poll), overlap observability, and the flight
recorder's lane state.  Bucket sets are pinned small (tier-1 budget).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, AdmissionError, make_lora_factors

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
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, params, cfg, **kw)


def _solo(params, prompt, cfg, n, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return np.asarray(gen.generate(params, np.asarray(prompt)[None], cfg, n, **kw))[0]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in lens]


#
# differential guarantees: async == sync == solo
#


class TestAsyncDifferential:
    def test_async_equals_sync_equals_solo_greedy(self, micro):
        """Acceptance: mixed-length greedy batch — the async engine's
        tokens are bit-identical to the synchronous engine's and to solo
        generate(), request by request."""
        cfg, params = micro
        prompts = _prompts(cfg, (3, 5, 9, 14))
        reqs = [{"prompt": p, "max_new_tokens": 5} for p in prompts]
        a = _engine(cfg, params).run([dict(r) for r in reqs])
        s = _engine(cfg, params, async_step=False).run([dict(r) for r in reqs])
        for p, ra, rs in zip(prompts, a, s):
            solo = _solo(params, p, cfg, 5)
            np.testing.assert_array_equal(ra.tokens, solo)
            np.testing.assert_array_equal(rs.tokens, solo)
            assert ra.finish_reason == rs.finish_reason == "length"

    def test_async_temperature_parity_with_request_keys(self, micro):
        cfg, params = micro
        p1, p2 = _prompts(cfg, (6, 11), seed=2)
        eng = _engine(cfg, params, temperature=0.7)
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

    def test_chunked_prefill_matches_solo(self, micro):
        """A chunked long prompt (3 pieces at chunk=8) produces exactly the
        solo tokens: intermediate chunks write KV without splitting the
        key, so the final piece's draw matches the unchunked prefill."""
        cfg, params = micro
        (long_p,) = _prompts(cfg, (23,), seed=3)
        eng = _engine(cfg, params, prefill_chunk=8)
        r = eng.run([{"prompt": long_p, "max_new_tokens": 6}])[0]
        np.testing.assert_array_equal(r.tokens, _solo(params, long_p, cfg, 6))
        assert eng.chunk_runs == 2 and eng.prefill_runs == 1
        assert eng.compile_counts["prefill_chunk"] >= 0  # counted per bucket
        assert sum(eng.compile_counts.values()) <= eng.stats()["bucket_bound"]

    def test_chunked_prefill_with_prefix_sharing(self, micro):
        """A second request over the same long prompt shares the chunked
        blocks (registered piece by piece as they are written) and still
        matches solo."""
        cfg, params = micro
        (base,) = _prompts(cfg, (23,), seed=4)
        eng = _engine(cfg, params, prefill_chunk=8)
        ha = eng.submit(base, max_new_tokens=4)
        for _ in range(4):   # chunks 1..2, final, first harvest
            eng.step()
        hb = eng.submit(base.copy(), max_new_tokens=4)
        eng.drain()
        ra, rb = ha.result(drive=False), hb.result(drive=False)
        assert rb.shared_prefix_blocks > 0
        solo = _solo(params, base, cfg, 4)
        np.testing.assert_array_equal(ra.tokens, solo)
        np.testing.assert_array_equal(rb.tokens, solo)
        assert eng.pool.num_free == eng.pool.num_usable

    def test_chunked_int8_parity(self, micro):
        """Chunked prefill composes with quantized block storage: the
        final piece reads earlier chunks dequantized — exactly like a
        shared-prefix resume — and greedy tokens still match solo."""
        cfg, params = micro
        (long_p,) = _prompts(cfg, (19,), seed=5)
        eng = _engine(cfg, params, prefill_chunk=8, kv_dtype="int8")
        r = eng.run([{"prompt": long_p, "max_new_tokens": 5}])[0]
        np.testing.assert_array_equal(r.tokens, _solo(params, long_p, cfg, 5))
        assert eng.chunk_runs >= 1

    def test_long_prompt_beyond_prefill_buckets_admitted(self, micro):
        """Without chunking a 23-token prompt exceeds the largest prefill
        bucket (16) and is rejected outright; with chunking the cap is the
        pool/block-bucket capacity instead."""
        cfg, params = micro
        (long_p,) = _prompts(cfg, (23,), seed=6)
        plain = _engine(cfg, params)
        with pytest.raises(AdmissionError, match="prefill"):
            plain.submit(long_p, max_new_tokens=4)
        chunked = _engine(cfg, params, prefill_chunk=8)
        r = chunked.run([{"prompt": long_p, "max_new_tokens": 4}])[0]
        np.testing.assert_array_equal(r.tokens, _solo(params, long_p, cfg, 4))


#
# the chunk bound: long prompts stop stalling running requests
#


class TestPrefillLane:
    def test_long_prompt_mid_decode_does_not_stall_tpot(self, micro):
        """Acceptance (satellite): a long prompt admitted mid-decode is
        chunked one piece per step, and the running request keeps emitting
        exactly one token per step throughout — its step-metered TPOT
        never exceeds the one-chunk bound."""
        cfg, params = micro
        a_p, b_p = _prompts(cfg, (4, 23), seed=7)
        eng = _engine(cfg, params, prefill_chunk=8)
        ha = eng.submit(a_p, max_new_tokens=16)
        eng.step()                                # admit + prefill dispatch A
        eng.step()                                # harvest token 0, decode A
        assert len(ha.tokens_so_far()) == 1
        hb = eng.submit(b_p, max_new_tokens=4)    # long prompt arrives mid-decode
        chunks_before = eng.chunk_runs
        while hb._req.pos < hb._req.prompt_len:   # B's chunked prefill window
            n_before = len(ha.tokens_so_far())
            eng.step()
            # A advanced one token in the same step a chunk was dispatched
            assert len(ha.tokens_so_far()) == n_before + 1
        assert eng.chunk_runs - chunks_before == 2
        eng.drain()
        np.testing.assert_array_equal(
            ha.result(drive=False).tokens, _solo(params, a_p, cfg, 16))
        np.testing.assert_array_equal(
            hb.result(drive=False).tokens, _solo(params, b_p, cfg, 4))

    def test_chunk_validation(self, micro):
        cfg, params = micro
        with pytest.raises(ValueError, match="multiple of the pool block_size"):
            _engine(cfg, params, prefill_chunk=6)        # not a multiple of 4
        with pytest.raises(ValueError, match="not itself a prefill bucket"):
            _engine(cfg, params, prefill_chunk=4)        # buckets start at 8
        with pytest.raises(ValueError, match="requires async_step=True"):
            _engine(cfg, params, prefill_chunk=8, async_step=False)

    def test_chunk_widths_stay_in_bucket_set(self, micro):
        """Chunk resume points extend the table-width set exactly like
        shared-prefix resume points: every width any piece can request is
        in the precomputed set bucket_bound counts."""
        cfg, params = micro
        eng = _engine(cfg, params, prefill_chunk=8, prefix_sharing=False)
        for k in range(1, max(eng._table_widths) + 1):
            assert eng._nbb(k) in eng._table_widths
        stats = eng.stats()
        sch = eng.scheduler
        assert stats["bucket_bound"] == (
            (len(sch.batch_buckets) + 2 * len(sch.prefill_buckets))
            * len(eng._table_widths)
            + len(sch.prefill_buckets)           # prefill_fresh reads no table: one a bucket
        )


#
# drive-loop discipline: the hot-spin fix + overlap observability
#


class TestEventLoop:
    def test_drain_step_calls_bounded(self, micro):
        """Regression (satellite): draining must not busy-step.  Every
        step() call either harvests the in-flight futures (blocking inside
        the wait — the idle backoff) or dispatches work, so the total call
        count is bounded by the work actually done."""
        cfg, params = micro
        eng = _engine(cfg, params, max_queue=2, num_blocks=16, max_batch=2)
        reqs = [{"prompt": p, "max_new_tokens": 6, "key": jax.random.PRNGKey(i)}
                for i, p in enumerate(_prompts(cfg, (3, 5, 7, 4, 6), seed=8))]
        results = eng.run(reqs)
        assert all(r.finish_reason == "length" for r in results)
        s = eng.stats()
        work = s["decode_steps"] + s["prefill_runs"] + s["chunk_runs"]
        assert s["step_calls"] <= 2 * work + 4, s

    def test_result_drive_bounded(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params)
        (p,) = _prompts(cfg, (5,), seed=9)
        h = eng.submit(p, max_new_tokens=8)
        r = h.result()                            # drives to completion
        assert r.finish_reason == "length"
        s = eng.stats()
        assert s["step_calls"] <= 2 * (s["decode_steps"] + s["prefill_runs"]) + 4

    def test_overlap_metrics_recorded(self, micro):
        """The async engine measures its own overlap: the decode-stall
        histogram and the overlap_frac gauge land in the registry, and the
        per-engine means surface in stats()."""
        cfg, params = micro
        eng = _engine(cfg, params)
        eng.run([{"prompt": p, "max_new_tokens": 6}
                 for p in _prompts(cfg, (3, 6), seed=10)])
        s = eng.stats()
        assert s["async_step"] is True
        assert s["decode_stall_s_mean"] is not None and s["decode_stall_s_mean"] >= 0
        assert s["overlap_frac_mean"] is not None and 0 <= s["overlap_frac_mean"] <= 1
        snap = tt.metrics_snapshot()
        assert snap["serving.decode.stall_s"]["count"] >= 1
        assert 0 <= snap["serving.step.overlap_frac"] <= 1

    def test_sync_engine_records_no_overlap_metrics(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, async_step=False)
        eng.run([{"prompt": p, "max_new_tokens": 4}
                 for p in _prompts(cfg, (3,), seed=11)])
        s = eng.stats()
        assert s["async_step"] is False
        assert s["overlap_frac_mean"] is None and s["decode_stall_s_mean"] is None
        # the registry keeps registered (zeroed) keys across resets; the
        # sync drive must not have OBSERVED into the stall histogram
        stall = tt.metrics_snapshot().get("serving.decode.stall_s")
        assert stall is None or stall["count"] == 0

    def test_flight_state_carries_lane_state(self, micro):
        """Mid-overlap the flight snapshot names what each lane holds: the
        in-flight decode batch and every partially-prefilled request."""
        cfg, params = micro
        eng = _engine(cfg, params, prefill_chunk=8)
        a_p, b_p = _prompts(cfg, (4, 23), seed=12)
        ha = eng.submit(a_p, max_new_tokens=12)
        eng.step(); eng.step()                    # A decoding, decode in flight
        eng.submit(b_p, max_new_tokens=4)         # B starts chunking
        eng.step()
        lanes = eng._flight_state()["lanes"]
        assert lanes["async_step"] is True
        assert lanes["decode_inflight"] is not None
        assert ha.rid in lanes["decode_inflight"]["rids"]
        assert [row["rid"] for row in lanes["prefilling"]]  # B mid-prefill
        for row in lanes["prefilling"]:
            assert 0 < row["pos"] < row["prompt_tokens"]
        eng.drain()
        lanes = eng._flight_state()["lanes"]
        assert lanes["decode_inflight"] is None and not lanes["prefilling"]

    def test_deadline_mid_flight_discards_unpromised_token(self, micro):
        """A request finished by deadline while its decode is in flight:
        the in-flight token is dropped (never promised), blocks reclaimed,
        and the engine keeps draining cleanly."""
        cfg, params = micro
        clk = {"t": 0.0}
        eng = _engine(cfg, params, clock=lambda: clk["t"])
        (p,) = _prompts(cfg, (5,), seed=13)
        h = eng.submit(p, max_new_tokens=20, deadline=5.0)
        while not h.done():
            eng.step()
            clk["t"] += 2.0
        r = h.result(drive=False)
        assert r.finish_reason == "deadline"
        assert 0 < len(r.new_tokens) < 20
        assert eng.pool.num_free == eng.pool.num_usable
        # drained: nothing left in any lane
        assert eng._inflight_decode is None or all(
            q.state != "running" for q in eng._inflight_decode["running"])

    def test_evict_mid_chunk_reclaims_blocks(self, micro):
        """Evicting a request whose prefill chunk is still in flight frees
        its blocks; the in-flight write lands harmlessly before any
        re-lease's writes (device program order) and the harvest skips the
        finished request."""
        cfg, params = micro
        eng = _engine(cfg, params, prefill_chunk=8)
        (long_p,) = _prompts(cfg, (23,), seed=14)
        h = eng.submit(long_p, max_new_tokens=4)
        eng.step()                                # chunk 1 in flight
        assert h._req.pos < h._req.prompt_len
        eng.evict(h)
        assert h.done() and h.result(drive=False).finish_reason == "evicted"
        assert eng.pool.num_free == eng.pool.num_usable
        # a fresh request reuses the pool and still matches solo
        (p2,) = _prompts(cfg, (6,), seed=15)
        r2 = eng.run([{"prompt": p2, "max_new_tokens": 4}])[0]
        np.testing.assert_array_equal(r2.tokens, _solo(params, p2, cfg, 4))


#
# soak (slow): every guarantee at once
#


@pytest.mark.slow
def test_async_soak_matches_sync_and_solo(micro):
    """Satellite soak: random prompt lengths (chunked and not), deadlines,
    a mid-flight eviction, and a LoRA adapter mix — async-served tokens ==
    sync-served == solo for every length-finished request; interrupted
    requests' tokens are a prefix of the solo run."""
    cfg, params = micro
    rng = np.random.default_rng(21)
    reg = AdapterRegistry(cfg, rank=2, max_adapters=4)
    reg.register("a", make_lora_factors(cfg, 2, jax.random.PRNGKey(31), std=0.5))
    reg.register("b", make_lora_factors(cfg, 2, jax.random.PRNGKey(32), std=0.5))

    def build(async_step):
        kw = dict(num_blocks=64, max_batch=4, max_queue=64, lora=reg)
        if async_step:
            kw["prefill_chunk"] = 8
        else:
            kw["async_step"] = False
        return _engine(cfg, params, **kw)

    reqs = []
    for i in range(18):
        # prompt + max_new stays within the 8-block (32-token) bucket cap
        n = int(rng.integers(2, 15)) if i % 3 else int(rng.integers(17, 25))
        reqs.append({
            "prompt": rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            "max_new_tokens": int(rng.integers(1, 7)),
            "adapter_id": ("a", "b", None)[i % 3],
        })

    async_eng = build(async_step=True)
    results = async_eng.run([dict(r) for r in reqs])
    # the sync engine rejects prompts beyond the largest prefill bucket, so
    # its comparison set is the unchunked subset; solo covers everything
    short = [(q, r) for q, r in zip(reqs, results)
             if q["prompt"].shape[0] <= async_eng.scheduler.prefill_buckets[-1]]
    sync_eng = build(async_step=False)
    sync_results = sync_eng.run([dict(q) for q, _ in short])
    for (q, ra), rs in zip(short, sync_results):
        np.testing.assert_array_equal(ra.tokens, rs.tokens)
    for q, r in zip(reqs, results):
        assert r.finish_reason == "length"
        # adapters change tokens (their parity vs the solo single-adapter
        # run is test_serving_lora's job); adapterless requests must match
        # plain solo generate() exactly, chunked or not
        if q["adapter_id"] is None:
            np.testing.assert_array_equal(
                r.tokens, _solo(params, q["prompt"], cfg, q["max_new_tokens"]))
    # deadline + eviction interruptions keep the pool clean (short prompts:
    # the reservation stays inside the block-bucket cap)
    clk_eng = build(async_step=True)
    h1 = clk_eng.submit(reqs[1]["prompt"], max_new_tokens=8, deadline=0.001)
    h2 = clk_eng.submit(reqs[4]["prompt"], max_new_tokens=8)
    clk_eng.step(); clk_eng.step()
    clk_eng.evict(h2)
    clk_eng.drain()
    assert h1.result(drive=False).finish_reason in ("deadline", "length")
    assert h2.result(drive=False).finish_reason == "evicted"
    assert clk_eng.pool.num_free == clk_eng.pool.num_usable
