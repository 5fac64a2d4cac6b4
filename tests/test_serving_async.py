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

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, AdmissionError, FaultPlan, FaultSpec, make_lora_factors
from thunder_tpu.serving.faults import FP_DECODE

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
# one decode step ahead: a steady batch's next step goes out before the harvest
#


def _dense(monkeypatch):
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params, dict(block_size=4, num_blocks=32, max_batch=3, cache_dtype=jnp.float32,
                             temperature=0.7, **BUCKETS)


def _hybrid(monkeypatch):
    """Gated-DeltaNet layers beside one attention layer: the chain carries ``sslots``."""
    from _hybrid_tiny import tiny_model

    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params = tiny_model()
    return cfg, params, dict(max_batch=3, num_blocks=40, block_size=16, prefill_buckets=[32, 64],
                             batch_buckets=[4], block_buckets=[8])


def _latent(monkeypatch):
    """One latent a token a layer in place of K and V, an expert share."""
    from test_mla_serving import tiny_model

    cfg, params = tiny_model()
    return cfg, params, dict(num_blocks=40, block_size=16, max_batch=3, prefill_buckets=(16, 32, 48))


def _ring(monkeypatch):
    """A selective scan's state, a ring of blocks a slot for the window layers,
    one global layer's blocks."""
    from test_hybrid_decoder_serving import ENGINE, model

    cfg, params = model()
    return cfg, params, {**ENGINE, "max_batch": 3}


def _window(monkeypatch):
    """A sliding window of 6 over blocks of 2: a block leaves a row's window every
    other step, so the window's count and the lengths' bound the chain together."""
    cfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 6})
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params, dict(block_size=2, num_blocks=48, max_batch=3, cache_dtype=jnp.float32, temperature=0.7,
                             **{**BUCKETS, "block_buckets": (16,)})


def _int8_kernel(monkeypatch):
    """An int8 arena, its scales beside K and V, walked by the interpreted kernel."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    cfg, params, opts = _dense(monkeypatch)
    return cfg, params, {**opts, "kv_dtype": "int8"}


def _prefix(monkeypatch):
    """Every prompt opens with the same block: a successor attends a block
    another request wrote, and a dead row-step lands beside one still shared."""
    cfg, params, opts = _dense(monkeypatch)
    return cfg, params, {**opts, "shared": 4}


def _chunked(monkeypatch):
    """Prompts past 8 tokens arrive in pieces between the decode steps; the
    synchronous loop, which has no pieces, takes them whole."""
    cfg, params, opts = _dense(monkeypatch)
    return cfg, params, {**opts, "prefill_chunk": 8}


def _fault(monkeypatch):
    """The third decode dispatch, one sent ahead, fails as out of memory: the
    step in flight is dropped with it and the rows replay through their prefill."""
    cfg, params, opts = _dense(monkeypatch)
    return cfg, params, {**opts, "fault_plan": FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)])}


KINDS = {"dense": _dense, "hybrid": _hybrid, "latent": _latent, "ring": _ring}
# options beside the step ahead, each against the synchronous loop
OPTIONS = {"int8_kernel": _int8_kernel, "prefix": _prefix, "chunked": _chunked, "fault": _fault}
ASYNC_ONLY = ("prefill_chunk", "fault_plan")    # the synchronous reference takes prompts whole and meets no fault
AHEAD_REQUESTS = [(9, 7), (14, 5), (5, 9), (12, 6), (7, 4)]     # prompt, new tokens: five through three slots
# under ``_window``: odd prompts, so the rows' blocks leave their windows at the same harvests, every other step
WINDOW_REQUESTS = [(9, 7), (13, 6), (5, 9), (11, 6), (7, 4)]


def _backlog(eng, cfg, lengths=AHEAD_REQUESTS, shared=0):
    """``lengths`` submitted, each request with a key of its own; the first
    ``shared`` tokens of every prompt the same."""
    rng = np.random.default_rng(21)
    head = rng.integers(0, cfg.vocab_size, (shared,)).astype(np.int32)
    return [eng.submit(np.concatenate([head, rng.integers(0, cfg.vocab_size, (n - shared,)).astype(np.int32)]),
                       max_new_tokens=m, key=jax.random.PRNGKey(100 + i)) for i, (n, m) in enumerate(lengths)]


def _drive(eng, cfg, lengths=AHEAD_REQUESTS, shared=0):
    """The requests of ``lengths``, stepped to the end: tokens, finish
    reasons and the key each request ended on."""
    return _results(_backlog(eng, cfg, lengths, shared), eng)


def _results(handles, eng=None):
    """Tokens, finish reasons and the key each request ended on, after a drain."""
    if eng is not None:
        eng.drain()
    res = [h.result(drive=False) for h in handles]
    return ([tuple(r.tokens) for r in res], [r.finish_reason for r in res],
            [np.asarray(h._req.key).tolist() for h in handles])


@functools.cache
def _through(kind):
    """One kind's backlog through three slots, once for the tests that read it:
    the async engine stepped to the end (what it handed a successor while the
    step past a row's end was on the device), then the synchronous one."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        cfg, params, opts = {**KINDS, **OPTIONS, "window": _window}[kind](monkeypatch)
        lengths = WINDOW_REQUESTS if kind == "window" else AHEAD_REQUESTS
        shared = opts.pop("shared", 0)
        eng = tt.serve(None, params, cfg, goodput=True, **opts)
        handles = _backlog(eng, cfg, lengths, shared)
        handed = {"blocks": 0, "slots": 0}
        while eng.scheduler.queue or eng.scheduler.running:
            held = {r.rid: (set(r.block_table), r.state_slot) for r in eng.scheduler.running}
            eng.step()
            rec = eng._inflight_decode
            if rec is None or not rec["ending"]:
                continue
            # step k+1 is in flight, the ended row one of its rows
            ended = [r.rid for r in rec["running"] if r.state != "running"]
            assert len(ended) == rec["ending"] == 1 and eng._decode_state is None
            blocks, slot = held[ended[0]]
            for r in eng.scheduler.running:
                if r.rid not in held:                       # admitted under that step
                    handed["blocks"] += bool(blocks & set(r.block_table) - {0})
                    handed["slots"] += bool(slot) and r.state_slot == slot
        sync_eng = tt.serve(None, params, cfg, async_step=False, **{k: v for k, v in opts.items() if k not in ASYNC_ONLY})
        out = {"cfg": cfg, "params": params, "opts": opts, "handed": handed, "hybrid": eng._hybrid,
               "requests": [(h._req.prompt, h._req.max_new_tokens) for h in handles],
               "served": _results(handles), "sync": _drive(sync_eng, cfg, lengths, shared),
               "shared_blocks": sum(h.result(drive=False).shared_prefix_blocks for h in handles),
               "stats": eng.stats(), "sync_stats": sync_eng.stats(), "steps": (eng.decode_steps, sync_eng.decode_steps),
               "pool_clean": eng.pool.num_free == eng.pool.num_usable and eng.pool.n_retired <= 1}
        eng.shutdown(), sync_eng.shutdown()
        return out


class TestDecodeAhead:
    @pytest.mark.parametrize("kind", [*KINDS, *OPTIONS])
    def test_a_step_ahead_serves_what_the_synchronous_loop_serves(self, kind):
        """Tokens, finish reasons and the keys the requests end on are those of
        ``async_step=False``, for a dense, a hybrid (``sslots``), a latent and a
        ring engine, and for a dense one with an int8 arena under the kernel,
        shared prefixes, prompts in pieces and a fault that replays the rows;
        the async engine ran steps ahead, the synchronous none."""
        ran = _through(kind)
        assert ran["served"] == ran["sync"]
        st = ran["stats"]["decode_ahead"]
        assert 0 < st["ahead"] < st["dispatches"] == ran["steps"][0]
        assert st["share"] == st["ahead"] / st["dispatches"]
        assert ran["sync_stats"]["decode_ahead"] == {"dispatches": ran["steps"][1], "ahead": 0, "share": 0.0}
        assert ran["pool_clean"]
        assert (ran["shared_blocks"] > 0) == (kind == "prefix")
        assert bool(ran["stats"]["chunk_runs"]) == (kind in ("chunked", "fault"))      # a replay runs the chunk programs
        assert ran["stats"]["recoveries"] == (kind == "fault")
        assert (ran["stats"]["attn"]["path"] != "xla") == (kind in ("int8_kernel", "hybrid"))

    def test_a_step_ahead_may_see_a_row_end_at_its_harvest_and_none_join(self, micro):
        """A backlog through three slots, step by step.  A step that dispatched
        ahead left the batch as it was (nobody joined); the harvest under it
        finished exactly the rows the dispatch knew were past their end
        (``ending``: here one or none), and then the chain is gone: no second
        step past an end.  A chain sends ahead what its rows' lengths say: the
        steps before the first end, and one more where a row outlives it."""
        cfg, params = micro
        eng = _engine(cfg, params, max_batch=3, goodput=True)
        rng = np.random.default_rng(22)
        handles = [eng.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), max_new_tokens=m)
                   for n, m in AHEAD_REQUESTS]
        chains = []                     # a chain: [steps ahead its rows' lengths allow, steps ahead it sent]
        while eng.scheduler.queue or eng.scheduler.running:
            ahead, steps = eng.decode_ahead_steps, eng.decode_steps
            done = sum(h.done() for h in handles)
            rec = eng._inflight_decode
            rids = None if rec is None else [r.rid for r in rec["running"]]
            eng.step()
            rec = eng._inflight_decode
            if eng.decode_ahead_steps > ahead:
                chains[-1][1] += 1
                assert [r.rid for r in rec["running"]] == rids                   # none joined
                assert sum(h.done() for h in handles) - done == rec["ending"] <= 1   # who ended was known to
                assert (eng._decode_state is None) == bool(rec["ending"])        # one step past, never two
            elif eng.decode_steps > steps:
                assert not rec["steady"] and rec["ending"] == 0                  # a turnover: a rebuild
                left = [r.max_new_tokens - len(r.generated) - 1 for r in rec["running"]]
                chains.append([min(left) + (max(left) > min(left)), 0])
                assert eng._decode_state["ahead"] == chains[-1][0]
            else:
                assert sum(h.done() for h in handles) - done in (0, 2)           # the last two rows end together
        assert all(sent == allowed for allowed, sent in chains)
        # r1 (5 new tokens) ends first, r0 and r2 while their successors' chains are one step old,
        # r3 and r4 together: nobody outlives them, no step runs for nobody
        assert chains == [[4, 4], [1, 1], [1, 1], [0, 0]]
        st = eng.stats()
        assert st["decode_ahead"] == {"dispatches": 10, "ahead": 6, "share": 0.6, "through_end": 3}
        assert st["decode_rebuild"]["rebuilds"] == len(chains) == 1 + st["decode_ahead"]["through_end"]
        assert st["goodput"]["waste"]["dead_scan_row"] == 3
        assert tt.metrics_snapshot()["serving.steps.decode_ahead"] >= 6
        assert all(h.result(drive=False).finish_reason == "length" for h in handles)

    @pytest.mark.parametrize("kind", [*KINDS, "window"])
    def test_a_row_that_ends_by_length_costs_one_dead_row_step_and_one_rebuild(self, kind):
        """The backlog through three slots, for each kind of thing a dead row-step
        writes: K/V blocks, a state slot, a ring under a window, a latent table,
        blocks a window sinks.  While the step past a row's end is on the device
        the row's blocks and its state slot are the successor's already (the pool
        hands the last freed out first); the tokens are the synchronous loop's
        all the same, and the two successors' solo ``generate()``'s; every dead
        row-step is one the host knew of, and a finished row costs one rebuild
        (the step its successor joins), not two."""
        ran = _through(kind)
        assert ran["served"] == ran["sync"] and ran["pool_clean"]
        for i in (3, 4):                                    # who was handed a dead row's blocks and slot
            p, m = ran["requests"][i]
            solo = gen.generate(ran["params"], p[None], ran["cfg"], m, T_max=64, cache_dtype=jnp.float32,
                                temperature=ran["opts"].get("temperature", 0.0), key=jax.random.PRNGKey(100 + i))
            assert ran["served"][0][i] == tuple(np.asarray(solo)[0].tolist()), i
        st = ran["stats"]
        through = st["decode_ahead"]["through_end"]
        assert st["goodput"]["waste"].get("dead_scan_row", 0) == through
        if kind == "window":
            # the harvest of every other step frees a block and stops the chain, whatever the
            # lengths say: one end of the three falls on a step between, and goes through
            assert st["decode_ahead"] == {"dispatches": 11, "ahead": 2, "share": 2 / 11, "through_end": 1}
        else:
            assert st["decode_ahead"] == {"dispatches": 10, "ahead": 6, "share": 0.6, "through_end": 3}
            # r1, r0 and r2 each end under a step ahead and cost the rebuild their successor
            # joins at (r2 has none: the batch shrinks); r3 and r4 end together, nobody after them
            assert st["decode_rebuild"]["rebuilds"] == 1 + through
            assert ran["handed"] == {"blocks": 2, "slots": 2 if ran["hybrid"] else 0}

    def test_a_lone_request_runs_every_step_but_its_first_ahead(self, micro):
        """One request of 10 new tokens: token 0 is the prefill's, nine decode
        steps follow; the first builds the chain, the other eight go out ahead
        of the harvest before them (none of those harvests ends the row: the
        ninth's does, and no dispatch follows it).  The chain counts down: 8
        steps at its rebuild, none left after the last."""
        cfg, params = micro
        eng = _engine(cfg, params)
        (p,) = _prompts(cfg, (5,), seed=23)
        h = eng.submit(p, max_new_tokens=10)
        left = []
        while not h.done():
            eng.step()
            if eng._decode_state is not None:
                left.append(eng._decode_state["ahead"])
        np.testing.assert_array_equal(h.result(drive=False).tokens, _solo(params, p, cfg, 10))
        assert left[:9] == list(range(8, -1, -1))
        assert eng.stats()["decode_ahead"] == {"dispatches": 9, "ahead": 8, "share": 8 / 9}

    @pytest.mark.parametrize("how", ["constraint", "speculation"])
    def test_what_the_next_dispatch_needs_from_the_host_keeps_the_old_order(self, micro, how):
        cfg, params = micro
        (p,) = _prompts(cfg, (5,), seed=24)
        if how == "constraint":
            from thunder_tpu.serving import TokenSetConstraint

            eng = _engine(cfg, params, constraints=True)
            h = eng.submit(p, max_new_tokens=8, constraint=TokenSetConstraint(cfg.padded_vocab_size, {3, 4, 9}))
        else:
            from thunder_tpu.serving import SpecConfig

            dcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "intermediate_size": 16})
            draft = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
            eng = _engine(cfg, params, speculative=SpecConfig(draft, dcfg, K=2))
            h = eng.submit(p, max_new_tokens=8)
        assert h.result().finish_reason == "length"
        st = eng.stats()["decode_ahead"]
        assert st["dispatches"] > 0 and st["ahead"] == 0 and st["share"] == 0.0

    def test_an_unconstrained_batch_of_a_constrained_engine_runs_ahead(self, micro):
        """The condition is the batch's, not the engine's: with no automaton in
        the batch the mask is the cached all-true one, nothing of the host's."""
        cfg, params = micro
        (p,) = _prompts(cfg, (5,), seed=25)
        eng = _engine(cfg, params, constraints=True)
        eng.submit(p, max_new_tokens=8).result()
        assert eng.stats()["decode_ahead"]["ahead"] > 0

    def test_a_row_that_ends_unseen_costs_one_dead_row_step(self, micro):
        """With ``eos_id`` armed a row may end at step k while step k+1 is on the
        device: that step holds one ``dead_scan_row``, no token follows the EOS,
        the chain is dropped at the harvest, and the tokens are the synchronous
        loop's."""
        cfg, params = micro
        prompts = _prompts(cfg, (5, 8, 6), seed=26)
        free = _engine(cfg, params, temperature=0.9)
        hs = [free.submit(p, max_new_tokens=12, key=jax.random.PRNGKey(i)) for i, p in enumerate(prompts)]
        free.drain()
        eos = hs[0].result(drive=False).new_tokens[5]      # request 0's sixth token ends it
        out = {}
        for mode in (True, False):
            eng = _engine(cfg, params, temperature=0.9, eos_id=eos, goodput=True, async_step=mode)
            hs = [eng.submit(p, max_new_tokens=12, key=jax.random.PRNGKey(i)) for i, p in enumerate(prompts)]
            eng.drain()
            out[mode] = ([h.result(drive=False).new_tokens for h in hs],
                         [h.result(drive=False).finish_reason for h in hs])
            if mode:
                waste = eng.stats()["goodput"]["waste"]
                n_eos = sum(r == "eos" for r in out[mode][1])
                assert n_eos >= 1 and 1 <= waste.get("dead_scan_row", 0) <= n_eos
                assert eng.stats()["decode_ahead"]["ahead"] > 0
        assert out[True] == out[False]
        for toks, reason in zip(*out[True]):
            assert (reason == "eos") == (eos in toks)
            if reason == "eos":
                assert toks.index(eos) == len(toks) - 1

    def test_a_row_that_ends_unseen_leaves_the_sink_out_of_what_is_attended(self, micro, attn_form):
        """EOS sampled at step k while k+1 is on the device, in both forms of the
        program's attention, with the sink block poisoned once the first step is
        out: what the padding rows and the dead row-step write and read there
        reaches nothing a live row attends.  The tokens are those of a
        synchronous engine whose sink is clean, the other row's all eight."""
        cfg, params = micro
        prompts = _prompts(cfg, (3, 7), seed=30)

        def serve(poison=False, **kw):
            eng = _engine(cfg, params, max_batch=2, temperature=0.9, goodput=True, **kw)
            hs = [eng.submit(p, max_new_tokens=8, key=jax.random.PRNGKey(i)) for i, p in enumerate(prompts)]
            if poison:
                eng.step(), eng.step()                     # the prompts, then the first decode step: in flight
                assert eng.decode_steps == 1 and not any(h.done() for h in hs)
                arenas = dict(eng.pool.arenas)
                arenas["k"], arenas["v"] = arenas["k"].at[0].set(997.0), arenas["v"].at[0].set(-997.0)
                eng.pool.set_arenas(arenas)
            eng.drain()
            res = [h.result(drive=False) for h in hs]
            return eng, [r.new_tokens for r in res], [r.finish_reason for r in res]

        eos = serve(async_step=False)[1][0][2]             # request 0's third token ends it
        _, want, why = serve(async_step=False, eos_id=eos)
        assert why == ["eos", "length"] and len(want[0]) == 3
        eng, got, reasons = serve(poison=True, eos_id=eos)
        assert (got, reasons) == (want, why)
        st = eng.stats()
        assert st["attn"]["path"] == ("walk" if attn_form == "interpreted" else "xla")
        # the step after the EOS left before the EOS was seen: one dead row-step, the chain dropped, a rebuild for one row
        assert st["decode_ahead"] == {"dispatches": 7, "ahead": 5, "share": 5 / 7}
        assert st["goodput"]["waste"]["dead_scan_row"] == 1 and st["decode_rebuild"]["rebuilds"] == 2

    @pytest.mark.parametrize("where", ["on", "inside"])
    def test_a_length_that_ends_on_or_inside_the_steps_sent_ahead_is_served_to_the_token(self, micro, where):
        """Two rows; the first ends after five tokens, at the chain's fourth step.
        ``on``: the second has six, so its last token is the one the step sent
        ahead *through* the first row's end brings: it is emitted, the row is not
        dispatched again, and nothing runs for nobody.  ``inside``: the second
        has five too, one step inside what the chain would have sent: both end
        at the fourth step and no step leaves past it.  Either way each row has
        its tokens to the last and no more, and they are the synchronous loop's."""
        cfg, params = micro
        new = (5, 6 if where == "on" else 5)
        prompts = _prompts(cfg, (5, 6), seed=31)
        out = {}
        for mode in (False, True):
            eng = _engine(cfg, params, max_batch=2, goodput=True, async_step=mode)
            hs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, new)]
            eng.drain()
            res = [h.result(drive=False) for h in hs]
            assert [r.finish_reason for r in res] == ["length"] * 2 and [len(r.new_tokens) for r in res] == list(new)
            assert eng.pool.num_free == eng.pool.num_usable
            out[mode] = [r.new_tokens for r in res]
        assert out[True] == out[False]
        st = eng.stats()                                    # the async engine's
        through = int(where == "on")
        # four steps to the first end, three of them ahead; ``on``: one more, ahead and through the end
        assert st["decode_ahead"] == {"dispatches": 4 + through, "ahead": 3 + through, "share": (3 + through) / (4 + through),
                                      **({"through_end": 1} if through else {})}
        assert st["goodput"]["waste"].get("dead_scan_row", 0) == through and st["decode_rebuild"]["rebuilds"] == 1
        # a harvest a dispatch, and every token but a row's first (its prompt's) from one
        assert st["host_visits"] == st["decode_steps"] == 4 + through
        assert st["tokens_per_host_visit"] == (sum(new) - 2) / st["host_visits"]

    def test_a_window_that_lets_blocks_go_keeps_the_old_order_at_that_step(self, micro):
        """A sliding window of 6 over blocks of 2: a block leaves the window
        every other step.  The step whose harvest frees one is never ahead (its
        successor's tables change), the steps between are, and the tokens are
        the synchronous loop's."""
        cfg, params = micro
        wcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 6})
        p = np.arange(4, dtype=np.int32) + 2
        sync = _engine(wcfg, params, block_size=2, num_blocks=16, max_batch=1, async_step=False)
        want = sync.submit(p, max_new_tokens=12).result().tokens
        eng = _engine(wcfg, params, block_size=2, num_blocks=16, max_batch=1)
        h = eng.submit(p, max_new_tokens=12)
        freed_ahead = freed = 0
        while not h.done():
            free, ahead = eng.pool.num_free, eng.decode_ahead_steps
            eng.step()
            if eng.pool.num_free > free and not h.done():
                freed += 1
                freed_ahead += eng.decode_ahead_steps > ahead
        np.testing.assert_array_equal(h.result(drive=False).tokens, want)
        assert freed >= 3 and freed_ahead == 0 and eng.decode_ahead_steps >= 3

    def test_a_deadline_that_has_passed_keeps_the_old_order(self, micro):
        """The chain knows its rows' first deadline: once the engine's clock is
        past it no step goes out ahead (the expiry follows the harvest and would
        take the row out of that batch)."""
        cfg, params = micro
        clk = {"t": 0.0}
        eng = _engine(cfg, params, clock=lambda: clk["t"])
        (p,) = _prompts(cfg, (5,), seed=29)
        h = eng.submit(p, max_new_tokens=24, deadline=9.0)
        while not h.done():
            ahead = eng.decode_ahead_steps
            late = clk["t"] >= 9.0
            eng.step()
            assert not (late and eng.decode_ahead_steps > ahead)
            clk["t"] += 1.5
        assert h.result(drive=False).finish_reason == "deadline" and eng.decode_ahead_steps >= 3
        assert eng.pool.num_free == eng.pool.num_usable

    def test_the_harvest_keeps_the_handles_the_dispatch_ahead_parked(self, micro):
        """A pool that counts what ``release_retired`` drops: the harvest after a
        dispatch ahead drops what was parked before that dispatch and keeps its
        own entry (its program is on the device), so a handle is dropped one
        harvest after its consumer's, never with it."""
        cfg, params = micro
        eng = _engine(cfg, params)
        pool = eng.pool
        log = []
        real = pool.release_retired

        def counting(upto=None):
            before = pool.n_retired
            real(upto)
            log.append((eng.decode_ahead_steps, before, pool.n_retired))

        pool.release_retired = counting
        (p,) = _prompts(cfg, (5,), seed=27)
        h = eng.submit(p, max_new_tokens=8)
        seen_ahead = 0
        while not h.done():
            log.clear()
            ahead_before = eng.decode_ahead_steps
            eng.step()
            if eng.decode_ahead_steps > ahead_before:
                seen_ahead += 1
                # one release in the step, at the harvest: everything but the
                # entry the dispatch ahead had just parked
                assert len(log) == 1 and log[0][2] == 1 and log[0][1] >= 1
                assert pool.n_retired == 1
            else:
                assert all(after == 0 for _, _, after in log)
        assert seen_ahead > 0 and pool.n_retired == 0

    def test_the_overlap_accounting_counts_a_step_ahead_from_the_harvest_before_it(self, micro):
        """Two dispatches precede a wait: the record dispatched ahead takes its
        place in ``overlap_frac`` and in the device seconds from the moment the
        step before it was fetched, so neither counts that step twice."""
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        (p,) = _prompts(cfg, (5,), seed=28)
        h = eng.submit(p, max_new_tokens=8)
        stamps = []
        while not h.done():
            eng.step()
            rec = eng._inflight_decode
            if rec is not None and "parked" in rec:
                assert rec["t_dev"] >= rec["t_disp"]       # the wait for the step before ended after this dispatch
                stamps.append(rec["t_dev"])
        assert stamps == sorted(stamps) and len(stamps) == eng.decode_ahead_steps > 0
        s = eng.stats()
        assert 0 <= s["overlap_frac_mean"] <= 1


#
# a rebuild of the chain's inputs carries the standing rows over by request
#

REBUILT = ("toks", "host_pos", "tables", "keys", "slots", "sslots", "stop", "facts", "live", "constrained", "deadline")


def _watch_rebuilds(eng):
    """Holds every rebuild of ``eng`` to the full build: the arrays it hands
    on (and what it recorded of each row, and what the chain may run ahead)
    are, element for element and dtype for dtype, those of a build that
    carries nothing.  Returns the log of rebuilds: the rids, whether it was a
    full build, the rows written, and the rows that had to be: new to the
    build before, or whose table, slots or stop differ from what it held."""
    log = []
    inner = eng._decode_inputs

    def watched(running, sig, old):
        host = inner(running, sig, old)
        full = inner(running, sig, None)
        assert full["full"] and full["written"] == len(running)
        for name in REBUILT:
            assert host[name].dtype == full[name].dtype, name
            np.testing.assert_array_equal(host[name], full[name], err_msg=name)
        assert eng._ahead_steps(host) == eng._ahead_steps(full)
        changed = None
        if not host["full"]:
            at = old["at"]
            changed = sum(
                rid not in at or any(not np.array_equal(full[a][i], old[a][at[rid]])
                                     for a in ("tables", "slots", "sslots", "stop"))
                for i, rid in enumerate(sig[0]))
        log.append({"rids": sig[0], "full": host["full"], "written": host["written"], "changed": changed,
                    "epochs": [r.preemptions for r in running], "pos": host["host_pos"][:len(running)].copy()})
        return host

    eng._decode_inputs = watched
    return log


def _staggered(eng, cfg, lengths, seed=31, **kw):
    rng = np.random.default_rng(seed)
    return [eng.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), max_new_tokens=m,
                       key=jax.random.PRNGKey(200 + i), **kw) for i, (n, m) in enumerate(lengths)]


class TestDecodeRebuild:
    @pytest.mark.parametrize("case", ["turnover", "turnover_sync", "block_boundary", "window", "preempted",
                                      "bucket_and_recover", "state_slots", "deadlines"])
    def test_a_rebuild_hands_on_what_the_full_build_would(self, micro, case, monkeypatch):
        """At every rebuild of a run with staggered lengths the patched host
        arrays equal the full build's (``_watch_rebuilds``), and the rows
        written from Python are the rows that changed, not the batch."""
        cfg, params = micro
        opts = dict(temperature=0.7)
        lengths = AHEAD_REQUESTS + [(6, 8), (11, 3)]           # seven through three slots
        if case == "turnover_sync":
            opts["async_step"] = False
        elif case == "window":
            cfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "sliding_window": 6})
            opts.update(block_size=2, num_blocks=48, block_buckets=(16,))
        elif case == "state_slots":
            cfg, params, opts = _hybrid(monkeypatch)
        if case == "preempted":
            eng = _engine(cfg, params, max_batch=2, batch_buckets=(2,), num_blocks=12, priorities=True, **opts)
        elif case == "bucket_and_recover":
            eng = _engine(cfg, params, max_batch=3, batch_buckets=(2, 4), **opts)
        elif case == "state_slots":
            eng = tt.serve(None, params, cfg, **opts)
        else:
            eng = _engine(cfg, params, max_batch=3, **opts)
        log = _watch_rebuilds(eng)

        if case == "preempted":
            # two low rows fill the batch; a high arrival takes the younger
            # one's slot, and the victim comes back an epoch later
            lows = _staggered(eng, cfg, [(6, 14), (7, 14)], priority="low")
            for _ in range(5):
                eng.step()
            high = _staggered(eng, cfg, [(5, 4)], seed=32, priority="high")
            eng.drain()
            assert eng.preempted == 1 and all(h.done() for h in lows + high)
            stood = next(h.rid for h in lows if h._req.preemptions == 0)
            joins = [e for e in log if not e["full"]]
            assert joins and all(e["written"] <= 1 for e in joins)
            # the row that stood was written once, by the first build
            assert any(stood in e["rids"] and e["written"] == 1 and 1 in e["epochs"] for e in joins)
        elif case == "bucket_and_recover":
            hs = _staggered(eng, cfg, [(5, 12), (7, 10)])
            for _ in range(4):
                eng.step()
            assert [e["full"] for e in log] == [True]           # two rows: the bucket of 2
            hs += _staggered(eng, cfg, [(6, 9), (5, 6)], seed=33)   # a third row: the bucket of 4; a fourth waits
            for _ in range(4):
                eng.step()
            assert [e["full"] for e in log] == [True, True] and len(log[-1]["rids"]) == 3
            eng.recover()
            eng.drain()
            assert [e["full"] for e in log[:3]] == [True, True, True]
            assert eng.stats()["decode_rebuild"]["full"] == sum(e["full"] for e in log) >= 3
            assert all(h.result(drive=False).finish_reason == "length" for h in hs)
        else:
            hs = _staggered(eng, cfg, lengths if case != "block_boundary" else [(5, 24), (6, 4), (7, 9)],
                            **({"deadline": 1e6} if case == "deadlines" else {}))
            eng.drain()
            assert all(h.result(drive=False).finish_reason == "length" for h in hs)

        st = eng.stats()["decode_rebuild"]
        assert st["rebuilds"] == len(log) and st["full"] == sum(e["full"] for e in log)
        assert st["rows_written"] == sum(e["written"] for e in log)
        assert st["rows_carried"] == sum(len(e["rids"]) - e["written"] for e in log)
        assert st["carried_share"] == st["rows_carried"] / (st["rows_carried"] + st["rows_written"])
        carried = [e for e in log if not e["full"]]
        assert carried and st["rows_carried"] > 0
        if case != "preempted":                                 # a resumed row may be given its old blocks again
            assert all(e["written"] == e["changed"] for e in carried)
        if case in ("turnover", "turnover_sync", "state_slots", "deadlines"):
            # a successor is the one row written; a rebuild after a row's end
            # with nobody waiting writes none
            for before, e in zip(log, log[1:]):
                if not e["full"]:
                    assert e["written"] == len(set(e["rids"]) - set(before["rids"])) <= 1
            assert log[0]["full"] and not any(e["full"] for e in log[1:])
        if case == "block_boundary":
            # the long row crosses a block boundary between rebuilds and is
            # carried all the same: its table was leased whole at admission
            bs = eng.pool.block_size
            crossed = [e for before, e in zip(log, log[1:])
                       if e["rids"][0] == before["rids"][0] and e["pos"][0] // bs > before["pos"][0] // bs]
            assert crossed and all(e["written"] == 0 for e in crossed)
        if case == "window":
            # a block leaves a row's window every other step: that row is
            # written, its neighbours at another phase are not
            assert any(e["written"] == 1 and len(e["rids"]) == 3 for e in carried)
            assert sum(e["written"] for e in carried) < sum(len(e["rids"]) for e in carried)
        if case == "turnover":
            sync = _engine(cfg, params, max_batch=3, async_step=False, **opts)
            want = _staggered(sync, cfg, lengths)
            sync.drain()
            assert [h.result(drive=False).tokens.tolist() for h in hs] == [h.result(drive=False).tokens.tolist() for h in want]
        eng.shutdown()


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
