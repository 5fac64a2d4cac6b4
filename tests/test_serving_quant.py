"""Int8-quantized KV block storage (serving/quant.py + kv_pool kv_dtype).

The load-bearing guarantees, tested differentially on the micro model:

- **exact greedy parity**: tokens served off the int8 cache match the f32
  cache AND solo ``generate()`` exactly (argmax margins dominate the ~1e-2
  quantization noise at these shapes);
- **determinism**: quantization is per-token (absmax over ``hs``), so a
  request's stored KV never depends on batch composition;
- **capacity math**: an int8 pool at equal arena bytes holds
  ``hs*4/(hs+4)``x the blocks of the f32 pool;
- the ``scatter_blocks`` silent-downcast fix: any storage-dtype mismatch
  raises ``ArenaMismatchError`` at trace time instead of truncating.

Bucket sets are pinned small so the whole file compiles a handful of tiny
programs (tier-1 budget).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import (
    ArenaMismatchError,
    PagedKVPool,
    arena_block_bytes,
    blocks_for_arena_bytes,
)
from thunder_tpu.serving.kv_pool import SINK_BLOCK, scatter_blocks, scatter_token
from thunder_tpu.serving.quant import (
    dequantize_kv,
    gather_dense_q,
    quantize_kv,
    resolve_kv_dtype,
    scatter_token_q,
)

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(4,), prefill_buckets=(16,))


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


#
# quantize/dequantize primitives
#


class TestQuantPrimitives:
    def test_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 5, 16), dtype=jnp.float32)
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert q.shape == x.shape and s.shape == x.shape[:-1]
        dq = dequantize_kv(q, s)
        rel = float(jnp.sum(jnp.abs(dq - x)) / jnp.sum(jnp.abs(x)))
        assert 0 < rel < 0.03       # the documented ~1e-2 int8 tolerance

    def test_zero_rows_exact_and_scale_one(self):
        x = jnp.zeros((2, 4, 8), jnp.float32)
        q, s = quantize_kv(x)
        assert jnp.all(q == 0) and jnp.all(s == 1.0)
        np.testing.assert_array_equal(dequantize_kv(q, s), x)

    def test_deterministic_per_token(self):
        """A token's quantization depends only on its own values: the same
        row quantizes identically inside different batch shapes (the
        serving bit-exactness contract)."""
        row = jax.random.normal(jax.random.PRNGKey(1), (6, 16), dtype=jnp.float32)
        alone = quantize_kv(row)
        batched = quantize_kv(jnp.stack([row, row * 7.0 + 1.0]))
        np.testing.assert_array_equal(alone[0], batched[0][0])
        np.testing.assert_array_equal(alone[1], batched[1][0])

    def test_resolve_kv_dtype(self):
        assert resolve_kv_dtype(None, jnp.float32) == jnp.dtype(jnp.float32)
        assert resolve_kv_dtype("int8", jnp.float32) == jnp.dtype(jnp.int8)
        assert resolve_kv_dtype(jnp.int8, jnp.bfloat16) == jnp.dtype(jnp.int8)
        with pytest.raises(ValueError, match="unsupported kv_dtype"):
            resolve_kv_dtype(jnp.float16, jnp.float32)  # silent truncation class

    def test_resolve_kv_dtype_fp8_aliases(self):
        for alias in ("fp8", "e4m3", "float8_e4m3fn", jnp.float8_e4m3fn):
            assert resolve_kv_dtype(alias, jnp.float32) == jnp.dtype(jnp.float8_e4m3fn)

    def test_fp8_roundtrip_error_bound(self):
        """e4m3 has 3 mantissa bits: expect a few-percent mean relative
        error — worse than int8's uniform grid at the top of the range,
        but still inside the serving tolerance the gauge documents."""
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 5, 16), dtype=jnp.float32)
        q, s = quantize_kv(x, jnp.float8_e4m3fn)
        assert q.dtype == jnp.float8_e4m3fn and s.dtype == jnp.float32
        assert q.shape == x.shape and s.shape == x.shape[:-1]
        dq = dequantize_kv(q, s)
        rel = float(jnp.sum(jnp.abs(dq - x)) / jnp.sum(jnp.abs(x)))
        assert 0 < rel < 0.05
        # the absmax element lands exactly on ±448 — representable, so the
        # per-row max survives the round trip bit-exactly
        amax_in = jnp.max(jnp.abs(x), axis=-1)
        amax_out = jnp.max(jnp.abs(dq), axis=-1)
        np.testing.assert_allclose(np.asarray(amax_out), np.asarray(amax_in), rtol=1e-6)

    def test_fp8_deterministic_per_token(self):
        row = jax.random.normal(jax.random.PRNGKey(1), (6, 16), dtype=jnp.float32)
        alone = quantize_kv(row, jnp.float8_e4m3fn)
        batched = quantize_kv(jnp.stack([row, row * 7.0 + 1.0]), jnp.float8_e4m3fn)
        np.testing.assert_array_equal(alone[0], batched[0][0])
        np.testing.assert_array_equal(alone[1], batched[1][0])


#
# quantized pool geometry + capacity math
#


class TestQuantizedPool:
    def test_arena_dtypes_and_scale_shape(self, micro):
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32,
                           kv_dtype="int8")
        assert pool.quantized_kv and pool.kv_dtype == jnp.dtype(jnp.int8)
        assert pool.dtype == jnp.float32                  # compute dtype unchanged
        assert pool.k_arena.dtype == jnp.int8
        assert pool.k_scale.shape == pool.k_arena.shape[:-1]
        assert pool.k_scale.dtype == jnp.float32
        assert set(pool.arenas) == {"k", "v", "k_scale", "v_scale"}
        snap = pool.state_snapshot()
        assert snap["kv_dtype"] == "int8"
        assert snap["arena_bytes"] == pool.arena_bytes()

    def test_block_bytes_capacity_multiple(self, micro):
        """hs=8 micro: int8+scale costs (8+4) bytes per slot-head vs 32 for
        f32 — and the pool's own accounting agrees with the analytic
        helper."""
        cfg, _ = micro
        f32 = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32)
        i8 = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32,
                         kv_dtype="int8")
        assert f32.block_bytes() == arena_block_bytes(cfg, 4, jnp.float32)
        assert i8.block_bytes() == arena_block_bytes(cfg, 4, jnp.float32, kv_dtype="int8")
        hs = cfg.head_size
        assert f32.block_bytes() / i8.block_bytes() == pytest.approx(hs * 4 / (hs + 4))
        # equal-bytes sizing: the helper affords proportionally more blocks
        budget = 20 * f32.block_bytes()
        assert blocks_for_arena_bytes(cfg, 4, budget, jnp.float32) == 20
        assert blocks_for_arena_bytes(cfg, 4, budget, jnp.float32, kv_dtype="int8") == (
            budget // i8.block_bytes()
        )

    def test_set_arenas_validates_scales(self, micro):
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.float32,
                           kv_dtype="int8")
        good = pool.arenas
        with pytest.raises(ArenaMismatchError, match="k_scale"):
            pool.set_arenas({**good, "k_scale": good["k_scale"].astype(jnp.float16)})
        with pytest.raises(ArenaMismatchError, match="arena keys"):
            pool.set_arenas({"k": good["k"], "v": good["v"]})  # scales missing
        pool.set_arenas(good)                              # self-install passes

    def test_low_water_mark_tracks_floor(self, micro):
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32)
        assert pool.free_blocks_low_water == 7
        got = pool.alloc(5)
        assert pool.free_blocks_low_water == 2
        pool.free(got)
        assert pool.num_free == 7
        assert pool.free_blocks_low_water == 2             # floor, not current
        assert pool.state_snapshot()["free_blocks_low_water"] == 2


#
# the scatter_blocks silent-downcast fix (satellite)
#


class TestScatterDtypeValidation:
    def test_scatter_blocks_rejects_mismatched_dtype(self, micro):
        """Regression: scatter_blocks used to `astype` the dense cache into
        the arena dtype silently — an f32 cache written into a narrower
        arena truncated without a trace.  Now it raises at trace time."""
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.bfloat16)
        dense = jnp.zeros(pool.dense_shape(1, 2), jnp.float32)
        with pytest.raises(ArenaMismatchError, match="silent truncation"):
            scatter_blocks(pool.k_arena, dense, jnp.zeros(2, jnp.int32))
        ok = scatter_blocks(pool.k_arena, dense.astype(jnp.bfloat16),
                            jnp.zeros(2, jnp.int32))
        assert ok.dtype == pool.k_arena.dtype

    def test_scatter_token_rejects_mismatched_dtype(self, micro):
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.bfloat16)
        tok = jnp.zeros((1, cfg.n_layer, cfg.n_query_groups, cfg.head_size), jnp.float32)
        with pytest.raises(ArenaMismatchError, match="silent truncation"):
            scatter_token(pool.k_arena, tok, jnp.zeros(1, jnp.int32),
                          jnp.zeros(1, jnp.int32))

    def test_quantized_scatter_gather_roundtrip(self, micro):
        """scatter_token_q + gather_dense_q reproduce the written token up
        to the int8 tolerance, in the requested compute dtype."""
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.float32,
                           kv_dtype="int8")
        kv = jax.random.normal(
            jax.random.PRNGKey(2),
            (1, cfg.n_layer, cfg.n_query_groups, cfg.head_size), dtype=jnp.float32)
        k_arena, k_scale = scatter_token_q(
            pool.k_arena, pool.k_scale, kv, jnp.asarray([2]), jnp.asarray([1]))
        table = jnp.asarray([[2]], jnp.int32)
        kd, _ = gather_dense_q(k_arena, pool.v_arena, k_scale, pool.v_scale,
                               table, jnp.float32)
        got = kd[:, 0, :, 1, :]                            # (L, ng, hs) at slot 1
        want = kv[0]
        assert kd.dtype == jnp.float32
        rel = float(jnp.sum(jnp.abs(got - want)) / jnp.sum(jnp.abs(want)))
        assert 0 <= rel < 0.03


#
# engine end-to-end on the int8 cache
#


@pytest.fixture(scope="module")
def quant_served(micro):
    """One int8-engine drive shared by several assertions: mixed-length
    greedy batch, metrics snapshotted eagerly (the autouse observability
    reset wipes the registry between tests)."""
    cfg, params = micro
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (3, 5, 9)]
    eng = _engine(cfg, params, kv_dtype="int8")
    results = eng.run([{"prompt": p, "max_new_tokens": 5} for p in prompts])
    snap = tt.metrics_snapshot()
    return cfg, params, prompts, results, eng, snap


class TestQuantizedEngine:
    def test_greedy_argmax_parity_vs_f32_and_solo(self, quant_served):
        """Acceptance: exact argmax-token match — int8-cache served tokens
        equal both the f32-cache engine AND solo generate() for every
        request in a mixed batch."""
        cfg, params, prompts, results, _, _ = quant_served
        f32 = _engine(cfg, params).run(
            [{"prompt": p, "max_new_tokens": 5} for p in prompts])
        for p, r8, r32 in zip(prompts, results, f32):
            solo = _solo(params, p, cfg, 5)
            np.testing.assert_array_equal(r8.tokens, solo)
            np.testing.assert_array_equal(r8.tokens, r32.tokens)

    def test_quant_error_gauge_within_tolerance(self, quant_served):
        """The measured per-prefill quantization error lands in the gauge
        and stays inside the documented ~1e-2 tolerance."""
        *_, snap = quant_served
        err = snap.get("serving.kv_quant.rel_err")
        assert err is not None and 0 < err < 0.03

    def test_stats_and_flight_carry_kv_dtype_and_low_water(self, quant_served):
        *_, eng, snap = quant_served
        stats = eng.stats()
        assert stats["kv_dtype"] == "int8"
        assert stats["arena_bytes"] == eng.pool.arena_bytes()
        # the flood dipped the pool; the floor survives after drain
        assert stats["pool_free_blocks_low_water"] < eng.pool.num_usable
        flight = eng._flight_state()
        assert flight["pool"]["kv_dtype"] == "int8"
        assert flight["pool"]["free_blocks_low_water"] == (
            stats["pool_free_blocks_low_water"])
        assert snap["serving.pool.free_blocks_low_water"] == (
            stats["pool_free_blocks_low_water"])

    def test_temperature_parity_with_request_keys(self, micro):
        """The sampling chain is independent of KV storage: temperature
        tokens off the int8 cache match the int8 solo-batch run with the
        same key (per-request chains survive quantized storage)."""
        cfg, params = micro
        key = jax.random.PRNGKey(11)
        p = (np.arange(7) * 5 + 2).astype(np.int32) % cfg.vocab_size
        mixed = _engine(cfg, params, kv_dtype="int8", temperature=0.7)
        ha = mixed.submit(p, max_new_tokens=4, key=key)
        hb = mixed.submit((p * 3 + 1) % cfg.vocab_size, max_new_tokens=4,
                          key=jax.random.PRNGKey(5))
        mixed.drain()
        alone = _engine(cfg, params, kv_dtype="int8", temperature=0.7)
        np.testing.assert_array_equal(
            ha.result(drive=False).tokens,
            alone.submit(p, max_new_tokens=4, key=key).result().tokens,
        )

    def test_prefix_sharing_on_quantized_blocks(self, micro):
        """Shared-prefix admission reuses quantized physical blocks and
        still matches solo generate() exactly."""
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8")
        base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size
        ha = eng.submit(base, max_new_tokens=4)
        eng.step()
        hb = eng.submit(base.copy(), max_new_tokens=4)
        eng.step()
        assert hb._req.n_shared_blocks == 2
        eng.drain()
        solo = _solo(params, base, cfg, 4)
        np.testing.assert_array_equal(ha.result(drive=False).tokens, solo)
        np.testing.assert_array_equal(hb.result(drive=False).tokens, solo)
        assert eng.pool.num_free == eng.pool.num_usable

    def test_equal_bytes_pool_admits_more_requests(self, micro):
        """The capacity acceptance at unit scale: at one arena-byte budget
        the int8 engine keeps strictly more requests resident than the f32
        engine (the 3x multiple at hs=16:
        tests/test_serving_invariants.py)."""
        cfg, params = micro
        budget = 13 * arena_block_bytes(cfg, 4, jnp.float32)
        nb_f32 = blocks_for_arena_bytes(cfg, 4, budget, jnp.float32)
        nb_i8 = blocks_for_arena_bytes(cfg, 4, budget, jnp.float32, kv_dtype="int8")
        assert nb_i8 > nb_f32

        def peak(**kw):
            eng = _engine(cfg, params, max_batch=16, batch_buckets=(16,), **kw)
            for i in range(8):
                eng.submit(np.arange(4, dtype=np.int32) + i, max_new_tokens=12)
            top = 0
            while eng.scheduler.queue or eng.scheduler.running:
                eng.step()
                top = max(top, len(eng.scheduler.running))
            return top

        assert peak(num_blocks=nb_i8, kv_dtype="int8") > peak(num_blocks=nb_f32)

    def test_bytes_needed_reflects_storage_dtype(self, micro):
        """Admission accounting in quantized bytes: the same request
        reserves ~hs*4/(hs+4) fewer bytes on the int8 pool."""
        cfg, params = micro
        f32 = _engine(cfg, params)
        i8 = _engine(cfg, params, kv_dtype="int8")
        p = np.arange(6, dtype=np.int32)
        rf = f32.scheduler.submit(p, 10, key=jax.random.PRNGKey(0))
        ri = i8.scheduler.submit(p, 10, key=jax.random.PRNGKey(0))
        assert f32.scheduler.blocks_needed(rf) == i8.scheduler.blocks_needed(ri)
        ratio = f32.scheduler.bytes_needed(rf) / i8.scheduler.bytes_needed(ri)
        hs = cfg.head_size
        assert ratio == pytest.approx(hs * 4 / (hs + 4))
        row = i8.scheduler.state_snapshot()["requests"][0]
        assert row["reserved_bytes"] == i8.scheduler.bytes_needed(ri)


class TestFp8Engine:
    """fp8 e4m3 block storage behind the same ``kv_dtype=`` seam (ROADMAP
    item 5 remainder): identical arena geometry and capacity bytes as int8,
    differential greedy parity, measured rel err inside tolerance."""

    def test_pool_geometry_and_capacity_bytes_match_int8(self, micro):
        cfg, _ = micro
        fp8 = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32,
                          kv_dtype="fp8")
        assert fp8.quantized_kv and fp8.kv_dtype == jnp.dtype(jnp.float8_e4m3fn)
        assert fp8.k_arena.dtype == jnp.float8_e4m3fn
        assert fp8.k_scale.shape == fp8.k_arena.shape[:-1]
        assert set(fp8.arenas) == {"k", "v", "k_scale", "v_scale"}
        # both 1-byte storages + f32 scales: identical capacity math, so
        # the admitted-concurrency multiple carries over unchanged
        assert fp8.block_bytes() == arena_block_bytes(cfg, 4, jnp.float32,
                                                      kv_dtype="int8")
        assert arena_block_bytes(cfg, 4, jnp.float32, kv_dtype="fp8") == (
            arena_block_bytes(cfg, 4, jnp.float32, kv_dtype="int8"))

    def test_greedy_parity_and_rel_err_gauge(self, micro):
        """Acceptance: fp8-cache served tokens equal the f32 engine AND
        solo generate() exactly, and the measured per-prefill error lands
        in the gauge inside the documented tolerance."""
        cfg, params = micro
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 5, 9)]
        eng = _engine(cfg, params, kv_dtype="fp8")
        results = eng.run([{"prompt": p, "max_new_tokens": 5} for p in prompts])
        snap = tt.metrics_snapshot()
        f32 = _engine(cfg, params).run(
            [{"prompt": p, "max_new_tokens": 5} for p in prompts])
        for p, r8, r32 in zip(prompts, results, f32):
            solo = _solo(params, p, cfg, 5)
            np.testing.assert_array_equal(r8.tokens, solo)
            np.testing.assert_array_equal(r8.tokens, r32.tokens)
        err = snap.get("serving.kv_quant.rel_err")
        assert err is not None and 0 < err < 0.05
        assert eng.stats()["kv_dtype"] == "float8_e4m3fn"

    def test_temperature_parity_on_fp8(self, micro):
        cfg, params = micro
        key = jax.random.PRNGKey(11)
        p = (np.arange(7) * 5 + 2).astype(np.int32) % cfg.vocab_size
        mixed = _engine(cfg, params, kv_dtype="fp8", temperature=0.7)
        ha = mixed.submit(p, max_new_tokens=4, key=key)
        mixed.submit((p * 3 + 1) % cfg.vocab_size, max_new_tokens=4,
                     key=jax.random.PRNGKey(5))
        mixed.drain()
        alone = _engine(cfg, params, kv_dtype="fp8", temperature=0.7)
        np.testing.assert_array_equal(
            ha.result(drive=False).tokens,
            alone.submit(p, max_new_tokens=4, key=key).result().tokens,
        )


@pytest.mark.slow
def test_quantized_soak_matches_solo(micro):
    """Mixed-shape int8 soak: every request still matches solo generate()
    exactly (greedy) under saturation with block reuse."""
    cfg, params = micro
    rng = np.random.default_rng(7)
    eng = _engine(cfg, params, kv_dtype="int8", num_blocks=24, max_batch=4)
    reqs = []
    for _ in range(16):
        n = int(rng.integers(2, 12))
        reqs.append({
            "prompt": rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            "max_new_tokens": int(rng.integers(1, 6)),
        })
    results = eng.run(reqs)
    for q, r in zip(reqs, results):
        np.testing.assert_array_equal(
            r.tokens, _solo(params, q["prompt"], cfg, q["max_new_tokens"])
        )
