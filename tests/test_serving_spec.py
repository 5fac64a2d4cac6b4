"""Speculative continuous batching (serving/speculative.py, ISSUE 14).

The load-bearing guarantee is differential and bit-exact at the token
level: an engine built with ``speculative=SpecConfig(draft_params,
draft_cfg, K)`` must serve tokens identical to solo
``speculative_generate()`` — greedy AND temperature, K∈{2,4}, int8 KV,
LoRA-on-target, prefix sharing, chunked prefill, async on/off, the verify
kernel or its XLA form, and across fault retry / re-prefill recovery.  The PRNG
chain only advances at harvest, so the draft arena is soft state and a
recovered run replays bit-identically.

Structural pillars: with the kernel in it the ``verify_paged`` program contains
zero arena gathers and zero scatters (the kernel's XLA form as positive control); the
program set stays within ``stats()["bucket_bound"]``; and
``speculative=None`` engines are byte-identical to a world where the
subsystem does not exist (module program cache gains no entries).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from conftest import arena_census, set_attn_form
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.models import speculative as mspec
from thunder_tpu.serving import (
    AdapterRegistry,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    SpecConfig,
    make_lora_factors,
)
from thunder_tpu.serving.faults import FAULT_POINTS, FP_DRAFT, FP_VERIFY

# 2 layers (layer-indexed arena reads), GQA 4:2, tiny widths; the draft is
# the same family at 1 layer — a real draft/target pair, not a toy alias
MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(8,), prefill_buckets=(16,))


@pytest.fixture(scope="module")
def models():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    dcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "n_layer": 1})
    tp = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    dp = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    return cfg, dcfg, tp, dp


def _engine(models, *, K=2, **kw):
    cfg, dcfg, tp, dp = models
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("retry", RetryPolicy(sleep=lambda s: None))
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, tp, cfg, speculative=SpecConfig(dp, dcfg, K=K), **kw)


def _solo(models, prompt, n, *, K=2, temperature=0.0, key=None, **kw):
    """The solo speculative row (prompt + generated) — what
    ``RequestResult.tokens`` must equal bit-for-bit."""
    cfg, dcfg, tp, dp = models
    kw.setdefault("cache_dtype", jnp.float32)
    out = mspec.speculative_generate(
        tp, dp, jnp.asarray(prompt)[None], cfg, dcfg, n, K=K,
        temperature=temperature, key=key, **kw)
    return np.asarray(out)[0]


def _prompt(seed, n, cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


#
# config validation + the public acceptance rule (single implementation)
#


class TestSpecConfig:
    def test_rejects_non_specconfig(self, models):
        cfg, dcfg, tp, dp = models
        with pytest.raises(TypeError, match="SpecConfig"):
            tt.serve(None, tp, cfg, speculative=42, **BUCKETS,
                     block_size=4, num_blocks=64, max_batch=4)

    def test_rejects_bad_k(self, models):
        cfg, dcfg, tp, dp = models
        with pytest.raises(ValueError, match="K"):
            _engine(models, K=0)

    def test_rejects_vocab_mismatch(self, models):
        cfg, dcfg, tp, dp = models
        bad = llama.Config.from_name(
            "tiny-llama-debug", **{**MICRO, "n_layer": 1, "vocab_size": 128})
        assert bad.padded_vocab_size != cfg.padded_vocab_size
        bad_p = llama.init_params(bad, jax.random.PRNGKey(1), dtype=jnp.float32)
        with pytest.raises(ValueError, match="vocab"):
            tt.serve(None, tp, cfg, speculative=SpecConfig(bad_p, bad, K=2),
                     **BUCKETS, block_size=4, num_blocks=64, max_batch=4)

    def test_rejects_sliding_window(self, models):
        cfg, dcfg, tp, dp = models
        wcfg = llama.Config.from_name(
            "tiny-llama-debug", **{**MICRO, "sliding_window": 8})
        wp = llama.init_params(wcfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        with pytest.raises(ValueError, match="[sS]liding"):
            tt.serve(None, wp, wcfg, speculative=SpecConfig(dp, dcfg, K=2),
                     **BUCKETS, block_size=4, num_blocks=64, max_batch=4)

    def test_specconfig_exported(self):
        import thunder_tpu.serving as serving

        assert "SpecConfig" in serving.__all__
        assert serving.SpecConfig is SpecConfig

    def test_accept_tokens_is_public_and_single(self):
        """Satellite: ONE rejection-rule implementation, used by both the
        solo path and the serving verify program."""
        from thunder_tpu.serving import speculative as sspec

        assert "accept_tokens" in mspec.__all__
        assert mspec._accept_tokens is mspec.accept_tokens  # back-compat alias
        assert sspec.accept_tokens is mspec.accept_tokens   # serving reuses it


#
# greedy parity
#


class TestGreedyParity:
    @pytest.mark.parametrize(
        "K,async_step",
        [(2, True), (2, False),
         pytest.param(4, True, marks=pytest.mark.slow)])
    def test_served_equals_solo(self, models, K, async_step):
        cfg = models[0]
        eng = _engine(models, K=K, async_step=async_step)
        p0, p1 = _prompt(1, 7, cfg), _prompt(2, 5, cfg)
        h0 = eng.submit(p0, max_new_tokens=14)
        h1 = eng.submit(p1, max_new_tokens=9)
        np.testing.assert_array_equal(h0.result().tokens, _solo(models, p0, 14, K=K))
        np.testing.assert_array_equal(h1.result().tokens, _solo(models, p1, 9, K=K))
        st = eng.stats()["spec"]
        assert st["rounds"] > 0 and st["K"] == K

    @pytest.mark.slow
    def test_perfect_draft_accepts_everything(self, models):
        """Draft == target: 100% acceptance, K+1 tokens per round, tokens
        equal to plain greedy generate — the positive control proving the
        acceptance lane does more than fall back to the correction token."""
        cfg, _, tp, _ = models
        eng = tt.serve(None, tp, cfg, speculative=SpecConfig(tp, cfg, K=4),
                       **BUCKETS, block_size=4, num_blocks=64, max_batch=4,
                       cache_dtype=jnp.float32)
        p = _prompt(1, 7, cfg)
        r = eng.submit(p, max_new_tokens=12).result()
        ref = np.asarray(gen.generate(tp, jnp.asarray(p)[None], cfg, 12,
                                      cache_dtype=jnp.float32))[0]
        np.testing.assert_array_equal(r.tokens, ref)
        st = eng.stats()["spec"]
        assert st["acceptance_rate"] == 1.0
        assert st["tokens_per_round"] == 5.0


#
# sampling parity: the per-request key chain must mirror solo exactly
#


class TestSamplingParity:
    @pytest.mark.parametrize(
        "K", [2, pytest.param(4, marks=pytest.mark.slow)])
    def test_temperature_served_equals_solo(self, models, K):
        cfg = models[0]
        eng = _engine(models, K=K, temperature=0.7)
        p0, p1 = _prompt(1, 7, cfg), _prompt(2, 5, cfg)
        k0, k1 = jax.random.PRNGKey(11), jax.random.PRNGKey(5)
        h0 = eng.submit(p0, max_new_tokens=12, key=k0)
        h1 = eng.submit(p1, max_new_tokens=8, key=k1)
        np.testing.assert_array_equal(
            h0.result().tokens, _solo(models, p0, 12, K=K, temperature=0.7, key=k0))
        np.testing.assert_array_equal(
            h1.result().tokens, _solo(models, p1, 8, K=K, temperature=0.7, key=k1))

    def test_batch_composition_independence(self, models):
        """A request's sampled tokens depend only on its own key — never on
        what else happens to share the speculative batch."""
        cfg = models[0]
        p = _prompt(3, 6, cfg)
        key = jax.random.PRNGKey(21)
        alone = _engine(models, temperature=0.7)
        ref = alone.submit(p, max_new_tokens=8, key=key).result().new_tokens
        mixed = _engine(models, temperature=0.7)
        ha = mixed.submit(p, max_new_tokens=8, key=key)
        hb = mixed.submit(_prompt(4, 9, cfg), max_new_tokens=8,
                          key=jax.random.PRNGKey(99))
        assert ha.result().new_tokens == ref
        hb.result()


#
# multi-tenancy riding along: int8 KV, LoRA-on-target, prefix sharing
#


class TestTenancy:
    @pytest.mark.slow
    def test_int8_kv_greedy_parity(self, models):
        """Greedy argmax margins dominate int8 noise at this scale, in the
        acceptance rule AND the correction token — both arenas quantized."""
        cfg = models[0]
        eng = _engine(models, kv_dtype="int8")
        p = _prompt(1, 7, cfg)
        r = eng.submit(p, max_new_tokens=10).result()
        np.testing.assert_array_equal(r.tokens, _solo(models, p, 10))

    @pytest.mark.slow
    def test_lora_on_target_parity(self, models):
        from thunder_tpu.serving.lora import gather_adapter_slots

        cfg, dcfg, tp, dp = models
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2)
        reg.register("t1", make_lora_factors(cfg, 2, jax.random.PRNGKey(10), std=0.5))
        eng = _engine(models, lora=reg)
        p = _prompt(1, 7, cfg)
        r = eng.submit(p, max_new_tokens=10, adapter_id="t1").result()
        lf = gather_adapter_slots(reg.arenas, jnp.asarray([reg.slot("t1")]))
        ref = _solo(models, p, 10, lora=lf, lora_scaling=reg.scaling)
        np.testing.assert_array_equal(r.tokens, ref)

    def test_prefix_sharing_under_speculation(self, models):
        """The draft arena shares the target pool's block tables, and a
        prefix block's draft KV holds the same tokens' draft cache — so a
        shared prefix skips BOTH prefills and still serves exact tokens."""
        cfg = models[0]
        eng = _engine(models)
        p = _prompt(5, 10, cfg)
        ha = eng.submit(p, max_new_tokens=8)
        eng.step()
        hb = eng.submit(p.copy(), max_new_tokens=8)
        eng.step()
        assert hb._req.n_shared_blocks == 2
        eng.drain()
        ref = _solo(models, p, 8)
        np.testing.assert_array_equal(ha.result(drive=False).tokens, ref)
        np.testing.assert_array_equal(hb.result(drive=False).tokens, ref)
        assert eng.pool.num_free == eng.pool.num_usable


class TestChunkedPrefill:
    @pytest.mark.slow
    def test_chunked_spec_prefill_parity(self, models):
        cfg = models[0]
        eng = _engine(models, prefill_chunk=8, prefill_buckets=(8, 16))
        p = _prompt(6, 13, cfg)
        r = eng.submit(p, max_new_tokens=8).result()
        np.testing.assert_array_equal(r.tokens, _solo(models, p, 8))
        cc = eng.stats()["compile_counts"]
        assert cc["spec_prefill_chunk"] >= 1 and cc["spec_prefill"] >= 1


#
# the paged verify path: multi-token-query kernel, purity, fallback
#


def _verify_args(eng, Bb, nbb):
    cfg, K = eng.cfg, eng.spec.K
    V = cfg.padded_vocab_size
    key = jax.random.PRNGKey(0)
    return (
        eng.params,
        jnp.zeros((Bb,), jnp.int32),
        jnp.zeros((Bb,), jnp.int32),
        jnp.zeros((Bb, nbb), jnp.int32),
        eng.pool.arenas,
        jnp.zeros((Bb, K), jnp.int32),
        jnp.zeros((Bb, K, V), jnp.float32),
        jnp.zeros((Bb, *key.shape), key.dtype),
        eng._lora_arenas(),
        jnp.zeros((Bb,), jnp.int32),
    )


def _census(eng, kind, Bb=4, nbb=8):
    """Arena gathers + all scatters in the verify program's jaxpr, skipping
    pallas kernel bodies (the test_paged_attention walk)."""
    prog, _ = eng._program(kind, Bb, nbb)
    return arena_census(eng.pool.arenas, jax.make_jaxpr(prog)(*_verify_args(eng, Bb, nbb)).jaxpr)


class TestPagedVerify:
    def test_paged_verify_parity_greedy_and_sampled(self, models, monkeypatch):
        set_attn_form(monkeypatch, "interpreted")
        cfg = models[0]
        p = _prompt(1, 7, cfg)
        eng = _engine(models)
        r = eng.submit(p, max_new_tokens=10).result()
        np.testing.assert_array_equal(r.tokens, _solo(models, p, 10))
        st = eng.stats()
        assert st["attn"]["path"] == "walk" and st["attn"]["fallback_steps"] == 0 and st["decode_steps"] > 0
        k = jax.random.PRNGKey(7)
        teng = _engine(models, temperature=0.7)
        rt = teng.submit(p, max_new_tokens=8, key=k).result()
        np.testing.assert_array_equal(
            rt.tokens, _solo(models, p, 8, temperature=0.7, key=k))

    def test_paged_verify_program_is_pure(self, models, monkeypatch):
        set_attn_form(monkeypatch, "interpreted")
        assert _census(_engine(models), "verify_paged") == (0, 0)

    def test_gather_verify_is_the_positive_control(self, models, monkeypatch):
        """The verify program built with Pallas off: the kernel's XLA form
        gathers a layer's rows and puts the chunk's among them."""
        set_attn_form(monkeypatch, "xla")
        arena_gathers, scatters = _census(_engine(models), "verify_paged")
        assert arena_gathers > 0 and scatters > 0

    def test_quantized_paged_verify_is_pure_too(self, models, monkeypatch):
        set_attn_form(monkeypatch, "interpreted")
        assert _census(_engine(models, kv_dtype="int8"), "verify_paged") == (0, 0)

    def test_without_interpret_every_round_is_a_counted_fallback(self, models, monkeypatch):
        set_attn_form(monkeypatch, "xla")
        if jax.default_backend() == "tpu":
            pytest.skip("the entry takes the kernel on TPU")
        cfg = models[0]
        eng = _engine(models)
        p = _prompt(1, 6, cfg)
        r = eng.submit(p, max_new_tokens=6).result()
        np.testing.assert_array_equal(r.tokens, _solo(models, p, 6))
        st = eng.stats()
        assert st["attn"]["path"] == "xla" and st["attn"]["fallback_steps"] == st["decode_steps"] > 0
        assert "verify_paged" in st["compile_counts"] and "verify" not in st["compile_counts"]

    @pytest.mark.parametrize("storage", ["bfloat16", "int8", "fp8"])
    def test_the_xla_form_of_verify_is_the_kernel_and_the_reference(self, storage, monkeypatch):
        """``paged_attn_verify``'s XLA form (T queries a row, the causal mask a
        query) against the interpreted kernel and against ``gather_dense`` + the
        plain float32 reference on the same bytes: ragged positions, one row
        with no cached token."""
        from thunder_tpu.executors import pallasex as px
        from thunder_tpu.serving.kernel_check import _ref_attend
        from thunder_tpu.serving.kv_pool import gather_dense
        from thunder_tpu.serving.quant import gather_dense_q, quantize_kv

        B, nh, ng, T, hs, bs, L, nbb, layer = 3, 4, 2, 5, 16, 4, 2, 6, 1
        keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
        rnd = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32).astype(jnp.bfloat16)
        nb = 1 + B * nbb
        k, v = rnd(nb, L, ng, bs, hs), rnd(nb, L, ng, bs, hs)
        tables = (1 + jnp.arange(B * nbb, dtype=jnp.int32)).reshape(B, nbb)
        q, fk, fv = rnd(B, nh, T, hs), rnd(B, ng, T, hs), rnd(B, ng, T, hs)
        pos = jnp.asarray([13, 0, nbb * bs - T], jnp.int32)
        ks = vs = None
        kd, vd = gather_dense(k, v, tables)
        if storage != "bfloat16":
            dt = jnp.int8 if storage == "int8" else jnp.float8_e4m3fn
            (k, ks), (v, vs) = quantize_kv(k, dt), quantize_kv(v, dt)
            kd, vd = gather_dense_q(k, v, ks, vs, tables, jnp.bfloat16)
        ref = _ref_attend(q, kd[layer], vd[layer], fk, fv, pos, None)
        call = lambda: px.paged_attn_verify(q, k, v, fk, fv, tables, pos, layer=layer, k_scale=ks, v_scale=vs)
        set_attn_form(monkeypatch, "interpreted")
        kernel = call()
        set_attn_form(monkeypatch, "xla")
        xla = call()
        assert "pallas_call" not in str(jax.make_jaxpr(call)())
        tol = 8 * float(jnp.finfo(jnp.bfloat16).eps)
        assert xla.shape == kernel.shape == (B, nh, T, hs) and xla.dtype == kernel.dtype
        assert float(jnp.max(jnp.abs(xla.astype(jnp.float32) - kernel.astype(jnp.float32)))) <= tol
        assert float(jnp.max(jnp.abs(xla.astype(jnp.float32) - ref))) <= tol


#
# fault injection + recovery: the chain must survive bit-identically
#


class TestFaults:
    def test_spec_fault_points_registered(self):
        assert FP_DRAFT in FAULT_POINTS and FP_VERIFY in FAULT_POINTS
        assert FP_DRAFT == "draft.dispatch" and FP_VERIFY == "verify.dispatch"

    @pytest.mark.parametrize("point", [FP_DRAFT, FP_VERIFY])
    def test_transient_fault_retries_in_place(self, models, point):
        cfg = models[0]
        eng = _engine(models, temperature=0.7,
                      fault_plan=FaultPlan(specs=[FaultSpec(point=point, at=3)]))
        p = _prompt(1, 7, cfg)
        k = jax.random.PRNGKey(11)
        r = eng.submit(p, max_new_tokens=10, key=k).result()
        np.testing.assert_array_equal(
            r.tokens, _solo(models, p, 10, temperature=0.7, key=k))
        assert eng.recoveries == 0
        assert eng.stats()["faults"]["injected"] == 1

    @pytest.mark.parametrize("point", [FP_DRAFT, FP_VERIFY])
    def test_oom_triggers_recovery_bit_identical(self, models, point):
        """Re-prefill recovery rebuilds BOTH arenas; the replay writes the
        same draft KV the live run wrote (the attended slots always hold
        emitted tokens' draft cache), so sampled streams continue exactly."""
        cfg = models[0]
        eng = _engine(models, temperature=0.7,
                      fault_plan=FaultPlan(
                          specs=[FaultSpec(point=point, kind="oom", at=3)]))
        p = _prompt(1, 7, cfg)
        k = jax.random.PRNGKey(11)
        r = eng.submit(p, max_new_tokens=10, key=k).result()
        np.testing.assert_array_equal(
            r.tokens, _solo(models, p, 10, temperature=0.7, key=k))
        assert eng.recoveries == 1

    @pytest.mark.slow
    def test_seeded_chaos_soak_bit_identical(self, models):
        """Seeded random faults across every point; after the dust settles,
        every surviving stream equals its solo run bit-for-bit."""
        cfg = models[0]
        eng = _engine(models, temperature=0.7,
                      fault_plan=FaultPlan(seed=0, rate=0.05, max_faults=6))
        subs = []
        for i in range(6):
            p = _prompt(30 + i, 5 + (i % 3), cfg)
            k = jax.random.PRNGKey(100 + i)
            subs.append((p, k, eng.submit(p, max_new_tokens=10, key=k)))
        for p, k, h in subs:
            r = h.result()
            assert r.finish_reason == "length"
            np.testing.assert_array_equal(
                r.tokens, _solo(models, p, 10, temperature=0.7, key=k))


#
# program-set discipline + the off path
#


class TestProgramSet:
    def test_compile_counts_within_bucket_bound(self, models):
        cfg = models[0]
        eng = _engine(models)
        for i, n in enumerate((4, 7, 11)):
            eng.submit(_prompt(40 + i, n, cfg), max_new_tokens=6)
        eng.drain()
        st = eng.stats()
        assert sum(st["compile_counts"].values()) <= st["bucket_bound"]

    def test_off_path_is_byte_identical(self, models):
        """speculative=None: the engine compiles the exact programs a
        spec-free world compiles (module cache gains nothing on the second
        build) and serves the exact tokens."""
        from thunder_tpu.serving.engine import _program_cache

        cfg, dcfg, tp, dp = models
        p = _prompt(1, 6, cfg)

        def plain():
            return tt.serve(None, tp, cfg, **BUCKETS, block_size=4,
                            num_blocks=64, max_batch=4, cache_dtype=jnp.float32)

        e1 = plain()
        ref = e1.submit(p, max_new_tokens=5).result().new_tokens
        n_progs = len(_program_cache)
        assert "spec" not in e1.stats()
        e2 = plain()
        r = e2.submit(p, max_new_tokens=5).result()
        assert len(_program_cache) == n_progs          # same cache keys: hits
        assert r.new_tokens == ref
        solo = np.asarray(gen.generate(tp, jnp.asarray(p)[None], cfg, 5,
                                       cache_dtype=jnp.float32))[0]
        np.testing.assert_array_equal(r.tokens, solo)


#
# observability: acceptance histogram, counters, flight lane
#


class TestObservability:
    def test_spec_stats_and_metrics(self, models):
        cfg = models[0]
        eng = _engine(models)
        eng.submit(_prompt(1, 7, cfg), max_new_tokens=10)
        eng.drain()
        st = eng.stats()["spec"]
        assert st["K"] == 2
        # one histogram entry per (live row, round); one request → equal
        assert sum(st["accept_len_hist"].values()) == st["rounds"] > 0
        assert set(st["accept_len_hist"]) == {1, 2, 3}
        assert 0.0 <= st["acceptance_rate"] <= 1.0
        assert 1.0 <= st["tokens_per_round"] <= 3.0
        snap = tt.metrics_snapshot()
        assert snap["serving.spec.rounds"] >= st["rounds"]
        assert snap["serving.spec.accept_len"]["count"] >= st["rounds"]

    def test_flight_recorder_tags_spec_rounds(self, models):
        cfg = models[0]
        eng = _engine(models, flight_recorder=True)
        eng.submit(_prompt(1, 7, cfg), max_new_tokens=8)
        eng.drain()
        recs = [r for r in eng._flight.events() if r.get("kind") == "decode"
                and r.get("spec")]
        assert recs and all(len(r["accept_len"]) >= 1 for r in recs)
        lane = eng._flight_state()["lanes"]["speculative"]
        assert lane["K"] == 2 and lane["rounds"] > 0
        assert isinstance(lane["chained"], bool)


#
# occupancy soak (slow): sustained mixed traffic at max_batch=8
#


@pytest.mark.slow
class TestSoak:
    def test_occupancy8_mixed_traffic_bit_identical(self, models):
        cfg = models[0]
        eng = _engine(models, max_batch=8, batch_buckets=(8,), num_blocks=128,
                      temperature=0.7)
        subs = []
        for i in range(10):
            p = _prompt(60 + i, 4 + (i % 5), cfg)
            k = jax.random.PRNGKey(200 + i)
            subs.append((p, k, eng.submit(p, max_new_tokens=12, key=k)))
        for p, k, h in subs:
            np.testing.assert_array_equal(
                h.result().tokens, _solo(models, p, 12, temperature=0.7, key=k))
        st = eng.stats()
        assert st["spec"]["rounds"] > 0
        assert sum(st["compile_counts"].values()) <= st["bucket_bound"]


class TestDraftKvDtype:
    """``SpecConfig(draft_kv_dtype=)``: the draft arena quantizes
    independently of the target arena (the draft's K/V is soft state — its
    numerics only shape *proposals*, never emitted tokens, so an int8
    draft over a float32 target must stay bit-identical to the all-float32
    solo rule)."""

    @staticmethod
    def _spec_engine(models, *, K, draft_kv_dtype=None, **kw):
        cfg, dcfg, tp, dp = models
        kw.setdefault("block_size", 4)
        kw.setdefault("num_blocks", 64)
        kw.setdefault("max_batch", 4)
        kw.setdefault("cache_dtype", jnp.float32)
        for k, v in BUCKETS.items():
            kw.setdefault(k, v)
        spec = SpecConfig(dp, dcfg, K=K, draft_kv_dtype=draft_kv_dtype)
        return tt.serve(None, tp, cfg, speculative=spec, **kw)

    def test_int8_draft_f32_target_parity(self, models):
        cfg = models[0]
        eng = self._spec_engine(models, K=3, draft_kv_dtype="int8")
        assert str(eng.draft_pool.kv_dtype) == "int8"
        assert eng.pool.quantized_kv is False            # target untouched
        p = _prompt(3, 7, cfg)
        r = eng.submit(p, max_new_tokens=10).result()
        np.testing.assert_array_equal(r.tokens, _solo(models, p, 10, K=3))
        assert eng.stats()["spec"]["rounds"] > 0

    def test_draft_dtype_is_program_identity(self, models):
        """Two engines differing only in draft_kv_dtype must not alias
        programs in the shared module cache (the draft gather/scatter
        dtype is baked into the compiled round)."""
        a = self._spec_engine(models, K=2)
        b = self._spec_engine(models, K=2, draft_kv_dtype="int8")
        assert a._static_key() != b._static_key()

    def test_none_means_engine_kv_dtype(self, models):
        """Unset draft_kv_dtype inherits the engine-wide kv_dtype — the
        pre-field behavior, so existing configs are untouched."""
        eng = self._spec_engine(models, K=2, kv_dtype="int8", quantized=True)
        assert str(eng.draft_pool.kv_dtype) == "int8"
