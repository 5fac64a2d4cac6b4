"""Mesh-parallel serving: sharded KV block arena + SPMD bucket programs.

The load-bearing guarantee is differential and sharded: tokens served by a
mesh engine (``tt.serve(..., mesh=...)``) must be *identical* to solo
``generate(..., mesh=mesh)`` with the same placed params on the same mesh —
greedy AND temperature, with prefix sharing active.  Program identity is
the second pillar: one compile per (mesh, bucket), shared across engines
via the module program cache, never shared across distinct device sets.

Everything runs on the conftest 8-virtual-device CPU mesh with the micro
model (1 layer, 16-wide) so the whole file stays inside the tier-1 budget.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import thunder_tpu as tt
from conftest import set_attn_form
from thunder_tpu import distributed as dist
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import ArenaMismatchError, PagedKVPool
from thunder_tpu.serving.mesh import arena_sharding, mesh_fingerprint, per_shard_bytes

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32, block_size=64,
)


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def tp2(micro):
    """A 2-device tp mesh plus the params placed the way the engine places
    them (the default llama TP×FSDP rules == ``dist.tp_fsdp``)."""
    cfg, params = micro
    mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    return mesh, dist.tp_fsdp(params, mesh)


def _engine(cfg, params, mesh, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_dtype", jnp.float32)
    return tt.serve(None, params, cfg, mesh=mesh, **kw)


def _solo_sharded(p_tp, prompt, cfg, n, mesh, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return np.asarray(
        gen.generate(p_tp, np.asarray(prompt)[None], cfg, n, mesh=mesh, **kw)
    )[0]


#
# the one spec rule (satellite): serving and generate() share it
#


class TestKVCacheSpec:
    def test_heads_over_tp_when_divisible(self, micro):
        cfg, _ = micro
        mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
        assert dist.kv_cache_spec(cfg, mesh) == P(None, None, "tp")

    def test_replicated_fallbacks(self, micro):
        cfg, _ = micro  # n_query_groups == 2
        assert dist.kv_cache_spec(cfg, None) == P()
        dp = dist.make_mesh({"dp": 2}, devices=jax.devices()[:2])
        assert dist.kv_cache_spec(cfg, dp) == P()          # no tp axis
        tp1 = dist.make_mesh({"tp": 1}, devices=jax.devices()[:1])
        assert dist.kv_cache_spec(cfg, tp1) == P()         # trivial axis
        tp8 = dist.make_mesh({"tp": 8})
        assert dist.kv_cache_spec(cfg, tp8) == P()         # 8 doesn't divide ng=2

    def test_init_cache_and_arena_share_the_rule(self, micro):
        """The dense generate() cache and the paged arena both carry the
        helper's spec (heads dim at axis 2 in both layouts)."""
        cfg, _ = micro
        mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
        cache = gen.init_cache(cfg, 1, 16, dtype=jnp.float32, mesh=mesh)
        want = NamedSharding(mesh, dist.kv_cache_spec(cfg, mesh))
        assert cache["k"].sharding.is_equivalent_to(want, cache["k"].ndim)
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.float32, mesh=mesh)
        assert pool.arena_sharding == arena_sharding(cfg, mesh)
        assert pool.k_arena.sharding.is_equivalent_to(want, pool.k_arena.ndim)


#
# sharded pool: placement + the update_arenas validation satellite
#


class TestMeshedPool:
    def test_arena_bytes_split_across_shards(self, micro):
        cfg, _ = micro
        mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
        pool = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32, mesh=mesh)
        assert pool.per_shard_bytes() == pool.k_arena.nbytes // 2
        solo = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32)
        assert solo.per_shard_bytes() == solo.k_arena.nbytes
        assert per_shard_bytes(np.zeros((4, 2), np.float32)) == 32  # no shards attr
        snap = pool.state_snapshot()
        assert snap["arena_spec"] == "PartitionSpec(None, None, 'tp')"
        assert snap["arena_shard_bytes"] == pool.per_shard_bytes()

    def test_update_arenas_validates_shape_dtype(self, micro):
        cfg, _ = micro
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.float32)
        good_k, good_v = pool.k_arena, pool.v_arena
        with pytest.raises(ArenaMismatchError, match="k-arena.*shape") as ei:
            pool.update_arenas(jnp.zeros((1, 1)), good_v)
        assert (ei.value.arena, ei.value.field) == ("k", "shape")
        with pytest.raises(ArenaMismatchError, match="v-arena.*dtype"):
            pool.update_arenas(good_k, good_v.astype(jnp.bfloat16))
        # failed installs left the pool untouched
        assert pool.k_arena is good_k and pool.v_arena is good_v
        pool.update_arenas(good_k + 1, good_v + 1)         # matching swap works

    def test_update_arenas_validates_sharding(self, micro):
        cfg, _ = micro
        mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
        pool = PagedKVPool(cfg, num_blocks=4, block_size=4, dtype=jnp.float32, mesh=mesh)
        # same shape/dtype, but replicated instead of heads-over-tp
        repl = jax.device_put(
            jnp.zeros(pool._arena_shape, jnp.float32), NamedSharding(mesh, P())
        )
        with pytest.raises(ArenaMismatchError, match="k-arena.*sharding"):
            pool.update_arenas(repl, pool.v_arena)
        pool.update_arenas(pool.k_arena, pool.v_arena)     # self-install passes


#
# the differential guarantee + program identity
#


@pytest.fixture(scope="module")
def mesh_served(micro, tp2):
    """One mesh-engine drive shared by several assertions: two greedy
    requests with a shared block-aligned prefix (prefix sharing active),
    snapshotting stats/metrics eagerly (the autouse observability reset
    wipes the registry between tests)."""
    cfg, params = micro
    mesh, _ = tp2
    base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size
    eng = _engine(cfg, params, mesh)
    ha = eng.submit(base, max_new_tokens=4)
    eng.step()                                             # prefill A, register prefix
    hb = eng.submit(base.copy(), max_new_tokens=4)
    eng.step()                                             # admit B via shared blocks
    shared_blocks = hb._req.n_shared_blocks
    eng.drain()
    results = (ha.result(drive=False), hb.result(drive=False))
    snap = tt.metrics_snapshot()
    return cfg, base, eng, results, shared_blocks, snap


class TestMeshEngine:
    def test_greedy_parity_with_prefix_sharing(self, mesh_served, tp2):
        """Acceptance: mesh-served tokens — including a request admitted
        through shared prefix blocks — are identical to solo sharded
        generate() on the same mesh."""
        cfg, base, _, (ra, rb), shared_blocks, _ = mesh_served
        mesh, p_tp = tp2
        assert shared_blocks == 2 and rb.shared_prefix_blocks == 2
        solo = _solo_sharded(p_tp, base, cfg, 4, mesh)
        np.testing.assert_array_equal(ra.tokens, solo)
        np.testing.assert_array_equal(rb.tokens, solo)

    def test_temperature_parity(self, micro, tp2):
        """Per-request PRNG chains survive SPMD: temperature samples match
        the solo sharded run with the same key."""
        cfg, params = micro
        mesh, p_tp = tp2
        key = jax.random.PRNGKey(42)
        p = (np.arange(6) * 3 + 1).astype(np.int32) % cfg.vocab_size
        eng = _engine(cfg, params, mesh, temperature=0.7)
        h = eng.submit(p, max_new_tokens=4, key=key)
        np.testing.assert_array_equal(
            h.result().tokens,
            _solo_sharded(p_tp, p, cfg, 4, mesh, temperature=0.7, key=key),
        )

    def test_one_compile_per_mesh_bucket(self, mesh_served, micro, tp2):
        """Program identity: a second engine with the same (mesh, static
        config) reuses every bucket program (zero fresh compiles), and the
        compile count of the first stayed inside the bucket bound."""
        cfg, base, eng, *_ = mesh_served
        _, params = micro
        mesh, _ = tp2
        stats = eng.stats()
        compiles = stats["compile_counts"]
        assert sum(compiles.values()) <= stats["bucket_bound"]
        eng2 = _engine(cfg, params, mesh)
        h = eng2.submit(base, max_new_tokens=4)
        h.result()
        assert sum(eng2.compile_counts.values()) == 0

    def test_distinct_device_sets_never_share_programs(self, mesh_served, micro):
        """A same-shape mesh over different devices fingerprints — and
        therefore program-caches — differently (host-side check: no
        compile is paid)."""
        cfg, _, eng, *_ = mesh_served
        _, params = micro
        mesh_b = dist.make_mesh({"tp": 2}, devices=jax.devices()[2:4])
        eng_b = _engine(cfg, params, mesh_b)
        assert mesh_fingerprint(mesh_b) != mesh_fingerprint(eng.mesh)
        assert eng_b._static_key() != eng._static_key()
        # solo engines ignore the mesh component entirely
        solo = tt.serve(None, params, cfg, block_size=4, num_blocks=32,
                        cache_dtype=jnp.float32)
        assert solo._mesh_key is None and None in solo._static_key()

    def test_mesh_observability(self, mesh_served):
        """stats()['mesh'], the flight-state snapshot, and serving.mesh.*
        gauges all report the mesh shape, per-shard arena bytes, and the
        decode collective census."""
        _, _, eng, _, _, snap = mesh_served
        m = eng.stats()["mesh"]
        assert m["axes"] == {"tp": 2} and m["devices"] == 2
        # K+V total over 2 shards: one device holds a quarter of the bytes
        assert m["arena_shard_bytes"] == m["arena_total_bytes"] // 4
        # the decode program crosses devices: >=1 all-reduce (wo projection)
        assert m["collectives_decode"]["total"] >= 1
        assert m["collectives_decode"].get("all-reduce", 0) >= 1
        flight = eng._flight_state()
        assert flight["engine"]["mesh"]["collectives_decode"] == m["collectives_decode"]
        assert flight["pool"]["arena_shard_bytes"] == m["arena_shard_bytes"]
        assert snap["serving.mesh.devices"] == 2
        assert snap["serving.mesh.axis.tp"] == 2
        assert snap["serving.mesh.arena_shard_bytes"] == m["arena_shard_bytes"]
        assert snap["serving.mesh.collectives.decode"] == m["collectives_decode"]["total"]

    def test_shardings_requires_mesh(self, micro):
        cfg, params = micro
        with pytest.raises(ValueError, match="requires mesh"):
            tt.serve(None, params, cfg, shardings={"any": None})

    def test_int8_arena_shards_scales_by_the_same_rule(self, micro, tp2):
        """Quantized mesh serving: the int8 data arenas AND their float32
        scale arenas carry the one kv_cache_spec placement (heads dim at
        axis 2 in both ranks), and mesh-served int8 tokens still match
        solo sharded f32 generate() exactly (greedy margins dominate the
        quantization noise at micro shapes)."""
        cfg, params = micro
        mesh, p_tp = tp2
        pool = PagedKVPool(cfg, num_blocks=8, block_size=4, dtype=jnp.float32,
                           kv_dtype="int8", mesh=mesh)
        want = NamedSharding(mesh, dist.kv_cache_spec(cfg, mesh))
        assert pool.k_arena.dtype == jnp.int8
        assert pool.k_arena.sharding.is_equivalent_to(want, pool.k_arena.ndim)
        assert pool.k_scale.sharding.is_equivalent_to(want, pool.k_scale.ndim)
        assert pool.per_shard_bytes() == pool.k_arena.nbytes // 2
        eng = _engine(cfg, params, mesh, kv_dtype="int8")
        base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size
        r = eng.submit(base, max_new_tokens=4).result()
        np.testing.assert_array_equal(r.tokens, _solo_sharded(p_tp, base, cfg, 4, mesh))
        # the donated update preserved the scale placement
        assert eng.pool.k_scale.sharding.is_equivalent_to(want, eng.pool.k_scale.ndim)


class TestMeshPagedAttention:
    """The paged kernels under SPMD (ISSUE 13): they run shard_map-local over
    tp with heads-local specs matching kv_cache_spec (here interpreted), and
    mesh-served tokens stay identical to the XLA form's."""

    def _drive(self, cfg, params, mesh, **kw):
        eng = _engine(cfg, params, mesh, max_batch=2, **kw)
        prompts = [(np.arange(n) * 5 + 2).astype(np.int32) % cfg.vocab_size
                   for n in (3, 8)]
        hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.drain()
        return [tuple(h.result(drive=False).tokens) for h in hs], eng

    def test_paged_parity_on_mesh(self, micro, tp2, monkeypatch):
        cfg, params = micro
        mesh, _ = tp2
        set_attn_form(monkeypatch, "xla")
        tg, eng = self._drive(cfg, params, mesh)
        assert eng.stats()["attn"]["path"] == "xla" and eng.stats()["attn"]["fallback_steps"] > 0
        set_attn_form(monkeypatch, "interpreted")
        tp_, eng = self._drive(cfg, params, mesh)
        assert tg == tp_
        st = eng.stats()
        assert st["attn"]["path"] == "walk" and st["attn"]["fallback_steps"] == 0 and st["decode_steps"] > 0

    def test_kv_chunk_tokens_follow_the_local_arena(self, micro, tp2, monkeypatch):
        """stats()["attn"]["kv_chunk_tokens"] is derived from the arena shard
        the kernel is handed under shard_map: half the KV groups a device,
        twice the table entries a chunk where the byte budget binds."""
        from thunder_tpu.executors import pallasex as px

        cfg, params = micro
        mesh, _ = tp2
        set_attn_form(monkeypatch, "interpreted")
        one = _engine(cfg, params, None, max_batch=2)
        _, _, ng, bs, hs = one.pool.k_arena.shape
        monkeypatch.setattr(px, "_PAGED_CHUNK_BYTES", 4 * (2 * 2 * ng * bs * hs * 4))
        for m, blocks in ((None, 4), (mesh, 8)):
            eng = _engine(cfg, params, m, max_batch=2)
            assert eng.stats()["attn"]["kv_chunk_tokens"] == blocks * bs

    def test_paged_int8_parity_on_mesh(self, micro, tp2, monkeypatch):
        cfg, params = micro
        mesh, _ = tp2
        set_attn_form(monkeypatch, "xla")
        tg, _ = self._drive(cfg, params, mesh, kv_dtype="int8")
        set_attn_form(monkeypatch, "interpreted")
        tp_, _ = self._drive(cfg, params, mesh, kv_dtype="int8")
        assert tg == tp_

    def test_unshardable_heads_take_the_xla_form(self, tp2, attn_form):
        """tp=2 with n_query_groups=1: kv_cache_spec degrades to replicated,
        which shard_map specs that split heads would disagree with.  The
        attention call is then the XLA form on the arrays as GSPMD holds them
        (counted: ``path`` "xla") and the writers run on each device's whole
        copy; the tokens are solo ``generate()``'s."""
        mesh, _ = tp2
        cfg = llama.Config.from_name(
            "tiny-llama-debug", n_layer=1, n_head=3, n_query_groups=1,
            n_embd=24, intermediate_size=32, vocab_size=32, block_size=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = tt.serve(None, params, cfg, mesh=mesh, block_size=4, num_blocks=16,
                       max_batch=2, cache_dtype=jnp.float32)
        prompt = (np.arange(6) * 5 + 2).astype(np.int32) % cfg.vocab_size
        got = eng.submit(prompt, max_new_tokens=5).result().tokens
        st = eng.stats()
        assert st["attn"]["path"] == "xla" and st["attn"]["fallback_steps"] == st["decode_steps"] > 0
        solo = np.asarray(gen.generate(params, prompt[None], cfg, 5, cache_dtype=jnp.float32))[0]
        np.testing.assert_array_equal(got, solo)
