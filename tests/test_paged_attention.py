"""Paged-attention decode: Pallas kernel over the KV block arena (ISSUE 13).

The load-bearing guarantee is differential and bit-exact at the token
level: an engine with ``attn="paged"`` (flash-decoding kernel reading K/V
straight from the block arena) must serve tokens identical to
``attn="gather"`` (dense gather/scatter round-trip) and to solo
``generate()`` — greedy AND temperature, int8/fp8 KV, LoRA mixes, chunked
prefill, prefix sharing, and fault-recovery replay.  Logits are only
ulp-close (online vs full softmax reorder), so every assertion here
compares tokens, never arena bytes.

The second pillar is structural: the compiled ``decode_paged`` program
must contain **zero** arena-sized gather primitives and zero scatters
(asserted on the jaxpr, with the gather program as positive control), and
physical block 0 (the sink / table padding target) must be dead weight —
poisoning it mid-run changes nothing on either path.

Everything runs on CPU with the kernels in Pallas interpret mode
(``attn="paged"`` forces the kernel regardless of backend), so tier-1
exercises the real kernel math, not a stand-in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, FaultPlan, FaultSpec, make_lora_factors
from thunder_tpu.serving.faults import FP_DECODE
from thunder_tpu.serving.kernel_check import _ref_attend
from thunder_tpu.serving.kv_pool import gather_dense
from thunder_tpu.serving.lora import valid_targets
from thunder_tpu.serving.paged_attention import paged_supported
from thunder_tpu.serving.quant import gather_dense_q, quantize_kv

# 2 layers (layer-indexed arena reads), GQA 4:2 (in-kernel q-group
# replication), tiny widths so interpret-mode kernels stay cheap
MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(6,), prefill_buckets=(16,))

_FP8 = getattr(jnp, "float8_e4m3fn", None)


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


def _prompts(cfg, lens=(3, 5, 9, 14), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in lens]


def _drive(eng, prompts, n=5, keys=None, **submit_kw):
    handles = []
    for i, p in enumerate(prompts):
        kw = dict(submit_kw)
        if keys is not None:
            kw["key"] = keys[i]
        handles.append(eng.submit(p, max_new_tokens=n, **kw))
    eng.drain()
    return [tuple(h.result(drive=False).tokens) for h in handles]


def _both(cfg, params, prompts, n=5, keys=None, engine_kw=None, submit_kw=None):
    """Tokens from a gather engine and a paged engine, same workload."""
    engine_kw = engine_kw or {}
    submit_kw = submit_kw or {}
    tg = _drive(_engine(cfg, params, attn="gather", **engine_kw), prompts, n,
                keys=keys, **submit_kw)
    tp = _drive(_engine(cfg, params, attn="paged", **engine_kw), prompts, n,
                keys=keys, **submit_kw)
    return tg, tp


#
# differential parity: the acceptance bar
#


class TestPagedParity:
    def test_greedy_vs_gather_and_solo(self, micro):
        cfg, params = micro
        prompts = _prompts(cfg)
        tg, tp = _both(cfg, params, prompts)
        assert tg == tp
        for p, t in zip(prompts, tp):
            solo = np.asarray(
                gen.generate(params, np.asarray(p)[None], cfg, 5,
                             cache_dtype=jnp.float32))[0]
            assert tuple(solo) == t

    def test_temperature_with_request_keys(self, micro):
        cfg, params = micro
        prompts = _prompts(cfg, lens=(4, 11))
        keys = [jax.random.PRNGKey(42), jax.random.PRNGKey(7)]
        tg, tp = _both(cfg, params, prompts, keys=keys,
                       engine_kw=dict(temperature=0.7))
        assert tg == tp

    def test_int8_kv(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg), engine_kw=dict(kv_dtype="int8"))
        assert tg == tp

    @pytest.mark.skipif(_FP8 is None, reason="jax build lacks float8_e4m3fn")
    def test_fp8_kv(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(3, 7)),
                       engine_kw=dict(kv_dtype="fp8", max_batch=2))
        assert tg == tp

    def test_lora_mix_with_mlp_targets(self, micro):
        cfg, params = micro
        targets = ("wq", "wk", "wv", "wo", "fc_1", "fc_2", "proj")

        def serve_one(attn):
            reg = AdapterRegistry(cfg, rank=2, max_adapters=2, targets=targets)
            reg.register("alice", make_lora_factors(
                cfg, 2, jax.random.PRNGKey(9), targets, std=0.5))
            eng = _engine(cfg, params, lora=reg, attn=attn)
            prompts = _prompts(cfg, lens=(3, 6, 10))
            hs = [eng.submit(prompts[0], max_new_tokens=5, adapter_id="alice"),
                  eng.submit(prompts[1], max_new_tokens=5),
                  eng.submit(prompts[2], max_new_tokens=5, adapter_id="alice")]
            eng.drain()
            return [tuple(h.result(drive=False).tokens) for h in hs]

        assert serve_one("gather") == serve_one("paged")

    def test_chunked_prefill(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(13, 14, 9)),
                       engine_kw=dict(prefill_chunk=8, prefill_buckets=(8, 16)))
        assert tg == tp

    def test_prefix_sharing(self, micro):
        cfg, params = micro
        base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size

        def serve_one(attn):
            eng = _engine(cfg, params, attn=attn, max_batch=2)
            ha = eng.submit(base, max_new_tokens=4)
            eng.step()                               # prefill A, register prefix
            hb = eng.submit(base.copy(), max_new_tokens=4)
            eng.step()                               # admit B via shared blocks
            eng.drain()
            ra, rb = ha.result(drive=False), hb.result(drive=False)
            assert rb.shared_prefix_blocks == 2      # sharing actually happened
            return tuple(ra.tokens), tuple(rb.tokens)

        assert serve_one("gather") == serve_one("paged")

    def test_fault_recovery_replay(self, micro):
        """Re-prefill recovery rebuilds the arena, then decode resumes on
        the kernel path — tokens still match the fault-free gather run."""
        cfg, params = micro
        p = (np.arange(6) * 3 + 1).astype(np.int32) % cfg.vocab_size
        ref = _drive(_engine(cfg, params, attn="gather"), [p], n=8)
        eng = _engine(
            cfg, params, attn="paged",
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]),
        )
        got = _drive(eng, [p], n=8)
        assert got == ref
        assert eng.recoveries == 1

    def test_sliding_window(self):
        cfg = llama.Config.from_name("tiny-llama-debug", **MICRO, sliding_window=5)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(3, 9)), n=8)
        assert tg == tp


#
# the decode kernel's walk: chunks of C table blocks, from the row's first
# live block to its last, against the jnp reference on the same bytes
#

W_BS, W_HS, W_REP, W_L, W_NBB, W_C = 4, 16, 2, 2, 8, 3
W_LAYER = 1
# contexts at every edge of a block and of a chunk (C * bs = 12 keys), the
# empty one (the fresh token alone) and a full table
W_CONTEXTS = (0, 1, W_BS - 1, W_BS, W_C * W_BS - 1, W_C * W_BS, W_C * W_BS + 1,
              W_NBB * W_BS)


@pytest.fixture
def small_chunks(monkeypatch):
    """C = 3 at the tiny shapes below, so a table of 8 blocks is three chunks."""
    monkeypatch.setattr(px, "_PAGED_CHUNK_KEYS", W_C * W_BS)
    assert px.paged_kv_chunk_blocks(2, W_BS, W_HS, 4) == W_C


def _walk_inputs(ng, B=1, nbb=W_NBB, seed=0):
    """Random float32 arenas in which row ``i`` owns blocks ``1 + i*nbb ...``
    (block 0 is the sink), queries and fresh K/V."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    rnd = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)
    nb = 1 + B * nbb
    arenas = rnd(nb, W_L, ng, W_BS, W_HS), rnd(nb, W_L, ng, W_BS, W_HS)
    tables = (1 + jnp.arange(B * nbb, dtype=jnp.int32)).reshape(B, nbb)
    q, fk, fv = rnd(B, ng * W_REP, W_HS), rnd(B, ng, W_HS), rnd(B, ng, W_HS)
    return arenas, tables, q, fk, fv


def _decode(q, k, v, fk, fv, tables, pos, window=None, ks=None, vs=None):
    return px.paged_attn_decode(q, k, v, fk, fv, tables, jnp.asarray(pos, jnp.int32),
                                layer=W_LAYER, window=window, k_scale=ks, v_scale=vs)


def _reference(q, kd, vd, fk, fv, pos, window=None):
    ref = _ref_attend(q[:, :, None], kd[W_LAYER], vd[W_LAYER], fk[:, :, None],
                      fv[:, :, None], jnp.asarray(pos, jnp.int32), window)
    return ref[:, :, 0]


_TOL = 8 * float(jnp.finfo(jnp.float32).eps)     # serving.kernel_check's bound


class TestDecodeWalk:
    @pytest.mark.parametrize("context", W_CONTEXTS)
    def test_block_and_chunk_edges(self, small_chunks, context):
        (k, v), tables, q, fk, fv = _walk_inputs(ng=2)
        got = _decode(q, k, v, fk, fv, tables, [context])
        ref = _reference(q, *gather_dense(k, v, tables), fk, fv, [context])
        assert float(jnp.max(jnp.abs(got - ref))) <= _TOL

    # (window, context): the window's first slot falls inside a block of the
    # first chunk; two chunks remain; the window holds the fresh token alone;
    # the window is wider than the context
    @pytest.mark.parametrize("window,context", [(10, 23), (18, 30), (6, 32),
                                                (1, 9), (64, 17)])
    def test_window_cuts_inside_a_chunk(self, small_chunks, window, context):
        (k, v), tables, q, fk, fv = _walk_inputs(ng=2, seed=1)
        got = _decode(q, k, v, fk, fv, tables, [context], window=window)
        ref = _reference(q, *gather_dense(k, v, tables), fk, fv, [context], window)
        assert float(jnp.max(jnp.abs(got - ref))) <= _TOL

    @pytest.mark.parametrize("context", (W_BS - 1, W_C * W_BS + 1, W_NBB * W_BS))
    @pytest.mark.parametrize("storage", ["int8", pytest.param(
        "fp8", marks=pytest.mark.skipif(_FP8 is None, reason="no float8_e4m3fn"))])
    def test_quantized_arenas(self, small_chunks, storage, context):
        (k, v), tables, q, fk, fv = _walk_inputs(ng=2, seed=2)
        dt = jnp.int8 if storage == "int8" else _FP8
        (kq, ks), (vq, vs) = quantize_kv(k, dt), quantize_kv(v, dt)
        got = _decode(q, kq, vq, fk, fv, tables, [context], ks=ks, vs=vs)
        kd, vd = gather_dense_q(kq, vq, ks, vs, tables, jnp.float32)
        ref = _reference(q, kd, vd, fk, fv, [context])
        assert float(jnp.max(jnp.abs(got - ref))) <= _TOL

    @pytest.mark.parametrize("ng", [1, 2, 8])
    def test_kv_groups(self, small_chunks, ng):
        (k, v), tables, q, fk, fv = _walk_inputs(ng=ng, B=2, seed=3)
        pos = [W_C * W_BS + 2, 5]
        got = _decode(q, k, v, fk, fv, tables, pos)
        ref = _reference(q, *gather_dense(k, v, tables), fk, fv, pos)
        assert float(jnp.max(jnp.abs(got - ref))) <= _TOL

    def test_row_is_bit_identical_alone_batched_and_under_a_wider_table(
            self, small_chunks):
        """A row's output depends on its own table, position and queries
        only: not on the rows beside it, nor on the bucket's table width."""
        (k, v), tables, q, fk, fv = _walk_inputs(ng=2, B=4, seed=4)
        pos = np.array([W_C * W_BS + 5, 2, W_NBB * W_BS, 0], np.int32)
        batched = np.asarray(_decode(q, k, v, fk, fv, tables, pos, window=20))
        wide = jnp.concatenate([tables, jnp.zeros_like(tables)], axis=1)  # sink-padded
        widened = np.asarray(_decode(q, k, v, fk, fv, wide, pos, window=20))
        for i in range(4):
            alone = np.asarray(_decode(q[i:i + 1], k, v, fk[i:i + 1], fv[i:i + 1],
                                       tables[i:i + 1], pos[i:i + 1], window=20))
            assert np.array_equal(alone[0], batched[i]), i
            assert np.array_equal(alone[0], widened[i]), i

    @pytest.mark.parametrize("ng", [1, 2])
    @pytest.mark.parametrize("storage", ["plain", "int8", pytest.param(
        "fp8", marks=pytest.mark.skipif(_FP8 is None, reason="no float8_e4m3fn"))])
    def test_narrow_heads_go_block_by_block(self, monkeypatch, storage, ng):
        """Where the walk cannot be compiled (on the TPU: a head size that is
        not whole 128-lane tiles) the token rides as query 0 of a verify
        chunk; the same attention, here interpreted."""
        monkeypatch.setattr(px, "paged_walk_lanes_ok", lambda lanes: False)
        assert px.paged_kv_chunk_blocks(ng, W_BS, W_HS, 4) == 1
        (k, v), tables, q, fk, fv = _walk_inputs(ng=ng, B=3, seed=5)
        pos, ks, vs = [W_C * W_BS + 2, 0, W_NBB * W_BS], None, None
        kd, vd = gather_dense(k, v, tables)
        if storage != "plain":
            dt = jnp.int8 if storage == "int8" else _FP8
            (k, ks), (v, vs) = quantize_kv(k, dt), quantize_kv(v, dt)
            kd, vd = gather_dense_q(k, v, ks, vs, tables, jnp.float32)
        got = _decode(q, k, v, fk, fv, tables, pos, ks=ks, vs=vs)
        assert float(jnp.max(jnp.abs(got - _reference(q, kd, vd, fk, fv, pos)))) <= _TOL
        with pytest.raises(NotImplementedError, match="sliding window"):
            _decode(q, k, v, fk, fv, tables, pos, window=8, ks=ks, vs=vs)

    def test_chunk_follows_the_shapes_not_the_table(self):
        # offline-batch's shapes: 8 groups of 16 x 128 bfloat16 -> 16 blocks,
        # 256 keys, 1 MiB of K+V a chunk; int8 and a tp=4 shard reach the key cap
        assert px.paged_kv_chunk_blocks(8, 16, 128, 2) == 16
        assert px.paged_kv_chunk_blocks(8, 16, 128, 1) == 32
        assert px.paged_kv_chunk_blocks(2, 16, 128, 2) == 32
        assert px.paged_kv_chunk_blocks(8, 256, 128, 4) == 1


#
# sink-block hygiene (satellite): physical block 0 is dead weight
#


class TestSinkBlockHygiene:
    @pytest.mark.parametrize("attn", ["gather", "paged"])
    def test_tokens_invariant_to_block0_garbage(self, micro, attn):
        """Block 0 backs every table's padding; neither decode path may
        ever read it into scores.  Poison it mid-run: tokens unchanged."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(3, 7))
        ref = _drive(_engine(cfg, params, attn=attn, max_batch=2), prompts, n=6)

        eng = _engine(cfg, params, attn=attn, max_batch=2, async_step=False)
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(3):
            eng.step()                                # past prefill, mid-decode
        arenas = dict(eng.pool.arenas)
        arenas["k"] = arenas["k"].at[0].set(997.0)
        arenas["v"] = arenas["v"].at[0].set(-997.0)
        eng.pool.set_arenas(arenas)
        eng.drain()
        got = [tuple(h.result(drive=False).tokens) for h in handles]
        assert got == ref


#
# structural: the paged decode program really is gather/scatter-free
#


def _prim_names(jaxpr, *, skip=("pallas_call",)):
    """All primitive names in a jaxpr, recursing into sub-jaxprs (pjit,
    custom_vjp, scan, ...) but not into pallas kernel bodies."""
    names = []
    for eqn in jaxpr.eqns:
        names.append((eqn.primitive.name, eqn))
        if eqn.primitive.name in skip:
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                names.extend(_prim_names(sub, skip=skip))
            elif hasattr(v, "eqns"):
                names.extend(_prim_names(v, skip=skip))
    return names


def _decode_args(eng, Bb, nbb):
    cfg = eng.cfg
    key = jax.random.PRNGKey(0)
    return (
        eng.params,
        jnp.zeros((Bb,), jnp.int32),
        jnp.zeros((Bb,), jnp.int32),
        jnp.zeros((Bb, nbb), jnp.int32),
        eng.pool.arenas,
        jnp.zeros((Bb, *key.shape), key.dtype),
        eng._lora_arenas(),
        jnp.zeros((Bb,), jnp.int32),
    )


def _census(eng, kind, Bb=4, nbb=4):
    prog, _ = eng._program(kind, Bb, nbb)
    jaxpr = jax.make_jaxpr(prog)(*_decode_args(eng, Bb, nbb)).jaxpr
    arena_shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(eng.pool.arenas)}
    arena_gathers = scatters = 0
    for name, eqn in _prim_names(jaxpr):
        if name == "gather" and tuple(eqn.invars[0].aval.shape) in arena_shapes:
            arena_gathers += 1
        if name.startswith("scatter"):
            scatters += 1
    return arena_gathers, scatters


class TestProgramPurity:
    def test_paged_decode_has_zero_arena_gathers_and_scatters(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, attn="paged")
        assert _census(eng, "decode_paged") == (0, 0)

    def test_gather_decode_is_the_positive_control(self, micro):
        """The same census on the gather program finds both op families —
        proving the walk actually sees through pjit into the program."""
        cfg, params = micro
        eng = _engine(cfg, params, attn="gather")
        arena_gathers, scatters = _census(eng, "decode")
        assert arena_gathers > 0 and scatters > 0

    def test_quantized_paged_program_is_pure_too(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, attn="paged", kv_dtype="int8")
        assert _census(eng, "decode_paged") == (0, 0)


#
# knob resolution + observability
#


class TestAttnKnob:
    def test_paged_stats_counters_and_census(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, attn="paged")
        _drive(eng, _prompts(cfg, lens=(3, 5)), n=4)
        st = eng.stats()["attn"]
        assert st["mode"] == "paged" and st["requested"] == "paged"
        assert st["fallback_reason"] is None
        assert st["kernel_steps"] > 0 and st["fallback_steps"] == 0
        # what walk the run measured: at these tiny widths the cap of 512
        # keys a chunk binds, not the byte budget
        assert st["kv_chunk_tokens"] == 512
        # the module program cache may satisfy this engine's decode_paged
        # program from an earlier engine; the census key exists either way
        assert "decode_paged" in eng.compile_counts
        assert eng.compile_counts["decode"] == 0
        snap = tt.metrics_snapshot()
        assert snap["serving.attn.kernel_steps"] == st["kernel_steps"]

    def test_gather_mode_counts_nothing(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, attn="gather")
        _drive(eng, _prompts(cfg, lens=(3,)), n=4)
        st = eng.stats()["attn"]
        assert st["mode"] == "gather" and st["requested"] == "gather"
        assert st["kernel_steps"] == 0 and st["fallback_steps"] == 0
        assert st["fallback_reason"] is None and st["kv_chunk_tokens"] is None

    def test_auto_falls_back_on_cpu_and_counts(self, micro, monkeypatch):
        """Without THUNDER_TPU_PALLAS_INTERPRET=1, auto on CPU keeps the
        gather path (tier-1 speed) and counts every decode as a fallback."""
        monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
        cfg, params = micro
        eng = _engine(cfg, params, attn="auto")
        _drive(eng, _prompts(cfg, lens=(3,)), n=4)
        st = eng.stats()["attn"]
        assert st["mode"] == "gather" and st["requested"] == "auto"
        assert st["fallback_reason"]
        assert st["fallback_steps"] > 0
        assert tt.metrics_snapshot()["serving.attn.fallback_steps"] == st["fallback_steps"]

    def test_forced_paged_rejects_custom_model_fn(self, micro):
        cfg, params = micro
        with pytest.raises(ValueError, match="custom model_fn"):
            tt.serve(lambda *a, **k: None, params, cfg, block_size=4,
                     num_blocks=16, max_batch=2, cache_dtype=jnp.float32,
                     attn="paged")

    def test_invalid_knob_value(self, micro):
        cfg, params = micro
        with pytest.raises(ValueError, match="attn="):
            _engine(cfg, params, attn="fancy")

    @pytest.mark.parametrize("attn", ["auto", "paged"])
    @pytest.mark.parametrize("hs", [64, 96])
    def test_narrow_windowed_heads_take_the_gather_path_on_tpu(self, hs, attn, monkeypatch):
        """Compiled for the TPU the decode walk cannot copy arena slabs whose
        rows are not whole 128-lane tiles (test_pallas_tpu_lowering holds the
        compiler to that), and the per-block kernel that serves such arenas has
        no sliding window: a model with both resolves to the gather path when
        the engine is built, with a counted reason, and an explicit
        attn="paged" is refused there, not at the first decode step.  A head
        of 96 is such an arena always; a head of 64 where its KV heads cannot
        lie two to a row: a quantised arena here (at the compute dtype the pool
        packs them, and the walk takes the window)."""
        def cfg_of(**kw):
            return llama.Config.from_name("tiny-llama-debug", **{
                **MICRO, "n_head": 2, "n_query_groups": 2, "n_embd": 2 * hs, **kw})

        cfg = cfg_of(sliding_window=8)
        assert cfg.head_size == hs
        store = {"kv_dtype": "int8"} if hs == 64 else {}
        monkeypatch.setattr(px, "_interpret", lambda: False)   # as on the chip
        ok, why = paged_supported(cfg, True, arena_lanes=hs)
        assert not ok and f"head_size={hs}" in why and "window" in why
        assert paged_supported(cfg, True)[0] == (hs == 64)            # two heads of 64 a row: walked, window and all
        assert paged_supported(cfg_of(), True) == (True, "")           # no window
        assert paged_supported(cfg_of(n_embd=256, sliding_window=8), True) == (True, "")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        if attn == "paged":
            with pytest.raises(ValueError, match=f"head_size={hs}"):
                _engine(cfg, params, attn=attn, **store)
            return
        monkeypatch.setattr(px, "_pallas_available", lambda: True)
        st = _engine(cfg, params, attn=attn, **store).stats()["attn"]
        assert st["mode"] == "gather" and f"head_size={hs}" in st["fallback_reason"]
        assert st["kv_chunk_tokens"] is None and st["path"] is None
        # without the window the same heads stay on the kernels, a block a step
        st = _engine(cfg_of(), params, attn=attn, **store).stats()["attn"]
        assert st["mode"] == "paged" and st["kv_chunk_tokens"] == 4 and st["path"] == "by_blocks"
        if hs == 64:    # and at the compute dtype on the walk, in packed rows, with the window
            st = _engine(cfg, params, attn=attn).stats()["attn"]
            assert (st["mode"], st["path"], st["lane_pack"]) == ("paged", "walk", 2)

    def test_paged_supported_reasons(self, micro):
        cfg, _ = micro
        ok, why = paged_supported(cfg, True)
        assert ok and why == ""
        ok, why = paged_supported(cfg, False)
        assert not ok and "model_fn" in why
