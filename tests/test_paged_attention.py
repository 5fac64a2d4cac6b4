"""Paged-attention decode: Pallas kernel over the KV block arena (ISSUE 13).

The load-bearing guarantee is differential and bit-exact at the token
level: the decode program with the flash-decoding kernel in it (reading K/V
straight from the block arena; here under the Pallas interpreter,
``THUNDER_TPU_PALLAS_INTERPRET=1``) must serve tokens identical to the same
program with the kernel's XLA form in it (Pallas off: what a default CPU
engine builds) and to solo ``generate()`` — greedy AND temperature, int8/fp8
KV, LoRA mixes, chunked prefill, prefix sharing, and fault-recovery replay.
Logits are only ulp-close (online vs full softmax reorder), so every
assertion here compares tokens, never arena bytes.

The second pillar is structural: with the kernel in it the compiled
``decode_paged`` program must contain **zero** arena gather primitives and
zero scatters (asserted on the jaxpr, with the XLA form as positive
control), and physical block 0 (the sink / table padding target) must be
dead weight — poisoning it mid-run changes nothing in either form.

The third is the entry's own choice (``pallasex.paged_decode_path``): which
form ``paged_attn_decode`` takes follows from what it can observe, and the
engine's ``stats()["attn"]`` says which.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from conftest import arena_census, in_each_attn_form, set_attn_form
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import generate as gen
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, FaultPlan, FaultSpec, make_lora_factors
from thunder_tpu.serving.faults import FP_DECODE
from thunder_tpu.serving.kernel_check import _ref_attend
from thunder_tpu.serving.kv_pool import gather_dense
from thunder_tpu.serving.lora import valid_targets
from thunder_tpu.serving.paged_attention import decode_path
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
    """Tokens from an engine of each form, same workload."""
    engine_kw = engine_kw or {}
    submit_kw = submit_kw or {}
    return in_each_attn_form(lambda: _drive(_engine(cfg, params, **engine_kw), prompts, n, keys=keys, **submit_kw))


#
# differential parity: the acceptance bar
#


class TestPagedParity:
    def test_greedy_vs_xla_form_and_solo(self, micro):
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

        def serve_one():
            reg = AdapterRegistry(cfg, rank=2, max_adapters=2, targets=targets)
            reg.register("alice", make_lora_factors(
                cfg, 2, jax.random.PRNGKey(9), targets, std=0.5))
            eng = _engine(cfg, params, lora=reg)
            prompts = _prompts(cfg, lens=(3, 6, 10))
            hs = [eng.submit(prompts[0], max_new_tokens=5, adapter_id="alice"),
                  eng.submit(prompts[1], max_new_tokens=5),
                  eng.submit(prompts[2], max_new_tokens=5, adapter_id="alice")]
            eng.drain()
            return [tuple(h.result(drive=False).tokens) for h in hs]

        tx, tk = in_each_attn_form(serve_one)
        assert tx == tk

    def test_chunked_prefill(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(13, 14, 9)),
                       engine_kw=dict(prefill_chunk=8, prefill_buckets=(8, 16)))
        assert tg == tp

    def test_prefix_sharing(self, micro):
        cfg, params = micro
        base = (np.arange(10) * 7 + 3).astype(np.int32) % cfg.vocab_size

        def serve_one():
            eng = _engine(cfg, params, max_batch=2)
            ha = eng.submit(base, max_new_tokens=4)
            eng.step()                               # prefill A, register prefix
            hb = eng.submit(base.copy(), max_new_tokens=4)
            eng.step()                               # admit B via shared blocks
            eng.drain()
            ra, rb = ha.result(drive=False), hb.result(drive=False)
            assert rb.shared_prefix_blocks == 2      # sharing actually happened
            return tuple(ra.tokens), tuple(rb.tokens)

        tx, tk = in_each_attn_form(serve_one)
        assert tx == tk

    def test_fault_recovery_replay(self, micro, monkeypatch):
        """Re-prefill recovery rebuilds the arena, then decode resumes on
        the kernel — tokens still match the fault-free run of the XLA form."""
        cfg, params = micro
        p = (np.arange(6) * 3 + 1).astype(np.int32) % cfg.vocab_size
        set_attn_form(monkeypatch, "xla")
        ref = _drive(_engine(cfg, params), [p], n=8)
        set_attn_form(monkeypatch, "interpreted")
        eng = _engine(
            cfg, params,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE, kind="oom", at=3)]),
        )
        got = _drive(eng, [p], n=8)
        assert got == ref
        assert eng.recoveries == 1 and eng.stats()["attn"]["path"] == "walk"

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
    @pytest.fixture(autouse=True)
    def interpreted(self, monkeypatch):
        set_attn_form(monkeypatch, "interpreted")

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
        chunk; the same attention, here interpreted.  With a sliding window,
        which that kernel has not, the entry takes its XLA form."""
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
        assert px.paged_decode_path(W_HS, None) == "by_blocks" and px.paged_decode_path(W_HS, 8) == "xla"
        jaxpr = jax.make_jaxpr(lambda *a: _decode(*a, tables, pos, window=8, ks=ks, vs=vs))(q, k, v, fk, fv)
        assert "pallas_call" not in str(jaxpr)
        pos[-1] -= 1    # the XLA form puts the fresh row in its slot of the table, as the writer will
        got = _decode(q, k, v, fk, fv, tables, pos, window=8, ks=ks, vs=vs)
        assert float(jnp.max(jnp.abs(got - _reference(q, kd, vd, fk, fv, pos, 8)))) <= _TOL

    @pytest.mark.parametrize("window", [None, 10])
    @pytest.mark.parametrize("storage,layout", [
        ("bfloat16", "head_a_row"), ("bfloat16", "lane_packed"), ("bfloat16", "packed_out"),
        ("int8", "head_a_row"),
        pytest.param("fp8", "head_a_row", marks=pytest.mark.skipif(_FP8 is None, reason="no float8_e4m3fn"))])
    def test_the_xla_form_is_the_kernel_and_the_reference(self, small_chunks, monkeypatch, storage, layout, window):
        """``paged_attn_xla`` (what the entry takes where Pallas is off) against
        the interpreted kernel and against ``gather_dense`` + the plain float32
        reference, on the same bytes: bfloat16 and quantised arenas, with and
        without the window, a head a row, two heads a row, and the rows whole
        (``packed_out``); ragged positions, one row with no cached token."""
        ng, B, P = 4, 4, 1 if layout == "head_a_row" else 2
        keys = iter(jax.random.split(jax.random.PRNGKey(7), 8))
        rnd = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32).astype(jnp.bfloat16)
        nb = 1 + B * W_NBB
        k, v = rnd(nb, W_L, ng, W_BS, W_HS), rnd(nb, W_L, ng, W_BS, W_HS)
        tables = (1 + jnp.arange(B * W_NBB, dtype=jnp.int32)).reshape(B, W_NBB)
        q, fk, fv = rnd(B, ng * W_REP, W_HS), rnd(B, ng, W_HS), rnd(B, ng, W_HS)
        pos = jnp.asarray([W_C * W_BS + 5, 0, W_NBB * W_BS - 1, W_BS], jnp.int32)
        ks = vs = None
        kd, vd = gather_dense(k, v, tables)
        if storage != "bfloat16":
            dt = jnp.int8 if storage == "int8" else _FP8
            (k, ks), (v, vs) = quantize_kv(k, dt), quantize_kv(v, dt)
            kd, vd = gather_dense_q(k, v, ks, vs, tables, jnp.bfloat16)
        ref = _reference(q, kd, vd, fk, fv, pos, window)
        if P > 1:   # two KV heads side by side in a row, as the pool lays them out
            pack = lambda a: a.transpose(0, 1, 3, 2, 4).reshape(nb, W_L, W_BS, ng // P, P * W_HS).transpose(0, 1, 3, 2, 4)
            k, v = pack(k), pack(v)
        call = lambda: px.paged_attn_decode(q, k, v, fk, fv, tables, pos, layer=W_LAYER, window=window,
                                            k_scale=ks, v_scale=vs, packed_out=layout == "packed_out")
        kernel = call()
        set_attn_form(monkeypatch, "xla")
        assert px.paged_decode_path(k.shape[-1], window) == "xla"
        xla = call()
        assert "pallas_call" not in str(jax.make_jaxpr(call)())
        tol = 8 * float(jnp.finfo(jnp.bfloat16).eps)            # serving.kernel_check's bound
        assert xla.shape == kernel.shape and xla.dtype == kernel.dtype
        assert float(jnp.max(jnp.abs(xla.astype(jnp.float32) - kernel.astype(jnp.float32)))) <= tol
        if layout == "packed_out":  # a row's own head's lanes are the attention; the others its weights on the neighbour's values
            xla = px._lane_packed_outputs(xla.reshape(B, ng // P, P * W_REP, P * W_HS), P).reshape(B, ng * W_REP, W_HS)
        assert float(jnp.max(jnp.abs(xla.astype(jnp.float32) - ref))) <= tol

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
    def test_tokens_invariant_to_block0_garbage(self, micro, attn_form):
        """Block 0 backs every table's padding; neither form of the decode
        program's attention may ever read it into scores.  Poison it mid-run:
        tokens unchanged."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(3, 7))
        ref = _drive(_engine(cfg, params, max_batch=2), prompts, n=6)

        eng = _engine(cfg, params, max_batch=2, async_step=False)
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
    return arena_census(eng.pool.arenas, jax.make_jaxpr(prog)(*_decode_args(eng, Bb, nbb)).jaxpr)


class TestProgramPurity:
    def test_paged_decode_has_zero_arena_gathers_and_scatters(self, micro, monkeypatch):
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        assert _census(_engine(cfg, params), "decode_paged") == (0, 0)

    def test_gather_decode_is_the_positive_control(self, micro, monkeypatch):
        """The same census on the program built with Pallas off (the kernel's
        XLA form gathers a layer's rows and puts the fresh one among them)
        finds both op families — proving the walk actually sees through pjit
        into the program."""
        cfg, params = micro
        set_attn_form(monkeypatch, "xla")
        arena_gathers, scatters = _census(_engine(cfg, params), "decode_paged")
        assert arena_gathers > 0 and scatters > 0

    def test_quantized_paged_program_is_pure_too(self, micro, monkeypatch):
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        assert _census(_engine(cfg, params, kv_dtype="int8"), "decode_paged") == (0, 0)


#
# the entry's own choice + observability
#


NINE_KINDS = {"prefill", "prefill_fresh", "prefill_chunk", "prefill_chunk_paged", "decode_paged",
              "spec_prefill", "spec_prefill_chunk", "draft_decode", "verify_paged"}


class TestEntryChoice:
    def test_kernel_steps_count_no_fallback(self, micro, monkeypatch):
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        eng = _engine(cfg, params)
        _drive(eng, _prompts(cfg, lens=(3, 5)), n=4)
        st = eng.stats()
        assert st["attn"]["path"] == "walk" and st["attn"]["fallback_steps"] == 0 and st["decode_steps"] > 0
        # what walk the run measured: at these tiny widths the cap of 512
        # keys a chunk binds, not the byte budget
        assert st["attn"]["kv_chunk_tokens"] == 512
        # one decode program a job: no gather twin among the kinds
        assert set(eng.compile_counts) == NINE_KINDS
        assert tt.metrics_snapshot().get("serving.attn.fallback_steps", 0) == 0

    def test_the_stats_keys_the_benchmark_reads(self, micro):
        cfg, params = micro
        st = _engine(cfg, params).stats()["attn"]
        assert {"fallback_steps", "path", "lane_pack", "kv_chunk_tokens"} <= set(st)
        assert "mode" not in st and "requested" not in st and "kinds" not in st

    def test_pallas_off_counts_every_decode_step(self, micro, monkeypatch):
        """Without THUNDER_TPU_PALLAS_INTERPRET=1 a CPU engine builds the same
        ``decode_paged`` program with the kernel's XLA form in it, and counts
        every decode step as a fallback step: a cell that lost its kernel on
        the chip would read the same, and not be ``correct``."""
        set_attn_form(monkeypatch, "xla")
        cfg, params = micro
        eng = _engine(cfg, params)
        _drive(eng, _prompts(cfg, lens=(3,)), n=4)
        st = eng.stats()
        assert st["attn"]["path"] == "xla" and "decode_paged" in st["compile_counts"]
        assert st["attn"]["fallback_steps"] == st["decode_steps"] > 0
        assert tt.metrics_snapshot()["serving.attn.fallback_steps"] == st["attn"]["fallback_steps"]

    def test_a_custom_model_fn_is_refused(self, micro):
        cfg, params = micro
        with pytest.raises(NotImplementedError, match="llama.Config"):
            tt.serve(lambda *a, **k: None, params, cfg, block_size=4,
                     num_blocks=16, max_batch=2, cache_dtype=jnp.float32)

    def test_the_attn_option_is_gone(self, micro):
        cfg, params = micro
        with pytest.raises(TypeError, match="attn"):
            _engine(cfg, params, attn="paged")

    def test_the_decode_steps_option_is_gone(self, micro):
        """One way to keep the chip fed between decode steps, the dispatch
        ahead: no horizon to set, no scan program kind, nothing of a look-ahead
        in the scheduler or on a constraint; and ``eos_id``, which only the scan
        baked in, is the host's alone (engines that differ in it share programs)."""
        import inspect

        from thunder_tpu.serving import Constraint, DFAConstraint, TokenSetConstraint
        from thunder_tpu.serving.engine import ServingEngine
        from thunder_tpu.serving.scheduler import Scheduler

        cfg, params = micro
        with pytest.raises(TypeError, match="decode_steps"):
            _engine(cfg, params, decode_steps=2)
        with pytest.raises(TypeError, match="decode_steps"):
            tt.serve(None, params, cfg, decode_steps=2)
        options = [p for p in inspect.signature(ServingEngine.__init__).parameters.values() if p.kind is p.KEYWORD_ONLY]
        assert len(options) == 32 and "decode_steps" not in {p.name for p in options}
        eng = _engine(cfg, params, eos_id=7)
        assert set(eng.compile_counts) == NINE_KINDS and len(NINE_KINDS) == 9
        assert isinstance(eng.decode_steps, int) and "decode_steps_per_visit" not in eng.stats()   # the dispatch counter stays
        assert eng._static_key() == _engine(cfg, params)._static_key()
        assert "decode_horizon" not in inspect.signature(Scheduler.__init__).parameters
        assert not hasattr(eng.scheduler, "decode_horizon") and "decode_horizon" not in eng.scheduler.state_snapshot()
        assert not any(hasattr(c, "masks") for c in (Constraint, DFAConstraint, TokenSetConstraint))

    @pytest.mark.parametrize("what", ["entry", "engine"])
    @pytest.mark.parametrize("hs", [64, 96])
    def test_narrow_windowed_heads_take_the_xla_form_on_tpu(self, hs, what, monkeypatch):
        """Compiled for the TPU the decode walk cannot copy arena slabs whose
        rows are not whole 128-lane tiles (test_pallas_tpu_lowering holds the
        compiler to that), and the per-block kernel that serves such arenas has
        no sliding window: for a model with both the entry takes its XLA form,
        the engine says so when it is built (``path`` "xla") and counts the
        steps.  A head of 96 is such an arena always; a head of 64 where its KV
        heads cannot lie two to a row: a quantised arena here (at the compute
        dtype the pool packs them, and the walk takes the window)."""
        def cfg_of(**kw):
            return llama.Config.from_name("tiny-llama-debug", **{
                **MICRO, "n_head": 2, "n_query_groups": 2, "n_embd": 2 * hs, **kw})

        cfg = cfg_of(sliding_window=8)
        assert cfg.head_size == hs
        store = {"kv_dtype": "int8"} if hs == 64 else {}
        monkeypatch.setattr(px, "_interpret", lambda: False)   # as on the chip
        monkeypatch.setattr(px, "_pallas_available", lambda: True)
        if what == "entry":
            assert decode_path(cfg, arena_lanes=hs) == "xla"
            assert decode_path(cfg) == ("walk" if hs == 64 else "xla")   # two heads of 64 a row: walked, window and all
            assert decode_path(cfg_of()) == ("walk" if hs == 64 else "by_blocks")        # no window
            assert decode_path(cfg_of(), arena_lanes=hs) == "by_blocks"
            assert decode_path(cfg_of(n_embd=256, sliding_window=8)) == "walk"
            # and the entry does what the path says: no kernel in its trace
            (k, v), tables, q, fk, fv = _walk_inputs(ng=2)
            jaxpr = jax.make_jaxpr(lambda *a: _decode(*a, tables, [9], window=8))(q, k, v, fk, fv)
            assert px.paged_decode_path(W_HS, 8) == "xla" and "pallas_call" not in str(jaxpr)
            return
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        st = _engine(cfg, params, **store).stats()["attn"]
        assert st["path"] == "xla" and st["kv_chunk_tokens"] == 4
        # without the window the same heads stay on the kernels, a block a step
        st = _engine(cfg_of(), params, **store).stats()["attn"]
        assert st["kv_chunk_tokens"] == 4 and st["path"] == "by_blocks"
        if hs == 64:    # and at the compute dtype on the walk, in packed rows, with the window
            st = _engine(cfg, params).stats()["attn"]
            assert (st["path"], st["lane_pack"]) == ("walk", 2)

    def test_decode_path_follows_the_backend_and_the_mesh(self, micro, monkeypatch):
        from jax.sharding import Mesh

        cfg, _ = micro
        set_attn_form(monkeypatch, "interpreted")
        assert decode_path(cfg) == "walk"
        dp = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
        tp = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        assert decode_path(cfg, dp) == "xla"                      # no tp axis: the arena is replicated
        assert decode_path(cfg, tp) == "walk"
        odd = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "n_head": 3, "n_query_groups": 1, "n_embd": 24})
        assert decode_path(odd, tp) == "xla"                      # heads that tp does not split
        set_attn_form(monkeypatch, "xla")
        assert decode_path(cfg) == "xla" and decode_path(cfg, tp) == "xla"
