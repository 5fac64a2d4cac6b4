"""Ragged paged decode + chunked-prefill Pallas kernel (ISSUE 19).

Three pillars, all differential and CPU-cheap (MICRO model; the kernels under
the Pallas interpreter where a case asks for them, ``conftest.attn_form``):

- **Chunked-prefill paged kernel**: the ``prefill_chunk_paged`` program
  must serve the same tokens with the multi-query kernel in it as with the
  kernel's XLA form (greedy, int8/fp8, LoRA, session re-attach), contain zero
  arena gather/scatter primitives (the XLA form as positive control), and keep
  physical block 0 (the sink) dead weight — mirroring the PR 13 decode
  hygiene test.
- **Fused epilogues**: the quantized kernel programs carry no standalone
  quantize/dequantize HLO (the absmax math lives inside the writer kernels),
  and attn-target LoRA adds zero HLO einsums to the paged decode program (the
  delta runs the fused kernel) — both censused on the jaxpr, with the XLA
  form and the gather chunk as positive controls.
- **The chunk's kind + ragged observability**: a prompt piece's program
  follows from shapes alone (``stats()["attn"]["chunk"]``), whatever form
  the decode program's attention takes, and the goodput ledger's ``blocks``
  figure shows bucketed-vs-real block walks per kernel decode dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from conftest import arena_census, in_each_attn_form, prim_names, set_attn_form
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, make_lora_factors

MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(6, 12), prefill_buckets=(16,))
# chunked engines: chunk 8 over block_size 4 — two blocks per chunk, all
# boundaries block-aligned, so the paged chunk kind resolves
CHUNKED = dict(prefill_chunk=8, prefill_buckets=(8, 16))

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


def _prompts(cfg, lens=(13, 21, 9), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in lens]


def _drive(eng, prompts, n=5, **submit_kw):
    handles = [eng.submit(p, max_new_tokens=n, **submit_kw) for p in prompts]
    eng.drain()
    return [tuple(h.result(drive=False).tokens) for h in handles]


#
# the chunk's kind: chosen once, from shapes alone
#


class TestChunkKind:
    def test_aligned_chunk_resolves_paged(self, micro, attn_form):
        cfg, params = micro
        st = _engine(cfg, params, **CHUNKED).stats()["attn"]
        assert st["path"] == ("walk" if attn_form == "interpreted" else "xla")
        assert st["chunk"] == "paged" and st["chunk_why"] is None

    def test_non_aligned_buckets_keep_the_gather_chunk(self, micro, monkeypatch):
        """A non-block-aligned prefill bucket: decode keeps the kernel, the
        chunk kind alone is the gather program."""
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        eng = _engine(cfg, params, prefill_chunk=8, prefill_buckets=(8, 18))
        _drive(eng, _prompts(cfg, lens=(13,)), n=3)
        st = eng.stats()
        assert st["attn"]["path"] == "walk" and st["attn"]["fallback_steps"] == 0
        assert st["attn"]["chunk"] == "gather" and "multiples of block_size" in st["attn"]["chunk_why"]
        assert st["chunk_runs"] > 0 and st["compile_counts"]["prefill_chunk_paged"] == 0
        assert not any(k[0] == "prefill_chunk_paged" for k in eng._programs)

    def test_sliding_window_keeps_gather_chunk(self, attn_form):
        cfg = llama.Config.from_name("tiny-llama-debug", **MICRO, sliding_window=5)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        st = _engine(cfg, params, **CHUNKED).stats()["attn"]
        assert st["chunk"] == "gather" and "window" in st["chunk_why"]

    def test_chunk_steps_counted_and_kind_dispatched(self, micro, attn_form):
        cfg, params = micro
        eng = _engine(cfg, params, **CHUNKED)
        _drive(eng, _prompts(cfg, lens=(13, 21)), n=3)
        assert eng.stats()["chunk_runs"] > 0
        assert any(k[0] == "prefill_chunk_paged" for k in eng._programs)
        assert not any(k[0] == "prefill_chunk" for k in eng._programs)

    def test_flight_recorder_surfaces_chunk_attn(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, flight_recorder=True, **CHUNKED)
        _drive(eng, _prompts(cfg, lens=(13,)), n=3)
        evs = [e for e in eng._flight.events() if e.get("kind") == "prefill_chunk"]
        assert evs and all(e["attn"] == "paged" for e in evs)


#
# differential parity: the paged chunk with its kernel, with the kernel's XLA
# form, and the gather chunk
#


def _both(cfg, params, prompts, n=5, engine_kw=None, submit_kw=None):
    engine_kw = dict(engine_kw or {})
    submit_kw = dict(submit_kw or {})
    return in_each_attn_form(lambda: _drive(_engine(cfg, params, **CHUNKED, **engine_kw), prompts, n, **submit_kw))


class TestChunkPagedParity:
    def test_greedy_multi_chunk(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg), n=3)
        assert tg == tp
        # and the gather chunk's (a bucket off the block grid keeps it)
        gather = _engine(cfg, params, prefill_chunk=8, prefill_buckets=(8, 16, 22))
        assert gather.stats()["attn"]["chunk"] == "gather" and _drive(gather, _prompts(cfg), n=3) == tg

    def test_int8_kv(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(13, 9)), n=3,
                       engine_kw=dict(kv_dtype="int8"))
        assert tg == tp

    @pytest.mark.skipif(_FP8 is None, reason="jax build lacks float8_e4m3fn")
    def test_fp8_kv(self, micro):
        cfg, params = micro
        tg, tp = _both(cfg, params, _prompts(cfg, lens=(13, 9)), n=3,
                       engine_kw=dict(kv_dtype="fp8", max_batch=2))
        assert tg == tp

    def test_lora_mix(self, micro):
        cfg, params = micro
        targets = ("wq", "wk", "wv", "wo")

        def serve_one():
            reg = AdapterRegistry(cfg, rank=2, max_adapters=2, targets=targets)
            reg.register("alice", make_lora_factors(
                cfg, 2, jax.random.PRNGKey(9), targets, std=0.5))
            eng = _engine(cfg, params, lora=reg, **CHUNKED)
            prompts = _prompts(cfg, lens=(13, 9))
            hs = [eng.submit(prompts[0], max_new_tokens=3, adapter_id="alice"),
                  eng.submit(prompts[1], max_new_tokens=3)]
            eng.drain()
            return [tuple(h.result(drive=False).tokens) for h in hs]

        tx, tk = in_each_attn_form(serve_one)
        assert tx == tk

    def test_session_reattach(self, micro, attn_form):
        """Turn-2 re-attach re-prefills the un-shared tail through the
        paged chunk programs — tokens match a cold engine prefilling the
        identical full history."""
        cfg, params = micro
        p1 = _prompts(cfg, lens=(13,), seed=3)[0]
        tail = _prompts(cfg, lens=(9,), seed=4)[0]
        eng = _engine(cfg, params, sessions=True, **CHUNKED)
        r1 = eng.submit(p1, max_new_tokens=4, session_id="chat").result()
        p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32), tail])
        r2 = eng.submit(p2, max_new_tokens=4, session_id="chat").result()
        assert eng.stats()["sessions"]["reattach_hits"] == 1
        assert r2.shared_prefix_blocks > 0
        cold = _engine(cfg, params, **CHUNKED)
        rc = cold.submit(p2, max_new_tokens=4).result()
        assert r2.new_tokens == rc.new_tokens


#
# sink-block hygiene (satellite): the chunk writer never leaks block 0
#


class TestChunkSinkHygiene:
    def test_chunk_tokens_invariant_to_block0_garbage(self, micro, attn_form):
        """Physical block 0 backs every chunk table's padding and absorbs
        every sunk chunk write; neither form of the chunk's attention may ever read it into
        scores.  Poison it before the first chunked prefill and again
        between requests (so the second prefill's chunk reads run over a
        freshly-poisoned arena): tokens unchanged."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(13, 21))
        clean = _engine(cfg, params, max_batch=2, **CHUNKED)
        ref = [_drive(clean, [p], n=4)[0] for p in prompts]

        eng = _engine(cfg, params, max_batch=2, **CHUNKED)

        def poison():
            arenas = dict(eng.pool.arenas)
            arenas["k"] = arenas["k"].at[0].set(997.0)
            arenas["v"] = arenas["v"].at[0].set(-997.0)
            eng.pool.set_arenas(arenas)

        poison()                                  # before any chunk runs
        got = [_drive(eng, [prompts[0]], n=4)[0]]
        poison()                                  # between chunked prefills
        got.append(_drive(eng, [prompts[1]], n=4)[0])
        assert got == ref

    def test_chunk_tokens_invariant_quantized(self, micro, attn_form):
        cfg, params = micro
        prompts = _prompts(cfg, lens=(13,))
        kw = dict(kv_dtype="int8", max_batch=2)
        ref = _drive(_engine(cfg, params, **CHUNKED, **kw),
                     prompts, n=4)
        eng = _engine(cfg, params, **CHUNKED, **kw)
        arenas = dict(eng.pool.arenas)
        arenas["k"] = arenas["k"].at[0].set(127)
        arenas["v"] = arenas["v"].at[0].set(-127)
        arenas["k_scale"] = arenas["k_scale"].at[0].set(997.0)
        arenas["v_scale"] = arenas["v_scale"].at[0].set(997.0)
        eng.pool.set_arenas(arenas)
        assert _drive(eng, prompts, n=4) == ref


#
# structural censuses: purity, fused quant, fused LoRA
#


def _chunk_args(eng, Tb, nbb):
    return (
        eng.params,
        jnp.zeros((1, Tb), jnp.int32),
        jnp.int32(0),
        eng.pool.arenas,
        jnp.zeros((nbb,), jnp.int32),
        jnp.zeros((nbb,), jnp.int32),
        eng._lora_arenas(),
        jnp.zeros((1,), jnp.int32),
    )


def _chunk_jaxpr(eng, kind, Tb=8, nbb=4):
    prog, _ = eng._program(kind, Tb, nbb)
    return jax.make_jaxpr(prog)(*_chunk_args(eng, Tb, nbb)).jaxpr


def _decode_args(eng, Bb, nbb):
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


def _decode_jaxpr(eng, kind, Bb=4, nbb=4):
    prog, _ = eng._program(kind, Bb, nbb)
    return jax.make_jaxpr(prog)(*_decode_args(eng, Bb, nbb)).jaxpr


def _purity(eng, jaxpr):
    return arena_census(eng.pool.arenas, jaxpr)


def _quant_ops(jaxpr):
    """Standalone quantize/dequantize ops outside kernel bodies: any
    convert_element_type into or out of a quantized KV dtype.  (The absmax
    round/clamp are not counted — integer position clipping would alias
    them — but a quantize or dequantize cannot exist without the dtype
    cast, so the cast count alone is the load-bearing census.)"""
    qdtypes = {jnp.dtype(jnp.int8)}
    if _FP8 is not None:
        qdtypes.add(jnp.dtype(_FP8))
    n = 0
    for name, eqn in prim_names(jaxpr):
        if name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.params.get("new_dtype")
            if src in qdtypes or (dst is not None and jnp.dtype(dst) in qdtypes):
                n += 1
    return n


def _dots(jaxpr):
    return sum(1 for name, _ in prim_names(jaxpr) if name == "dot_general")


@pytest.fixture
def kernels(monkeypatch):
    set_attn_form(monkeypatch, "interpreted")


@pytest.fixture
def pallas_off(monkeypatch):
    set_attn_form(monkeypatch, "xla")


GATHER_CHUNK = dict(prefill_chunk=8, prefill_buckets=(8, 18))      # a bucket off the block grid


class TestChunkPurity:
    def test_paged_chunk_is_gather_and_scatter_free(self, micro, kernels):
        cfg, params = micro
        eng = _engine(cfg, params, **CHUNKED)
        assert _purity(eng, _chunk_jaxpr(eng, "prefill_chunk_paged")) == (0, 0)

    @pytest.mark.parametrize("control", ["xla_form", "gather_chunk"])
    def test_gather_chunk_is_the_positive_control(self, micro, pallas_off, control):
        """The same census finds both op families in the paged chunk built with
        Pallas off (the kernel's XLA form) and in the gather chunk."""
        cfg, params = micro
        if control == "xla_form":
            eng = _engine(cfg, params, **CHUNKED)
            g, s = _purity(eng, _chunk_jaxpr(eng, "prefill_chunk_paged"))
        else:
            eng = _engine(cfg, params, **GATHER_CHUNK)
            g, s = _purity(eng, _chunk_jaxpr(eng, "prefill_chunk"))
        assert g > 0 and s > 0

    def test_quantized_paged_chunk_is_pure_too(self, micro, kernels):
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8", **CHUNKED)
        assert _purity(eng, _chunk_jaxpr(eng, "prefill_chunk_paged")) == (0, 0)


class TestFusedQuantEpilogue:
    def test_paged_decode_has_no_standalone_quant_ops(self, micro, kernels):
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8")
        assert _quant_ops(_decode_jaxpr(eng, "decode_paged")) == 0

    def test_paged_chunk_has_no_standalone_quant_ops(self, micro, kernels):
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8", **CHUNKED)
        assert _quant_ops(_chunk_jaxpr(eng, "prefill_chunk_paged")) == 0

    def test_gather_programs_are_the_positive_control(self, micro, pallas_off):
        """With Pallas off the same two programs dequantise in HLO (the XLA form
        of their attention), and so does the gather chunk."""
        cfg, params = micro
        eng = _engine(cfg, params, kv_dtype="int8", **CHUNKED)
        assert _quant_ops(_decode_jaxpr(eng, "decode_paged")) > 0
        assert _quant_ops(_chunk_jaxpr(eng, "prefill_chunk_paged")) > 0
        eng = _engine(cfg, params, kv_dtype="int8", **GATHER_CHUNK)
        assert _quant_ops(_chunk_jaxpr(eng, "prefill_chunk")) > 0


class TestFusedLoraEpilogue:
    def _registry(self, cfg):
        targets = ("wq", "wk", "wv", "wo")
        reg = AdapterRegistry(cfg, rank=2, max_adapters=2, targets=targets)
        reg.register("alice", make_lora_factors(
            cfg, 2, jax.random.PRNGKey(9), targets, std=0.5))
        return reg

    def test_paged_decode_lora_adds_zero_hlo_einsums(self, micro, kernels):
        """Attn-target LoRA deltas run the fused kernel in the paged programs:
        the program's dot_general count equals the no-LoRA program's."""
        cfg, params = micro
        plain = _engine(cfg, params)
        lora = _engine(cfg, params, lora=self._registry(cfg))
        assert (_dots(_decode_jaxpr(lora, "decode_paged"))
                == _dots(_decode_jaxpr(plain, "decode_paged")))

    def test_gather_decode_is_the_positive_control(self, micro):
        """The same census on the gather chunk, whose deltas are HLO einsums,
        finds them."""
        cfg, params = micro
        plain = _engine(cfg, params, **GATHER_CHUNK)
        lora = _engine(cfg, params, lora=self._registry(cfg), **GATHER_CHUNK)
        assert (_dots(_chunk_jaxpr(lora, "prefill_chunk"))
                > _dots(_chunk_jaxpr(plain, "prefill_chunk")))


#
# ragged-decode observability: the goodput blocks figure
#


class TestRaggedBlocksLedger:
    def test_blocks_walked_vs_real(self, micro, kernels):
        """A mixed-length batch in one decode bucket: the compiled grid
        walks Bb x nbb blocks per step, the ragged clamp streams far
        fewer — and the ledger shows exactly that, per kind and in the
        fleet-aggregatable snapshot."""
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        _drive(eng, _prompts(cfg, lens=(3, 15)), n=5)
        blk = eng.stats()["goodput"]["blocks"]
        assert blk["walked"] > blk["real"] > 0
        assert 0.0 < blk["real_frac"] < 1.0
        per = eng.goodput_report()["blocks_per_kind"]
        assert "decode_paged" in per
        assert per["decode_paged"]["walked"] == blk["walked"]

    def test_gather_engine_records_no_blocks(self, micro, pallas_off):
        """The XLA form gathers the whole table: no walk to account."""
        cfg, params = micro
        eng = _engine(cfg, params, goodput=True)
        _drive(eng, _prompts(cfg, lens=(3,)), n=3)
        blk = eng.stats()["goodput"]["blocks"]
        assert blk["walked"] == blk["real"] == 0
        assert blk["real_frac"] is None
