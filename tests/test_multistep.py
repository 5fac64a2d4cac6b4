"""Device-resident multi-step decode: N tokens per host visit (ISSUE 16).

The load-bearing guarantee is differential and bit-exact at the token
level: an engine with ``decode_steps=N`` (the ``decode_multi_paged``
program kind — the decode body wrapped in a
``lax.scan`` with in-program EOS/length stopping and per-request liveness
masks) must serve tokens identical to the 1-step engine across the whole
matrix: greedy AND temperature, int8 KV, LoRA, prefix sharing, chunked
prefill, sliding window, and fault-recovery replay.

The second pillar is the off-path contract: ``decode_steps=1`` (default)
builds the same program kinds with the same static keys as a pre-knob
engine — a decode_steps=1 engine constructed after a default engine with
the same static config compiles nothing.

The third pillar is structural: a request finishing at step k < N must
not over-serve, its remaining scan iterations keep-mask KV writes to the
sink block (poisoned-sink regression, in both forms of the program's
attention), and with the kernel in it the compiled ``decode_multi_paged``
program still contains zero arena gathers/scatters (the kernel's XLA form as
positive control).

Everything runs on CPU: by default the attention kernel's XLA form, and under
``THUNDER_TPU_PALLAS_INTERPRET=1`` (``conftest.attn_form``) the kernel in
Pallas interpret mode; the interpreted multi-step tests are kept few — an
N-step interpret-mode scan costs N kernel evaluations per visit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from conftest import arena_census, set_attn_form
from thunder_tpu.models import llama
from thunder_tpu.serving import AdapterRegistry, FaultPlan, FaultSpec, make_lora_factors
from thunder_tpu.serving.faults import FP_DECODE

MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(8,), prefill_buckets=(16,))


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


def _drive(eng, prompts, n=6, keys=None, **submit_kw):
    handles = []
    for i, p in enumerate(prompts):
        kw = dict(submit_kw)
        if keys is not None:
            kw["key"] = keys[i]
        handles.append(eng.submit(p, max_new_tokens=n, **kw))
    eng.drain()
    return [tuple(h.result(drive=False).tokens) for h in handles]


def _vs_one_step(cfg, params, prompts, n=6, N=4, keys=None, engine_kw=None,
                 submit_kw=None):
    """Tokens from a 1-step engine and a decode_steps=N engine, same load."""
    engine_kw = engine_kw or {}
    submit_kw = submit_kw or {}
    t1 = _drive(_engine(cfg, params, **engine_kw), prompts, n,
                keys=keys, **submit_kw)
    tn = _drive(_engine(cfg, params, decode_steps=N, **engine_kw), prompts, n,
                keys=keys, **submit_kw)
    return t1, tn


#
# differential parity: the acceptance bar
#


class TestMultiStepParity:
    def test_greedy_xla_form(self, micro):
        cfg, params = micro
        t1, t4 = _vs_one_step(cfg, params, _prompts(cfg))
        assert t1 == t4

    def test_greedy_xla_form_off_pow2_horizon(self, micro):
        """N=3: the horizon is one static knob, not a power-of-two bucket —
        any N compiles one program and serves identical tokens."""
        cfg, params = micro
        t1, t3 = _vs_one_step(cfg, params, _prompts(cfg), N=3)
        assert t1 == t3

    def test_greedy_kernel(self, micro, monkeypatch):
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        t1, t4 = _vs_one_step(cfg, params, _prompts(cfg, lens=(3, 7)),
                              engine_kw=dict(max_batch=2))
        assert t1 == t4

    def test_temperature_with_request_keys(self, micro):
        """The per-request PRNG chain splits once per *emitted* token —
        dead scan iterations must not advance a finished row's key."""
        cfg, params = micro
        keys = [jax.random.PRNGKey(42), jax.random.PRNGKey(7)]
        t1, t4 = _vs_one_step(cfg, params, _prompts(cfg, lens=(4, 11)),
                              keys=keys, engine_kw=dict(temperature=0.7))
        assert t1 == t4

    def test_int8_kv_xla_form_and_kernel(self, micro, monkeypatch):
        cfg, params = micro
        t1, t4 = _vs_one_step(cfg, params, _prompts(cfg),
                              engine_kw=dict(kv_dtype="int8"))
        assert t1 == t4
        set_attn_form(monkeypatch, "interpreted")
        p1, p4 = _vs_one_step(cfg, params, _prompts(cfg, lens=(3, 7)),
                              engine_kw=dict(kv_dtype="int8", max_batch=2))
        assert p1 == p4

    def test_lora_mix(self, micro):
        cfg, params = micro

        def serve_one(N):
            reg = AdapterRegistry(cfg, rank=2, max_adapters=2,
                                  targets=("wq", "wv"))
            reg.register("alice", make_lora_factors(
                cfg, 2, jax.random.PRNGKey(9), ("wq", "wv"), std=0.5))
            eng = _engine(cfg, params, lora=reg, decode_steps=N)
            prompts = _prompts(cfg, lens=(3, 6))
            hs = [eng.submit(prompts[0], max_new_tokens=6, adapter_id="alice"),
                  eng.submit(prompts[1], max_new_tokens=6)]
            eng.drain()
            return [tuple(h.result(drive=False).tokens) for h in hs]

        assert serve_one(1) == serve_one(4)

    def test_prefix_sharing(self, micro):
        cfg, params = micro
        base = _prompts(cfg, lens=(14,))[0]
        shared = [np.concatenate([base, np.array([1], np.int32)]),
                  np.concatenate([base, np.array([2], np.int32)])]
        t1, t4 = _vs_one_step(cfg, params, shared)
        assert t1 == t4

    def test_chunked_prefill(self, micro):
        cfg, params = micro
        rng = np.random.default_rng(3)
        long = [rng.integers(0, cfg.vocab_size, (22,)).astype(np.int32)]
        kw = dict(prefill_chunk=8, prefill_buckets=(8, 16), block_buckets=(12,))
        t1, t4 = _vs_one_step(cfg, params, long, engine_kw=kw)
        assert t1 == t4

    def test_sliding_window(self):
        """Window expiry happens at visit boundaries on the host; the
        in-program positional keep-mask covers the intra-visit steps."""
        cfg = llama.Config.from_name("tiny-llama-debug", **MICRO,
                                     sliding_window=8)
        params = llama.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
        t1, t4 = _vs_one_step(cfg, params, _prompts(cfg), n=10)
        assert t1 == t4

    def test_fault_recovery_replay(self, micro):
        """Re-prefill recovery replays through the multi-step program and
        still lands on the fault-free 1-step stream (keys advance only at
        harvest, so the KV arena stays soft state under N too)."""
        cfg, params = micro
        p = (np.arange(6) * 3 + 1).astype(np.int32) % cfg.vocab_size
        ref = _drive(_engine(cfg, params), [p], n=8)
        eng = _engine(
            cfg, params, decode_steps=4,
            fault_plan=FaultPlan(specs=[FaultSpec(point=FP_DECODE,
                                                  kind="oom", at=2)]),
        )
        assert _drive(eng, [p], n=8) == ref
        assert eng.recoveries == 1


#
# in-program stopping at and inside the visit boundary
#


class TestBoundaryStopping:
    def test_eos_inside_visit_with_poisoned_sink(self, micro, attn_form):
        """A request hitting EOS at step k < N stops there — and its
        remaining scan iterations keep-mask to the sink block.  Poisoning
        the sink mid-run proves no dead iteration's write (or read)
        reaches anything attended; the co-running longer request proves
        the shared batch is unperturbed."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(3, 7))
        ref1 = _drive(_engine(cfg, params, max_batch=2),
                      prompts, n=8)
        # an EOS the reference stream emits mid-visit: generated token #2
        # of request 0 (prompt excluded), i.e. finish at step 2 of the
        # first 4-step visit (the first generated token comes from prefill)
        eos = ref1[0][len(prompts[0]) + 2]
        ref = _drive(_engine(cfg, params, max_batch=2,
                             eos_id=int(eos)), prompts, n=8)
        assert len(ref[0]) < len(ref1[0])                  # EOS really fired early

        eng = _engine(cfg, params, max_batch=2, eos_id=int(eos),
                      decode_steps=4, async_step=False)
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            eng.step()                                     # past prefill, mid-decode
        arenas = dict(eng.pool.arenas)
        arenas["k"] = arenas["k"].at[0].set(997.0)
        arenas["v"] = arenas["v"].at[0].set(-997.0)
        eng.pool.set_arenas(arenas)
        eng.drain()
        got = [tuple(h.result(drive=False).tokens) for h in handles]
        assert got == ref
        assert handles[0].result(drive=False).finish_reason == "eos"

    def test_length_exactly_on_visit_boundary(self, micro):
        """max_new_tokens landing exactly on a visit boundary: the last
        visit harvests exactly N tokens and the request must not be
        dispatched again (no over-serving past FINISH_LENGTH)."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(5,))
        # 9 generated = 1 (prefill) + 2 full 4-step visits
        t1, t4 = _vs_one_step(cfg, params, prompts, n=9)
        assert t1 == t4
        eng = _engine(cfg, params, decode_steps=4)
        h = eng.submit(prompts[0], max_new_tokens=9)
        eng.drain()
        res = h.result(drive=False)
        assert res.finish_reason == "length"
        assert len(res.tokens) - len(prompts[0]) == 9
        assert eng.stats()["host_visits"] == 2

    def test_length_just_inside_visit_boundary(self, micro):
        """max_new_tokens one short of the boundary: the final visit
        emits k = N-1 tokens, the N-th iteration keep-masks."""
        cfg, params = micro
        prompts = _prompts(cfg, lens=(5,))
        t1, t4 = _vs_one_step(cfg, params, prompts, n=8)
        assert t1 == t4
        eng = _engine(cfg, params, decode_steps=4)
        h = eng.submit(prompts[0], max_new_tokens=8)
        eng.drain()
        res = h.result(drive=False)
        assert res.finish_reason == "length"
        assert len(res.tokens) - len(prompts[0]) == 8

    def test_deadline_expires_at_visit_boundary_no_overserve(self, micro):
        """A deadline passing mid-visit finishes the request at the next
        harvest with the visit's tokens delivered — never more than
        max_new_tokens, and never a token the program didn't serve."""
        cfg, params = micro

        class Clock:
            def __init__(self):
                self.t = 0.0

            def __call__(self):
                return self.t

        ck = Clock()
        p = _prompts(cfg, lens=(5,))[0]
        eng = _engine(cfg, params, decode_steps=4, clock=ck)
        h = eng.submit(p, max_new_tokens=24, deadline=5.0)
        for _ in range(3):
            eng.step()
        ck.t = 10.0                                        # deadline passes mid-stream
        eng.drain()
        res = h.result(drive=False)
        assert res.finish_reason == "deadline"
        gen = len(res.tokens) - len(p)
        assert 0 < gen < 24
        # tokens delivered in whole visits: 1 prefill token + k*N decode
        assert (gen - 1) % 4 == 0


#
# structural: the multi-step paged program is still gather/scatter-free
#


def _multi_decode_args(eng, Bb, nbb):
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
        jnp.full((Bb,), -1, jnp.int32),                    # stop positions
    )


def _census(eng, kind, Bb=4, nbb=4):
    prog, _ = eng._program(kind, Bb, nbb)
    return arena_census(eng.pool.arenas, jax.make_jaxpr(prog)(*_multi_decode_args(eng, Bb, nbb)).jaxpr)


class TestMultiProgramPurity:
    def test_paged_multi_has_zero_arena_gathers_and_scatters(self, micro, monkeypatch):
        cfg, params = micro
        set_attn_form(monkeypatch, "interpreted")
        eng = _engine(cfg, params, decode_steps=4)
        assert _census(eng, "decode_multi_paged") == (0, 0)

    def test_gather_multi_is_the_positive_control(self, micro, monkeypatch):
        """The same census on the multi program built with Pallas off (the
        kernel's XLA form) finds both op families — proving the walk sees
        through pjit AND the scan."""
        cfg, params = micro
        set_attn_form(monkeypatch, "xla")
        eng = _engine(cfg, params, decode_steps=4)
        arena_gathers, scatters = _census(eng, "decode_multi_paged")
        assert arena_gathers > 0 and scatters > 0


#
# off-path + knob contract
#


class TestKnobContract:
    def test_decode_steps_one_shares_module_program_cache(self, micro):
        """decode_steps=1 is byte-identical off-path: its static key equals
        a default engine's, so every program comes from the module cache —
        zero compiles on the second engine."""
        cfg, params = micro
        temp = 0.271828                                    # unique static key for this test
        ea = _engine(cfg, params, temperature=temp)
        _drive(ea, _prompts(cfg, lens=(4,)), n=4)
        eb = _engine(cfg, params, temperature=temp, decode_steps=1)
        _drive(eb, _prompts(cfg, lens=(4,)), n=4)
        assert eb.stats()["compile_counts"]["prefill"] == 0
        assert eb.stats()["compile_counts"]["decode_paged"] == 0

    def test_the_eos_a_scan_stops_at_is_program_identity(self, micro):
        """The multi-step program has its EOS baked in (a row dies at it inside
        the scan): two horizons' engines that differ in it share no program,
        and the 1-step engines, which stop on the host, share all of theirs."""
        cfg, params = micro
        keys = {eos: _engine(cfg, params, decode_steps=4, eos_id=eos)._static_key() for eos in (None, 7, 9)}
        assert len(set(keys.values())) == 3
        assert _engine(cfg, params, eos_id=7)._static_key() == _engine(cfg, params)._static_key()

    def test_rejects_bad_horizon(self, micro):
        cfg, params = micro
        with pytest.raises(ValueError, match="decode_steps"):
            _engine(cfg, params, decode_steps=0)

    def test_rejects_speculative_with_reason(self, micro):
        cfg, params = micro
        from thunder_tpu.serving.speculative import SpecConfig, multi_step_supported

        ok, why = multi_step_supported(
            SpecConfig(draft_params=params, draft_cfg=cfg, K=2))
        assert not ok and "data-dependent" in why
        with pytest.raises(ValueError, match="unsupported.*data-dependent"):
            _engine(cfg, params, decode_steps=4,
                    speculative=SpecConfig(draft_params=params,
                                           draft_cfg=cfg, K=2))

    def test_bucket_bound_holds_with_horizon(self, micro):
        """N joins the static key as one knob — the per-engine compiled
        decode program count stays inside the bucket bound."""
        cfg, params = micro
        eng = _engine(cfg, params, decode_steps=4)
        _drive(eng, _prompts(cfg), n=6)
        st = eng.stats()
        decode_compiles = sum(
            st["compile_counts"][k]
            for k in ("decode_paged", "decode_multi_paged"))
        assert decode_compiles <= st["bucket_bound"]


#
# host-visit accounting + observability (satellites 1 and 2)
#


class TestHostVisitAccounting:
    def test_one_step_baseline(self, micro):
        """The 1-step engine reports one visit per decode dispatch and
        tokens_per_host_visit == mean decode occupancy."""
        cfg, params = micro
        eng = _engine(cfg, params)
        _drive(eng, _prompts(cfg), n=6)
        st = eng.stats()
        assert st["decode_steps_per_visit"] == 1
        assert st["host_visits"] == st["decode_steps"]
        assert st["tokens_per_host_visit"] == pytest.approx(
            (st["tokens_generated"] - 4) / st["host_visits"])  # 4 prefill tokens

    def test_multi_step_amortizes_visits(self, micro):
        """Same workload at N=4: >= 4x fewer host visits per decode
        token."""
        cfg, params = micro
        e1 = _engine(cfg, params)
        _drive(e1, _prompts(cfg), n=9)
        e4 = _engine(cfg, params, decode_steps=4)
        t4 = _drive(e4, _prompts(cfg), n=9)
        s1, s4 = e1.stats(), e4.stats()
        assert s4["decode_steps_per_visit"] == 4
        v1 = s1["host_visits"] / s1["tokens_generated"]
        v4 = s4["host_visits"] / s4["tokens_generated"]
        assert v4 <= v1 / 4 * 1.1
        assert s4["tokens_per_host_visit"] > s1["tokens_per_host_visit"]
        # counters survive into the registry
        from thunder_tpu.observability.metrics import registry
        assert registry().counter("serving.decode.host_visits").value >= \
            s4["host_visits"]

    def test_flight_state_carries_horizon(self, micro):
        cfg, params = micro
        from thunder_tpu.observability.flight import FlightRecorder

        fr = FlightRecorder(capacity=64)
        eng = _engine(cfg, params, decode_steps=4, flight_recorder=fr)
        _drive(eng, _prompts(cfg, lens=(4,)), n=6)
        snap = eng._flight_state()
        assert snap["scheduler"]["decode_horizon"] == 4
        assert snap["engine"]["decode_steps_per_visit"] == 4
        decs = [e for e in fr.events() if e["kind"] == "decode"]
        assert decs and all(e["steps"] == 4 for e in decs)
        assert all(1 <= k <= 4 for e in decs for k in e["harvested"])

    def test_decode_spans_are_per_visit(self, micro):
        """One decode span per request per HOST VISIT tagged steps=N and
        harvested=k — not N phantom per-token spans."""
        cfg, params = micro
        from thunder_tpu.observability.events import clear_events, events

        clear_events()
        eng = _engine(cfg, params, decode_steps=4, trace=True)
        p = _prompts(cfg, lens=(5,))[0]
        h = eng.submit(p, max_new_tokens=9)                # 1 prefill + 2 visits
        eng.drain()
        assert h.result(drive=False).finish_reason == "length"
        rid = 0
        begins = [e for e in events()
                  if e["ph"] == "b" and e["name"] == "decode"
                  and e.get("id") == rid]
        ends = [e for e in events()
                if e["ph"] == "e" and e["name"] == "decode"
                and e.get("id") == rid]
        assert len(begins) == len(ends) == eng.stats()["host_visits"] == 2
        assert all(e["args"]["steps"] == 4 for e in begins)
        assert sorted(e["args"]["harvested"] for e in ends) == [4, 4]
