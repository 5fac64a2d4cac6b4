"""Counts and parity bits that used to be asserted only on recorded runs.

Each case of ``test_count_invariant[<target>-<key>]`` is one count, parity
bit or conservation identity (compiled programs against the bucket bound, zero
programs for a warm engine, blocks, bytes from shapes, rows a decode step
held, tokens a session did not prefill again) read off a live engine or a
compiled program at a tiny debug config.  No case reads a clock.  A case is
here only where no other tier-1 test holds the same line; where one does, it
stays there (CHANGES.md, PR 30, lists which).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from conftest import set_attn_form
from thunder_tpu.models import llama
from thunder_tpu.serving import (
    AdapterRegistry,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    SpecConfig,
    arena_block_bytes,
    blocks_for_arena_bytes,
    make_lora_factors,
)

MICRO = dict(
    n_layer=2, n_head=4, n_query_groups=2, n_embd=32,
    intermediate_size=64, vocab_size=64, block_size=64,
)
BUCKETS = dict(batch_buckets=(4,), block_buckets=(8,), prefill_buckets=(16,))


@functools.cache
def _model(**over):
    """``over`` empty: the model the cases share.  Otherwise a model no other
    test builds, so that this file's first engine on it finds no program in
    the module cache: the cache is keyed by the config, and the config carries
    this file's name (widths alone can meet another file's: ``vocab_size`` 40
    and 48 are ``test_prefill_fresh.py``'s too, and whichever file a worker ran
    first had compiled the other's programs)."""
    cfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, **over})
    if over:
        cfg = dataclasses.replace(
            cfg, name="serving-invariants-" + "-".join(f"{k}{v}" for k, v in sorted(over.items())))
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def micro():
    return _model()


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    for k, v in BUCKETS.items():
        kw.setdefault(k, v)
    return tt.serve(None, params, cfg, **kw)


def _prompt(seed, n, cfg):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size)
    ).astype(np.int32)


def _reqs(cfg, lens, n, seed=0):
    return [{"prompt": _prompt(seed + i, m, cfg), "max_new_tokens": n}
            for i, m in enumerate(lens)]


def _tokens(results):
    return [tuple(int(t) for t in r.tokens) for r in results]


def _drive_peak(eng, reqs):
    """Submit everything, step to the end; the most requests that ran at once
    and the most distinct adapters among them."""
    handles = [eng.submit(**r) for r in reqs]
    peak = distinct = 0
    while eng.scheduler.queue or eng.scheduler.running:
        running = eng.scheduler.running
        peak = max(peak, len(running))
        distinct = max(distinct, len({r.adapter_slot for r in running if r.adapter_slot}))
        if not eng.step():
            break
    return handles, peak, distinct


CASES = {}


def case(name):
    def register(fn):
        CASES[name] = fn
        return fn
    return register


#
# serving: the plain engine
#


@case("serving-compiles_within_bucket_bound")
def _(micro):
    """Mixed lengths through the default (power-of-two) bucket ladders: what
    the engine compiled is inside what its bucket sets allow, kind by kind."""
    cfg, params = micro
    eng = tt.serve(None, params, cfg, block_size=4, num_blocks=48, max_batch=4,
                   cache_dtype=jnp.float32)
    eng.run(_reqs(cfg, (3, 5, 9, 14, 2, 7), 6))
    st = eng.stats()
    counts = st["compile_counts"]
    # every prompt here is whole and at position 0: the fresh kind, which reads
    # no table and so is built once a prefill bucket, whatever the table's width
    assert counts["prefill_fresh"] >= 1 and counts["prefill"] == 0 and counts["decode_paged"] >= 1
    assert st["prefill_fresh_runs"] == st["prefill_runs"] == 6
    assert sum(counts.values()) <= st["bucket_bound"]
    widths = len(eng._table_widths)
    assert counts["decode_paged"] <= len(eng.scheduler.batch_buckets) * widths
    assert counts["prefill_fresh"] <= len(eng.scheduler.prefill_buckets)


@case("serving-prefill_fresh_is_one_program_a_bucket")
def _(micro):
    """The fresh kind reads no table: requests whose tables differ in width
    share one program a prefill bucket (the general kinds go by width too); its
    runs are the whole prompts among the prefill runs."""
    cfg, params = _model(vocab_size=40)
    eng = _engine(cfg, params, block_buckets=(4, 8, 16), prefill_buckets=(8, 16), prefill_chunk=16,
                  prefix_sharing=False)
    lens_new = ((5, 2), (6, 20), (7, 40), (12, 2), (13, 30), (24, 3))     # the last in two pieces
    eng.run([{"prompt": _prompt(70 + i, m, cfg), "max_new_tokens": n} for i, (m, n) in enumerate(lens_new)])
    st = eng.stats()
    counts = st["compile_counts"]
    assert counts["prefill_fresh"] == 2 and counts["prefill"] == 1          # buckets 8 and 16; one last piece
    assert {b for k, _, b in eng._programs if k == "prefill_fresh"} == {2, 4}      # the blocks a bucket fills
    assert len(eng._table_widths) >= 3          # tables of 2 to 12 blocks: a width each for the general kinds
    assert st["prefill_runs"] == 6 and st["prefill_fresh_runs"] == 5 and st["chunk_runs"] == 1
    assert sum(counts.values()) <= st["bucket_bound"]


@case("serving-cold_compile_prefills_measured")
def _(micro):
    """The per-request compile tag: on a cold program cache as many prefills
    are tagged as prefill programs were compiled; a second engine of the same
    configuration tags none and compiles nothing."""
    cfg, params = _model(vocab_size=48)
    reqs = _reqs(cfg, (3, 6, 11, 5), 4)
    cold = _engine(cfg, params, prefill_buckets=(8, 16))
    res = cold.run([dict(r) for r in reqs])
    tagged = sum(1 for r in res if r.prefill_compiled)
    assert tagged == cold.compile_counts["prefill_fresh"] == 2      # one a bucket
    warm = _engine(cfg, params, prefill_buckets=(8, 16))
    res_w = warm.run([dict(r) for r in reqs])
    assert not any(r.prefill_compiled for r in res_w)
    assert sum(warm.compile_counts.values()) == 0
    assert _tokens(res_w) == _tokens(res)


@case("serving-occupancy_accounts_for_every_decode_token")
def _(micro):
    """Rows summed over decode steps are the tokens that decode produced:
    everything generated less the one token a request that prefill samples."""
    cfg, params = micro
    eng = _engine(cfg, params)
    res = eng.run(_reqs(cfg, (3, 5, 9, 4, 6), 5, seed=10))
    st = eng.stats()
    generated = sum(len(r.new_tokens) for r in res)
    assert st["tokens_generated"] == generated == 25
    rows = st["mean_batch_occupancy"] * st["decode_steps"]
    assert rows == pytest.approx(generated - len(res))
    assert st["mean_batch_occupancy"] > 1.0


#
# serving_async: chunked prefill
#


@case("serving_async-cold_compile_prefills_measured")
def _(micro):
    """A second chunking engine finds every program, the chunk kind among
    them, and serves the same tokens."""
    cfg, params = micro
    reqs = _reqs(cfg, (37, 5, 7), 4, seed=20)
    kw = dict(prefill_chunk=8, prefill_buckets=(8, 16), num_blocks=48, block_buckets=(16,))
    first = _engine(cfg, params, **kw)
    res = first.run([dict(r) for r in reqs])
    assert first.chunk_runs > 0
    second = _engine(cfg, params, **kw)
    res2 = second.run([dict(r) for r in reqs])
    assert second.chunk_runs == first.chunk_runs
    assert "prefill_chunk" in second.compile_counts
    assert sum(second.compile_counts.values()) == 0
    assert not any(r.prefill_compiled for r in res2)
    assert _tokens(res2) == _tokens(res)


@case("serving_async-mean_batch_occupancy")
def _(micro):
    """Short requests queued behind a long chunked prompt still share decode
    steps with each other: the lane does not serialise the batch."""
    cfg, params = micro
    eng = _engine(cfg, params, prefill_chunk=8, prefill_buckets=(8, 16), num_blocks=48,
                  block_buckets=(16,))
    res = eng.run(_reqs(cfg, (37, 5, 7, 6), 6, seed=30))
    st = eng.stats()
    assert st["chunk_runs"] >= 4                  # 37 tokens in pieces of 8
    assert st["mean_batch_occupancy"] > 1.0
    rows = st["mean_batch_occupancy"] * st["decode_steps"]
    assert rows == pytest.approx(sum(len(r.new_tokens) for r in res) - len(res))


#
# capacity: quantized block storage and adapters
#


@functools.cache
def _capacity():
    """A flood of sixteen requests into a float32 and an int8 pool of one
    arena-byte budget, at head size 16 (the note in ``serving/quant.py``)."""
    cfg, params = _model(n_embd=64)
    assert cfg.head_size == 16
    bs, prompt_len, max_new, n_flood, usable = 4, 8, 8, 16, 16
    budget = (usable + 1) * arena_block_bytes(cfg, bs, jnp.float32)   # + the sink block
    out = {"budget": budget}
    for name, kv in (("f32", None), ("int8", "int8")):
        nb = blocks_for_arena_bytes(cfg, bs, budget, jnp.float32, kv_dtype=kv)
        eng = _engine(cfg, params, block_size=bs, num_blocks=nb, max_batch=n_flood,
                      max_queue=2 * n_flood, batch_buckets=(n_flood,),
                      **({"kv_dtype": kv} if kv else {}))
        _, peak, _ = _drive_peak(eng, _reqs(cfg, (prompt_len,) * n_flood, max_new, seed=40))
        out[name] = {"blocks": nb, "peak": peak, "stats": eng.stats(),
                     "block_bytes": eng.pool.block_bytes()}
    return out


@case("capacity-admitted_ratio")
def _(micro):
    """At one arena-byte budget the int8 pool keeps at least three times as
    many requests resident as the float32 pool (head size 16: 64 bytes a
    slot-head against 16 + 4)."""
    run = _capacity()
    assert run["int8"]["peak"] > run["f32"]["peak"] >= 2
    assert run["int8"]["peak"] >= 3 * run["f32"]["peak"]


@case("capacity-arena_bytes_within_budget")
def _(micro):
    """Both pools of the comparison were built inside the same budget, and one
    block more would not have fitted: the live arenas' bytes, from shapes."""
    run = _capacity()
    for name in ("f32", "int8"):
        side = run[name]
        assert side["stats"]["arena_bytes"] == side["blocks"] * side["block_bytes"]
        assert side["stats"]["arena_bytes"] <= run["budget"] < (
            side["stats"]["arena_bytes"] + side["block_bytes"])
    assert run["int8"]["stats"]["kv_dtype"] == "int8"


@case("capacity-compiles_within_bucket_bound")
def _(micro):
    """The storage dtype is program identity, not a new ladder: the flooded
    int8 engine stays inside its bucket bound."""
    st = _capacity()["int8"]["stats"]
    assert 0 < sum(st["compile_counts"].values()) <= st["bucket_bound"]


@case("capacity-adapter_mix_max_distinct")
def _(micro):
    """Three tenants' rows are in one decode batch at the same time (not one
    after another), beside a base-model row."""
    cfg, params = micro
    reg = AdapterRegistry(cfg, rank=2, max_adapters=4)
    for i, name in enumerate(("a", "b", "c")):
        reg.register(name, make_lora_factors(cfg, 2, jax.random.PRNGKey(10 + i), std=0.5))
    eng = _engine(cfg, params, lora=reg)
    reqs = [dict(r, adapter_id=a) for r, a in
            zip(_reqs(cfg, (4, 6, 9, 5), 5, seed=50), ("a", "b", "c", None))]
    handles, peak, distinct = _drive_peak(eng, reqs)
    assert peak == 4 and distinct == 3
    assert all(len(h.result(drive=False).new_tokens) == 5 for h in handles)


#
# tracing: an armed engine records, an explicitly disarmed one is the default
#


@functools.cache
def _armed():
    """Three requests through an engine with spans, SLO monitor and flight
    recorder all on."""
    cfg, params = _model()
    eng = _engine(cfg, params, trace=True, flight_recorder=True,
                  slo={"ttft_s": 30.0, "tpot_s": 30.0, "queue_s": 30.0})
    eng.run(_reqs(cfg, (3, 7, 11), 4, seed=60))
    return eng


@case("tracing-slo_dimensions")
def _(micro):
    """Three latency targets arm four dimensions (the deadline rides along),
    and every finished request is counted once in each."""
    dims = _armed().slo_report()["dimensions"]
    assert sorted(dims) == ["deadline", "queue_s", "tpot_s", "ttft_s"]
    for name, d in dims.items():
        assert d["good"] + d["bad"] == 3, (name, d)
        assert d["bad"] == 0, (name, d)


@case("tracing-flight_events")
def _(micro):
    """The armed engine's flight ring saw every decode dispatch."""
    eng = _armed()
    kinds = [e.get("kind") for e in eng._flight.events()]
    assert eng._flight.events_recorded >= len(kinds) > 0
    assert kinds.count("decode") == eng.stats()["decode_steps"]


@case("tracing-explicit_off_is_the_default_engine")
def _(micro):
    """``trace=False, slo=None, flight_recorder=False`` is the default engine:
    no tracer, monitor or recorder object, the same static program key, no
    program compiled beyond the default engine's."""
    # the module, not the events() that the package re-exports under its name
    ev = importlib.import_module("thunder_tpu.observability.events")

    cfg, params = micro
    reqs = _reqs(cfg, (3, 7), 4, seed=70)
    plain = _engine(cfg, params)
    res = plain.run([dict(r) for r in reqs])
    ev.clear_events()
    off = _engine(cfg, params, trace=False, slo=None, flight_recorder=False)
    res_off = off.run([dict(r) for r in reqs])
    assert off._tracer is None and off._slo is None and off._flight is None
    assert off._static_key() == plain._static_key()
    assert sum(off.compile_counts.values()) == 0
    assert off.slo_report() == {"enabled": False}
    assert not [e for e in ev.events() if e.get("cat", "").startswith("serving")]
    assert _tokens(res_off) == _tokens(res)


#
# paged attention, ragged decode, paged chunk prefill
#


PAGED = dict(prefill_chunk=8, prefill_buckets=(8, 16), block_buckets=(12,), num_blocks=64)


def _with_kernels(fn):
    """``fn()`` while the paged programs are built with their kernels (under the
    interpreter) and not the XLA forms."""
    with pytest.MonkeyPatch.context() as env:
        set_attn_form(env, "interpreted")
        return fn()


@functools.cache
def _ragged():
    """One long row among three short ones through the engine with the kernels in its programs."""
    cfg, params = _model()
    reqs = _reqs(cfg, (3, 3, 3, 40), 5, seed=80)

    def serve():
        eng = _engine(cfg, params, goodput=True, **PAGED)
        return reqs, eng, eng.run([dict(r) for r in reqs])
    return _with_kernels(serve)


@case("ragged-blocks_walked")
def _(micro):
    """One long row among three short ones: the tables span Bb x nbb blocks a
    decode dispatch, the rows' live ranges less than half of that."""
    _, eng, _ = _ragged()
    blk = eng.stats()["goodput"]["blocks"]
    per = eng.goodput_report()["blocks_per_kind"]["decode_paged"]
    assert per["dispatches"] == eng.stats()["decode_steps"]
    assert blk["walked"] == per["dispatches"] * 4 * 12
    assert blk["walked"] >= 2 * blk["real"] > 0


@case("ragged-warm_engine_new_programs")
def _(micro):
    """A second paged engine with paged chunk prefill compiles nothing and
    serves the same tokens."""
    cfg, params = micro
    reqs, eng, res = _ragged()
    warm = _with_kernels(lambda: _engine(cfg, params, goodput=True, **PAGED))
    res_w = _with_kernels(lambda: warm.run([dict(r) for r in reqs]))
    st = warm.stats()
    assert st["attn"]["path"] == "walk" and st["attn"]["chunk"] == "paged" and st["chunk_runs"] > 0
    assert sum(warm.compile_counts.values()) == 0
    assert _tokens(res_w) == _tokens(res)


@case("ragged-compiles_within_bucket_bound")
def _(micro):
    """A job has one decode kind and one chunk kind."""
    cfg, params = _model(vocab_size=56)
    eng = _with_kernels(lambda: _engine(cfg, params, **PAGED))
    _with_kernels(lambda: eng.run(_reqs(cfg, (3, 5, 21, 40), 4, seed=90)))
    st = eng.stats()
    counts = {k: v for k, v in st["compile_counts"].items() if v}
    assert set(counts) <= {"prefill", "prefill_fresh", "prefill_chunk_paged", "decode_paged"}, counts
    assert 0 < sum(counts.values()) <= st["bucket_bound"]


@case("paged_attn-kernel_steps")
def _(micro):
    """Every decode dispatch of the paged engine went through the kernel."""
    _, eng, _ = _ragged()
    st = eng.stats()
    assert st["attn"]["path"] == "walk" and st["decode_steps"] > 0
    assert st["attn"]["fallback_steps"] == 0


#
# serving_dp: two replicas behind the router
#


def _fleet(cfg, params, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("block_buckets", (4, 16))
    kw.setdefault("prefill_buckets", (8, 16, 64))
    kw.setdefault("num_blocks", 48)
    return _engine(cfg, params, **kw)


def _family(cfg, n, length):
    base = _prompt(77, length, cfg)
    out = []
    for i in range(n):
        p = base.copy()
        p[-1] = (i + 1) % cfg.vocab_size
        out.append(p)
    return out


@functools.cache
def _segregated():
    """A long shared-prefix family, then three short strangers, through two
    replicas."""
    cfg, params = _model()
    longs = _family(cfg, 3, 40)
    shorts = [_prompt(100 + i, 5, cfg) for i in range(3)]
    reqs = [{"prompt": p, "max_new_tokens": 4} for p in longs + shorts]
    fleet = _fleet(cfg, params)
    handles = [fleet.submit(**r) for r in reqs]
    fleet.drain()
    return reqs, fleet, handles, fleet.stats()


@case("serving_dp-routed_is_everything_submitted")
def _(micro):
    """Nothing stays in the router: every request was routed, the lanes' counts
    add up, and a drained fleet is level."""
    reqs, _, handles, st = _segregated()
    r = st["router"]
    assert r["submitted"] == r["routed"] == len(reqs)
    assert sum(r["routed_by_replica"]) == r["routed"] and all(r["routed_by_replica"])
    assert r["queue_depth"] == 0 and r["expired"] == 0 and r["imbalance"] == 0
    assert all(len(h.result(drive=False).new_tokens) == 4 for h in handles)


@case("serving_dp-shape_segregation")
def _(micro):
    """What the router is for: the long family stays on one lane, and the
    other lane never builds a decode program at the wide table."""
    _, fleet, handles, st = _segregated()
    long_lanes = {h.replica for h in handles[:3]}
    assert len(long_lanes) == 1 and st["router"]["affinity_hits"] >= 2
    (long_lane,) = long_lanes
    short_eng = fleet.engines[1 - long_lane]
    widths = {k[2] for k in short_eng._programs if k[0] == "decode_paged"}
    assert widths == {4}, widths
    wide = {k[2] for k in fleet.engines[long_lane]._programs if k[0] == "decode_paged"}
    assert 16 in wide


@case("serving_dp-decode_compiles_within_bucket_bound")
def _(micro):
    """Each lane's programs are inside its own bucket bound."""
    _, fleet, _, st = _segregated()
    for eng, per in zip(fleet.engines, st["per_replica"]):
        assert sum(per["compile_counts"].values()) <= per["bucket_bound"]
        assert per["decode_steps"] > 0


#
# serving_spec: the speculative lane
#


def _spec_engine(**kw):
    """The shared model as target, a one-layer model of the family as draft."""
    cfg, params = _model()
    dcfg = llama.Config.from_name("tiny-llama-debug", **{**MICRO, "n_layer": 1})
    draft = llama.init_params(dcfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    return _engine(cfg, params, num_blocks=64, speculative=SpecConfig(draft, dcfg, K=2),
                   retry=RetryPolicy(sleep=lambda s: None), **kw)


@functools.cache
def _speculated():
    cfg, _ = _model()
    reqs = _reqs(cfg, (5, 6, 7), 8, seed=110)
    eng = _spec_engine()
    return reqs, eng, eng.run([dict(r) for r in reqs])


@case("serving_spec-accept_len_hist_counts_every_live_row")
def _(micro):
    """With several rows a round, the histogram has one entry a live row a
    round: at least the rounds, and as many as the draft tokens over K."""
    _, eng, _ = _speculated()
    sp = eng.stats()["spec"]
    entries = sum(sp["accept_len_hist"].values())
    assert entries > sp["rounds"] > 0                      # rows shared rounds
    assert entries == eng.spec_draft_tokens // 2
    assert eng.spec_accepted_tokens == sum((n - 1) * c for n, c in sp["accept_len_hist"].items())


@case("serving_spec-warm_engine_new_programs")
def _(micro):
    """A second speculative engine compiles none of the lane's kinds."""
    reqs, _, res = _speculated()
    second = _spec_engine()
    res2 = second.run([dict(r) for r in reqs])
    assert {"draft_decode", "verify_paged", "spec_prefill"} <= set(second.compile_counts)
    assert sum(second.compile_counts.values()) == 0
    assert not any(r.prefill_compiled for r in res2)
    assert _tokens(res2) == _tokens(res)


#
# goodput, sessions, recovery
#


@case("goodput-new_programs_with_goodput_speculative")
def _(micro):
    """The ledger is no part of the speculative lane's program keys either."""
    from thunder_tpu.serving.engine import _program_cache

    cfg, _ = micro
    reqs = _reqs(cfg, (5, 6, 7), 6, seed=120)
    off = _spec_engine()
    res = off.run([dict(r) for r in reqs])
    keys = set(_program_cache)
    on = _spec_engine(goodput=True)
    res_on = on.run([dict(r) for r in reqs])
    assert set(_program_cache) == keys
    assert sum(on.compile_counts.values()) == 0
    snap = on.stats()["goodput"]
    assert snap["committed"] + sum(snap["waste"].values()) == snap["positions"]
    assert _tokens(res_on) == _tokens(res)


@case("sessions-prefill_tokens_saved")
def _(micro):
    """Turn 2 of a resident session prefills its tail only: fewer prefill
    positions than a cold engine given the same history, by exactly the
    tokens of the blocks it re-attached."""
    cfg, params = micro
    kw = dict(goodput=True, num_blocks=48, prefill_buckets=(8, 16, 32), block_buckets=(8,))

    def prefill_positions(eng):
        per = eng.goodput_report()["per_kind"]
        return sum(v["positions"] - v["waste"].get("pad_prefill", 0)
                   for k, v in per.items() if k.startswith("prefill"))

    p1 = _prompt(130, 13, cfg)
    eng = _engine(cfg, params, sessions=True, **kw)
    r1 = eng.submit(p1, max_new_tokens=5, session_id="chat").result()
    before = prefill_positions(eng)
    p2 = np.concatenate([p1, np.asarray(r1.new_tokens, np.int32), _prompt(131, 3, cfg)])
    r2 = eng.submit(p2, max_new_tokens=4, session_id="chat").result()
    resident = prefill_positions(eng) - before
    cold_eng = _engine(cfg, params, **kw)
    rc = cold_eng.submit(p2, max_new_tokens=4).result()
    cold = prefill_positions(cold_eng)
    assert r2.new_tokens == rc.new_tokens
    assert r2.shared_prefix_blocks == (len(p1) + 5 - 1) // 4 and rc.shared_prefix_blocks == 0
    assert cold == len(p2)
    assert cold - resident == r2.shared_prefix_blocks * 4


@case("recovery-retry_and_rebuild_in_one_drive")
def _(micro):
    """A transient dispatch failure (retried in place) and an out-of-memory
    at harvest (arenas rebuilt, requests replayed) in the same drive: the
    tokens of the fault-free run, both paths taken, no block leaked."""
    cfg, params = micro
    reqs = _reqs(cfg, (5, 9, 6), 8, seed=140)
    retry = RetryPolicy(sleep=lambda s: None)
    ref = _engine(cfg, params, retry=retry).run([dict(r) for r in reqs])
    plan = FaultPlan(specs=[FaultSpec(point="decode.dispatch", kind="fail", at=2),
                            FaultSpec(point="harvest", kind="oom", at=5)])
    eng = _engine(cfg, params, retry=retry, fault_plan=plan)
    res = eng.run([dict(r) for r in reqs])
    assert _tokens(res) == _tokens(ref)
    assert plan.injected == 2 and eng.recoveries == 1
    assert eng.pool.num_free == eng.pool.num_usable


@case("recovery-tokens_replayed")
def _(micro):
    """A recovery mid-decode replays each running request's known tokens and
    no more: its prompt and what it has generated, less the newest token
    (whose KV the next decode step writes).  The requests' own bills say so,
    and the ledger's ``replay_recovery`` is those positions plus the slots
    of the one decode dispatch that was in flight and thrown away; the
    streams go on as if nothing had happened."""
    cfg, params = micro
    reqs = _reqs(cfg, (6, 9), 10, seed=170)
    ref = _engine(cfg, params).run([dict(r) for r in reqs])
    eng = _engine(cfg, params, goodput=True)
    handles = [eng.submit(**r) for r in reqs]
    while any(len(h._req.generated) < 4 for h in handles):
        eng.step()
    in_flight = eng._inflight_decode
    assert in_flight is not None and in_flight["bucket"][0] == 4
    eng.recover()
    expected = [len(r["prompt"]) + len(h._req.generated) - 1 for r, h in zip(reqs, handles)]
    eng.drain()
    res = [h.result(drive=False) for h in handles]
    assert eng.recoveries == 1
    assert [r.tokens_recomputed for r in res] == expected
    assert eng.stats()["goodput"]["waste"]["replay_recovery"] == sum(expected) + 4
    assert _tokens(res) == _tokens(ref)


#
# one decode step ahead
#


@case("decode_ahead-every_chained_step_between_turnovers")
def _(micro):
    """Four requests of nine tokens through four slots: token 0 is the
    prefill's, eight decode steps follow on one batch; the first builds the
    chain, the other seven go out before the harvest of the step before."""
    cfg, params = micro
    eng = _engine(cfg, params)
    res = eng.run(_reqs(cfg, (5, 6, 7, 8), 9, seed=180))
    assert all(r.finish_reason == "length" for r in res)
    assert eng.stats()["decode_ahead"] == {"dispatches": 8, "ahead": 7, "share": 7 / 8}
    assert eng.stats()["mean_batch_occupancy"] == 4.0


@case("decode_ahead-new_programs")
def _(micro):
    """The step ahead calls the programs the old order called: a model no
    other engine has served compiles the same kinds as often under
    ``async_step=False``, and a second async engine compiles none."""
    reqs = None
    counts = {}
    for mode, vocab in ((True, 72), (False, 80)):
        cfg, params = _model(vocab_size=vocab)
        reqs = _reqs(cfg, (5, 9, 6, 4, 7), 7, seed=190)
        eng = _engine(cfg, params, async_step=mode, max_batch=3)
        eng.run([dict(r) for r in reqs])
        counts[mode] = {k: v for k, v in eng.compile_counts.items() if v}
        assert (eng.stats()["decode_ahead"]["ahead"] > 0) == mode
    assert counts[True] == counts[False]
    cfg, params = _model(vocab_size=72)
    second = _engine(cfg, params, max_batch=3)
    second.run([dict(r) for r in _reqs(cfg, (5, 9, 6, 4, 7), 7, seed=190)])
    assert sum(second.compile_counts.values()) == 0 and second.stats()["decode_ahead"]["ahead"] > 0


@case("decode_ahead-none_under_speculation")
def _(micro):
    """A speculative round's next inputs hang on what the verify accepted:
    every round keeps the old order."""
    _, eng, _ = _speculated()
    st = eng.stats()["decode_ahead"]
    assert st["dispatches"] == eng.spec_rounds > 0 and st["ahead"] == 0 and st["share"] == 0.0


@case("decode_ahead-a_mesh_engine_runs_as_far_ahead")
def _(micro):
    """Under a ``tp`` mesh the chained state is placed as the programs place
    it: the same steps go out ahead as on one device, for the same tokens."""
    from thunder_tpu import distributed as dist

    cfg, params = micro
    reqs = _reqs(cfg, (3, 5, 9, 4, 6), 6, seed=200)
    single = _engine(cfg, params, max_batch=3)
    res = single.run([dict(r) for r in reqs])
    mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = _engine(cfg, params, mesh=mesh, max_batch=3)
    res_m = eng.run([dict(r) for r in reqs])
    assert eng.stats()["decode_ahead"] == single.stats()["decode_ahead"]
    assert 0 < eng.stats()["decode_ahead"]["ahead"] < eng.decode_steps
    assert _tokens(res_m) == _tokens(res)


@case("decode_ahead-quantised_arena_lora_and_sessions")
def _(micro):
    """An int8 arena (its scales are donated and parked with K and V), a LoRA
    mix and a session's parked blocks: the tokens of ``async_step=False``."""
    cfg, params = micro
    out = {}
    for mode in (True, False):
        reg = AdapterRegistry(cfg, max_adapters=2, rank=2)
        reg.register("a", make_lora_factors(cfg, 2, jax.random.PRNGKey(5), std=0.5))
        eng = _engine(cfg, params, async_step=mode, kv_dtype="int8", lora=reg, sessions=True, max_batch=3)
        reqs = _reqs(cfg, (5, 9, 6, 7), 8, seed=210)
        reqs[1]["adapter_id"] = reqs[3]["adapter_id"] = "a"
        reqs[0]["session_id"] = "s"
        res = eng.run([dict(r) for r in reqs])
        turn2 = np.concatenate([res[0].tokens, _prompt(215, 3, cfg)])
        res.append(eng.run([{"prompt": turn2, "max_new_tokens": 5, "session_id": "s"}])[0])
        out[mode] = _tokens(res)
        assert (eng.stats()["decode_ahead"]["ahead"] > 0) == mode
    assert out[True] == out[False]


#
# donation: a parameter tree's update
#


@case("donation-param_tree_update_aliases_every_leaf")
def _(micro):
    """``p - lr * g`` over a model's whole parameter tree under
    ``donate=True``: both trees are donated leaf by leaf, every new leaf
    lands in a donated buffer, and the peak falls by the tree's bytes."""
    from thunder_tpu.examine import memory_timeline
    from thunder_tpu.observability.metrics import registry

    _, params = micro
    grads = jax.tree_util.tree_map(lambda a: jnp.full_like(a, 0.5), params)
    leaves = jax.tree_util.tree_leaves(params)
    nbytes = sum(a.size * a.dtype.itemsize for a in leaves)

    def sgd(p, g):
        return jax.tree_util.tree_map(lambda a, b: a - 0.01 * b, p, g)

    off = tt.jit(sgd, donate=False)
    off(params, grads)
    donated = registry().counter("donation.buffers_donated").value
    on = tt.jit(sgd, donate=True)

    def copy(tree):
        return jax.tree_util.tree_map(lambda x: x.copy(), tree)

    new = on(copy(params), copy(grads))
    assert registry().counter("donation.buffers_donated").value - donated == 2 * len(leaves)
    regions = tt.donation_stats(on)["forward"]["regions"]
    assert sum(len(r["aliases"]) for r in regions) == len(leaves)
    t_on = memory_timeline(tt.last_traces(on)[-1])
    t_off = memory_timeline(tt.last_traces(off)[-1])
    assert t_on["donated_bytes"] == 2 * nbytes and t_off["donated_bytes"] == 0
    assert t_off["peak_bytes_estimate"] - t_on["peak_bytes_estimate"] == nbytes
    for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(off(params, grads))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#
# serving_mesh: a tensor-parallel engine
#


@case("serving_mesh-mean_batch_occupancy")
def _(micro):
    """The SPMD engine batches as the single-device engine does: the same
    rows a decode step, the same steps, for the same requests."""
    from thunder_tpu import distributed as dist

    cfg, params = micro
    reqs = _reqs(cfg, (3, 5, 9, 4), 5, seed=160)
    single = _engine(cfg, params)
    res = single.run([dict(r) for r in reqs])
    mesh = dist.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = _engine(cfg, params, mesh=mesh)
    res_m = eng.run([dict(r) for r in reqs])
    st, st1 = eng.stats(), single.stats()
    assert st["mesh"]["devices"] == 2
    assert st["decode_steps"] == st1["decode_steps"]
    assert st["mean_batch_occupancy"] == st1["mean_batch_occupancy"] > 1.0
    assert [len(r.new_tokens) for r in res_m] == [len(r.new_tokens) for r in res]


#
# the delta rule's state arena: a row's value heads side by side
#


@case("state_arena-two_value_heads_a_key_head_keep_solos_bits")
def _(micro):
    """Two value heads read one key head (Qwen3-Next's ratio; Olmo-Hybrid's is
    one to one, ``tests/test_hybrid_engine.py``): served through the arena's
    rows of heads side by side, in both forms of the decode program, the tokens
    are solo ``generate()``'s; and the pool counts a slot's bytes and what the
    chip's tiles hold for them."""
    from _hybrid_tiny import TINY, arch
    from chipbench import common
    from thunder_tpu.models import generate as G

    hf = {**TINY, "model_name": "serving-invariants-two-values-a-key", "linear_num_key_heads": 1, "num_hidden_layers": 2,
          "layer_types": ["linear_attention", "full_attention"]}
    cfg = llama.Config(**arch.program_config(hf))
    params = arch.make_params(hf, common.seed_words(3), dtype=jnp.float32)
    reqs = [(21, 7), (9, 6), (30, 5)]
    prompts = [_prompt(40 + i, n, cfg) for i, (n, _) in enumerate(reqs)]
    solo = [np.asarray(G.generate(params, p[None], cfg, n, T_max=64))[0, len(p):] for p, (_, n) in zip(prompts, reqs)]
    for form in ("xla", "interpreted"):
        with pytest.MonkeyPatch.context() as env:
            set_attn_form(env, form)
            eng = tt.serve(None, params, cfg, max_batch=2, num_blocks=24, block_size=16, prefill_buckets=[32],
                           batch_buckets=[2], block_buckets=[4])
            res = eng.run([{"prompt": p, "max_new_tokens": n} for p, (_, n) in zip(prompts, reqs)])
            st = eng.stats()["state"]
            eng.shutdown()
        for r, want in zip(res, solo):
            assert np.array_equal(np.asarray(r.new_tokens), want), form
        assert eng.pool.state.state.shape == (3, 1, 12, 2 * 24) and eng.pool.state.state_heads == 2
        assert st["arena_bytes"] == 3 * st["slot_bytes"] < st["arena_laid_out_bytes"] == 3 * st["slot_laid_out_bytes"]


@pytest.mark.parametrize("name", list(CASES))
def test_count_invariant(name, micro):
    CASES[name](micro)
