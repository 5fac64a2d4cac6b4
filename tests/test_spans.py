"""The program's spans on the profiler's clock.

``observability.events.span`` opens a ``jax.profiler.TraceAnnotation`` named
``thunder_tpu.<name>`` always, and writes the ring only where it did before
(the compile pipeline always, serving under ``trace=True``) and around what a
process does once a program (``serve.compile``, a ``TrainStep``'s first call as
``xla_compile``): a start-up is a timeline, a steady step writes nothing.  A
profiler session is the only switch for the rest: these tests start one over a few steps of the
micro engine and of a tiny ``TrainStep`` and read the ``.xplane.pb`` back.
Everything runs on the micro model, on the CPU.
"""
from __future__ import annotations

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import thunder_tpu as tt
from thunder_tpu import distributed as dist
from thunder_tpu.core import compile_cache
from thunder_tpu.models import llama
from thunder_tpu.observability import clear_events, events, span
from thunder_tpu.serving import engine as engine_mod

MICRO = dict(
    n_layer=1, n_head=2, n_embd=16, intermediate_size=32, vocab_size=32, block_size=64,
)
PREFIX = "thunder_tpu."

# every span of one engine step, with the arguments it has to carry and the
# span it has to lie inside (None: the step itself, or a first call's compile
# span, which lies in whichever dispatch made that call)
SERVE_SPANS = {
    "serve.step": (("step", "queued", "running", "t_ns"), None),
    "serve.harvest": ((), "serve.step"),
    "serve.harvest.wait": (("kind",), "serve.harvest"),
    "serve.harvest.emit": ((), "serve.harvest"),
    "serve.expire": ((), "serve.step"),
    "serve.decode_dispatch": (("rows", "bucket", "steady", "ahead", "ending"), "serve.step"),
    "serve.decode_dispatch.call": ((), "serve.decode_dispatch"),
    "serve.admit": (("admitted",), "serve.step"),
    "serve.prefill_dispatch": (("rid", "tokens", "bucket", "piece"), "serve.step"),
    "serve.gauges": ((), "serve.step"),
    "serve.compile": (("kind", "bucket"), "serve.step"),
}


@pytest.fixture(scope="module")
def micro():
    cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_dtype", jnp.float32)
    return tt.serve(None, params, cfg, **kw)


def _submit(eng, cfg, max_new=(4, 6, 5)):
    """Three prompts of 2, 5 and 8 tokens through two slots: the first row ends with the second standing."""
    rng = np.random.default_rng(7)
    return [eng.submit(rng.integers(0, cfg.vocab_size, (2 + 3 * i,)).astype(np.int32), max_new_tokens=m)
            for i, m in enumerate(max_new)]


class Profile:
    """The ``thunder_tpu.*`` host spans of one profiler session."""

    def __init__(self, directory):
        self.directory = str(directory)

    def __enter__(self):
        jax.profiler.start_trace(self.directory)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData

        (path,) = glob.glob(os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        self.spans = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        self.spans.append({"name": e.name[len(PREFIX):], "line": line.name,
                                           "start": e.start_ns, "end": e.start_ns + e.duration_ns,
                                           "args": {k: v for k, v in e.stats}})

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def parent(self, s):
        """The innermost other span on the same thread that holds ``s``."""
        around = [p for p in self.spans if p is not s and p["line"] == s["line"]
                  and p["start"] <= s["start"] and s["end"] <= p["end"]]
        return min(around, key=lambda p: p["end"] - p["start"]) if around else None

    def ancestors(self, s):
        out = []
        while (s := self.parent(s)) is not None:
            out.append(s["name"])
        return out


class TestSpanPrimitive:
    def test_span_writes_the_ring_pair_and_the_annotation(self, tmp_path):
        clear_events()
        with Profile(tmp_path) as prof:
            with span("unit.outer", a=1, label="x") as sp:
                sp.set(late=7)
                with span("unit.inner", ring=False):
                    pass
        ring = [(e["ph"], e["name"], e.get("args")) for e in events()]
        # one name a span: the ring's, and the profile's but for the prefix
        assert ring == [("B", "unit.outer", {"a": 1, "label": "x"}),
                        ("E", "unit.outer", {"late": 7})]
        (outer,), (inner,) = prof.named("unit.outer"), prof.named("unit.inner")
        assert outer["args"] == {"a": 1, "label": "x", "late": 7} and inner["args"] == {}
        assert prof.parent(inner) is outer

    def test_span_without_a_session_costs_no_ring_entry_when_told_so(self):
        clear_events()
        with span("unit.quiet", ring=False, k=1):
            pass
        assert events() == []

    def test_span_as_a_decorator_reenters_and_survives_an_exception(self):
        clear_events()

        @span("unit.fn", k=2)
        def down(n):
            if n == 0:
                raise ValueError("bottom")
            return down(n - 1)

        with pytest.raises(ValueError):
            down(2)
        phases = [e["ph"] for e in events() if e["name"] == "unit.fn"]
        assert phases == ["B"] * 3 + ["E"] * 3

    def test_compile_pipeline_spans_land_in_the_profile(self, tmp_path):
        with Profile(tmp_path) as prof:
            out = tt.jit(lambda a, b: (a @ b).sum())(np.ones((8, 8), np.float32),
                                                     np.ones((8, 8), np.float32))
        assert float(out) == 512.0
        names = {s["name"] for s in prof.spans}
        assert {"compile", "transform:dce", "lower", "codegen"} <= names
        assert {e["name"] for e in events() if e["ph"] == "B"} >= {"compile", "lower", "codegen"}
        # every stage lies inside `compile`; the fusion's own XLA compile comes
        # with its first run, after it
        assert all("compile" in prof.ancestors(s) for s in prof.spans
                   if s["name"] not in ("compile", "xla_compile"))
        assert prof.named("xla_compile")


class TestEngineSpans:
    @pytest.mark.parametrize("async_step", [True, False], ids=["async", "sync"])
    def test_a_profiler_session_yields_every_span_of_a_step(self, micro, tmp_path, monkeypatch,
                                                            async_step):
        cfg, params = micro
        # fresh programs, so that each first call is a serve.compile span
        monkeypatch.setattr(engine_mod, "_program_cache", {})
        eng = _engine(cfg, params, async_step=async_step)
        clear_events()
        handles = _submit(eng, cfg)
        calls0 = eng.step_calls
        with Profile(tmp_path) as prof:
            while not all(h.done() for h in handles):
                eng.step()
        steps = eng.step_calls - calls0
        eng.shutdown(drain=False)

        for name, (args, inside) in SERVE_SPANS.items():
            found = prof.named(name)
            assert found, f"no {name} span in the profile"
            for s in found:
                assert set(args) <= set(s["args"]), (name, s["args"])
                if inside is not None:
                    assert inside in prof.ancestors(s), (name, prof.ancestors(s))
        # children directly inside their parents, as the table nests them
        for child, parent in (("serve.harvest.wait", "serve.harvest"),
                              ("serve.harvest.emit", "serve.harvest"),
                              ("serve.decode_dispatch.call", "serve.decode_dispatch")):
            assert all(prof.parent(s)["name"] == parent for s in prof.named(child)), child
        step_spans = prof.named("serve.step")
        assert len(step_spans) == steps                          # once a step
        assert [s["args"]["step"] for s in step_spans] == list(range(calls0 + 1, calls0 + steps + 1))
        # t_ns is the ring's clock (perf_counter_ns) at the step's entry
        now = time.perf_counter_ns()
        assert all(0 < now - s["args"]["t_ns"] < 600e9 for s in step_spans)
        kinds = {s["args"]["kind"] for s in prof.named("serve.harvest.wait")}
        assert kinds == {"decode", "prefill"}
        assert all(("rows" in s["args"]) == (s["args"]["kind"] == "decode")
                   and ("rid" in s["args"]) == (s["args"]["kind"] != "decode")
                   for s in prof.named("serve.harvest.wait"))
        admitted = sum(s["args"]["admitted"] for s in prof.named("serve.admit"))
        assert admitted == len(handles)
        pieces = prof.named("serve.prefill_dispatch")
        assert sorted(s["args"]["tokens"] for s in pieces) == [2, 5, 8]   # not the padded bucket
        assert {s["args"]["piece"] for s in pieces} == {"prefill_fresh"}   # whole prompts at position 0
        compiled = {(s["args"]["kind"], s["args"]["bucket"]) for s in prof.named("serve.compile")}
        built = {(c["kind"], "{}x{}".format(*c["bucket"])) for c in eng._compile_log}
        assert compiled == built and len(prof.named("serve.compile")) == len(built)
        decodes = prof.named("serve.decode_dispatch")
        assert {s["args"]["steady"] for s in decodes} <= {0, 1}
        assert all(s["args"]["rows"] in (1, 2) for s in decodes)
        # ahead: the dispatch came before its step's harvest (a steady batch of
        # the async loop, and then it was chained); the benchmark's reader
        # counts them off the same file
        ahead = [s for s in decodes if s["args"]["ahead"]]
        assert {s["args"]["ahead"] for s in decodes} <= {0, 1} and bool(ahead) == async_step
        assert len(ahead) == eng.decode_ahead_steps and all(s["args"]["steady"] for s in ahead)
        # ending: the rows a step ahead carries past their end by length (dead row-steps the
        # host knew of); none in a steady step, nor in any step of the synchronous loop
        through = [s for s in decodes if s["args"]["ending"]]
        assert all(s["args"]["ahead"] and s["args"]["ending"] >= 1 for s in through)
        assert len(through) == eng.stats()["decode_ahead"].get("through_end", 0) and bool(through) == async_step
        assert any(s["args"]["ending"] == 0 for s in ahead) or not async_step
        for s in decodes if async_step else ():
            step = next(p for p in step_spans if p["start"] <= s["start"] and s["end"] <= p["end"])
            (h,) = [h for h in prof.named("serve.harvest") if step["start"] <= h["start"] <= step["end"]]
            assert (s["end"] <= h["start"]) == bool(s["args"]["ahead"])
        from chipbench import common, program_spans

        reader = common.load_reader("decode_ahead_share.serve")
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        spans = program_spans.load(path)
        assert reader.read({"program_spans": spans}) == len(ahead) / len(decodes)
        for sp in spans:
            sp.args.pop("ahead", None)      # a program that gives the span no such argument, as this PR's parent
        assert reader.read({"program_spans": spans}) is None and reader.read({"program_spans": []}) is None
        # no session's worth of ring entries: trace=False leaves the ring the first calls alone
        assert {e["name"] for e in events()} == {"serve.compile"}

    def test_no_trace_leaves_a_compile_pair_a_program_and_nothing_a_steady_step(self, micro, monkeypatch):
        cfg, params = micro
        monkeypatch.setattr(engine_mod, "_program_cache", {})
        eng = _engine(cfg, params, num_blocks=32)
        clear_events()
        prompt = np.arange(5, dtype=np.int32)
        eng.run([{"prompt": prompt, "max_new_tokens": 56}])     # every bucket a context of 61 reaches, built on the way
        ring = events()
        # one pair a program built, on the process's own track, each closed before the next opens
        assert [(e["ph"], e["name"]) for e in ring] == [("B", "serve.compile"), ("E", "serve.compile")] * len(eng._compile_log)
        assert ([(e["args"]["kind"], e["args"]["bucket"]) for e in ring if e["ph"] == "B"]
                == [(c["kind"], "{}x{}".format(*c["bucket"])) for c in eng._compile_log])
        assert {(e["cat"], e["pid"]) for e in ring} == {("thunder_tpu", os.getpid())} and len(ring) >= 4
        # fifty steps of the warm engine: not one event more
        handle = eng.submit(prompt, max_new_tokens=56)
        for _ in range(50):
            eng.step()
        assert not handle.done() and events() == ring
        eng.shutdown(drain=False)

    def test_trace_true_puts_the_step_spans_in_the_ring_under_the_same_names(self, micro):
        cfg, params = micro
        eng = _engine(cfg, params, trace=True)
        clear_events()
        eng.run([{"prompt": np.arange(5, dtype=np.int32), "max_new_tokens": 3}])
        eng.shutdown(drain=False)
        engine_track = [e for e in events() if e["cat"] == "serving.engine"]
        names = {e["name"] for e in engine_track}
        assert names >= set(SERVE_SPANS) - {"serve.compile"}
        for name in names:
            assert (sum(e["ph"] == "B" for e in engine_track if e["name"] == name)
                    == sum(e["ph"] == "E" for e in engine_track if e["name"] == name) > 0)
        ends = [e for e in engine_track if e["ph"] == "E" and e["name"] == "serve.admit"]
        assert sum(e["args"]["admitted"] for e in ends) == 1


class TestTrainSpans:
    def test_train_step_and_snapshot_spans(self, tmp_path):
        from thunder_tpu.train import train_loop

        cfg = llama.Config.from_name("tiny-llama-debug", **MICRO)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, 8)

        def loss_fn(p, i, t, c, s):
            return llama.gpt_loss(p, i, t, c, s, cfg)

        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        step = dist.make_train_step(loss_fn, optax.sgd(0.1), mesh, donate=False)
        opt_state = step.init_optimizer_state(params)
        batch = (idx, idx, cos, sin)
        clear_events()
        with Profile(tmp_path) as prof:
            res = train_loop(step, params, opt_state, lambda s: batch, steps=3)
        assert res.steps_run == 3
        assert [s["args"]["step"] for s in prof.named("train.step")] == [0, 1, 2]
        (snap,) = prof.named("train.snapshot")
        state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves((params, opt_state)))
        assert snap["args"]["bytes"] == state_bytes > 0
        # the first call builds the step: tt.jit's pipeline lies inside it
        first = prof.named("train.step")[0]
        assert any(first["start"] <= s["start"] and s["end"] <= first["end"]
                   for s in prof.named("compile"))
        # a ring pair a step would evict the compile spans: the step stays out
        ring = {e["name"] for e in events()}
        assert "train.step" not in ring and "train.snapshot" in ring
        # the first call of the step just built is a ring span of its own, after the build's
        first_calls = [e for e in events() if e["name"] == "xla_compile"]
        assert [(e["ph"], e.get("args")) for e in first_calls] == [("B", {"fn": "train_step"}), ("E", None)]
        build_end = next(e["ts"] for e in events() if e["ph"] == "E" and e["name"] == "compile")
        assert build_end <= first_calls[0]["ts"]
        (in_profile,) = [s for s in prof.named("xla_compile") if s["args"].get("fn") == "train_step"]
        assert first["start"] <= in_profile["start"] and in_profile["end"] <= first["end"]
        # twenty more steps: nothing in the ring
        before = events()
        p, o = res.params, res.opt_state
        for _ in range(20):
            p, o, loss = step(p, o, *batch)
        assert np.isfinite(float(loss)) and events() == before


def test_compile_seconds_grow_after_a_fresh_jit(jax_stretches):
    before = compile_cache.stats()
    assert {"jaxpr_trace_s", "lower_s", "backend_compile_s"} <= set(before)
    x = jnp.ones((32, 32))
    jax.jit(lambda a: jnp.tanh(a @ a) + 25.0)(x).block_until_ready()   # a program nobody built yet
    after = compile_cache.stats()
    for k in ("jaxpr_trace_s", "lower_s", "backend_compile_s"):
        assert after[k] > before[k], k
    jax.jit(lambda a: a)(x)                     # the listener survives a second registration
    compile_cache._register_listeners()
    again = compile_cache.stats()
    jax.jit(lambda a: jnp.tanh(a @ a) + 26.0)(x).block_until_ready()
    grown = compile_cache.stats()["backend_compile_s"] - again["backend_compile_s"]
    assert 0 < grown < 60


# the six entries that split `setup_s`, each with its bucket of `chipbench/setup_spans.py`'s table
SETUP_ENTRIES = {"setup_import_s": "import_s", "setup_prefill_programs_s": "prefill_programs_s",
                 "setup_decode_programs_s": "decode_programs_s", "setup_step_programs_s": "step_programs_s",
                 "setup_other_programs_s": "other_programs_s", "setup_unspanned_s": "unspanned_s"}


@pytest.mark.parametrize("entry", sorted(SETUP_ENTRIES))
def test_a_setup_reader_reads_its_bucket_of_a_hand_written_ring(entry, monkeypatch):
    from chipbench import common, setup_spans
    from thunder_tpu import observability as obs

    doc = common.load_json("tests", "data", "setup_ring.json")
    ring = doc["events"]
    monkeypatch.setattr(obs, "events", lambda: ring)
    reader = common.load_reader(entry)
    ctx = {"t_process": doc["t_process"], "setup_s": doc["setup_s"]}
    assert reader.read(ctx) == doc["expected"][SETUP_ENTRIES[entry]]
    # every second of set-up is in one bucket: the six entries, the build's spans and the snapshot
    table = setup_spans.of(ctx)
    assert {k: table[k] for k in doc["expected"]} == doc["expected"]
    assert sum(table[b] for b in SETUP_ENTRIES.values()) + table["pipeline_s"] + table["snapshot_s"] == doc["setup_s"]
    m = next(m for m in common.manifest()["per_layer"] if m["name"] == entry)
    assert (m["unit"], m["better"], m["source"], m["moves"]) == ("s", "lower", "program_span", "setup_s")
    # no part sums: a ring that is full has lost its oldest events, and one without the
    # `import` event is a program that keeps no such timeline (this PR's parent)
    fresh = {"t_process": doc["t_process"], "setup_s": doc["setup_s"]}
    monkeypatch.setattr(obs, "events", lambda: [e for e in ring if e["name"] != "import"])
    assert reader.read(dict(fresh)) is None
    monkeypatch.setattr(obs, "events", lambda: ring)
    monkeypatch.setattr(obs, "event_buffer_capacity", lambda: len(ring))
    assert reader.read(dict(fresh)) is None
