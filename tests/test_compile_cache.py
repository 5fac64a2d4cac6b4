"""Persistent XLA compilation cache (core/compile_cache.py).

Reference analog: nvFuser's serialized fusion cache
(``thunder/executors/nvfuserex_impl.py:527-568``) — compiled programs
survive the process, so a second process on a machine that keeps the
directory starts warm instead of recompiling.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when set (nothing in the
program sets a directory then), else the fixed ``<checkout>/.jax_cache``.
"""
import json
import os
import subprocess
import sys

import pytest

import thunder_tpu as tt
from thunder_tpu.core import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the serving entry point alone: no tt.jit, no TrainStep, params built (and
# so programs compiled) before the engine exists
_SERVE_CHILD = r"""
import json
from thunder_tpu._platform import force_cpu
force_cpu()
import jax, jax.numpy as jnp
import numpy as np
import thunder_tpu as tt
from thunder_tpu.core import compile_cache
from thunder_tpu.models import llama

cfg = llama.Config.from_name("tiny-llama-debug", n_layer=1, n_embd=32, n_head=2,
                             n_query_groups=1, intermediate_size=64, vocab_size=64)
params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
eng = tt.serve(None, params, cfg, block_size=4, num_blocks=8, max_batch=1)
h = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
eng.drain()
assert len(h.result(drive=False).new_tokens) == 2
ring = tt.observability.events()
print(json.dumps({**compile_cache.stats(),
                  "config_dir": jax.config.jax_compilation_cache_dir,
                  "built": eng._compile_log,
                  "ring": [e for e in ring if e["name"] in ("import", "serve.compile", "jax.backend_compile")]}))
"""

# no variable from outside, and a compile before the cache is switched on:
# jax latches "cache unused" at the first compile of a process
_DEFAULT_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
from thunder_tpu.core import compile_cache
compile_cache._default_dir = lambda: sys.argv[1]
jax.block_until_ready(jnp.ones(3) + 1)
compile_cache.enable()
jax.block_until_ready(jax.jit(lambda x: x * 3 - 1)(jnp.ones(5)))
print(json.dumps({**compile_cache.stats(),
                  "config_dir": jax.config.jax_compilation_cache_dir}))
"""


def _run_child(script, *argv, cache_dir=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, "-c", script, *map(str, argv)],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _listing(d):
    return set(os.listdir(d)) if os.path.isdir(d) else None


@pytest.fixture(scope="module")
def serve_runs(tmp_path_factory):
    """Two tt.serve processes, one after the other, sharing the directory
    JAX_COMPILATION_CACHE_DIR names."""
    cache_dir = tmp_path_factory.mktemp("serve_cache")
    default = compile_cache._default_dir()
    before = _listing(default)
    first = _run_child(_SERVE_CHILD, cache_dir=cache_dir)
    second = _run_child(_SERVE_CHILD, cache_dir=cache_dir)
    return {"dir": str(cache_dir), "first": first, "second": second,
            "default_untouched": _listing(default) == before}


class TestPersistentCompilationCache:
    def test_env_dir_is_honoured_and_not_overwritten(self, serve_runs):
        """The program set no directory of its own: jax's config still holds
        the variable's value, the artifacts are there, and nothing was
        written to the in-checkout default."""
        first = serve_runs["first"]
        assert first["dir"] == first["config_dir"] == serve_runs["dir"]
        assert os.listdir(serve_runs["dir"]), "no cache artifacts written"
        assert serve_runs["default_untouched"]

    def test_serve_process_populates_then_hits(self, serve_runs):
        """A process whose only entry point is tt.serve caches too (the
        engine switches the cache on, not just tt.jit and TrainStep):
        process 1 compiles and persists, process 2 loads from disk."""
        assert serve_runs["first"]["persistent_cache_misses"] > 0
        assert serve_runs["second"]["persistent_cache_hits"] > 0, serve_runs["second"]

    def test_default_dir_takes_effect_after_an_earlier_compile(self, tmp_path):
        """Without the variable the cache goes to the default path, and it
        works even though the process compiled before enable() ran."""
        d = tmp_path / "default"
        stats = _run_child(_DEFAULT_CHILD, d)
        assert stats["dir"] == stats["config_dir"] == str(d)
        assert stats["persistent_cache_misses"] > 0 and os.listdir(d)

    def test_cpu_suite_default_is_off(self, monkeypatch):
        """Platform pinned to the CPU and no variable: ensure_enabled() is a
        no-op (this suite must not fill the checkout's cache)."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(compile_cache, "_enabled_dir", None)
        assert compile_cache.ensure_enabled() is None
        assert compile_cache.cache_dir() is None

    def test_default_dir_is_the_fixed_in_checkout_path(self):
        assert compile_cache._default_dir() == os.path.join(_REPO, ".jax_cache")
        with open(os.path.join(_REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_compile_stats_surface(self):
        """compile_stats(jfn).persistent_cache exposes the counters in-process."""
        import numpy as np

        jfn = tt.jit(lambda x: x + 1)
        jfn(np.ones(4, dtype=np.float32))
        pc = tt.compile_stats(jfn).persistent_cache
        assert set(pc) == {"persistent_cache_hits", "persistent_cache_misses", "dir",
                           "jaxpr_trace_s", "lower_s", "backend_compile_s",
                           "trace_self_s", "by_program"}

    def test_a_process_start_up_is_in_the_ring(self, serve_runs):
        """`import` once, from before JAX's import to the package's last
        line; one `serve.compile` pair a program the engine built; and each
        program's backend stretch says whether the persistent cache answered
        (the first process wrote it, the second read it)."""
        for run, hit in ((serve_runs["first"], 0), (serve_runs["second"], 1)):
            ring = run["ring"]
            (imp,) = [e for e in ring if e["name"] == "import"]
            assert imp["ph"] == "X" and 0 < imp["args"]["jax_s"] < imp["dur"] / 1e6 < 120
            pairs = [e for e in ring if e["name"] == "serve.compile"]
            begins, ends = pairs[0::2], pairs[1::2]         # one at a time, on one thread
            assert [e["ph"] for e in pairs] == ["B", "E"] * len(begins) and pairs[0]["ts"] > imp["ts"] + imp["dur"]
            assert (sorted((b["args"]["kind"], b["args"]["bucket"]) for b in begins)
                    == sorted((c["kind"], "{}x{}".format(*c["bucket"])) for c in run["built"]))
            for b, e in zip(begins, ends):
                # the program under its own name, inside the span of its first call
                (mine,) = [x for x in ring if x["name"] == "jax.backend_compile"
                           and b["ts"] <= x["ts"] and x["ts"] + x["dur"] <= e["ts"]]
                assert mine["args"] == {"fun_name": b["args"]["kind"], "cache_hit": hit}


def _sleepy(seconds):
    import time

    time.sleep(seconds)         # at trace time: a stretch long enough to be told from the clock's grain


class TestStretchesByProgram:
    def test_a_jit_inside_a_jit_is_a_stretch_inside_a_stretch(self, jax_stretches):
        """JAX's three stages of both programs as complete events on the
        ring's clock, the inner's trace inside the outer's, and the inner's
        seconds counted once in `trace_self_s`."""
        import time

        import jax
        import jax.numpy as jnp

        from thunder_tpu.observability import clear_events, events

        @jax.jit
        def ring_inner(x):
            _sleepy(0.02)
            return jnp.tanh(x) * 2

        @jax.jit
        def ring_outer(x):
            _sleepy(0.01)
            return ring_inner(x) + 1

        clear_events()
        before = jax_stretches.stats()
        t0 = time.perf_counter_ns() / 1e3
        ring_outer(jnp.ones(4)).block_until_ready()
        t1 = time.perf_counter_ns() / 1e3
        after = jax_stretches.stats()
        ring = [e for e in events() if e["name"].startswith("jax.")]
        assert all(e["ph"] == "X" and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 for e in ring)
        by = {(e["name"], e["args"]["fun_name"]): e for e in ring}
        assert {("jax.trace", "ring_outer"), ("jax.lower", "ring_outer"), ("jax.backend_compile", "ring_outer"),
                ("jax.trace", "ring_inner")} <= set(by)
        outer, inner = by["jax.trace", "ring_outer"], by["jax.trace", "ring_inner"]
        assert outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["dur"] >= 20e3 and outer["dur"] >= 30e3
        # in order on one thread: traced, then lowered, then compiled
        assert (outer["ts"] + outer["dur"] <= by["jax.lower", "ring_outer"]["ts"]
                and by["jax.lower", "ring_outer"]["ts"] + by["jax.lower", "ring_outer"]["dur"]
                <= by["jax.backend_compile", "ring_outer"]["ts"])
        # JAX's own primitives, traced in a few hundred microseconds inside, stay out of the ring
        assert ("jax.trace", "tanh") not in by
        grown = {k: after[k] - before[k] for k in ("jaxpr_trace_s", "trace_self_s", "lower_s", "backend_compile_s")}
        # the inner's 20 ms are in the outer's stretch and in its own: twice in one sum, once in the other
        assert grown["jaxpr_trace_s"] >= grown["trace_self_s"] + 0.02 and 0.03 <= grown["trace_self_s"]
        rows = after["by_program"]
        assert rows["ring_outer"]["n"] == 1 and 0.01 <= rows["ring_outer"]["trace_self_s"] < 0.02 + 0.01
        assert rows["ring_inner"]["trace_self_s"] >= 0.02 and rows["ring_inner"]["backend_s"] == 0.0
        assert rows["ring_outer"]["lower_s"] > 0 and rows["ring_outer"]["backend_s"] > 0

    def test_the_rows_are_bounded_and_sum_to_the_counters(self, jax_stretches, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setattr(jax_stretches, "_programs", {})
        monkeypatch.setattr(jax_stretches, "_counts", {k: type(v)() for k, v in jax_stretches._counts.items()})
        for i in range(jax_stretches.BY_PROGRAM_ROWS + 4):
            fn = lambda x, i=i: x * i + 1                           # noqa: E731
            fn.__name__ = f"row_{i}"
            jax.jit(fn)(jnp.ones(3))
        st = jax_stretches.stats()
        rows = st["by_program"]
        assert len(rows) == jax_stretches.BY_PROGRAM_ROWS + 1 and rows["others"]["n"] >= 4
        assert sum(name.startswith("row_") for name in rows) >= jax_stretches.BY_PROGRAM_ROWS - 4
        for column, key in (("trace_self_s", "trace_self_s"), ("lower_s", "lower_s"),
                            ("backend_s", "backend_compile_s")):
            assert sum(r[column] for r in rows.values()) == pytest.approx(st[key], abs=1e-4), column
        assert 0 < st["trace_self_s"] <= st["jaxpr_trace_s"]
        # what the drivers subtract and print keeps its names
        assert set(st) == {"persistent_cache_hits", "persistent_cache_misses", "jaxpr_trace_s", "lower_s",
                           "backend_compile_s", "trace_self_s", "by_program", "dir"}
