"""Persistent XLA compilation cache (reference analog: nvFuser's serialized
fusion cache, ``thunder/executors/nvfuserex_impl.py:527-568``, env
``ENABLE_NVFUSER_SERIALIZATION``).

Every process that compiles the same HLO reuses the on-disk artifact instead
of recompiling: a serving process warms tens of bucket programs, a training
step takes the better part of a minute to compile, and a machine that keeps
the directory starts the next process warm.

Where the cache lives is not this module's choice:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it into
  ``jax_compilation_cache_dir``; nothing here sets a directory.
- otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored): a cache
  that moves between runs is never found again.

Switched on lazily by the entry points that compile large programs
(``thunder_tpu.jit``, ``TrainStep``, ``ServingEngine``), so a plain ``import
thunder_tpu`` never mutates jax config.  Cross-process hit/miss counters come
from jax's monitoring events (``/jax/compilation_cache/cache_hits`` /
``cache_misses``), and the seconds every ``jax.jit`` of the process spent
tracing to a jaxpr, lowering it to MLIR, and in the backend's compile (a
compilation on a cold cache, a load on a warm one) from its duration events
(``/jax/core/compile/*_duration``); both surface via ``stats()`` /
``thunder_tpu.compile_stats``.

JAX names the function with each of those stretches (``fun_name``), so they
are kept by program too.  Each stretch is one complete event in the event
ring (``observability/events.py``): ``jax.trace``, ``jax.lower`` and
``jax.backend_compile`` [``fun_name``; ``cache_hit`` 0/1 on the last where
the persistent cache answered or was written inside the stretch], on the
ring's clock, so a process's start-up is a timeline
(``tt.export_chrome_trace``) with every program under the span that asked
for it (a stretch inside another that lasts under a millisecond is left to
its parent: ``RING_MIN_NESTED_S``).  A jit traced inside another is a stretch
inside a stretch: JAX marks each stretch's start too (a scalar event), which
keeps one stack a thread, and ``stats()["trace_self_s"]`` counts every second
of tracing once.  ``stats()["by_program"]`` is the same by ``fun_name``.
"""
from __future__ import annotations

import os
import re
import threading
import time

__all__ = ["enable", "ensure_enabled", "stats", "cache_dir"]

_lock = threading.Lock()
_enabled_dir: str | None = None
_listener_registered = False
_counts = {"persistent_cache_hits": 0, "persistent_cache_misses": 0,
           "jaxpr_trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
           "trace_self_s": 0.0}
# JAX's event of each stage -> its counter, its column in a program's row and
# its name in the event ring
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": ("jaxpr_trace_s", 0, "jax.trace"),
           "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_s", 1, "jax.lower"),
           "/jax/core/compile/backend_compile_duration": ("backend_compile_s", 2, "jax.backend_compile")}
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": ("persistent_cache_hits", 1),
                 "/jax/compilation_cache/cache_misses": ("persistent_cache_misses", 0)}
BY_PROGRAM_ROWS = 16
# A stretch inside another that is shorter than this is counted but not written
# to the ring: JAX's own jitted primitives (``add``, ``less``, ``_where``),
# traced by the thousand inside a model's trace, would fill it (2,446 of a
# rehearsed serve cell's 2,504 trace events, 0.15 s of its 13).  In the
# timeline their seconds are their parent's.
RING_MIN_NESTED_S = 1e-3
# fun_name -> [stretches of each stage (3), trace_self_s, lower_s, backend_s]
_programs: dict[str, list] = {}
# one stack of open stretches a thread: [event, seconds of the stretches
# nested in it, cache_hit]
_open = threading.local()


def _checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _default_dir() -> str:
    return os.path.join(_checkout_root(), ".jax_cache")


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _on_event(name: str, **kwargs) -> None:
    counted = _CACHE_EVENTS.get(name)
    if counted is not None:
        _counts[counted[0]] += 1
        # JAX reads and writes the persistent cache inside the backend's
        # stretch, on the thread that asked: the stretch open on this thread
        # is the program the event is about
        stack = _stack()
        if stack and stack[-1][0] == _BACKEND:
            stack[-1][2] = counted[1]


def _on_scalar(name: str, value, **kwargs) -> None:
    """JAX marks the start of each stretch it times with a scalar event."""
    if name in _STAGES:
        _stack().append([name, 0.0, None])


def _on_duration(name: str, secs: float, **kwargs) -> None:
    stage = _STAGES.get(name)
    if stage is not None:
        _counts[stage[0]] += secs


_WRAPPED = re.compile(r"\w+\((.*)\)")


def _program_name(fun_name: str) -> str:
    """JAX's ``fun_name`` without the API's wrapper: tracing says ``step``,
    lowering and the backend ``jit(step)``; one program, one name."""
    m = _WRAPPED.fullmatch(fun_name)
    return m.group(1) if m else fun_name


def _on_time_span(name: str, start: float, end: float, fun_name: str = "", **kwargs) -> None:
    """One stretch of one program, as it ends: into the ring as a complete
    event on the ring's clock (JAX's own is ``time.time``: the event ends
    now and began JAX's elapsed seconds ago), and into the program's row."""
    stage = _STAGES.get(name)
    if stage is None:
        return
    now_us = time.perf_counter_ns() / 1e3
    _, column, ring_name = stage
    secs = end - start
    stack = _stack()
    nested_s, cache_hit = 0.0, None
    while stack:                        # its own mark; any above it was left by an exit nobody saw
        event, inside_s, hit = stack.pop()
        if event == name:
            nested_s, cache_hit = inside_s, hit
            break
    if stack:
        stack[-1][1] += secs
    program = _program_name(str(fun_name))
    row = _programs.setdefault(program, [0, 0, 0, 0.0, 0.0, 0.0])
    row[column] += 1
    if column == 0:
        # self time: what was traced (or, for an eager operation on the way,
        # lowered and compiled) inside this stretch has a stretch of its own
        secs_own = max(0.0, secs - nested_s)
        _counts["trace_self_s"] += secs_own
    else:
        secs_own = secs
    row[3 + column] += secs_own
    if stack and secs < RING_MIN_NESTED_S:
        return
    from thunder_tpu.observability.events import record_event

    args = {"fun_name": program}
    if cache_hit is not None:
        args["cache_hit"] = cache_hit
    record_event("X", ring_name, args, ts=now_us - secs * 1e6, dur=secs * 1e6)


def _register_listeners() -> None:
    global _listener_registered
    if not _listener_registered:
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_scalar_listener(_on_scalar)
        _listener_registered = True


def enable() -> str:
    """Switches the persistent cache on and registers the hit/miss counter.
    Returns the active directory: the one ``JAX_COMPILATION_CACHE_DIR``
    names, else ``<checkout>/.jax_cache``.  Idempotent."""
    global _enabled_dir
    with _lock:
        if _enabled_dir is not None:
            return _enabled_dir

        import jax
        from jax.experimental.compilation_cache import compilation_cache as jax_cc

        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            directory = jax.config.jax_compilation_cache_dir
        else:
            directory = _default_dir()
            os.makedirs(directory, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", directory)
            # jax decides once, at the first compile of the process, whether
            # the cache is in use; by now params have usually been built, so
            # make it decide again
            jax_cc.reset_cache()
        # A profile reads an operation's scope (``observability.scope``) and
        # source line from the executable's own metadata, and JAX leaves
        # metadata out of the cache key by default: a program that differs from
        # a cached one in its scopes alone would load the older executable and
        # show the older names.  So metadata is part of the key, with source
        # paths cut to the checkout's root so that a checkout elsewhere still
        # finds the entries (a line that moves in a traced frame does miss).
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        if not jax.config.jax_hlo_source_file_canonicalization_regex:
            jax.config.update("jax_hlo_source_file_canonicalization_regex",
                              "^" + re.escape(_checkout_root() + os.sep))
        # persist everything: device programs all cross any threshold, and the
        # small eager ops around them are cheap to store
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _register_listeners()
        _enabled_dir = directory
        return _enabled_dir


def ensure_enabled() -> str | None:
    """The lazy hook the entry points call.

    With ``JAX_COMPILATION_CACHE_DIR`` unset and the platform pinned to the
    CPU (the test suite) the cache stays off: XLA:CPU logs a machine-feature
    mismatch on every cached load, and warm CPU starts are not what the cache
    is for.  The check reads jax config only, never ``jax.devices()``."""
    if _enabled_dir is None and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        if jax.config.jax_platforms == "cpu":
            return None
    return enable()


def cache_dir() -> str | None:
    return _enabled_dir


def _row(row: list) -> dict:
    return {"n": max(row[:3]), "trace_self_s": round(row[3], 6),
            "lower_s": round(row[4], 6), "backend_s": round(row[5], 6)}


def stats() -> dict:
    """Process-wide persistent-cache counters: ``persistent_cache_hits`` is
    programs loaded from disk instead of compiled (cross-process reuse),
    ``persistent_cache_misses`` is fresh compilations written to the cache;
    ``jaxpr_trace_s``, ``lower_s`` and ``backend_compile_s`` are the seconds
    spent in Python tracing, in lowering and in the backend (compile or cache
    load) since the cache was switched on.  ``jaxpr_trace_s`` counts a jit
    traced inside another in both; ``trace_self_s`` counts it once (each
    stretch less the stretches inside it).  ``by_program`` is where they
    went: the ``BY_PROGRAM_ROWS`` programs with the most seconds, ``fun_name
    -> {n, trace_self_s, lower_s, backend_s}`` (``n``: how often the program
    went through the stage it went through most: 2 is a program built
    twice), and the rest summed as ``others``."""
    rows = sorted(_programs.items(), key=lambda kv: -sum(kv[1][3:]))
    by_program = {name: _row(row) for name, row in rows[:BY_PROGRAM_ROWS]}
    rest = [row for _, row in rows[BY_PROGRAM_ROWS:]]
    if rest:
        by_program["others"] = _row([sum(col) for col in zip(*rest)])
    return dict(_counts, by_program=by_program, dir=_enabled_dir)
