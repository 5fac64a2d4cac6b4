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
"""
from __future__ import annotations

import os
import re
import threading

__all__ = ["enable", "ensure_enabled", "stats", "cache_dir"]

_lock = threading.Lock()
_enabled_dir: str | None = None
_listener_registered = False
_counts = {"persistent_cache_hits": 0, "persistent_cache_misses": 0,
           "jaxpr_trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0}
_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "backend_compile_s"}


def _checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _default_dir() -> str:
    return os.path.join(_checkout_root(), ".jax_cache")


def _on_event(name: str, **kwargs) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _counts["persistent_cache_hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _counts["persistent_cache_misses"] += 1


def _on_duration(name: str, secs: float, **kwargs) -> None:
    key = _DURATIONS.get(name)
    if key is not None:
        _counts[key] += secs


def _register_listeners() -> None:
    global _listener_registered
    if not _listener_registered:
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
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


def stats() -> dict:
    """Process-wide persistent-cache counters: ``persistent_cache_hits`` is
    programs loaded from disk instead of compiled (cross-process reuse),
    ``persistent_cache_misses`` is fresh compilations written to the cache;
    ``jaxpr_trace_s``, ``lower_s`` and ``backend_compile_s`` are the seconds
    spent in Python tracing, in lowering and in the backend (compile or cache
    load) since the cache was switched on."""
    return dict(_counts, dir=_enabled_dir)
