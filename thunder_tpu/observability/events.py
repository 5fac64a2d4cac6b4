"""Spans: one primitive, two sinks.

:func:`span` is the program's one way to mark a stretch of host work.  It
opens a ``jax.profiler.TraceAnnotation("thunder_tpu." + name, **meta)``, so
while a ``jax.profiler`` trace is being recorded the span lies in the
``.xplane.pb`` on the profiler's clock, beside the device operations it
dispatched or waited for; with no profiler session the annotation is
inactive (under a microsecond) and there is nothing to switch on or off.  It
also records a ``B``/``E`` event pair, under the same name without the
prefix, into a bounded ring buffer that :func:`export_chrome_trace` writes
as Chrome-trace / Perfetto JSON.

Every stage of the compile pipeline (interpretation, each transform,
lowering/claiming, codegen, XLA compile) is a span that always writes the
ring.  So does what a process does once a program or once at all, which
makes its start-up a timeline: ``import`` (the package's own import, a
complete event with ``jax_s``, the part that was JAX's), ``xla_compile``
[``fn="train_step"``] around the first call of a ``TrainStep`` just built
(the name a fusion's first call has), and one complete event for each
stretch JAX itself timed, ``jax.trace`` / ``jax.lower`` /
``jax.backend_compile`` [``fun_name``] (``core/compile_cache.py`` writes
them from JAX's monitoring events, stamped on this ring's clock).  The
serving engine's step phases (``serve.step`` and its children) and the
training step (``train.step``) pass ``ring=False`` unless request tracing is
on (``tt.serve(trace=True)``): a ring pair every few milliseconds for ever
would only evict the compile spans.  Two of the engine's spans are the
exception and write the ring always, because no steady step opens them:
``serve.compile`` [``kind``, ``bucket``] around the first call of a program
the engine built (without it the ``jax.*`` stretches of a serving process
lie under nothing and nobody can say which bucket they were for), and
``serve.recover``.  The serving plane (``observability/tracing.py``) also
records *async* per-request lifecycle spans (``ph: "b"/"e"`` keyed by request
id) into the same ring; each ``serve.step`` carries ``t_ns``, the ring's
clock at its entry, so an operator can lay those on the profiler's timeline.
The oldest events drop first (an orphaned ``B`` from eviction is tolerated
by Perfetto), so long-running processes never grow unbounded.  Nothing on
``tt.jit``'s per-call *dispatch* path opens a span.

The ring capacity (``THUNDER_TPU_EVENT_BUFFER``) is re-read on every
append, so changing it after import takes effect on the next recorded
event.

``span`` is a ``contextlib.ContextDecorator``: it also works as a decorator
(each call re-creates the context).

:func:`scope` is ``span``'s twin for the device: it names the *operations*
traced inside it, not the host time spent tracing them.  A path of scopes
("blk0/mixer/qkv") ends up as the ``op_name`` of every HLO instruction the
enclosed code lowers to, which the device's profile reports for each
operation it ran (XProf's op name column; ``chipbench/op_scopes.py``).  It
is metadata on the instruction: the compiled code is the same with or
without it, so it too is always on.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import ContextDecorator

from jax import named_scope
from jax.profiler import TraceAnnotation

from thunder_tpu.core.symbol import scope_path
from thunder_tpu.core.trace import get_tracectx
from thunder_tpu.observability.config import event_buffer_capacity

__all__ = [
    "record_event",
    "span",
    "scope",
    "GROUPS",
    "events",
    "clear_events",
    "export_chrome_trace",
    "register_process_name",
    "register_thread_name",
]

_events: deque = deque(maxlen=event_buffer_capacity())
# display-name registries consulted at export time; serving tracers register
# their synthetic pid/tid tracks here ("thunder_tpu serving", "req 3", ...)
_process_names: dict[int, str] = {}
_thread_names: dict[tuple[int, int], str] = {}


def _ensure_capacity() -> None:
    """Re-applies the configured ring capacity when it changed since the
    last append (capacity is NOT frozen at import; see module docstring)."""
    global _events
    cap = event_buffer_capacity()
    if _events.maxlen != cap:
        _events = deque(_events, maxlen=cap)


def record_event(
    ph: str,
    name: str,
    args: dict | None = None,
    *,
    cat: str = "thunder_tpu",
    pid: int | None = None,
    tid: int | None = None,
    id: int | None = None,
    ts: float | None = None,
    dur: float | None = None,
) -> None:
    """Appends one Chrome-trace event (``ph``: "B"/"E"/"b"/"e"/"i"/"X"...)
    stamped with the monotonic clock in microseconds.  ``cat`` groups the
    event into a track family (``"thunder_tpu"`` = compile pipeline,
    ``"serving.*"`` = the serving plane); ``pid``/``tid`` default to the
    real process/thread but may name a synthetic display track; ``id`` keys
    async (``"b"``/``"e"``) span pairs — the serving tracer uses the
    request id.  A complete event (``"X"``) of a stretch somebody else
    timed gives its own ``ts`` (its start on the same clock,
    ``time.perf_counter_ns() / 1e3``) and ``dur``, both in microseconds; it
    is appended when it ends, so the ring is not in ``ts`` order."""
    ev = {
        "ph": ph,
        "name": name,
        "cat": cat,
        "ts": time.perf_counter_ns() / 1e3 if ts is None else ts,
        "pid": os.getpid() if pid is None else pid,
        "tid": threading.get_ident() if tid is None else tid,
    }
    if dur is not None:
        ev["dur"] = dur
    if id is not None:
        ev["id"] = id
    if args:
        ev["args"] = args
    _ensure_capacity()
    _events.append(ev)


PROFILE_PREFIX = "thunder_tpu."


class span(ContextDecorator):
    """Marks the enclosed host work as ``name`` (exception-safe): a profiler
    annotation ``thunder_tpu.<name>`` carrying ``meta`` as its arguments,
    always, and a ``B``/``E`` pair in the ring unless ``ring`` is false.
    ``track`` names the ring events' display track (``cat``/``pid``/``tid``
    of :func:`record_event`).  :meth:`set` adds arguments that are known
    only inside the span.  Usable as a context manager or as a decorator."""

    __slots__ = ("name", "ring", "track", "meta", "_annotation", "_late")

    def __init__(self, name: str, *, ring: bool = True, track: dict | None = None, **meta):
        self.name, self.ring, self.track, self.meta = name, ring, track or {}, meta
        self._late = None

    def set(self, **meta) -> None:
        """More arguments for the open span: on the annotation at once, in
        the ring on its ``E`` event."""
        self._annotation.set_metadata(**meta)
        if self.ring:
            self._late = {**(self._late or {}), **meta}

    def _recreate_cm(self):
        # as a decorator: a fresh context a call, so a decorated function may recurse
        return span(self.name, ring=self.ring, track=self.track, **self.meta)

    def __enter__(self):
        self._annotation = TraceAnnotation(PROFILE_PREFIX + self.name, **self.meta)
        self._annotation.__enter__()
        if self.ring:
            record_event("B", self.name, self.meta or None, **self.track)
        return self

    def __exit__(self, *exc):
        if self.ring:
            record_event("E", self.name, self._late, **self.track)
        self._annotation.__exit__(*exc)
        return False


#: The closed set of top-level kinds of device work.  One component of a
#: scope path should be one of these (``blk3/mixer/qkv``, ``head/loss``,
#: ``optimizer``): readers split a step's device time by them, and whatever
#: carries none counts as unscoped.  The lowering of a backward trace puts
#: ``bwd`` before the path.
GROUPS = ("embed", "mixer", "mlp", "head", "optimizer")


class scope(ContextDecorator):
    """Names the device operations traced inside as ``name``, one component
    of a "/"-separated path that nests with the scopes around it.

    Inside a ``tt.jit`` / ``make_train_step`` trace the path is stamped on
    every bound symbol recorded (``BoundSymbol.scope``), follows it through
    the passes as its source provenance does, and becomes a
    ``jax.named_scope`` when the symbol is lowered under ``jax.jit``.
    Anywhere else (plain JAX being traced, as the serving forward is) it is
    ``jax.named_scope(name)`` at once.  Names must depend on the model's
    structure alone (no ids, counters or addresses): they are part of the
    lowered text.  Usable as a context manager or as a decorator."""

    __slots__ = ("name", "_token", "_named")

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self):
        return scope(self.name)

    def __enter__(self):
        if get_tracectx() is not None:
            outer = scope_path.get()
            self._named = None
            self._token = scope_path.set(f"{outer}/{self.name}" if outer else self.name)
        else:
            self._named = named_scope(self.name)
            self._named.__enter__()
        return self

    def __exit__(self, *exc):
        if self._named is None:
            scope_path.reset(self._token)
        else:
            self._named.__exit__(*exc)
        return False


def events() -> list[dict]:
    """Snapshot of the ring buffer, oldest first."""
    return list(_events)


def clear_events() -> None:
    _events.clear()


def register_process_name(pid: int, name: str) -> None:
    """Names a (possibly synthetic) pid's process row in exported traces."""
    _process_names[pid] = name


def register_thread_name(pid: int, tid: int, name: str) -> None:
    """Names a (possibly synthetic) (pid, tid) track in exported traces."""
    _thread_names[(pid, tid)] = name


def _process_label(cats: set[str]) -> str:
    """Default process name derived from the event categories recorded under
    a pid, so serving spans never masquerade as compile work: any
    ``serving*`` category makes it a serving row; the bare ``thunder_tpu``
    category is the compile pipeline."""
    if any(c.split(".")[0] == "serving" for c in cats):
        return "thunder_tpu serving"
    return "thunder_tpu compile pipeline"


def _metadata_events(evs: list[dict]) -> list[dict]:
    """``process_name``/``thread_name`` metadata (``ph: "M"``) records so
    Perfetto labels the rows instead of showing bare pid/tid numbers.
    Registered names win; otherwise the process name derives from the
    categories seen under that pid."""
    metas = []
    by_pid: dict[int, set[str]] = {}
    for e in evs:
        by_pid.setdefault(e["pid"], set()).add(e.get("cat", "thunder_tpu"))
    for pid in sorted(by_pid):
        metas.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": _process_names.get(pid) or _process_label(by_pid[pid])},
        })
    for pid, tid in sorted({(e["pid"], e["tid"]) for e in evs}):
        metas.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": _thread_names.get((pid, tid), f"thread {tid}")},
        })
    return metas


def export_chrome_trace(path):
    """Writes the buffered events (compile pipeline + any traced serving
    spans) as a Chrome-trace JSON object (loadable in ``chrome://tracing``
    and https://ui.perfetto.dev), prefixed with process/thread-name metadata
    events.  ``path`` may be a filesystem path or an open file-like object
    (written to, left open).  Returns ``path``."""
    evs = list(_events)
    payload = {"traceEvents": _metadata_events(evs) + evs, "displayTimeUnit": "ms"}
    if hasattr(path, "write"):
        json.dump(payload, path)
        return path
    with open(path, "w") as f:
        json.dump(payload, f)
    return path
