"""Observability: spans, runtime profiling, and metrics.

**Spans** (``events.py``) are the one way the program marks host work.
``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` named
``thunder_tpu.<name>``: start a ``jax.profiler`` trace and the spans are
there, on the profiler's clock, beside the device operations.  No option,
keyword or environment variable switches them: with no profiler session an
annotation is inactive and costs about two microseconds.  The names:

- compile pipeline (always also in the ring buffer below): ``compile``,
  ``transform:*``, ``lower``, ``lower:*``, ``codegen``, ``xla_compile``
  (a fusion's first call, and [``fn="train_step"``] a ``TrainStep``'s);
- start-up, in the ring alone, as complete events: ``import`` [``jax_s``]
  and JAX's own ``jax.trace`` / ``jax.lower`` / ``jax.backend_compile``
  [``fun_name``] for every program (``core/compile_cache.py``);
- one serving engine step (``serving/engine.py``): ``serve.step`` [``step``,
  ``queued``, ``running``, ``t_ns``] with the children ``serve.harvest``
  (``serve.harvest.wait`` [``kind``, ``rows`` or ``rid``]: the host waits for
  the device; ``serve.harvest.emit``: the rest), ``serve.expire``,
  ``serve.decode_dispatch`` [``rows``, ``bucket``, ``steady``] (its
  ``serve.decode_dispatch.call`` is the jitted program's call alone),
  ``serve.admit`` [``admitted``], ``serve.prefill_dispatch`` [``rid``,
  ``tokens``, ``bucket``, ``piece``], ``serve.gauges``, and
  ``serve.compile`` [``kind``, ``bucket``] around the first call of a
  freshly built bucket program; ``serve.recover`` around a recovery (these
  two always also in the ring: no steady step opens them);
- training: ``train.step`` [``step``] around ``TrainStep.__call__`` and
  ``train.snapshot`` [``bytes``] around ``train_loop``'s host snapshot.

**Scopes** (``events.py``) are the same for the device: ``scope(name)``
(``tt.scope``) names the operations traced inside it.  Paths are
"/"-separated (``bwd/blk3/mixer/qkv``) and end up as the ``op_name`` of
each HLO instruction, which the device's profile reports for every
operation it ran.  One component is a group from the closed set
``events.GROUPS`` (``embed``, ``mixer``, ``mlp``, ``head``, ``optimizer``);
``blk<i>`` stands outside it, and the lowering of a backward trace puts
``bwd`` first.  The models under ``models/``, the paged cache's writes and
reads and the optimizer step write them; a user's model gets them by calling
``tt.scope`` and otherwise the names of its functions.  Always on: a scope
is metadata, and the compiled code is the same with or without it.

The pillars on top:

1. **Runtime profiling transform** (``profiler.py``) — a post-lowering pass
   wrapping each executed BoundSymbol / XLA fusion region in monotonic-clock
   timing (optional ``jax.block_until_ready`` fences) and a
   ``TraceAnnotation`` range named after the symbol.  Enable with
   ``tt.jit(fn, profile=True)`` or ``THUNDER_TPU_PROFILE=1``; query with
   ``thunder_tpu.profile_stats(cfn)``.

2. **The event ring** (``events.py``) — the compile pipeline's spans as
   begin/end events in a bounded ring buffer; export with
   ``thunder_tpu.export_chrome_trace(path)`` (Perfetto-loadable).

3. **Unified metrics registry** (``metrics.py``) — counters / gauges /
   histograms that the dispatcher, the compiler, and the profiler publish
   into, plus user hook callbacks (``on_compile_start/end``,
   ``on_cache_hit/miss``, ``on_dispatch``).

4. **Debug hooks + anomaly detection** (``debug.py``) — pre/post callbacks
   on every executed symbol (``tt.jit(fn, debug_hooks=...)``) and a NaN/Inf
   scan raising :class:`AnomalyError` with source provenance
   (``detect_anomalies=True`` / ``THUNDER_TPU_DETECT_ANOMALIES=1``).

5. **Memory accounting** (``memory.py``) — del-aware live/peak-bytes
   timeline behind ``examine.memory_estimate``, the ``live_bytes``/
   ``peak_bytes`` profile columns, and the ``memory.*`` gauges.

6. **Training-step telemetry** (``telemetry.py``) — ``StepLogger`` JSONL +
   registry mirror, driven by ``train_cli.py --telemetry``.

7. **Request-lifecycle tracing** (``tracing.py``) — async Chrome-trace
   spans per served request (queued / prefill compile-vs-cached / decode
   steps / finish) in the ring, where the engine's step spans then land
   too, merged with the compile pipeline's into one
   ``tt.export_chrome_trace`` Perfetto timeline; ``tt.serve(...,
   trace=True)`` / ``THUNDER_TPU_TRACE_SERVING=1``.

8. **SLO monitoring** (``slo.py``) — configurable TTFT/TPOT/queue/deadline
   targets, windowed good/bad counters, ``serving.slo.*`` burn-rate
   gauges, ``engine.slo_report()``.

9. **Flight recorder** (``flight.py``) — bounded ring of engine events +
   scheduler/pool state, auto-dumped to JSON when ``step()`` raises;
   ``tt.flight_record(path)``.

``config.py`` reads the environment dynamically, the event-ring capacity
included (re-applied on every append).
"""
from __future__ import annotations

from thunder_tpu.observability.config import (  # noqa: F401
    anomaly_env_enabled,
    event_buffer_capacity,
    flight_recorder_env_enabled,
    profiling_env_enabled,
    serving_trace_env_enabled,
)
from thunder_tpu.observability.events import (  # noqa: F401
    clear_events,
    events,
    export_chrome_trace,
    record_event,
    register_process_name,
    register_thread_name,
    scope,
    span,
)
from thunder_tpu.observability.flight import (  # noqa: F401
    FlightRecorder,
    active_recorder,
    flight_record,
)
from thunder_tpu.observability.slo import SLOConfig, SLOMonitor  # noqa: F401
from thunder_tpu.observability.tracing import RequestTracer  # noqa: F401
from thunder_tpu.observability.metrics import (  # noqa: F401
    HOOK_EVENTS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    clear_hooks,
    emit,
    export_text,
    has_hooks,
    register_hook,
    registry,
    unregister_hook,
)
from thunder_tpu.observability.goodput import (  # noqa: F401
    WASTE_CAUSES,
    GoodputConfig,
    GoodputLedger,
    fleet_goodput,
)

__all__ = [
    "profiling_env_enabled",
    "anomaly_env_enabled",
    "snapshot",
    "reset_observability",
    # events
    "span",
    "scope",
    "record_event",
    "events",
    "clear_events",
    "export_chrome_trace",
    "register_process_name",
    "register_thread_name",
    # serving plane
    "RequestTracer",
    "SLOConfig",
    "SLOMonitor",
    "FlightRecorder",
    "flight_record",
    "active_recorder",
    "serving_trace_env_enabled",
    "flight_recorder_env_enabled",
    # goodput ledger (ISSUE 18)
    "WASTE_CAUSES",
    "GoodputConfig",
    "GoodputLedger",
    "fleet_goodput",
    # metrics + hooks
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "export_text",
    "HOOK_EVENTS",
    "register_hook",
    "unregister_hook",
    "clear_hooks",
    "emit",
    # dispatch/compile glue (called by thunder_tpu.jit)
    "dispatch_event",
    "compile_begin",
    "compile_end",
]


def snapshot() -> dict:
    """One plain dict of every registered metric (see ``metrics.py``)."""
    return registry().snapshot()


def reset_observability() -> None:
    """One call clearing all accumulated observability state: the metrics
    registry (values zeroed, metric objects stay registered), the compile-
    event ring buffer, and every live ProfileReport's accumulated per-symbol
    records.  Registered user hooks are NOT touched.  Used by the test
    suite's autouse fixture to stop cross-test bleed."""
    registry().reset()
    clear_events()
    from thunder_tpu.observability.profiler import reset_profile_reports

    reset_profile_reports()


#
# Glue the dispatch/compile paths call.  Kept to ONE function call per event
# so the hot path stays cheap: counters are attribute increments, and hook
# payload dicts are only built when a hook is actually registered.
#


def dispatch_event(fn_name: str, *, ns: int, hit: bool) -> None:
    """Called once per dispatch of a compiled function (post-timing)."""
    reg = registry()
    reg.counter("dispatch.calls").inc()
    reg.counter("dispatch.cache_hits" if hit else "dispatch.cache_misses").inc()
    reg.histogram("dispatch.ns").observe(ns)
    if has_hooks("on_dispatch"):
        emit("on_dispatch", {"fn": fn_name, "ns": ns, "cache_hit": hit})
    event = "on_cache_hit" if hit else "on_cache_miss"
    if has_hooks(event):
        emit(event, {"fn": fn_name})


def compile_begin(fn_name: str) -> None:
    registry().counter("compile.count").inc()
    if has_hooks("on_compile_start"):
        emit("on_compile_start", {"fn": fn_name})


def compile_end(fn_name: str, ns: int) -> None:
    registry().histogram("compile.ns").observe(ns)
    if has_hooks("on_compile_end"):
        emit("on_compile_end", {"fn": fn_name, "ns": ns})
