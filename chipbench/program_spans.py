"""The program's own spans in a profiler trace.

thunder_tpu marks its host work with ``observability.events.span``: a
``jax.profiler.TraceAnnotation`` named ``thunder_tpu.<name>`` whose keyword
arguments are the event's stats.  While the benchmark records its trace
those spans lie in the ``.xplane.pb`` on the host's lines, on the same clock
as the device's operations (``trace.py``), nested as the program nests
them: ``serve.step`` > ``serve.harvest`` > ``serve.harvest.wait`` and so on
(PERF.md section 3 has the table).  A program that opens no such span (the
parent of the PR that brought them) gives an empty list, and every reader
built on this file then returns ``None``.

Nothing here imports the program under test.

By hand, on a kept trace (``CHIPBENCH_KEEP_TRACE=1``):

    python3 chipbench/program_spans.py chiprun_out/traces/<cell>/<file>.xplane.pb
"""
from __future__ import annotations

import dataclasses
import statistics

PREFIX = "thunder_tpu."
NO_SPAN = "(no span)"


@dataclasses.dataclass(eq=False)
class Span:
    name: str           # without the prefix
    start: float        # seconds, the profiler's clock (as trace.Op.start)
    dur: float
    args: dict
    line: str           # the host thread the span ran on
    parent: "Span | None" = None
    children: list = dataclasses.field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.dur

    def inside(self, name: str) -> list:
        """Every span called ``name`` under this one, at any depth, in time order."""
        out = []
        for c in self.children:
            if c.name == name:
                out.append(c)
            out.extend(c.inside(name))
        return out


def nest(spans: list) -> list:
    """Sets ``parent`` and ``children`` by containment in time, thread by thread."""
    for line in {s.line for s in spans}:
        stack: list = []
        # a parent starts no later and ends no earlier; the longer first on a tie
        for s in sorted((s for s in spans if s.line == line), key=lambda s: (s.start, -s.dur)):
            while stack and s.start >= stack[-1].end:
                stack.pop()
            if stack:
                s.parent = stack[-1]
                stack[-1].children.append(s)
            stack.append(s)
    return spans


def load(path: str) -> list:
    """The ``thunder_tpu.*`` host spans of a trace, nested, in time order."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], e.start_ns / 1e9, e.duration_ns / 1e9,
                                      {k: v for k, v in e.stats}, line.name))
    return sorted(nest(spans), key=lambda s: s.start)


def of(ctx: dict) -> list:
    """The spans of this run's trace: read once from the ``.xplane.pb`` under
    ``ctx['trace_dir']`` (it still stands while the readers run) and kept in
    ``ctx`` for the next reader."""
    if "program_spans" not in ctx:
        from chipbench import trace

        ctx["program_spans"] = load(trace.find_xplane(ctx["trace_dir"]))
    return ctx["program_spans"]


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


# ---- the arithmetic of the readers -------------------------------------------

def turnarounds(spans: list) -> list:
    """For each engine step that harvested a decode record and dispatched the
    next: seconds from the end of that record's ``serve.harvest.wait`` (the
    host has the tokens) to the end of the same step's last
    ``serve.decode_dispatch.call`` (the device has the next step)."""
    out = []
    for step in named(spans, "serve.step"):
        waits = [w for w in step.inside("serve.harvest.wait") if w.args.get("kind") == "decode"]
        calls = step.inside("serve.decode_dispatch.call")
        if waits and calls and calls[-1].end > waits[0].end:
            out.append(calls[-1].end - waits[0].end)
    return out


def host_seconds(spans: list) -> list:
    """For each engine step: its length less the time it only waited for the device."""
    return [step.dur - sum(w.dur for w in step.inside("serve.harvest.wait"))
            for step in named(spans, "serve.step")]


def prefill_pairs(spans: list, modules: list) -> list:
    """``(span, run)`` for each ``serve.prefill_dispatch`` span and the run of a
    prefill program it started: the first run on the modules line, not yet
    taken, that begins after the span does.  A run that is the line's last
    event may be cut by the trace's end and is left out, as are runs whose
    dispatch came before the trace began."""
    runs = [m for m in modules[:-1] if "prefill" in m.name]
    pairs, k = [], 0
    for sp in named(spans, "serve.prefill_dispatch"):
        while k < len(runs) and runs[k].start < sp.start:
            k += 1
        if k < len(runs):
            pairs.append((sp, runs[k]))
            k += 1
    return pairs


def median_ms(seconds: list):
    return 1e3 * statistics.median(seconds) if seconds else None


def idle_by_span(trace, spans: list) -> dict:
    """Seconds of each gap between the first device's operations, put down to
    the innermost program span over the gap's middle (``(no span)`` where
    none is), largest first.  ``trace`` is a ``trace.Trace``."""
    if not trace.devices:
        return {}
    a0, _ = trace.window()
    agg: dict[str, float] = {}
    prev = a0
    for a, b in trace._union(trace.devices[0].ops):
        if a > prev:
            mid = (prev + a) / 2
            cover = [s for s in spans if s.start <= mid <= s.end]
            name = min(cover, key=lambda s: s.dur).name if cover else NO_SPAN
            agg[name] = agg.get(name, 0.0) + (a - prev)
        prev = max(prev, b)
    return dict(sorted(agg.items(), key=lambda kv: -kv[1]))


def clock_bounds(spans: list, modules: list) -> dict:
    """How far the host's and the device's clocks can disagree, from causality.
    A decode program cannot start before the ``serve.decode_dispatch.call``
    that queues it begins, so the device's clock is ahead of the host's by at
    most the least ``run.start - call.start``; the ``serve.harvest.wait`` for
    its tokens cannot end before the program does, so it is behind by at most
    the least ``wait.end - run.end``.  ``run.start - call.end`` says whether
    the device waits for the call to return (it does not: the program starts
    while the call is still on its way back).  Each call is paired with the
    first decode run that starts after the call began, each such run with the
    first decode wait that ends after the run began."""
    runs = [m for m in modules if "decode" in m.name]
    calls = named(spans, "serve.decode_dispatch.call")
    waits = [w for w in named(spans, "serve.harvest.wait") if w.args.get("kind") == "decode"]
    start_after_begin, start_after_call, wait_after_end = [], [], []
    for c in calls:
        run = next((r for r in runs if r.start >= c.start), None)
        if run is None:
            continue
        start_after_begin.append(run.start - c.start)
        start_after_call.append(run.start - c.end)
        w = next((w for w in waits if w.end >= run.start), None)
        if w is not None:
            wait_after_end.append(w.end - (run.start + run.dur))
    return {"run_start_minus_call_start_s": start_after_begin,
            "run_start_minus_call_end_s": start_after_call,
            "wait_end_minus_run_end_s": wait_after_end}


def main() -> None:
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench import trace

    path = sys.argv[1]
    spans = load(path)
    tr = trace.load(path, sys.argv[2] if len(sys.argv) > 2 else "/device:TPU:")
    modules = tr.devices[0].modules if tr.devices else []
    decode = sorted((m for m in modules if "decode" in m.name), key=lambda m: m.start)
    gaps = [b.start - (a.start + a.dur) for a, b in zip(decode, decode[1:])]
    idle = idle_by_span(tr, spans)
    names: dict[str, list] = {}
    for s in spans:
        names.setdefault(s.name, []).append(s.dur)
    bounds = clock_bounds(spans, modules)
    pairs = prefill_pairs(spans, modules)
    turn, host = turnarounds(spans), host_seconds(spans)
    print(json.dumps({
        "window_s": tr.window_s(), "busy_s": tr.busy_s(),
        "idle_s": tr.window_s() - tr.busy_s(), "idle_by_span_s": idle,
        "idle_named_share": (1 - idle.get(NO_SPAN, 0.0) / sum(idle.values())) if idle else None,
        "spans": {n: {"count": len(d), "median_ms": median_ms(d), "max_ms": 1e3 * max(d),
                      "total_s": sum(d)} for n, d in sorted(names.items())},
        "turnaround_ms": {"median": median_ms(turn), "all": [1e3 * t for t in turn]},
        "host_ms_per_step": {"median": median_ms(host), "all": [1e3 * t for t in host]},
        "device_gap_between_decode_runs_ms": {"median": median_ms(gaps),
                                              "all": [1e3 * g for g in gaps]},
        "prefills": [{"tokens": sp.args.get("tokens"), "bucket": sp.args.get("bucket"),
                      "device_ms": 1e3 * run.dur, "dispatch_ms": 1e3 * sp.dur} for sp, run in pairs],
        "clock_bounds_ms": {k: {"min": 1e3 * min(v), "median": median_ms(v), "max": 1e3 * max(v)}
                            for k, v in bounds.items() if v},
    }, indent=1))


if __name__ == "__main__":
    main()
