"""By hand: a kept trace's split of a serving engine's turnover steps (PR 46).

    CHIPBENCH_KEEP_TRACE=1 python3 chipbench/run.py --workload <serve cell> --seed <n> --seconds 30 --trace 1
    JAX_PLATFORMS=cpu python3 tools/turnover_split.py chiprun_out/traces/<cell>/<file>.xplane.pb [repo root]

A decode dispatch is a ``turnover`` where it rebuilt the chain's inputs
(``steady`` = 0), ``ahead`` where it went out before the harvest
(``through`` where a row of it was past its end by length: ``ending`` > 0,
PR 52), else ``steady``.  Prints, as JSON: every span's count, median and total with the
kind of the dispatch it is or lies under; the device's idle seconds by the
innermost span over each gap's middle (the two clocks can differ by a
millisecond or two: trust the total, not the split); a step's host
milliseconds and its children's by what the step did; and the rows a
turnover wrote (``written``).  ``repo root``: whose ``chipbench`` reads the
trace (a parent under ``_checkout/`` has the same one).
"""
import json
import os
import statistics
import sys


def _kind(s):
    if s.name == "serve.decode_dispatch":
        if not int(s.args.get("steady", 0)):
            return "turnover"
        if not int(s.args.get("ahead", 0)):
            return "steady"
        return "through" if int(s.args.get("ending", 0)) else "ahead"
    return None


def _label(s):
    """A span's name, with the kind of the decode dispatch it is or lies under."""
    p = s
    while p is not None:
        k = _kind(p)
        if k:
            return f"{s.name}[{k}]"
        p = p.parent
    return s.name


def _step_kind(step):
    kinds = [_kind(d) for d in step.inside("serve.decode_dispatch")]
    return ("+".join(kinds) if kinds else "none") + ("+prefill" if step.inside("serve.prefill_dispatch") else "")


def _median_ms(xs):
    return 1e3 * statistics.median(xs) if xs else None


def split(path: str, root: str) -> dict:
    sys.path.insert(0, root)
    from chipbench import program_spans as ps
    from chipbench import trace

    spans = ps.load(path)
    tr = trace.load(path, "/device:TPU:")
    out = {"window_s": tr.window_s(), "busy_s": tr.busy_s(), "idle_s": tr.window_s() - tr.busy_s(), "spans": {}}
    groups: dict[str, list] = {}
    for s in spans:
        groups.setdefault(_label(s), []).append(s)
    for name, ss in sorted(groups.items()):
        own = [s.dur - sum(c.dur for c in s.children) for s in ss]
        out["spans"][name] = {"count": len(ss), "median_ms": _median_ms([s.dur for s in ss]),
                              "total_s": sum(s.dur for s in ss), "self_median_ms": _median_ms(own), "self_total_s": sum(own)}
    # idle by the innermost span over a gap's middle, decode dispatches told apart by kind
    idle: dict[str, float] = {}
    prev = tr.window()[0] if tr.devices else 0.0
    for a, b in (tr._union(tr.devices[0].ops) if tr.devices else []):
        if a > prev:
            mid = (prev + a) / 2
            cover = [s for s in spans if s.start <= mid <= s.end]
            name = _label(min(cover, key=lambda s: s.dur)) if cover else ps.NO_SPAN
            idle[name] = idle.get(name, 0.0) + (a - prev)
        prev = max(prev, b)
    out["idle_by_span_s"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    # a step's host time (its length less its waits), and its children summed a step, by what the step did
    host: dict[str, list] = {}
    parts: dict[str, dict] = {}
    for step in ps.named(spans, "serve.step"):
        k = _step_kind(step)
        host.setdefault(k, []).append(step.dur - sum(w.dur for w in step.inside("serve.harvest.wait")))
        tot: dict[str, float] = {}
        stack = list(step.children)
        while stack:
            c = stack.pop()
            tot[c.name] = tot.get(c.name, 0.0) + c.dur
            stack.extend(c.children)
        for name, v in tot.items():
            parts.setdefault(k, {}).setdefault(name, []).append(v)
    out["host_ms_by_step_kind"] = {k: {"count": len(v), "median_ms": _median_ms(v), "total_s": sum(v)}
                                   for k, v in sorted(host.items())}
    out["ms_a_step_by_kind"] = {k: {n: {"steps": len(v), "median_ms": _median_ms(v), "total_s": sum(v)}
                                    for n, v in sorted(d.items())} for k, d in sorted(parts.items())}
    written = [int(s.args["written"]) for s in ps.named(spans, "serve.decode_dispatch")
               if "written" in s.args and _kind(s) == "turnover"]
    out["written_a_turnover"] = {"count": len(written), "median": statistics.median(written) if written else None,
                                 "max": max(written, default=None), "sum": sum(written)}
    return out


if __name__ == "__main__":
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps(split(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else here), indent=1))
