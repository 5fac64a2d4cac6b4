"""The program's spans, from a trace to numbers.

`data/rehearsal_spans.xplane.pb` was recorded by a CPU rehearsal of
`mistral7b-serve-1chip.offline-batch` (`--rehearse --trace 1 --seconds 0.4`, 31
engine steps) and then cut down to its `thunder_tpu.*` and `chipbench.*` host
events (11 MB of XLA:CPU thunk events went; the events kept are as recorded).
A CPU trace has no device plane, so the readers that need device time, and
`idle_by_span`, are held to spans and operations written out here by hand."""
import os
import shutil

from chipbench import common, program_spans as ps, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "rehearsal_spans.xplane.pb")
MS = 1e-3


def reader(metric):
    return common.load_reader(metric)


def raw_events():
    """The recorded events straight from the file, flat, in time order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(RECORDED).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ps.PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns, e.name[len(ps.PREFIX):],
                                {k: v for k, v in e.stats}))
    return sorted(out)


def test_recorded_spans_nest_as_the_program_nests_them():
    spans = ps.load(RECORDED)
    steps = ps.named(spans, "serve.step")
    assert len(steps) == 31 and all(s.parent is None for s in steps)
    assert [s.args["step"] for s in steps] == list(range(steps[0].args["step"], steps[0].args["step"] + 31))
    assert all({"queued", "running", "t_ns"} <= set(s.args) for s in steps)
    parents = {"serve.harvest": "serve.step", "serve.expire": "serve.step",
               "serve.decode_dispatch": "serve.step", "serve.admit": "serve.step",
               "serve.gauges": "serve.step", "serve.harvest.wait": "serve.harvest",
               "serve.harvest.emit": "serve.harvest",
               "serve.decode_dispatch.call": "serve.decode_dispatch"}
    for s in spans:
        if s.name in parents:
            assert s.parent is not None and s.parent.name == parents[s.name], s.name
            assert s.parent.start <= s.start and s.end <= s.parent.end
    pieces = ps.named(spans, "serve.prefill_dispatch")
    assert len(pieces) == 22 and all(p.parent.name in ("serve.admit", "serve.step") for p in pieces)
    assert all(p.args["piece"] == "prefill" and 0 < p.args["tokens"] <= 48 for p in pieces)


def test_turnaround_and_host_time_match_a_flat_scan_of_the_recorded_events(tmp_path):
    events = raw_events()
    turn, host = [], []
    for a, b, name, _ in events:
        if name != "serve.step":
            continue
        inside = [e for e in events if a <= e[0] and e[1] <= b]
        waits = [e for e in inside if e[2] == "serve.harvest.wait"]
        decode_waits = [e for e in waits if e[3]["kind"] == "decode"]
        calls = [e for e in inside if e[2] == "serve.decode_dispatch.call"]
        host.append(((b - a) - sum(e[1] - e[0] for e in waits)) / 1e9)
        if decode_waits and calls:
            turn.append((calls[-1][1] - decode_waits[0][1]) / 1e9)
    spans = ps.load(RECORDED)
    assert len(turn) == 31 and len(host) == 31
    assert all(abs(x - y) < 1e-12 for x, y in zip(ps.turnarounds(spans), turn))
    assert all(abs(x - y) < 1e-12 for x, y in zip(ps.host_seconds(spans), host))

    # the readers find the file where run.py leaves it, read it once, and keep it
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    ctx = {"trace_dir": str(tmp_path), "trace": trace.Trace([], [])}
    t = reader("engine_turnaround_ms.serve").read(ctx)
    shutil.rmtree(tmp_path / "plugins")
    h = reader("engine_host_ms_per_step.serve").read(ctx)
    assert abs(t - 1e3 * sorted(turn)[15]) < 1e-9 and abs(h - 1e3 * sorted(host)[15]) < 1e-9
    assert 0.1 < t < 50 and 0.1 < h < 50                          # milliseconds on this CPU
    assert reader("prefill_device_ms_per_ktok.serve").read(ctx) is None   # no device plane


def span(name, start_ms, dur_ms, **args):
    return ps.Span(name, start_ms * MS, dur_ms * MS, args, "main")


def op(name, start_ms, dur_ms):
    return trace.Op(name, "", start_ms * MS, dur_ms * MS)


def two_steps():
    """Two engine steps written out by hand (milliseconds).  Step 1 waits for a
    prefill piece and for the decode tokens; step 2 dispatches a prefill."""
    return ps.nest([
        span("serve.step", 0, 20, step=1),
        span("serve.harvest", 0, 9),
        span("serve.harvest.wait", 1, 4, kind="decode", rows=2),
        span("serve.harvest.emit", 5, 1),
        span("serve.harvest.wait", 6, 2, kind="prefill", rid=7),
        span("serve.decode_dispatch", 10, 5, rows=2),
        span("serve.decode_dispatch.call", 11, 3),
        span("serve.step", 30, 40, step=2),
        span("serve.harvest", 30, 12),
        span("serve.harvest.wait", 31, 10, kind="decode", rows=2),
        span("serve.decode_dispatch", 43, 4, rows=2),
        span("serve.compile", 43.5, 3.2, kind="decode_paged"),
        span("serve.decode_dispatch.call", 44, 2.5),
        span("serve.admit", 50, 15, admitted=1),
        span("serve.prefill_dispatch", 52, 10, rid=8, tokens=1500, bucket="2048x224", piece="prefill"),
    ])


def test_span_arithmetic_on_known_spans():
    spans = two_steps()
    # end of the decode wait to end of the same step's dispatch call
    assert [round(t / MS, 6) for t in ps.turnarounds(spans)] == [9.0, 5.5]
    # the step less every wait in it, whichever record the wait was for
    assert [round(t / MS, 6) for t in ps.host_seconds(spans)] == [14.0, 30.0]
    assert abs(ps.median_ms(ps.turnarounds(spans)) - 7.25) < 1e-9
    (call,) = [s for s in spans if s.name == "serve.decode_dispatch.call" and s.start > 0.04]
    assert call.parent.name == "serve.compile" and call.parent.parent.name == "serve.decode_dispatch"


def test_prefill_runs_are_paired_with_the_spans_that_started_them():
    spans = two_steps() + ps.nest([
        span("serve.step", 80, 30, step=3),
        span("serve.prefill_dispatch", 85, 5, rid=9, tokens=500, bucket="1024x224", piece="prefill"),
        span("serve.step", 120, 30, step=4),
        span("serve.prefill_dispatch", 125, 5, rid=10, tokens=900, bucket="1024x224", piece="prefill")])
    modules = [op("jit_prefill(1)", 3, 4),             # dispatched before the trace began: no span
               op("jit_decode_paged(2)", 15, 20),
               op("jit_prefill(1)", 70, 120),          # rid 8: 1500 tokens in 120 ms
               op("jit_decode_paged(2)", 190, 20),
               op("jit_prefill(3)", 210, 35),          # rid 9: 500 tokens in 35 ms
               op("jit_prefill(3)", 250, 1)]           # rid 10, the line's last event: may be cut
    pairs = ps.prefill_pairs(spans, modules)
    assert [(sp.args["rid"], round(run.dur / MS, 6)) for sp, run in pairs] == [(8, 120.0), (9, 35.0)]
    tr = trace.Trace([trace.Device("/device:TPU:0", [op("fusion.1", 3, 4)], modules)], [])
    got = reader("prefill_device_ms_per_ktok.serve").read({"trace": tr, "program_spans": spans})
    assert abs(got - (120.0 + 35.0) / 2.0) < 1e-9     # 155 ms for 2.0 thousand tokens
    # none in the stretch, or a program without spans: nothing to report
    assert reader("prefill_device_ms_per_ktok.serve").read(
        {"trace": tr, "program_spans": two_steps()[:7]}) is None


def test_idle_is_put_down_to_the_innermost_span_over_each_gap():
    spans = two_steps()
    ops = [op("fusion.1", 0, 2),        # gap 2-12: middle 7, inside the prefill wait (6-8)
           op("paged_attn_decode.1", 12, 20),   # gap 32-45: middle 38.5, inside the decode wait
           op("paged_attn_decode.2", 45, 10),   # gap 55-57: middle 56, inside the prefill dispatch
           op("fusion.2", 57, 14),              # gap 71-75: after the last step
           op("fusion.3", 75, 1)]
    tr = trace.Trace([trace.Device("/device:TPU:0", ops, [])], [])
    idle = ps.idle_by_span(tr, spans)
    assert {k: round(v / MS, 6) for k, v in idle.items()} == {
        "serve.harvest.wait": 23.0, ps.NO_SPAN: 4.0, "serve.prefill_dispatch": 2.0}
    assert list(idle)[0] == "serve.harvest.wait"                      # largest first
    assert abs(sum(idle.values()) - (tr.window_s() - tr.busy_s())) < 1e-12
    assert ps.idle_by_span(trace.Trace([], []), spans) == {}


def test_clock_bounds_pair_each_call_with_its_run_and_its_wait():
    spans = two_steps()
    modules = [op("jit_decode_paged(2)", 14.5, 27),   # queued by the call that ended at 14
               op("jit_decode_paged(2)", 46, 20)]     # queued by the call that ended at 46.5: the clocks differ
    b = ps.clock_bounds(spans, modules)
    assert [round(x / MS, 6) for x in b["run_start_minus_call_start_s"]] == [3.5, 2.0]
    assert [round(x / MS, 6) for x in b["run_start_minus_call_end_s"]] == [0.5, -0.5]
    assert [round(x / MS, 6) for x in b["wait_end_minus_run_end_s"]] == [-0.5]   # the wait that ended at 41


def test_a_program_without_spans_gives_none_not_an_error():
    ctx = {"trace": trace.Trace([], []), "program_spans": [],
           "counters": {"compile_cache": {"persistent_cache_hits": 3, "persistent_cache_misses": 0}}}
    for metric in ("engine_turnaround_ms.serve", "engine_host_ms_per_step.serve",
                   "prefill_device_ms_per_ktok.serve", "setup_jax_trace_s", "setup_xla_s"):
        assert reader(metric).read(ctx) is None, metric
    # a step that harvested nothing, or dispatched before it harvested (the
    # synchronous loop), has no turn-around
    sync = ps.nest([span("serve.step", 0, 10), span("serve.decode_dispatch", 1, 3),
                    span("serve.decode_dispatch.call", 2, 1), span("serve.harvest", 5, 4),
                    span("serve.harvest.wait", 5, 3, kind="decode")])
    assert ps.turnarounds(sync) == [] and ps.median_ms([]) is None
    assert [round(t / MS, 6) for t in ps.host_seconds(sync)] == [7.0]


def test_setup_counters_and_the_ring_reader(monkeypatch):
    cc = {"jaxpr_trace_s": 11.5, "lower_s": 2.25, "backend_compile_s": 7.0}
    ctx = {"counters": {"compile_cache": cc}}
    assert reader("setup_jax_trace_s").read(ctx) == 13.75
    assert reader("setup_xla_s").read(ctx) == 7.0

    def ev(ph, name, ts_s, tid=1):
        return {"ph": ph, "name": name, "ts": ts_s * 1e6, "pid": 1, "tid": tid, "cat": "thunder_tpu"}

    ring = [ev("B", "compile", 0), ev("B", "lower", 1), ev("B", "xla_compile", 2),
            ev("E", "xla_compile", 5), ev("E", "lower", 6), ev("E", "compile", 10),
            ev("B", "xla_compile", 11), ev("E", "xla_compile", 19),   # a first run's, after its compile: XLA's
            ev("B", "compile", 20, tid=2), ev("B", "compile", 21, tid=2),   # tt.jit inside a TrainStep build
            ev("E", "compile", 22, tid=2), ev("E", "compile", 24, tid=2),
            {"ph": "b", "name": "compile", "ts": 0.0, "pid": 9, "tid": 9, "id": 1}]  # a request's async span
    r = reader("setup_compiler_s")
    assert r.self_seconds(ring) == (10 - 3) + 4
    assert r.self_seconds([]) == 0.0

    from thunder_tpu import observability as obs
    monkeypatch.setattr(obs, "events", lambda: ring)
    assert r.read({}) == 11.0
    monkeypatch.setattr(obs, "event_buffer_capacity", lambda: len(ring))    # full: the oldest are gone
    assert r.read({}) is None
