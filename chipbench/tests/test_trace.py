"""The reduction from a trace to numbers, on a small trace recorded on a TPU v5e
(three rounds of a matmul program and a Pallas kernel, a 3 ms sleep after each;
`data/tiny_events.json` is the same trace's events, listed when it was recorded)."""
import json
import os

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def events(line):
    rows = json.load(open(os.path.join(DATA, "tiny_events.json")))
    return [(name, start / 1e9, dur / 1e9) for plane, ln, name, start, dur in rows
            if plane.startswith("/device:TPU:") and ln == line]


def test_busy_idle_kernel_and_program_times_match_the_listed_events():
    tr = trace.load(os.path.join(DATA, "tiny.xplane.pb"))
    ops = events("XLA Ops")
    assert len(tr.devices) == 1 and len(tr.devices[0].ops) == len(ops) == 21
    # brute force: mark every nanosecond-grid cell an operation covers
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    cells = set()
    for _, s, d in ops:
        cells.update(range(round((s - lo) * 1e9), round((s + d - lo) * 1e9)))
    assert abs(tr.window_s() - (hi - lo)) < 1e-12
    assert abs(tr.busy_s() - len(cells) / 1e9) < 2e-9
    assert 0.98 < tr.idle_share() < 0.99          # three 54 us bursts in 11.9 ms
    kernel = [d for n, _, d in ops if n.startswith("%_tiny_kernel")]
    assert len(kernel) == 3
    assert abs(tr.op_seconds(lambda o: o.name.startswith("_tiny_kernel")) - sum(kernel)) < 1e-12
    assert abs(tr.pallas_seconds() - sum(kernel)) < 1e-12      # the one Mosaic custom call
    programs = [d for n, _, d in events("XLA Modules") if "matmul_program" in n]
    assert tr.module_runs("matmul_program") == programs and len(programs) == 3


def test_breakdown_names_operations_and_the_host_span_over_each_gap():
    tr = trace.load(os.path.join(DATA, "tiny.xplane.pb"))
    b = tr.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "fusion" and "_tiny_kernel" in names and len(names) <= 10
    gaps = dict(b["idle_gaps"])
    # the long gaps are the sleeps; the short ones lie inside a round
    assert max(gaps, key=gaps.get) == "chipbench.sleep"
    assert abs(sum(gaps.values()) - (tr.window_s() - tr.busy_s())) < 1e-9


def test_a_gap_takes_the_programs_span_inside_the_benchmarks():
    """A serve cell's step: ``chipbench.engine_step`` around the program's ``thunder_tpu.serve.*``
    spans.  The innermost span over a gap's middle names it, whichever prefix it carries, a span
    with another prefix is not read at all, and the sum is still the window's idle time."""
    op = lambda name, start, dur: trace.Op(name, "", start, dur)  # noqa: E731
    ops = [op("fusion.1", 0.0, 1.0), op("fusion.2", 1.5, 1.0), op("fusion.3", 4.5, 0.5),
           op("fusion.4", 5.25, 0.75), op("fusion.5", 7.0, 1.0)]
    spans = [op("chipbench.engine_step", 0.5, 4.25),                 # gaps 1.0-1.5, 2.5-4.5
             op("thunder_tpu.serve.step", 0.75, 3.9),
             op("thunder_tpu.serve.harvest", 2.6, 1.8), op("thunder_tpu.serve.harvest.wait", 2.75, 1.5),
             op("chipbench.engine_step", 4.9, 1.0),                  # gap 5.0-5.25: no program span over it
             op("thunder_tpu.serve.step", 5.3, 0.5)]                 # gap 6.0-7.0: nothing over its middle
    tr = trace.Trace([trace.Device("/device:TPU:0", ops, [])], spans)
    gaps = dict(tr.idle_gaps())
    assert gaps == {"thunder_tpu.serve.harvest.wait": 2.0, "(no span)": 1.0, "thunder_tpu.serve.step": 0.5,
                    "chipbench.engine_step": 0.25}
    assert list(gaps) == ["thunder_tpu.serve.harvest.wait", "(no span)", "thunder_tpu.serve.step",
                          "chipbench.engine_step"]                   # largest first
    assert abs(sum(gaps.values()) - (tr.window_s() - tr.busy_s())) < 1e-12
    assert tr.idle_share() == 1.0 - 4.25 / 8.0                       # no span moves the idle share
    # the spans in any order, and one that the file's loader would not have kept
    assert all(n.startswith(trace.HOST_SPAN_PREFIX) for n in gaps if n != "(no span)")
    shuffled = trace.Trace(tr.devices, spans[::-1])
    assert dict(shuffled.idle_gaps()) == gaps
