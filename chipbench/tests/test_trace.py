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
