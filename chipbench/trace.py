"""From a profiler trace (``.xplane.pb``) to numbers.

A device plane (``/device:TPU:<n>``) has one line of operations (``XLA Ops``:
what the core runs, one after another) and one of programs (``XLA Modules``:
each run of a jitted program).  The host plane has one line a thread, with
the ``chipbench.*`` spans that the benchmark's ``TraceAnnotation`` writes and
the program's own ``thunder_tpu.*`` spans (``observability.events.span``) on
the same clock.

- busy: the union of the operation intervals of a device; idle is the rest
  of the window, which runs from the first operation's start to the last
  one's end over all devices (a trace starts and stops between steps).
- an operation's name on a TPU is its whole HLO line (``%name.7 = type
  op(operands), attributes``): ``Op.name`` keeps what stands before `` = ``
  without the ``%``, and ``Op.meta`` the rest.
- a kernel's time: the sum of the durations of the operations that the
  kernel's own file under ``kernels/`` recognises (``matches(op)``): no
  ``pallas_call`` of the program carries ``name=`` yet, so a kernel is known
  by the jitted wrapper its custom call is named after, or by its operands.
- a program's time: the durations of its runs on the modules line.
- an idle gap is named after the innermost ``chipbench.*`` or ``thunder_tpu.*``
  host span that covers its middle, or ``(no span)``: in a serve cell the
  engine's phase (``thunder_tpu.serve.harvest.wait``, ``.prefill_dispatch``),
  not the ``chipbench.engine_step`` around all of them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = ("chipbench.", "thunder_tpu.")
# Pallas kernels reach XLA as a custom call to Mosaic
PALLAS_MARKS = ('custom_call_target="tpu_custom_call"',)


@dataclasses.dataclass
class Op:
    name: str
    meta: str
    start: float        # seconds
    dur: float


@dataclasses.dataclass
class Device:
    name: str
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list
    host_spans: list    # Op, name starting with one of HOST_SPAN_PREFIX

    # ---- window and busy -------------------------------------------------
    def window(self) -> tuple[float, float]:
        starts = [o.start for d in self.devices for o in d.ops]
        ends = [o.start + o.dur for d in self.devices for o in d.ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    def window_s(self) -> float:
        a, b = self.window()
        return b - a

    @staticmethod
    def _union(ops) -> list[tuple[float, float]]:
        out: list[list[float]] = []
        for o in sorted(ops, key=lambda o: o.start):
            if out and o.start <= out[-1][1]:
                out[-1][1] = max(out[-1][1], o.start + o.dur)
            else:
                out.append([o.start, o.start + o.dur])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self._union(d.ops))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float | None:
        w = self.window_s()
        return None if w <= 0 else 1.0 - self.busy_s() / w

    # ---- operations --------------------------------------------------------
    def op_seconds(self, match) -> float:
        """Seconds in operations that ``match(op)`` accepts, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(o.dur for d in self.devices for o in d.ops if match(o)) / len(self.devices)

    def op_count(self, match) -> int:
        """Operations that ``match(op)`` accepts, on the first device."""
        return sum(1 for o in self.devices[0].ops if match(o)) if self.devices else 0

    def pallas_seconds(self) -> float:
        return self.op_seconds(lambda o: any(m in o.meta for m in PALLAS_MARKS))

    def module_runs(self, part: str) -> list[float]:
        """Durations of the runs of every program whose name holds ``part``
        (first device: all run the same programs)."""
        if not self.devices:
            return []
        return [m.dur for m in self.devices[0].modules if part in m.name]

    # ---- the breakdown the ledger keeps -----------------------------------------
    def top_ops(self, n: int = 10) -> list:
        agg: dict[str, float] = {}
        for d in self.devices:
            for o in d.ops:
                key = re.sub(r"[.\d]+$", "", o.name) or o.name
                agg[key] = agg.get(key, 0.0) + o.dur / len(self.devices)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        if not self.devices:
            return []
        a0, _ = self.window()
        busy = self._union(self.devices[0].ops)
        gaps, prev = [], a0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        agg: dict[str, float] = {}
        # the gaps come in time order, so one pass over the spans by start serves them all (with the
        # program's spans a traced stretch holds thousands of each)
        spans, cover, k = sorted(self.host_spans, key=lambda s: s.start), [], 0
        for a, b in gaps:
            mid = (a + b) / 2
            while k < len(spans) and spans[k].start <= mid:
                cover.append(spans[k])
                k += 1
            cover = [s for s in cover if mid <= s.start + s.dur]
            # the innermost span names the gap
            name = min(cover, key=lambda s: s.dur).name if cover else "(no span)"
            agg[name] = agg.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            dev = Device(plane.name, [], [])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                target = dev.ops if line.name == OPS_LINE else dev.modules
                for e in line.events:
                    own, _, rest = e.name.partition(" = ")
                    target.append(Op(own.lstrip("%"), rest, e.start_ns / 1e9, e.duration_ns / 1e9))
            if dev.ops:
                devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(Op(e.name, "", e.start_ns / 1e9, e.duration_ns / 1e9))
    return Trace(devices, spans)


def summary(path: str) -> dict:
    """What is in a trace, for a look by hand before code is written against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            names: dict[str, list] = {}
            for e in evs:
                ent = names.setdefault(e.name, [0, 0.0, None])
                ent[0] += 1
                ent[1] += e.duration_ns / 1e9
                if ent[2] is None:
                    ent[2] = {k: (v if not isinstance(v, str) else v[:300]) for k, v in e.stats}
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:40]
            lines[line.name] = {"events": len(evs), "top": [[k, *v] for k, v in top]}
        out[plane.name] = lines
    return out
