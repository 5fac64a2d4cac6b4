"""The program's device scopes in a profiler trace.

thunder_tpu names its device work with ``observability.events.scope``: the
path of scopes an operation was traced under (``bwd/blk3/mixer/qkv``) becomes
the ``op_name`` of the HLO instructions it lowers to.  A device plane of the
``.xplane.pb`` holds one *event metadata* entry an instruction, and its stats
carry that name (``tf_op``: ``jit(step)/bwd/blk3/mixer/qkv/dot_general``)
beside the compiler's own estimates of the instruction's work (``flops``,
``bytes_accessed``), its ``hlo_category`` and its ``source``.
``jax.profiler.ProfileData`` (``trace.py``) hands out an event's own stats
only, so this file reads the metadata itself.

- No TensorFlow and no generated protobuf module: a wire-format reader of
  the seven messages it needs (``XSpace.planes``; of a device plane its name,
  ``event_metadata``, ``stat_metadata``; ``XEventMetadata``, ``XStatMetadata``,
  ``XStat`` and the two map entries).  The lines of events, nearly all of the
  file, are skipped by their length prefix.
- An operation of ``trace.py`` is joined to its metadata by its full name:
  ``"%" + op.name + " = " + op.meta`` is the metadata's ``name``.  Where two
  programs hold the same line under different groups (or one forward and one
  backward), the operation counts as unscoped.
- A path's components are what stands between ``/``, ``(`` and ``)``, so a
  transform that JAX wraps around a scope (``vmap(head/sample)``) hides
  nothing.  The **group** is the first component from the closed set
  ``GROUPS``; ``bwd`` anywhere marks the backward pass.
- A fusion is attributed by its own metadata: the ``op_name`` XLA kept for it,
  which is its root instruction's.  What it fused from another scope counts
  with the root's.
- A program without scopes (the parent of the PR that brought them) has no
  group anywhere: every operation is unscoped, ``share`` gives 1.0 for
  ``None`` and ``None`` for every group, and nothing raises.

Nothing here imports the program under test.

By hand, on a kept trace (``CHIPBENCH_KEEP_TRACE=1``):

    python3 chipbench/op_scopes.py chiprun_out/traces/<cell>/<file>.xplane.pb
"""
from __future__ import annotations

import dataclasses
import re
import struct

GROUPS = ("embed", "mixer", "mlp", "head", "optimizer")
BACKWARD = "bwd"
_SPLIT = re.compile(r"[/();]+")
# what JAX writes around a scope of the program's, and not the program
_TRANSFORMS = frozenset({"jit", "pjit", "vmap", "jvp", "transpose", "checkpoint", "custom_jvp_call", "custom_vjp_call",
                         "shard_map", "closed_call", "core_call"})


@dataclasses.dataclass
class Scoped:
    tf_op: str              # the instruction's op_name: JAX's name stack and the primitive
    group: str | None       # one of GROUPS, or None: unscoped
    bwd: bool
    flops: float | None     # the compiler's estimates, a run of the instruction
    bytes_accessed: float | None
    category: str = ""      # hlo_category
    source: str = ""


UNSCOPED = Scoped("", None, False, None, None)


def components(tf_op: str) -> list:
    """What stands between ``/``, ``(`` and ``)``; a TPU's ``tf_op`` ends in ``:`` and an (empty) op type."""
    return [c for c in _SPLIT.split(tf_op.partition(":")[0]) if c]


def classify(tf_op: str) -> tuple:
    """``(group or None, backward)`` of an ``op_name``."""
    parts = components(tf_op)
    return next((p for p in parts if p in GROUPS), None), BACKWARD in parts


def path_of(tf_op: str) -> str:
    """The scopes of an ``op_name`` without JAX's own wrappers and the
    primitive: from ``bwd`` or the first group or ``blk<i>`` on, to the
    component before the last, the names of JAX's transforms left out (an
    inner ``jit(_flash_fwd)`` stays as ``_flash_fwd``)."""
    parts = components(tf_op)
    for i, p in enumerate(parts):
        if p == BACKWARD or p in GROUPS or re.fullmatch(r"blk\d+", p):
            return "/".join(c for c in parts[i:-1] if c not in _TRANSFORMS) or p
    return ""


# ---- the wire format ------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of a message: a varint as an int, a
    fixed-width field as its bytes, a length-delimited one as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> tuple:
    """An ``XStat``: ``(metadata id, value)``; a ``ref_value`` comes back as
    ``("ref", id of the stat metadata whose name is the string)``."""
    sid, val = 0, None
    for num, wt, v in _fields(buf):
        if num == 1:
            sid = v
        elif num == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            val = ("ref", v)
    return sid, val


def _map_value(buf):
    """The value (field 2) of a map entry."""
    for num, _wt, v in _fields(buf):
        if num == 2:
            return v
    return b""


def _event_metadata(buf) -> tuple:
    name, stats = "", []
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 5:
            stats.append(_stat(v))
    return name, stats


def _stat_metadata(buf) -> tuple:
    sid, name = 0, ""
    for num, _wt, v in _fields(buf):
        if num == 1:
            sid = v
        elif num == 2:
            name = bytes(v).decode("utf-8", "replace")
    return sid, name


def read_planes(path: str, device_prefix: str = "/device:TPU:") -> list:
    """``[(plane name, [(metadata name, {stat name: value})])]`` for the device
    planes of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = "", [], {}
        for pnum, _pwt, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode("utf-8", "replace")
                if not name.startswith(device_prefix):
                    break
            elif pnum == 4:
                events.append(v)
            elif pnum == 5:
                sid, sname = _stat_metadata(_map_value(v))
                stat_names[sid] = sname
            # field 3, the lines: stepped over by _fields' length prefix
        if not name.startswith(device_prefix):
            continue
        entries = []
        for ev in events:
            ename, stats = _event_metadata(_map_value(ev))
            named = {}
            for sid, val in stats:
                if isinstance(val, tuple):
                    val = stat_names.get(val[1], "")
                named[stat_names.get(sid, str(sid))] = val
            entries.append((ename, named))
        out.append((name, entries))
    return out


# ---- the index and the join -----------------------------------------------------------

def _number(v):
    return float(v) if isinstance(v, (int, float)) else None


def index(planes: list) -> dict:
    """``{metadata name: Scoped}`` over the device planes.  A name that two
    entries give different groups or directions maps to an unscoped record."""
    out: dict = {}
    for _plane, entries in planes:
        for name, stats in entries:
            tf_op = stats.get("tf_op") or ""
            if not isinstance(tf_op, str):
                tf_op = ""
            group, bwd = classify(tf_op)
            new = Scoped(tf_op, group, bwd, _number(stats.get("flops")),
                         _number(stats.get("bytes_accessed")),
                         str(stats.get("hlo_category", "")), str(stats.get("source", "")))
            old = out.get(name)
            if old is None:
                out[name] = new
            elif (old.group, old.bwd) != (new.group, new.bwd):
                out[name] = dataclasses.replace(UNSCOPED, tf_op=old.tf_op or new.tf_op)
    return out


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    return index(read_planes(path, device_prefix))


def lookup(idx: dict, op) -> Scoped:
    """The record of a ``trace.Op``."""
    full = f"{op.name} = {op.meta}" if op.meta else op.name
    return idx.get("%" + full) or idx.get(full) or UNSCOPED


def of(ctx: dict) -> dict:
    """The index of this run's trace: read once from the ``.xplane.pb`` under
    ``ctx['trace_dir']`` and kept in ``ctx`` for the next reader."""
    if "op_scopes" not in ctx:
        from chipbench import trace

        cpu = ctx["devices"][0].platform != "tpu"
        ctx["op_scopes"] = load(trace.find_xplane(ctx["trace_dir"]), "/host:CPU" if cpu else "/device:TPU:")
    return ctx["op_scopes"]


# ---- the arithmetic of the readers ----------------------------------------------------

def seconds(tr, idx: dict) -> dict:
    """Seconds of the trace's operations, averaged over its devices: by group
    (``None`` the unscoped), ``"bwd"`` across groups, and ``"all"``."""
    out: dict = {g: 0.0 for g in (*GROUPS, None, BACKWARD, "all")}
    n = len(tr.devices) or 1
    for d in tr.devices:
        for o in d.ops:
            rec = lookup(idx, o)
            out[rec.group] += o.dur / n
            out["all"] += o.dur / n
            if rec.bwd:
                out[BACKWARD] += o.dur / n
    return out


def share(ctx: dict, *groups):
    """The share of the operations' seconds under ``groups`` (``None``: the
    unscoped; ``"bwd"``: the backward pass), or ``None`` where there is none.
    The shares of the five groups and of ``None`` sum to one."""
    if "op_scope_seconds" not in ctx:
        ctx["op_scope_seconds"] = seconds(ctx["trace"], of(ctx))
    secs = ctx["op_scope_seconds"]
    got = sum(secs[g] for g in groups)
    return got / secs["all"] if secs["all"] > 0 and got > 0 else None


# ---- by hand ------------------------------------------------------------------------------

def tree(tr, idx: dict) -> tuple:
    """``({path: [seconds, flops, bytes]}, {unscoped operation: seconds})``,
    work as the compiler's estimate times the runs."""
    paths: dict = {}
    loose: dict = {}
    n = len(tr.devices) or 1
    for d in tr.devices:
        for o in d.ops:
            rec = lookup(idx, o)
            if rec.group is None:
                key = re.sub(r"[.\d]+$", "", o.name) or o.name
                loose[key] = loose.get(key, 0.0) + o.dur / n
                continue
            ent = paths.setdefault(path_of(rec.tf_op), [0.0, 0.0, 0.0])
            ent[0] += o.dur / n
            ent[1] += (rec.flops or 0.0) / n
            ent[2] += (rec.bytes_accessed or 0.0) / n
    return paths, loose


def compiler_names(tr, idx: dict) -> dict:
    """``{kind of name XLA gave: {group, None, "bwd": seconds}}`` for the
    operations that carry no name of the program's: ``fusion``,
    ``convolution_*_fusion``, ``*_reduce_fusion``, every other ``*fusion``."""
    out: dict = {}
    n = len(tr.devices) or 1
    for d in tr.devices:
        for o in d.ops:
            key = re.sub(r"[.\d]+$", "", o.name) or o.name
            if key == "fusion":
                kind = "fusion"
            elif key.startswith("convolution") and key.endswith("fusion"):
                kind = "convolution_*_fusion"
            elif key.endswith("reduce_fusion"):
                kind = "*_reduce_fusion"
            elif key.endswith("fusion"):
                kind = "other *_fusion"
            else:
                continue
            rec, row = lookup(idx, o), out.setdefault(kind, {})
            row[rec.group] = row.get(rec.group, 0.0) + o.dur / n
            if rec.bwd:
                row[BACKWARD] = row.get(BACKWARD, 0.0) + o.dur / n
    return out


def main(argv: list) -> None:
    import os
    import sys
    import time

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench import trace

    path = argv[1]
    prefix = argv[2] if len(argv) > 2 else "/device:TPU:"
    t0 = time.perf_counter()
    idx = load(path, prefix)
    t_read = time.perf_counter() - t0
    tr = trace.load(path, prefix)
    secs = seconds(tr, idx)
    paths, loose = tree(tr, idx)
    busy = tr.busy_s()
    print(f"metadata entries {len(idx)}, read in {t_read:.2f} s; busy {busy:.4f} s, "
          f"operations {secs['all']:.4f} s, window {tr.window_s():.4f} s")
    for g in (*GROUPS, None, BACKWARD):
        print(f"  {str(g):10s} {secs[g]:9.4f} s  {secs[g] / secs['all'] if secs['all'] else 0.0:7.4f}")

    def fold(depth: int) -> dict:
        """Paths cut to ``depth`` components after ``bwd`` and ``blk<i>`` are taken out."""
        agg: dict = {}
        for p, (s, fl, by) in paths.items():
            parts = [c for c in p.split("/") if not re.fullmatch(r"blk\d+", c)]
            key = "/".join(parts[:depth + (parts[:1] == [BACKWARD])])
            ent = agg.setdefault(key, [0.0, 0.0, 0.0])
            ent[0] += s
            ent[1] += fl
            ent[2] += by
        return agg

    print("path (layers summed)                        seconds   share  Tflops/s    GB/s")
    for key, (s, fl, by) in sorted(fold(3).items(), key=lambda kv: -kv[1][0]):
        print(f"  {key:40s} {s:9.4f} {s / secs['all']:7.4f} {fl / s / 1e12 if s else 0:9.2f} {by / s / 1e9 if s else 0:7.1f}")
    print("XLA's own names by group, seconds:   " + "".join(f"{str(g):>10s}" for g in (*GROUPS, None, BACKWARD)))
    for kind, row in sorted(compiler_names(tr, idx).items(), key=lambda kv: -sum(v for g, v in kv[1].items() if g != BACKWARD)):
        print(f"  {kind:34s} " + "".join(f"{row.get(g, 0.0):10.4f}" for g in (*GROUPS, None, BACKWARD)))
    print("the largest unscoped operations:")
    for k, s in sorted(loose.items(), key=lambda kv: -kv[1])[:10]:
        print(f"  {k:40s} {s:9.4f} {s / secs['all']:7.4f}")
    print(f"op_scopes: {time.perf_counter() - t0:.2f} s in all")


if __name__ == "__main__":
    import sys

    main(sys.argv)
