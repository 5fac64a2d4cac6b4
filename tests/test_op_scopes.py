"""``chipbench/op_scopes.py``: the wire-format reader of a trace's event
metadata, the join of an operation to its metadata by its full name, the rule
for a line two programs hold under different groups, and the partition the
six share readers rest on.  The ``XSpace`` is written here, byte by byte, so
the reader is held to the wire format and not to a library; where
TensorFlow's ``xplane_pb2`` imports, the same planes are written by it too.
"""
from __future__ import annotations

import os
import struct
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import common, op_scopes, trace  # noqa: E402


# --------------------------------------------------------------------------
# a writer of the wire format, for the messages an xplane holds
# --------------------------------------------------------------------------

def varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(sid: int, value, *, ref: bool = False) -> bytes:
    if ref:
        return field(1, sid) + field(7, value)
    if isinstance(value, float):
        return field(1, sid) + field(2, value)
    if isinstance(value, int):
        return field(1, sid) + (field(3, value) if value >= 0 else field(4, value))
    return field(1, sid) + field(5, value)


STATS = {1: "tf_op", 2: "flops", 3: "bytes_accessed", 4: "hlo_category", 5: "source", 6: "program_id",
         7: "occupancy", 20: "jit(step)/bwd/blk0/mlp/up/dot_general:"}

# name, tf_op (a string, or the id of the stat metadata that holds it), flops, bytes
ENTRIES = [
    ("%fusion.1 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %p0), kind=kLoop", "jit(step)/blk0/mixer/qkv/dot_general:", 4096, 512),
    ("%fusion.2 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %p1), kind=kOutput", 20, 8192, 1024),
    ("%copy.3 = bf16[8,16]{0,1} copy(bf16[8,16]{1,0} %p2)", "jit(step)/copy:", 0, 256),
    ("%adam.4 = f32[64]{0} fusion(f32[64]{0} %p3), kind=kLoop", "jit(step)/optimizer/mul:", 64, 768),
    ("%argmax.5 = s32[4]{0} fusion(f32[4,256]{1,0} %p4), kind=kInput", "jit(decode_paged)/vmap(head/sample)/argmax:", 0, 4096),
    # the same line in two programs, once forward and once backward: unscoped
    ("%fusion.6 = bf16[8]{0} fusion(bf16[8]{0} %p5), kind=kLoop", "jit(step)/blk1/mlp/norm/mul:", 8, 32),
    ("%fusion.6 = bf16[8]{0} fusion(bf16[8]{0} %p5), kind=kLoop", "jit(step)/bwd/blk1/mlp/norm/mul:", 8, 32),
    # the same line under one group twice: kept
    ("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p6), kind=kLoop", "jit(prefill)/blk0/mlp/down/dot_general:", 16, 64),
    ("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p6), kind=kLoop", "jit(prefill_fresh)/blk3/mlp/down/dot_general:", 16, 64),
    ("jit_step(123)", None, None, None),        # a program's entry on the modules line: no stats
]


def event_metadata(mid: int, name: str, tf_op, flops, nbytes) -> bytes:
    body = field(1, mid) + field(2, name)
    if tf_op is not None:
        body += field(5, stat(1, tf_op, ref=isinstance(tf_op, int)))
        body += field(5, stat(2, flops)) + field(5, stat(3, nbytes)) + field(5, stat(4, "loop fusion"))
        body += field(5, stat(5, "thunder_tpu/models/llama.py:664")) + field(5, stat(6, -7)) + field(5, stat(7, 0.5))
    return body


def device_plane(name: str) -> bytes:
    out = field(1, 7) + field(2, name)
    out += field(3, field(2, "XLA Ops") + field(4, b"\x08\x01" * 4000))    # a line of events: to be stepped over
    for mid, entry in enumerate(ENTRIES, start=1):
        out += field(4, field(1, mid) + field(2, event_metadata(mid, *entry)))
    for sid, sname in STATS.items():
        out += field(5, field(1, sid) + field(2, field(1, sid) + field(2, sname)))
    return out


def space() -> bytes:
    host = field(1, 1) + field(2, "/host:CPU") + field(4, field(1, 1) + field(2, event_metadata(1, "%fusion.1 = host", "x/mixer/y:", 1, 1)))
    return field(1, host) + field(1, device_plane("/device:TPU:0")) + field(4, "a-host-name")


@pytest.fixture
def xplane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space())
    return str(path)


def op(line: str, dur: float, start: float = 0.0) -> trace.Op:
    own, _, rest = line.partition(" = ")
    return trace.Op(own.lstrip("%"), rest, start, dur)


# --------------------------------------------------------------------------
# the wire reader
# --------------------------------------------------------------------------

def test_the_reader_finds_the_device_planes_metadata_and_steps_over_the_lines(xplane):
    planes = op_scopes.read_planes(xplane)
    assert [name for name, _ in planes] == ["/device:TPU:0"]            # the host plane is not a device's
    entries = planes[0][1]
    assert [name for name, _ in entries] == [e[0] for e in ENTRIES]
    first = entries[0][1]
    assert first == {"tf_op": "jit(step)/blk0/mixer/qkv/dot_general:", "flops": 4096, "bytes_accessed": 512,
                     "hlo_category": "loop fusion", "source": "thunder_tpu/models/llama.py:664", "program_id": -7,
                     "occupancy": 0.5}
    assert entries[1][1]["tf_op"] == STATS[20]                           # a ref_value names a stat metadata's name
    assert entries[-1][1] == {}
    # another prefix, another plane; a stat whose metadata the plane lacks goes by its number
    assert op_scopes.read_planes(xplane, "/host:CPU")[0][1][0][1]["1"] == "x/mixer/y:"


def test_the_reader_agrees_with_the_generated_protobuf_module(tmp_path):
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    sp = xplane_pb2.XSpace()
    plane = sp.planes.add(name="/device:TPU:0", id=3)
    plane.lines.add(name="XLA Ops").events.add(metadata_id=1, duration_ps=5)
    for sid, sname in STATS.items():
        plane.stat_metadata[sid].id, plane.stat_metadata[sid].name = sid, sname
    for mid, (name, tf_op, flops, nbytes) in enumerate(ENTRIES, start=1):
        em = plane.event_metadata[mid]
        em.id, em.name = mid, name
        if tf_op is None:
            continue
        s = em.stats.add(metadata_id=1)
        if isinstance(tf_op, int):
            s.ref_value = tf_op
        else:
            s.str_value = tf_op
        em.stats.add(metadata_id=2).uint64_value = flops
        em.stats.add(metadata_id=3).uint64_value = nbytes
    path = tmp_path / "pb.xplane.pb"
    path.write_bytes(sp.SerializeToString())
    (_, entries), = op_scopes.read_planes(str(path))
    got = {name: stats for name, stats in entries}
    assert got[ENTRIES[0][0]] == {"tf_op": ENTRIES[0][1], "flops": 4096, "bytes_accessed": 512}
    assert got[ENTRIES[1][0]]["tf_op"] == STATS[20]


# --------------------------------------------------------------------------
# names, groups, the join
# --------------------------------------------------------------------------

@pytest.mark.parametrize("tf_op, group, bwd, path", [
    ("jit(step)/bwd/blk3/mixer/qkv/dot_general:", "mixer", True, "bwd/blk3/mixer/qkv"),
    ("jit(decode_paged)/vmap(head/sample)/argmax:", "head", False, "head/sample"),
    ("jit(decode_paged)/blk1/mlp/experts/jit(_moe_grouped_mm)/moe_grouped_mm/pallas_call:", "mlp", False,
     "blk1/mlp/experts/_moe_grouped_mm/moe_grouped_mm"),
    ("jit(step)/optimizer/jit(_where)/select_n:", "optimizer", False, "optimizer/_where"),
    ("jit(step)/embed/gather:", "embed", False, "embed"),
    ("jit(step)/bwd/unscoped/optimization_barrier/optimization_barrier:", None, True, "bwd/unscoped/optimization_barrier"),
    ("jit(decode_paged)/jit(floor_divide)/div:", None, False, ""),      # the parent's: JAX's own stack alone
    ("jit(mlperf)/headroom/add:", None, False, ""),                     # a group is a whole component
    ("", None, False, ""),
])
def test_a_name_gives_its_group_its_direction_and_its_path(tf_op, group, bwd, path):
    assert op_scopes.classify(tf_op) == (group, bwd)
    assert op_scopes.path_of(tf_op) == path


def test_an_operation_is_joined_by_its_full_name_and_an_ambiguous_line_is_unscoped(xplane):
    idx = op_scopes.load(xplane)
    rec = op_scopes.lookup(idx, op(ENTRIES[0][0], 1.0))
    assert (rec.group, rec.bwd, rec.flops, rec.bytes_accessed) == ("mixer", False, 4096.0, 512.0)
    assert rec.category == "loop fusion" and rec.source.endswith("llama.py:664")
    assert op_scopes.lookup(idx, op(ENTRIES[1][0], 1.0)).bwd
    # the name alone does not do: another line of the same instruction name is not this one
    assert op_scopes.lookup(idx, op("%fusion.1 = bf16[4]{0} fusion(bf16[4]{0} %q), kind=kLoop", 1.0)) is op_scopes.UNSCOPED
    both = op_scopes.lookup(idx, op(ENTRIES[5][0], 1.0))                # forward in one program, backward in another
    assert both.group is None and not both.bwd and both.tf_op
    assert op_scopes.lookup(idx, op(ENTRIES[7][0], 1.0)).group == "mlp"  # two programs, one group
    assert op_scopes.lookup(idx, op("jit_step(123)", 1.0)).group is None


def _trace() -> trace.Trace:
    durs = {0: 2.0, 1: 3.0, 2: 0.5, 3: 1.0, 4: 0.25, 5: 0.75, 7: 0.5}
    ops, t = [], 0.0
    for i, d in durs.items():
        ops.append(op(ENTRIES[i][0], d, t))
        t += d
    ops.append(op("%while.9 = (s32[]) while((s32[]) %t), condition=%c, body=%b", 0.5, t))   # no metadata at all
    return trace.Trace([trace.Device("/device:TPU:0", ops, [])], [])


def test_the_groups_and_the_unscoped_share_sum_to_one(xplane):
    ctx = {"trace": _trace(), "op_scopes": op_scopes.load(xplane)}
    shares = {g: op_scopes.share(ctx, g) for g in (*op_scopes.GROUPS, None)}
    assert shares["embed"] is None                                       # nothing there: no number, not a zero
    assert sum(v for v in shares.values() if v is not None) == pytest.approx(1.0, abs=1e-12)
    total = 8.5
    assert shares["mixer"] == pytest.approx(2.0 / total) and shares["mlp"] == pytest.approx(3.5 / total)
    assert shares[None] == pytest.approx((0.5 + 0.75 + 0.5) / total)    # the copy, the ambiguous line, the while
    assert op_scopes.share(ctx, "head", "embed") == pytest.approx(0.25 / total)
    assert op_scopes.share(ctx, op_scopes.BACKWARD) == pytest.approx(3.0 / total)


def test_the_six_readers_read_what_the_file_gives_and_nothing_on_a_program_without_scopes(xplane, tmp_path):
    ctx = {"trace": _trace(), "op_scopes": op_scopes.load(xplane)}
    values = {q: common.load_reader(f"{q}.train").read(ctx) for q in (
        "mixer_share_of_busy", "mlp_share_of_busy", "head_share_of_busy", "optimizer_share_of_busy",
        "unscoped_share_of_busy", "backward_share_of_busy")}
    partition = [v for q, v in values.items() if q != "backward_share_of_busy"]
    assert sum(partition) == pytest.approx(1.0, abs=1e-12) and 0 < values["backward_share_of_busy"] < 1
    assert not any(getattr(common.load_reader(f"{q}.train"), "SHARE_OF_PEAK", False) for q in values)
    # the parent's trace: the same operations, no group in any name
    bare = {"trace": _trace(), "op_scopes": {}}
    got = {q: common.load_reader(f"{q}.serve").read(bare) for q in values}
    assert got.pop("unscoped_share_of_busy") == 1.0 and set(got.values()) == {None}
    empty = {"trace": trace.Trace([], []), "op_scopes": {}}
    assert all(common.load_reader(f"{q}.serve").read(empty) is None for q in values)


# ---- the six shares by scope in the manifest ------------------------------------------------------
# Held in both forms while both exist (PERF.md section 7, W0(q)): as PR 50 left them, one entry
# a split (`.train`, `.hyb`; `.offline`, `.hybserve`, `.mlaserve`, `.lfm2serve`, `.flashserve`,
# a later cell joined to the last one's list), and folded, one entry an end-to-end metric
# (`.train`, `.serve`) whose `workloads` are the cells of that kind in the manifest's order.
MANIFEST = common.manifest()
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
KIND_OF = {"train": "train_tok_per_s_per_chip", "serve": "serve_out_tok_per_s"}
CELLS = {kind: [w["name"] for w in MANIFEST["workloads"]
                if w["name"] in next(m for m in MANIFEST["end_to_end"] if m["name"] == e2e)["workloads"]]
         for kind, e2e in KIND_OF.items()}
SPLITS_BEFORE_THE_FOLD = {"train": ("train", "hyb"),
                          "serve": ("offline", "hybserve", "mlaserve", "lfm2serve", "flashserve")}


@pytest.mark.parametrize("q", ["mixer", "mlp", "head", "unscoped", "optimizer", "backward"])
def test_the_manifest_lists_a_share_by_scope_once_for_every_cell_of_its_kinds(q):
    quantity = f"{q}_share_of_busy"
    entries = {n: m for n, m in PER_LAYER.items() if n.partition(".")[0] == quantity}
    kinds = ("train",) if q in ("optimizer", "backward") else ("train", "serve")      # a server has neither
    assert {m["moves"] for m in entries.values()} == {KIND_OF[k] for k in kinds}
    for m in entries.values():
        assert m["unit"] == "fraction" and m["source"] == "device_trace", m["name"]
        assert common.load_reader(m["name"]).__name__.endswith(quantity)             # one reader, whatever the split
    for kind in kinds:
        mine = {n.partition(".")[2]: m for n, m in entries.items() if m["moves"] == KIND_OF[kind]}
        if set(mine) == {kind}:                                                       # folded
            assert mine[kind]["workloads"] == CELLS[kind]
            continue
        assert set(mine) <= set(SPLITS_BEFORE_THE_FOLD[kind]), sorted(mine)
        listed = [w for split in SPLITS_BEFORE_THE_FOLD[kind] if split in mine for w in mine[split]["workloads"]]
        assert listed == CELLS[kind], (quantity, kind)      # every cell of the kind, once, in the manifest's order


def test_no_quantity_is_listed_twice_for_a_cell():
    for w in (w["name"] for w in MANIFEST["workloads"]):
        quantities = [n.partition(".")[0] for n, m in PER_LAYER.items() if w in m.get("workloads", [w])]
        assert len(quantities) == len(set(quantities)), (w, sorted(q for q in quantities if quantities.count(q) > 1))
    assert len(PER_LAYER) == len(MANIFEST["per_layer"]) <= 96
