"""The program's device scopes, from a trace to shares.

`data/scopes_train.xplane.pb` was recorded on a TPU v5e by a traced run of
`mistral7b-train-1chip.seq8k` (PR 36) and then cut by this file's own `cut`
(`python3 chipbench/tests/test_op_scopes.py <recorded> <out>`): of the device
plane the event metadata with the six stats the reader uses, the stat
metadata, and of the `XLA Ops` line the operations of one whole train step,
the trace's second (the 49 MB of other events, the modules line, the host
planes and the long `source_stack` strings went; what is kept is as recorded).  The shares are
held to the readings of that run, written in `PERF.md` section 5."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))   # run as a script

from chipbench import common, op_scopes, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "scopes_train.xplane.pb")
KEEP_STATS = ("tf_op", "flops", "bytes_accessed", "hlo_category", "source", "program_id")


# ---- the cut ---------------------------------------------------------------------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    payload = bytes(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _copy(buf, keep) -> bytes:
    """A message again, field by field; ``keep(num, value)`` gives the bytes to
    write for a length-delimited field, or None to drop it."""
    out = b""
    for num, wt, v in op_scopes._fields(buf):
        if wt == 0:
            out += _field(num, v)
        elif wt == 2:
            kept = keep(num, v)
            if kept is not None:
                out += _field(num, kept)
        else:
            out += _varint(num << 3 | wt) + bytes(v)
    return out


def cut(src: str, dst: str, program: str = "step", device_prefix: str = "/device:TPU:") -> None:
    """``src`` cut to its device planes' metadata and the operations of one
    whole run of the program whose name holds ``program`` (the second on the
    modules line: a trace starts in the middle of a step)."""
    with open(src, "rb") as f:
        space = memoryview(f.read())
    dev = trace.load(src, device_prefix).devices[0]
    run = [m for m in dev.modules if program in m.name][1]
    kept_events = {i for i, o in enumerate(dev.ops) if run.start <= o.start < run.start + run.dur}

    def plane(buf):
        names = {}
        for num, _wt, v in op_scopes._fields(buf):
            if num == 2 and not bytes(v).decode().startswith(device_prefix):
                return None
            if num == 5:
                sid, name = op_scopes._stat_metadata(op_scopes._map_value(v))
                names[sid] = name
        wanted = {sid for sid, name in names.items() if name in KEEP_STATS}
        refs = set()

        def stat(num, v):
            if num != 5:
                return v
            sid, val = op_scopes._stat(v)
            if sid not in wanted:
                return None
            if isinstance(val, tuple):
                refs.add(val[1])
            return v

        def keep(num, v):
            if num == 3:        # a line: the operations line alone, its first events
                fields = list(op_scopes._fields(v))
                if not any(n == 2 and bytes(x).decode() == trace.OPS_LINE for n, _, x in fields):
                    return None
                out, seen = b"", 0
                for n, wt, x in fields:
                    if wt == 0:
                        out += _field(n, x)
                    elif n == 4:
                        if seen in kept_events:
                            out += _field(n, x)
                        seen += 1
                    elif wt == 2:
                        out += _field(n, x)
                return out
            if num == 4:        # event metadata: the entry's key, and the value with the stats wanted
                return _copy(v, lambda n, x: _copy(x, stat) if n == 2 else x)
            if num == 6:        # the plane's own stats
                return None
            return v

        first = _copy(buf, keep)
        # the stat metadata that is used: the kept stats' own, and the names a ref_value points at
        return _copy(memoryview(first), lambda n, x: x if n != 5 or op_scopes._stat_metadata(
            op_scopes._map_value(x))[0] in wanted | refs else None)

    out = b""
    for num, wt, v in op_scopes._fields(space):
        if num == 1 and wt == 2:
            kept = plane(v)
            if kept is not None:
                out += _field(1, kept)
    with open(dst, "wb") as f:
        f.write(out)


# ---- the tests ---------------------------------------------------------------------------

pytestmark = pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")


def test_the_recorded_steps_operations_all_find_their_metadata():
    tr, idx = trace.load(RECORDED), op_scopes.load(RECORDED)
    ops = tr.devices[0].ops
    assert len(tr.devices) == 1 and len(ops) > 1000 and len(idx) > 1000
    assert all(op_scopes.lookup(idx, o) is not op_scopes.UNSCOPED for o in ops)
    kernels = [op_scopes.lookup(idx, o) for o in ops if o.name.startswith("_flash")]
    assert len(kernels) == 15 and all(k.group == "mixer" for k in kernels)          # fwd, dq, dkv of five layers
    assert sum(k.bwd for k in kernels) == 10
    assert all("mixer/attn" in k.tf_op for k in kernels)
    products = [r for r in map(lambda o: op_scopes.lookup(idx, o), ops) if r.category == "convolution fusion"]
    assert products and all(r.group in ("mixer", "mlp", "head") for r in products)
    assert all(r.flops > 0 and r.bytes_accessed > 0 for r in products)
    assert all(r.source.startswith("thunder_tpu/") for r in products)                # the checkout's root is cut off


def test_the_shares_of_the_recorded_step_are_the_runs_readings():
    ctx = {"trace": trace.load(RECORDED), "op_scopes": op_scopes.load(RECORDED)}
    got = {q: common.load_reader(f"{q}_share_of_busy.train").read(ctx)
           for q in ("mixer", "mlp", "head", "optimizer", "unscoped", "backward")}
    assert sum(v for q, v in got.items() if q != "backward") == pytest.approx(1.0, abs=1e-9)
    # the traced run's readings over ten steps (PERF.md section 5); one step of it reads the same to a point
    want = {"mixer": 0.291, "mlp": 0.598, "head": 0.103, "optimizer": 0.0056, "unscoped": 0.0025, "backward": 0.718}
    for q, v in want.items():
        assert got[q] == pytest.approx(v, abs=0.01), q


def test_the_tree_sums_to_the_groups():
    tr, idx = trace.load(RECORDED), op_scopes.load(RECORDED)
    paths, loose = op_scopes.tree(tr, idx)
    secs = op_scopes.seconds(tr, idx)
    assert sum(s for s, _, _ in paths.values()) + sum(loose.values()) == pytest.approx(secs["all"])
    assert sum(loose.values()) == pytest.approx(secs[None])
    by_group = {}
    for p, (s, _, _) in paths.items():
        g = op_scopes.classify(p + "/x")[0]
        by_group[g] = by_group.get(g, 0.0) + s
    assert all(by_group[g] == pytest.approx(secs[g]) for g in by_group)
    assert "copy-done" in loose


if __name__ == "__main__":
    cut(*sys.argv[1:4])
    print(os.path.getsize(sys.argv[2]), "bytes")
