"""By hand: the expert share's time outside its grouped product, split by what each operation was traced from.

A kept trace of a cell with an expert share (`CHIPBENCH_KEEP_TRACE=1 python3 chipbench/run.py ... --trace 1`, run
from the working tree): the operations under the scopes `mlp` -> `experts` whose name does not start with
`moe_grouped_mm` (what `moe_glue_share_of_busy.*` counts), by the last component of their `op_name` (the primitive a
fusion's root was traced from; a Pallas kernel by its own name), a program's prompts apart from its decode steps (by
the run of the modules line an operation started in).  Prints one line `GLUE_SPLIT {json}`: seconds, calls, the
longest call (us) and the four largest instructions (seconds, calls) of the eight largest roots, `busy_s`, and the runs
of each kind of program; deletes the 47 MB `.xplane.pb` (the chip tool
brings back 64 MiB a call, or nothing) unless `--keep`.

    JAX_PLATFORMS=cpu python3 tools/glue_split.py axk1-serve-1chip.offline-longctx [--keep] [--cpu]

`--cpu` reads a rehearsal's trace (`run.py --rehearse` under `JAX_PLATFORMS=cpu`): the tool's own check, no measurement.
"""
import bisect
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import op_scopes, trace


def kind_of(module: str) -> str:
    return "prefill" if "prefill" in module else "decode" if "decode" in module else "step" if "step" in module else "other"


def split(path: str, prefix: str = "/device:TPU:") -> dict:
    tr = trace.load(path, prefix)
    idx = op_scopes.load(path, prefix)
    dev = tr.devices[0]
    mods = sorted(dev.modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    runs: dict = {}
    for m in mods:
        a = runs.setdefault(kind_of(m.name), [0, 0.0])
        a[0], a[1] = a[0] + 1, a[1] + m.dur
    out: dict = {}
    glue = total = 0.0
    for o in dev.ops:
        total += o.dur
        parts = op_scopes.components(op_scopes.lookup(idx, o).tf_op)
        if o.name.startswith("moe_grouped_mm") or not any(a == "mlp" and b == "experts" for a, b in zip(parts, parts[1:])):
            continue
        glue += o.dur
        i = bisect.bisect_right(starts, o.start) - 1
        where = kind_of(mods[i].name) if i >= 0 and o.start < mods[i].start + mods[i].dur else "other"
        root = re.sub(r"[.\d]+$", "", o.name) if parts[-1].startswith("pallas_call") else parts[-1]
        ent = out.setdefault(where, {}).setdefault(root, [0.0, 0, 0.0, {}])
        ent[0], ent[1], ent[2] = ent[0] + o.dur, ent[1] + 1, max(ent[2], o.dur)
        one = ent[3].setdefault(o.name, [0.0, 0])       # an instruction of a program: the gathers of one root apart
        one[0], one[1] = one[0] + o.dur, one[1] + 1
    for where, rows in out.items():
        out[where] = {k: [round(s, 6), n, round(mx * 1e6, 1),
                          {name: [round(t, 6), c] for name, (t, c) in sorted(ops.items(), key=lambda kv: -kv[1][0])[:4]}]
                      for k, (s, n, mx, ops) in sorted(rows.items(), key=lambda kv: -kv[1][0])[:8]}
    return {"busy_s": round(tr.busy_s(), 4), "ops_s": round(total, 4), "glue_s": round(glue, 4),
            "glue_share": round(glue / total, 4) if total else None, "runs": {k: [n, round(s, 4)] for k, (n, s) in runs.items()},
            "by_root": out}


def main():
    cell = sys.argv[1]
    path = glob.glob(os.path.join(ROOT, "chiprun_out", "traces", cell, "*.xplane.pb"))[0]
    print("GLUE_SPLIT " + json.dumps({"cell": cell, **split(path, "/host:CPU" if "--cpu" in sys.argv else "/device:TPU:")}), flush=True)
    if "--keep" not in sys.argv:
        os.remove(path)


if __name__ == "__main__":
    main()
