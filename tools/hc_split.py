"""By hand: a kept trace of a serve cell (`CHIPBENCH_KEEP_TRACE=1 python3 chipbench/run.py ... --trace 1`, run
from the working tree) split by program, by scope path (the layer index taken out) and by kernel name, and, for a
cell under hyper-connections, `chipbench/kernels/hc_mix.py`'s seconds and least seconds a program.  Prints one line
`HC_SPLIT {json}` and deletes the 47 MB `.xplane.pb` (the chip tool brings back 64 MiB a call, or nothing).

    JAX_PLATFORMS=cpu python3 tools/hc_split.py xing4-serve-1chip.offline-digest
"""
import json, os, sys, glob, re
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import common, op_scopes, program_spans, trace
cell = sys.argv[1]
path = glob.glob(os.path.join(ROOT, "chiprun_out", "traces", cell, "*.xplane.pb"))[0]
_, config, mix = common.open_cell(cell)
tr = trace.load(path, "/device:TPU:")
ctx = {"trace": tr, "config": config, "mix": mix, "op_scopes": op_scopes.load(path, "/device:TPU:"),
       "program_spans": program_spans.load(path)}
dev = tr.devices[0]
out = {"busy_s": tr.busy_s(), "window_s": tr.window_s()}
mods = {}
for m in dev.modules:
    k = "prefill" if "prefill" in m.name else "decode" if "decode" in m.name else m.name
    a = mods.setdefault(k, [0, 0.0]); a[0] += 1; a[1] += m.dur
out["modules"] = mods
if "hc_mult" in config:
    k = common.load_module("kernels", "hc_mix")
    parts = k.by_program(ctx)
    peaks = common.peaks("TPU v5 lite")
    for name, p in parts.items():
        p["least_s"] = k.least_seconds(config, p["rows"], p["runs"], peaks)
    out["hc"] = parts
paths, loose = op_scopes.tree(tr, ctx["op_scopes"])
agg = {}
for pth, (s, f, b) in paths.items():
    key = re.sub(r"blk\d+/", "", pth)
    agg[key] = agg.get(key, 0.0) + s
out["by_path"] = dict(sorted(agg.items(), key=lambda kv: -kv[1])[:45])
out["loose"] = dict(sorted(loose.items(), key=lambda kv: -kv[1])[:8])
names = {}
for o in dev.ops:
    key = re.sub(r"[.\d]+$", "", o.name)
    if key.startswith(("_flash", "moe_grouped", "mla_")):
        names[key] = names.get(key, 0.0) + o.dur
out["kernels"] = names
pairs = program_spans.prefill_pairs(ctx["program_spans"], dev.modules)
out["prefills"] = [(sp.args.get("tokens"), round(run.dur * 1e3, 2)) for sp, run in pairs]
print("HC_SPLIT " + json.dumps(out))
os.remove(path)
