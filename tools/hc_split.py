"""By hand, two forms.

A kept trace of a serve cell (`CHIPBENCH_KEEP_TRACE=1 python3 chipbench/run.py ... --trace 1`, run from the working
tree) split by program, by scope path (the layer index taken out) and by kernel name, and, for a cell under
hyper-connections, `chipbench/kernels/hc_mix.py`'s seconds and least seconds a program.  Prints one line
`HC_SPLIT {json}` and deletes the 47 MB `.xplane.pb` (the chip tool brings back 64 MiB a call, or nothing).

    JAX_PLATFORMS=cpu python3 tools/hc_split.py xing4-serve-1chip.offline-digest

`--kernel T [TQ ...]`, on the chip: the hyper-connections of one prompt of `T` tokens alone, at the cell's widths and
depth (every sublayer's boundary: the first open, the joined closes and opens, the last close; what a sublayer gives
is what it read, so nothing but the hyper-connections runs), a line a form: `models.generate`'s `jax.numpy` lines
(`hc_open` / `hc_close`, the fallback and the form before PR 56), then `pallasex.hc_mix` at `pallasex._HC_TILE`
tokens a grid step and at each `TQ` given.  ms a prompt from a device trace, by name, beside `hc_mix.least_seconds`; the kernel's
stream and last read are held to the `jax.numpy` lines' first.  Exits non-zero without a TPU.

    python3 tools/hc_split.py xing4-serve-1chip.offline-digest --kernel 8192 128 256 512
"""
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import common, op_scopes, program_spans, trace

KERNELS = ("_flash", "moe_grouped", "mla_", "hc_mix")
REPS = 3


def split(cell: str) -> dict:
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
        a = mods.setdefault(k, [0, 0.0])
        a[0], a[1] = a[0] + 1, a[1] + m.dur
    out["modules"] = mods
    if "hc_mult" in config:
        k = common.load_module("kernels", "hc_mix")
        parts = k.by_program(ctx)
        peaks = common.peaks("TPU v5 lite")
        for p in parts.values():
            p["least_s"] = k.least_seconds(config, p["rows"], p["runs"], peaks)
        out["hc"] = parts
    paths, loose = op_scopes.tree(tr, ctx["op_scopes"])
    agg = {}
    for pth, (s, _f, _b) in paths.items():
        key = re.sub(r"blk\d+/", "", pth)
        agg[key] = agg.get(key, 0.0) + s
    out["by_path"] = dict(sorted(agg.items(), key=lambda kv: -kv[1])[:45])
    out["loose"] = dict(sorted(loose.items(), key=lambda kv: -kv[1])[:8])
    names = {}
    for o in dev.ops:
        key = re.sub(r"[.\d]+$", "", o.name)
        if key.startswith(KERNELS):
            names[key] = names.get(key, 0.0) + o.dur
    out["kernels"] = names
    pairs = program_spans.prefill_pairs(ctx["program_spans"], dev.modules)
    out["prefills"] = [(sp.args.get("tokens"), round(run.dur * 1e3, 2)) for sp, run in pairs]
    os.remove(path)
    return out


def kernel(cell: str, T: int, tiles: list) -> None:
    import jax
    import jax.numpy as jnp

    from thunder_tpu._platform import device_info
    from thunder_tpu.executors import pallasex as px
    from thunder_tpu.models import generate as G
    from thunder_tpu.models import llama
    from tools.flash_tune import kernel_ms

    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"hc_split --kernel: times the hyper-connections on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    _, config, _ = common.open_cell(cell)
    arch = common.load_module("models", config["arch"])
    counted = common.load_module("kernels", "hc_mix")
    cfg = llama.Config(**arch.program_config(config))
    n, C, sublayers = cfg.hc_mult, cfg.n_embd, 2 * cfg.n_layer
    m, keys = n * (n + 2), jax.random.split(jax.random.PRNGKey(56), 2 * cfg.n_layer + 1)
    hps = [{"phi": 0.02 * jax.random.normal(k, (m, n * C), jnp.bfloat16), "norm": jnp.ones((n * C,), jnp.bfloat16),
            "alpha": jnp.full((3,), 0.4, jnp.float32), "bias": 0.5 * jax.random.normal(k, (m,), jnp.float32)} for k in keys[1:]]
    x = jax.random.normal(keys[0], (1, T, C), jnp.bfloat16)
    least_ms = counted.least_seconds(config, T, 1, common.peaks(device["kind"])) * 1e3

    def prompt(x, hps):
        xs, u = G._to_streams(x, cfg), None
        for hp in hps:
            x, u, maps = G.hc_step(hp, xs, cfg)
            xs = (x, (u.astype(x.dtype), maps))
        return G.hc_step(None, xs, cfg)[0], u

    def timed(name):
        fn = jax.jit(lambda x, hps: prompt(x, hps))     # traced anew a form: the tile and the switch are read at trace time
        got = jax.block_until_ready(fn(x, hps))
        ms = kernel_ms(lambda: jax.block_until_ready(fn(x, hps)), REPS)
        own = sum(t for k, t in ms.items() if k.startswith("hc_mix"))
        print(f"{name}: {sum(ms.values()):.3f} ms a prompt of {T} ({sublayers} sublayers), {least_ms / sum(ms.values()):.3f} of "
              f"the counted roofline ({least_ms:.3f} ms); hc_mix {own:.3f} ms, beside it {sum(ms.values()) - own:.3f} ms; "
              f"the largest: {', '.join(f'{k} {t:.3f}' for k, t in sorted(ms.items(), key=lambda kv: -kv[1])[:4])}", flush=True)
        return got

    enabled, px._enabled = px._enabled, lambda: False       # every boundary falls back to the lines
    want = timed("jax.numpy (hc_open / hc_close)")
    px._enabled = enabled
    derived = px._HC_TILE
    for TQ in [derived, *tiles]:
        px._HC_TILE = TQ        # `_hc_tile` and `_hc_vmem` read it a call
        before = dict(px.stats)
        try:
            got = timed(f"hc_mix, a tile of {TQ}" + (" (pallasex._HC_TILE)" if TQ == derived else ""))
        except Exception as e:  # noqa: BLE001 -- a tile Mosaic refuses is a line, not the end
            print(f"hc_mix, a tile of {TQ}: {type(e).__name__}: {str(e)[:300]}", flush=True)
            continue
        finally:
            px._HC_TILE = derived
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        off = [float(jnp.max(jnp.abs(f32(g) - f32(w))) / jnp.max(jnp.abs(f32(w)))) for g, w in zip(got, want)]
        print(f"   {px.hc_schedule}, boundaries fused {px.stats.get('hc_fused', 0) - before.get('hc_fused', 0)}, fell back "
              f"{px.stats.get('hc_fallback', 0) - before.get('hc_fallback', 0)}; the last stream and read differ from the "
              f"jax.numpy lines' by {off[0]:.5f} and {off[1]:.5f} of the largest element", flush=True)
        if max(off) > 0.05:        # a bfloat16 stream rounded after each of the sublayers' closes: a few thousandths
            sys.exit("hc_split --kernel: the kernel disagrees with the jax.numpy lines")


if __name__ == "__main__":
    if "--kernel" in sys.argv:
        at = sys.argv.index("--kernel")
        kernel(sys.argv[1], int(sys.argv[at + 1]), [int(a) for a in sys.argv[at + 2:]])
    else:
        print("HC_SPLIT " + json.dumps(split(sys.argv[1])))
