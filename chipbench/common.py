"""What every part of the benchmark shares: where the files are, the
manifest, the table of peaks, quantiles and the device's own description.

Nothing here imports the program under test, and nothing here touches JAX
at import time (a parent that touches JAX holds the chip)."""
from __future__ import annotations

import importlib.util
import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    cells = {w["name"]: w for w in manifest()["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    return cells[name]


def config_of(cell_: dict) -> dict:
    """The configuration file the manifest names for this cell."""
    entry = {c["name"]: c for c in manifest()["configs"]}[cell_["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def mix_of(cell_: dict) -> dict:
    return load_json("traffic", cell_["traffic"] + ".json")


def open_cell(workload: str, rehearse: bool = False) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its mix; at rehearsal, with the tiny
    sizes each file keeps under ``rehearse`` laid over them."""
    cell_ = cell(workload)
    config, mix = config_of(cell_), mix_of(cell_)
    if rehearse:
        for part in (config, mix):
            part.update(part.get("rehearse", {}))
    return cell_, config, mix


def claim_devices(cell_: dict, rehearse: bool = False) -> list:
    """The cell's chips, or no run at all: there is no CPU fallback."""
    import jax

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu" or len(devices) < cell_["chips"]):
        raise SystemExit(
            f"chipbench: {cell_['name']} needs {cell_['chips']} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind}); nothing was run")
    return devices[:cell_["chips"]]


def peaks(device_kind: str, missing_ok: bool = False) -> dict | None:
    """Published peaks of one chip; a device nobody wrote down is an error
    (at rehearsal, on a CPU, it is None and the readers that need it pass)."""
    table = load_json("peaks.json")["peaks"]
    if device_kind not in table and missing_ok:
        return None
    if device_kind not in table:
        raise ValueError(f"chipbench/peaks.json has no row for device_kind "
                         f"{device_kind!r} (known: {sorted(table)}); add one with its source")
    return table[device_kind]


def load_module(directory: str, name: str):
    """Imports ``chipbench/<directory>/<name>.py``; names may hold dots."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{directory}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``, or,
    where a quantity is split by the end-to-end metric it moves
    (``<quantity>.<split>``) and has no file of that name, the quantity's own
    ``layer_metrics/<quantity>.py``."""
    if not os.path.exists(os.path.join(HERE, "layer_metrics", metric + ".py")):
        metric = metric.rpartition(".")[0] or metric
    return load_module("layer_metrics", metric)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of all the values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(values) -> float:
    """The contract's spread: the distance between the first and the third
    quartile (``statistics.quantiles(values, n=4)``) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def whole_step_rate(t_first_dispatch: float, done_times: list, units_per_step: float) -> float:
    """Units a second over whole steps: every step that finished, over the time
    from the first step's dispatch to the last step's end.  A count of steps
    in a fixed window would quantise by a whole step; this does not."""
    return len(done_times) * units_per_step / (done_times[-1] - t_first_dispatch)


def seed_words(seed: int):
    """Any whole number (seeds run past 2**31) as two 32-bit words, to hand to
    a jitted program as an argument: a seed closed over is a constant of the
    program, and every new seed would then compile anew."""
    import numpy as np

    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def seed_key(words):
    """A PRNG key from :func:`seed_words`; works on traced words."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def init_on(fn, words, shardings_fn=None):
    """``fn(words)`` under one ``jit``, its leaves born where
    ``shardings_fn(shapes)`` puts them (``dist.init_sharded`` with an
    argument: that one takes a closure, which bakes the seed in)."""
    import jax

    if shardings_fn is None:
        return jax.jit(fn)(words)
    return jax.jit(fn, out_shardings=shardings_fn(jax.eval_shape(fn, words)))(words)


def compiled_memory(devices) -> dict:
    """Peak bytes on the fullest chip: what lives there now plus the largest
    compiled program's temporaries, from each loaded executable's own memory
    analysis (``memory_stats()['peak_bytes_in_use']``
    leaves the temporaries out on this runtime; PERF.md section 7)."""
    live = peak = limit = 0
    for d in devices:
        ms = d.memory_stats() or {}
        live = max(live, ms.get("bytes_in_use", 0))
        peak = max(peak, ms.get("peak_bytes_in_use", 0))
        limit = max(limit, ms.get("bytes_limit", 0))
    temps: dict[str, int] = {}
    for ex in devices[0].client.live_executables():
        name = ex.hlo_modules()[0].name
        temps[name] = max(temps.get(name, 0), ex.get_compiled_memory_stats().temp_size_in_bytes)
    top = sorted(temps.items(), key=lambda kv: -kv[1])[:4]
    extra = top[0][1] if top else 0
    return {"live_bytes": live, "allocator_peak_bytes": peak, "bytes_limit": limit,
            "largest_temporaries": top, "memory_peak_bytes": max(peak, live + extra)}
