#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips.  It fails without a TPU (no CPU
fallback), makes weights and inputs on the device from the seed, warms only
the cell's own shapes, checks the outputs against the plain reference
outside the window, measures for ``--seconds``, and prints one JSON object
as the last line of its standard output.  ``--trace 0`` gives the cell's
end-to-end metrics; ``--trace 1`` also records a profiler trace of the
window's last seconds and gives the per-layer metrics and a breakdown.

Which driver, architecture, configuration, mix and per-layer readers a cell
uses is data: the cell names a configuration and a mix, the configuration
names its driver and architecture, and each per-layer metric of the
manifest has a reader under ``layer_metrics/``, found by the metric's name.

``--rehearse`` (never passed by the driver) lets the command run where
there is no TPU, to find wrong paths and control flow at a tiny size; what
it prints names the platform it ran on and is not a measurement.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

SHARE_LIMIT = 1.05


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    manifest = common.manifest()
    cell, config, mix = common.open_cell(args.workload, args.rehearse)

    import thunder_tpu  # noqa: F401 — absent in a bare directory: fail before anything prints

    devices = common.claim_devices(cell, args.rehearse)
    from thunder_tpu.core import compile_cache

    cache_dir = compile_cache.enable()   # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, "chipbench", ".trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"cell": cell, "config": config, "mix": mix, "seed": args.seed,
           "seconds": args.seconds, "devices": devices, "t_process": T_PROCESS,
           "trace_dir": trace_dir,
           "trace_s": min(float(config.get("trace_seconds", 4.0)), args.seconds),
           "arch": common.load_module("models", config["arch"])}
    driver = common.load_module("drivers", config["driver"])
    out = driver.run(ctx)

    kind = devices[0].device_kind
    memory = out["memory"]
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory["memory_peak_bytes"]}
    reported = dict(out["end_to_end"], setup_s=out["setup_s"])

    def listed(metric: dict) -> bool:
        return cell["name"] in metric.get("workloads", [cell["name"]])

    metrics: dict = {}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if not args.trace:
        for m in manifest["end_to_end"]:
            if listed(m) and m["name"] in reported:
                metrics[m["name"]] = {"value": reported[m["name"]], "unit": m["unit"]}
    else:
        from chipbench import trace as trace_mod

        xplane = trace_mod.find_xplane(trace_dir)
        tr = trace_mod.load(xplane, "/device:TPU:" if devices[0].platform == "tpu" else "/host:CPU")
        device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s()
        result["breakdown"] = tr.breakdown()
        rctx = {**ctx, **out, "trace": tr, "end_to_end": reported,
                "peaks": common.peaks(kind, missing_ok=args.rehearse)}
        over_peak = {}
        for m in manifest["per_layer"]:
            if not listed(m):
                continue
            reader = common.load_reader(m["name"])
            value = reader.read(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
                # a share of a roofline or of a peak is a fraction of 1: one
                # that reads over 1.05 counts the work too high or the time too
                # short, and the run is not correct
                if getattr(reader, "SHARE_OF_PEAK", False) and value > SHARE_LIMIT:
                    over_peak[m["name"]] = value
        out["check"]["shares_over_peak"] = over_peak
        out["check"]["share_of_peak_limit"] = SHARE_LIMIT
        result["correct"] = bool(result["correct"] and not over_peak)
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            keep = os.path.join(ROOT, "chiprun_out", "traces", cell["name"])
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplane, keep)
            with open(os.path.join(keep, "summary.json"), "w") as f:
                json.dump(trace_mod.summary(xplane), f, indent=1, default=str)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # every number compared, beside its limit; then what else was counted
    print(json.dumps({"check": out["check"], "memory": memory, "cache_dir": cache_dir,
                      "counters": out["counters"],
                      "host": {k: v for k, v in out["host"].items() if not isinstance(v, list)},
                      "all_end_to_end": reported, "rehearsal": args.rehearse},
                     default=str), flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
