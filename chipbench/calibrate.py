#!/usr/bin/env python3
"""Readings a limit is set from, many seeds in one process with one set-up.
Not part of a benchmark run; the driver never calls it.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--control]

Prints, for each seed, the numbers the cell's comparison with the reference
gives: for the program as configured, and with ``--control`` for the control
(serving: ``--engine '{"kv_dtype": "fp8"}'``, the engine one precision lower;
training: the reference computed in float8, forward and backward, put in the
program's place).  Each line of output is one JSON object."""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import common, traffic  # noqa: E402


def context(args, seed):
    cell, config, mix = common.open_cell(args.workload, args.rehearse)
    devices = common.claim_devices(cell, args.rehearse)
    from thunder_tpu.core import compile_cache

    compile_cache.enable()
    return {"cell": cell, "config": config, "mix": mix, "seed": seed,
            "devices": devices, "t_process": time.perf_counter(), "trace_dir": None,
            "trace_s": 0.0, "arch": common.load_module("models", config["arch"])}


def emit(**row):
    print(json.dumps(row, default=str), flush=True)


def check_serve(args, driver, seeds):
    ctx = context(args, seeds[0])
    over = dict(json.loads(args.engine)) if args.engine else {}
    st = driver.build(ctx, **over)
    for seed in seeds:
        ctx["seed"] = seed
        if seed != seeds[0]:
            # weights are arguments of every engine program: new ones, same
            # programs; the old ones go first, two sets do not fit beside the arena
            st["params"] = st["engine"].params = None
            gc.collect()
            st["params"] = st["engine"].params = common.init_on(
                functools.partial(ctx["arch"].make_params, ctx["config"]), common.seed_words(seed))
        emit(workload=args.workload, seed=seed, engine=over, **driver.check(ctx, st))
    st["engine"].shutdown(drain=False)


def check_train(args, driver, seeds):
    from thunder_tpu import distributed as dist
    import jax.numpy as jnp

    ctx = context(args, seeds[0])
    st = driver.build(ctx)
    opts, hf = ctx["config"]["train"], ctx["config"]
    rule = getattr(dist, opts["shardings"])
    for seed in seeds:
        ctx["seed"] = seed
        if seed != seeds[0]:
            st["params"] = st["opt_state"] = None
            gc.collect()
            st["params"] = common.init_on(functools.partial(ctx["arch"].make_params, hf),
                                          common.seed_words(seed), lambda s: rule(s, st["mesh"]))
            st["opt_state"] = st["step"].init_optimizer_state(st["params"])
            idx, tgt = traffic.train_batch(ctx["mix"], seed, len(ctx["devices"]), hf["vocab_size"])
            st["batch"] = (jnp.asarray(idx), jnp.asarray(tgt)) + st["batch"][2:]
        if args.control:
            emit(workload=args.workload, seed=seed, control="float8 reference, forward and backward",
                 **driver.check(ctx, st, control=True))
        emit(workload=args.workload, seed=seed, **driver.check(ctx, st))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (serving control)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    config = common.config_of(common.cell(args.workload))
    driver = common.load_module("drivers", config["driver"])
    seeds = [int(s) for s in args.seeds.split(",")]
    (check_serve if config["driver"] == "serve" else check_train)(args, driver, seeds)


if __name__ == "__main__":
    main()
