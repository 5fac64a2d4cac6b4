"""``drivers/serve_window.py``'s command for calibration, for a model whose router
reads the block's input (``models/prerouted_moe_decoder.py``), with one planted
control more.  The cell's configuration names ``serve_window`` as its ``driver``,
as it stands; this file is run by hand.

**The controls.**  ``--router-input mlp`` serves the model with
``llama.Config.moe_route_block_input`` off: every expert layer's router reads what
its experts read, ``RMSNorm_2`` of the stream after attention, as every other
expert layer of the benchmark does, where the reference routes on the block's
input.  Nothing the engine holds of a request's first layer moves (layer 0's K/V
is projected from the embedding before any router ran); the choice of experts
does, from layer 0 on, and the served tokens must fail ``mean_logit_shortfall``.
``--router-dtype bfloat16`` is the router one precision lower: the program's stream
and router weights are bfloat16 already and the matrix unit sums in float32, so what
a bfloat16 router loses is its product's rounding, and the plant rounds the logits
``route_softmax`` reads to that dtype's bits.  Layer 0's router then parts from the
reference's (both read the embedding's own bits otherwise), so layer 1's ring shows
it.  The other controls are ``serve_window.py``'s own, under the same names.

    python3 chipbench/drivers/serve_prerouted.py --workload <cell> --seeds 1,2,3 [--router-input mlp | --router-dtype bfloat16 | --engine '{"quantized": true}' | --kv-store float8_e4m3fn | --swap-requests] [--witness-layers 4 [--check-requests '[[1000, 160], [2400, 160]]'] [--gmm-vmem-mib 16]]

prints the comparison's numbers a seed.  ``--witness-layers N`` runs the program in
float32 at a depth of ``N`` (with ``JAX_DEFAULT_MATMUL_PRECISION=highest``): it
reads what the reference reads.  ``--check-requests`` serves other requests than the
mix's three (the witness's: float32 weights and programs at ``highest`` fit the chip
and its compiler only at fewer buckets; PERF.md section 2 has the size that ran).
``--gmm-vmem-mib`` is the VMEM ``moe_grouped_mm`` may ask for, as ``tools/moe_tune.py
--vmem-mib`` sets it: at the chip's own 96 MiB the layout takes float32 weight blocks
that the compiler then refuses or never finishes (PERF.md section 7).
"""
from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

window = common.load_module("drivers", "serve_window")
build, check = window.build, window.check                 # what ``calibrate.check_serve`` calls
plant_kv_store, swap_requests = window.plant_kv_store, window.swap_requests


def route_on_mlp_input(arch):
    """The control: ``arch`` whose program routes on what the experts read."""
    def program_config(hf):
        return {**arch.program_config(hf), "moe_route_block_input": False}

    return types.SimpleNamespace(**{**vars(arch), "program_config": program_config})


def plant_router_dtype(dtype_name: str) -> None:
    """The control: the logits the softmax router reads, rounded to ``dtype_name`` (its product kept no wider)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.models import generate

    store, route = jnp.finfo(jnp.dtype(dtype_name)), generate.route_softmax
    generate.route_softmax = lambda logits, cfg: route(jax.lax.reduce_precision(logits, store.nexp, store.nmant), cfg)


if __name__ == "__main__":
    import argparse
    import functools
    import json

    import jax.numpy as jnp

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--router-input", default="", choices=("", "mlp"),
                    help="the control: the router reads what its experts read")
    ap.add_argument("--router-dtype", default="", help="the control: the dtype the router's logits are rounded to")
    ap.add_argument("--kv-store", default="", help="the control: the dtype every kept key and value is rounded to")
    ap.add_argument("--swap-requests", action="store_true", help="the control: another request's ring and blocks")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-layers", type=int, default=0)
    ap.add_argument("--check-requests", default="", help="JSON [[prompt, new], ...] in the mix's three's place")
    ap.add_argument("--gmm-vmem-mib", type=int, default=0, help="the VMEM moe_grouped_mm may ask for (the witness's)")
    args = ap.parse_args()
    if args.kv_store:           # before the process builds its first engine (built programs are cached)
        plant_kv_store(args.kv_store)
    if args.router_dtype:
        plant_router_dtype(args.router_dtype)
    if args.gmm_vmem_mib:
        from thunder_tpu.executors import pallasex

        pallasex._gmm_vmem_cap = lambda: args.gmm_vmem_mib << 20
    opened = calibrate.context

    def context(a, seed):
        ctx = opened(a, seed)
        arch = ctx["arch"]
        if args.witness_layers:     # with JAX_DEFAULT_MATMUL_PRECISION=highest: it reads what the reference reads
            ctx["config"]["num_hidden_layers"] = args.witness_layers
            arch = types.SimpleNamespace(**{**vars(arch), "make_params": functools.partial(
                arch.make_params, dtype=jnp.float32)})
        if args.router_input:
            arch = route_on_mlp_input(arch)
        if args.check_requests:
            ctx["mix"]["check"]["requests"] = json.loads(args.check_requests)
        ctx["arch"] = swap_requests(arch) if args.swap_requests else arch
        return ctx

    calibrate.context = context
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
