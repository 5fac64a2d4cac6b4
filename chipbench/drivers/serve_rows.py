"""``drivers/serve_kinds.py`` for a model of Mamba-2 layers and a served expert
share: the same build, check (``serve.check`` plus what the engine holds of a
request against ``arch.ref_caches``, by the kinds the architecture names), lead-in
and window, with the one row ``KINDS`` lacks and one thing more on the result.

**The row.**  A Mamba-2 layer keeps a matrix a head, ``(N, d)`` in the engine and
``(d, N)`` in the equations, and its conv's tail.  What is held to
``state_rel_err_limit`` is the shallowest layer's state on the eighth of its
heads that forget slowest (``arch.longest_memory_rows``: the smallest
``softplus(dt_bias) exp(A_log)``, 16 heads of 128), not on every element.  The
ground, measured (PERF.md section 2, PR 45): over all heads the program reads
0.0046-0.0060, the bfloat16 roundings of a token's ``x``, ``B`` and ``dt`` on
their way into the scan, the same share of a state however long it remembers;
a bfloat16 state arena rounds the state once a token, which stays for as long
as the head remembers and is lost within a few tokens where it forgets fast, so
over all heads it read 0.0062-0.0067, beside the program's own, and no limit
parts the two.  On the heads whose memory outlasts the check's 152 decode steps
the arena's rounding is summed over all of them.  ``held_rel_err_by_layer``
keeps every layer's reading on its own slowest heads.

**The counts.**  The engine's sums of how a decode step's rows fell on the held
experts (``engine.stats()["moe"]``: ``row_sums``), before and after the window,
which ``serve._slim`` would drop.  The readers
``layer_metrics/expert_rows_per_step.nemoserve.py`` and
``experts_hit_share.nemoserve.py`` take the window's own from the two.

    python3 chipbench/drivers/serve_rows.py --workload <cell> --seeds 1,2,3 [--engine '{"kv_dtype": "fp8"}' | --engine '{"quantized": true}' | --state-arena bfloat16] [--witness-pattern 'M*E']

prints the comparison's numbers a seed, as ``serve_kinds.py``'s own command
does; the controls are its (the engine takes an fp8 arena beside a Mamba-2
state, so the K/V control needs no plant).
"""
from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import common  # noqa: E402

kinds = common.load_module("drivers", "serve_kinds")
serve = kinds.serve
build, measure = kinds.build, kinds.measure

kinds.KINDS["mamba2"] = (("state", "conv"), "state", False)


def _on_the_longest_memories(arch):
    """``arch`` whose ``ref_caches`` keeps, of each Mamba-2 layer's state, the rows
    of the heads that forget slowest, and the turn of what the engine holds to
    the same rows (``held_check`` turns a layer's array right after the reference
    yields that layer)."""
    rows: list = [None]

    def ref_caches(hf, params, tokens, n_real):
        mixers = iter(bp["mamba2"] for bp in params["blocks"] if "mamba2" in bp)
        for kind, want in arch.ref_caches(hf, params, tokens, n_real):
            if kind == "mamba2":
                rows[0] = arch.longest_memory_rows(hf, next(mixers))
                want = (np.asarray(want[0])[rows[0]], *want[1:])
            yield kind, want

    kinds.TO_REFERENCE["mamba2", "state"] = lambda a: np.swapaxes(a, -1, -2)[rows[0]]      # (N, d) on the chip
    return types.SimpleNamespace(**{**vars(arch), "ref_caches": ref_caches})


def check(ctx: dict, st: dict) -> dict:
    return kinds.check({**ctx, "arch": _on_the_longest_memories(ctx["arch"])}, st)


def _slim_with_rows(stats: dict, slim=serve._slim) -> dict:
    """``serve._slim`` and, where the engine counts them, the expert share's sums."""
    return {**slim(stats), **({"moe": stats["moe"]} if "moe" in stats else {})}


def run(ctx: dict) -> dict:
    st = build(ctx)
    chk = check(ctx, st)
    serve._slim = _slim_with_rows
    try:
        out = measure(ctx, st, chk)
    finally:
        serve._slim = _slim_with_rows.__defaults__[0]
    st["engine"].shutdown(drain=False)
    return out


if __name__ == "__main__":
    import argparse
    import functools

    import jax.numpy as jnp

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--state-arena", default="", help="the control: the state arena's storage, planted in the pool")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-pattern", default="", help="the program in float32 on these layers, say 'M*E'")
    args = ap.parse_args()
    if args.state_arena:        # before the process builds its first engine (built programs are cached)
        from thunder_tpu.serving.kv_pool import StatePool

        StatePool.STATE_DTYPE = jnp.dtype(args.state_arena)
    if args.witness_pattern:    # with JAX_DEFAULT_MATMUL_PRECISION=highest: it reads what the reference reads
        opened = calibrate.context

        def in_float32(a, seed):
            ctx = opened(a, seed)
            ctx["config"].update(num_hidden_layers=len(args.witness_pattern), hybrid_override_pattern=args.witness_pattern)
            ctx["arch"] = types.SimpleNamespace(**{**vars(ctx["arch"]), "make_params": functools.partial(
                ctx["arch"].make_params, dtype=jnp.float32)})
            return ctx

        calibrate.context = in_float32
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
