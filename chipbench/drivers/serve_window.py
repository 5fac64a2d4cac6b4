"""``drivers/serve_kinds.py`` for an ordinary decoder whose attention layers are of
two kinds, a window kind that keeps a ring a request and a global kind that keeps
its whole length, beside a served expert share: the same build, check
(``serve.check`` plus what the engine holds of a request against
``arch.ref_caches``, by the kinds the architecture names: ``KINDS`` has both rows
as it stands), lead-in and window, with two things more.

**The counts.**  The engine's sums of how a decode step's rows fell on the held
experts (``engine.stats()["moe"]``: ``row_sums``) and of the keys a step's rows
attended in a layer of each kind (``engine.stats()["attn"]["attended_tokens"]``),
read here after the check and after the window and put beside what ``measure``
kept of the engine's stats (``counters``: ``stats0``, ``stats1``, which hold neither).
They span the lead-in's decode steps too (9 beside the window's 1,118 in a run on
the chip): the window opens inside ``measure``.  The readers
``layer_metrics/experts_hit_share.nemoserve.py`` and
``moe_grouped_mm_roofline_share.winserve.py`` take their sums from the two.

**The control.**  ``serve_kinds.py``'s ``--kv-store`` wraps
``generate.diff_attention``, which a plain attention layer never calls.  Here
``--kv-store float8_e4m3fn`` wraps ``generate._project_qkv`` (the one projection
of the dense cache's prefill and the paged decode step alike), so that every key
and value a layer projects is rounded to that dtype's exponent and mantissa bits
(``lax.reduce_precision``) before it is attended or kept: what an fp8 K/V arena
and ring would hold (the engine refuses ``kv_dtype`` for a model with rings).
``--swap-requests`` is the other control: what the engine holds of each request is
compared with the reference of the *next* one (another request's ring or blocks).

    python3 chipbench/drivers/serve_window.py --workload <cell> --seeds 1,2,3 [--engine '{"quantized": true}' | --kv-store float8_e4m3fn | --swap-requests] [--witness-layers 4]

prints the comparison's numbers a seed, as ``serve_kinds.py``'s own command does.
``--witness-layers N`` runs the program in float32 at a depth of ``N`` (with
``JAX_DEFAULT_MATMUL_PRECISION=highest``): it reads what the reference reads.
"""
from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

kinds = common.load_module("drivers", "serve_kinds")
serve = kinds.serve
build, measure, check = kinds.build, kinds.measure, kinds.check


def counts(stats: dict) -> dict:
    """Where the engine counts them, the expert share's sums and the keys attended by kind."""
    more = {"moe": stats["moe"]} if "moe" in stats else {}
    if "attended_tokens" in stats.get("attn", {}):
        more["attended_tokens"] = stats["attn"]["attended_tokens"]
    return more


def run(ctx: dict) -> dict:
    st = build(ctx)
    chk = check(ctx, st)
    before = counts(st["engine"].stats())
    out = measure(ctx, st, chk)
    out["counters"]["stats0"].update(before)
    out["counters"]["stats1"].update(counts(st["engine"].stats()))
    st["engine"].shutdown(drain=False)
    return out


def plant_kv_store(dtype_name: str) -> None:
    """The control: every key and value an attention layer projects, rounded to ``dtype_name`` and back."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.models import generate
    from thunder_tpu.serving import paged_attention

    store, project = jnp.finfo(jnp.dtype(dtype_name)), generate._project_qkv

    def narrow(*args, **kw):
        q, k, v = project(*args, **kw)
        # ``reduce_precision``, not a cast there and back: XLA may drop such a pair (chip call 2, PR 41)
        return q, *(jax.lax.reduce_precision(a, store.nexp, store.nmant) for a in (k, v))

    generate._project_qkv = paged_attention._project_qkv = narrow


def swap_requests(arch):
    """The control: ``arch`` whose ``ref_caches`` answers for the request served just
    before (the first for the last), cut or padded with zeros to the length asked for."""
    import numpy as np

    last: list = []

    def ref_caches(hf, params, tokens, n_real):
        now = arch.ref_caches(hf, params, tokens, n_real)
        before = last[0] if last else now
        last[:] = [now]

        def fit(a, like):
            out = np.zeros(like.shape, np.float32)
            n = min(a.shape[1], like.shape[1])
            out[:, :n] = np.asarray(a)[:, :n]
            return out

        return [(kind, tuple(fit(b, a) for a, b in zip(want, was))) for (kind, want), (_, was) in zip(now, before)]

    return types.SimpleNamespace(**{**vars(arch), "ref_caches": ref_caches})


if __name__ == "__main__":
    import argparse
    import functools

    import jax.numpy as jnp

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--kv-store", default="", help="the control: the dtype every kept key and value is rounded to")
    ap.add_argument("--swap-requests", action="store_true", help="the control: another request's ring and blocks")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-layers", type=int, default=0)
    args = ap.parse_args()
    if args.kv_store:           # before the process builds its first engine (built programs are cached)
        plant_kv_store(args.kv_store)
    opened = calibrate.context

    def context(a, seed):
        ctx = opened(a, seed)
        arch = ctx["arch"]
        if args.witness_layers:     # with JAX_DEFAULT_MATMUL_PRECISION=highest: it reads what the reference reads
            ctx["config"]["num_hidden_layers"] = args.witness_layers
            arch = types.SimpleNamespace(**{**vars(arch), "make_params": functools.partial(
                arch.make_params, dtype=jnp.float32)})
        ctx["arch"] = swap_requests(arch) if args.swap_requests else arch
        return ctx

    calibrate.context = context
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
