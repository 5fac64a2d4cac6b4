"""``drivers/serve.py`` for a looped model (``models/looped_dense_decoder.py``: a
stack of blocks run several times over one set of weights, a K/V slab a layer a
pass), with one more comparison in ``correct``: what the engine holds of a running
request in **the slabs the mix names**, against the reference's.

A served token's logit (``serve.check``) has passed every layer of every pass, 192
blocks whose norms on both sides of a sublayer carry the rounding of bfloat16 on
at a gain near one; a cache stored one precision lower, or a slab written where
another pass reads, hides in that.  So this driver also serves the check's
requests again, stops them short of their end, reads what the engine holds of each
(``engine.held(handle, layers=)``: ``k`` and ``v`` of the slabs asked for) and compares
the slabs ``check.slabs`` names (``[pass, layer]``; the engine's index is ``arch.slab``, the reference's own
statement of where a cache a pass lies, not the program's) with ``arch.ref_caches``
of the same tokens, as a relative error over the requests on the elements over
``kv_large_rms`` times the slab's root mean square (``drivers/serve_held.py`` has the
argument).  Three numbers are held to limits: ``kv_rel_err`` (the first slab named:
pass 0 of layer 0, whose input is the embedding on both sides, so a narrower
storage fails it), ``pass_rel_err`` (the second: layer 0 of pass 1, whose input is
the first pass's closed state: the loop's wiring) and ``kv_rel_err_max`` (the largest
of all the slabs named: layer 1 of pass 1 among them, the first whose keys a norm
left out of the loop moves (layer 0's are projected from ``RMSNorm_1`` of the closed
state, which no scale moves), and the last layer of the last pass: a wrong slab fails
it at once).  A slab the engine does not have
reads ``inf``.

**The counts.**  ``engine.stats()["passes"]`` (decode steps, the layers they applied,
the served tokens by the pass the exit rule chose and their exit probabilities)
and ``["attn"]["attended_tokens"]`` (the keys the decode steps' rows attended, a
layer of a pass and once a slab-walk), read after the check and after the window and
put beside what ``measure`` kept of the engine's stats (``counters.stats0/1``).

**The controls**, planted here and not options of the engine's (each must read
``ok: false``):

    python3 chipbench/drivers/serve_looped.py --workload <cell> --seeds 1,2,3 [--slab-map layer | --passes 3 | --norm-once | --engine '{"kv_dtype": "fp8"}' | --engine '{"quantized": true}'] [--witness-layers 6]

``--slab-map layer``: one slab a layer for all passes (``s = l``: the cache a pass
collapsed; ``llama.Config.kv_slab`` replaced, so every pass's walk reads its layer's
pass-0 slab; the served tokens fail, what is held does not: the fresh K/V leave the
loop in the slabs' own order).  ``--passes 3``: the program runs one
pass fewer than the reference.  ``--norm-once``: the last norm once, after the last
pass (``generate.close_pass`` replaced), where the reference closes every pass.  The
two storage controls are the engine's own options.  ``--witness-layers N`` runs the
program in float32 at a depth of ``N`` (with ``JAX_DEFAULT_MATMUL_PRECISION=highest``):
it reads what the reference reads.
"""
from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import common, traffic  # noqa: E402

serve = common.load_module("drivers", "serve")
build, measure = serve.build, serve.measure
REPORTED = ("kv_rel_err", "pass_rel_err")       # the first two slabs named, in order


def held_check(ctx: dict, st: dict) -> dict:
    eng, hf, arch, seed = st["engine"], ctx["config"], ctx["arch"], ctx["seed"]
    spec = ctx["mix"]["check"]
    vocab, pad = hf["vocab_size"], spec["reference_pad"]
    slabs = [tuple(s) for s in spec["slabs"]]
    clients = [serve.Client(traffic.Req(30_000_000 + i, p, n))
               for i, (p, n) in enumerate(spec["requests"])]
    for c in clients:
        serve.submit(eng, c, seed, vocab)
    # served together and stopped together, every request still running
    stop = min(n for _, n in spec["requests"]) - spec["held_tokens_before_end"]
    while max(len(c.tokens) for c in clients) < stop:
        eng.step()
    square = {(s, name): np.zeros(2) for s in slabs for name in "kv"}    # [sum of squares of the difference, of the reference]
    # the named slabs alone, those the engine has: all 192 of a request do not fit beside the arena
    have = [s for s in slabs if arch.slab(hf, *s) < eng.pool.kind_snapshot()["slabs"]]
    missing, tokens = set(slabs) - set(have), []
    for c in clients:
        held = jax.device_get(eng.held(c.handle, layers=[arch.slab(hf, *s) for s in have]))
        n, r = held["tokens"], c.req
        fed = np.concatenate([traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab),
                              np.asarray(c.tokens, np.int32)])[:n]
        padded = np.zeros(-(-n // pad) * pad, np.int32)
        padded[:n] = fed
        tokens.append(n)
        got = {s: (held["k"][i], held["v"][i]) for i, s in enumerate(have)}
        with jax.default_device(ctx["devices"][0]):
            for s, want in arch.ref_caches(hf, st["params"], jnp.asarray(padded), n, slabs):
                for name, g, w in zip("kv", got.get(s, ()), want):
                    g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
                    large = np.abs(w) > spec["kv_large_rms"] * np.sqrt(np.mean(w ** 2))
                    square[s, name] += [np.sum((g - w)[large] ** 2, dtype=np.float64),
                                        np.sum(w[large] ** 2, dtype=np.float64)]
    serve.drain(eng, clients)
    rel = {f"{t}.{l}": (float("inf") if (t, l) in missing else
                        max(float(np.sqrt(square[(t, l), name][0] / square[(t, l), name][1])) for name in "kv"))
           for t, l in slabs}
    out = {name: rel[f"{t}.{l}"] for name, (t, l) in zip(REPORTED, slabs)}
    out["kv_rel_err_max"] = max(rel.values())
    out.update({k + "_limit": spec[k + "_limit"] for k in list(out)})
    ok = (all(out[k] <= out[k + "_limit"] for k in (*REPORTED, "kv_rel_err_max"))
          and all(c.handle.result(drive=False).finish_reason == "length" for c in clients))
    stats = eng.stats()
    return {**out, "held_rel_err_by_slab": rel, "held_tokens": tokens, "decode_path": stats["attn"]["path"],
            "pool": eng.pool.kind_snapshot(), "held_ok": bool(ok)}


def check(ctx: dict, st: dict) -> dict:
    chk = serve.check(ctx, st)
    held = held_check(ctx, st)
    return {**chk, **held, "ok": bool(chk["ok"] and held["held_ok"])}


def counts(stats: dict) -> dict:
    """The loop's counters: the passes, and the keys attended a layer and a slab-walk."""
    more = {"passes": stats["passes"]} if "passes" in stats else {}
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


def plant_slab_per_layer() -> None:
    """The control: every pass reads and writes its layer's one slab (``s = l``)."""
    from thunder_tpu.models import llama

    llama.Config.kv_slab = lambda self, t, l: l + 0 * t


def plant_norm_once() -> None:
    """The control: the last norm once, after the last pass; the passes before it hand their state on as it is."""
    from thunder_tpu.models import generate

    close = generate.close_pass
    generate.close_pass = lambda params, u, cfg, t: jnp.where(t == cfg.n_pass - 1, close(params, u, cfg, t), u)


def fewer_passes(arch, passes: int):
    """The control: ``arch`` whose program runs ``passes`` passes where the reference runs the published count."""
    def program_config(hf):
        return {**arch.program_config(hf), "n_pass": passes}

    return types.SimpleNamespace(**{**vars(arch), "program_config": program_config})


if __name__ == "__main__":
    import argparse
    import functools

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the storage controls)")
    ap.add_argument("--slab-map", default="", choices=("", "layer"), help="the control: one slab a layer for all passes")
    ap.add_argument("--passes", type=int, default=0, help="the control: the passes the program runs")
    ap.add_argument("--norm-once", action="store_true", help="the control: the last norm after the last pass alone")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-layers", type=int, default=0)
    args = ap.parse_args()
    if args.slab_map:           # before the process builds its first engine (built programs are cached)
        plant_slab_per_layer()
    if args.norm_once:
        plant_norm_once()
    opened = calibrate.context

    def context(a, seed):
        ctx = opened(a, seed)
        arch = ctx["arch"]
        if args.witness_layers:     # with JAX_DEFAULT_MATMUL_PRECISION=highest: it reads what the reference reads
            ctx["config"]["num_hidden_layers"] = args.witness_layers
            last = ctx["mix"]["check"]["slabs"][-1]
            ctx["mix"]["check"]["slabs"][-1] = [last[0], min(last[1], args.witness_layers - 1)]
            arch = types.SimpleNamespace(**{**vars(arch), "make_params": functools.partial(
                arch.make_params, dtype=jnp.float32)})
        ctx["arch"] = fewer_passes(arch, args.passes) if args.passes else arch
        return ctx

    calibrate.context = context
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
