"""``drivers/serve.py`` with one more comparison in ``correct``: what the
engine's caches hold of a request, against what the reference holds.

A served token's logit (``serve.check``) has passed every layer, and in a
model whose blocks norm each sublayer's output the rounding of bfloat16
reaches it at a gain of one a layer: a cache stored one precision lower than
the configuration states hides in that.  So this driver also serves the
check's requests again, stops them short of their end, reads what the engine
holds of each (``engine.held``: the recurrent state's slot and the K/V of its
blocks) and compares it, layer by layer, with what the reference holds after
the same tokens (``arch.ref_caches``), as a relative error over the three
requests.  Two numbers are held to limits that a lower precision of storage
fails: the error of the first linear_attention layer's state (its input is
the embedding, the same numbers on both sides) and of the shallowest
full_attention layer's keys and values (the least rounding has gathered
before it).  The rounding carried in from the layers before lands on every
element of a key alike, while a narrower storage rounds each element in
proportion to its size; so keys and values are compared on their large
elements (over ``kv_large_rms`` times the layer's root mean square: the ones
that decide a score), where the second shows beside the first.  A third
number, the largest such error of any full_attention layer, is held to a
limit that a wrong block or layer fails at once.  The states of the deeper
linear_attention layers are printed and held to nothing: a head that
remembers a thousand tokens, with ``beta`` near 2, neither forgets nor damps
the rounding carried in from the layers before it (the transition's
eigenvalue along the key is ``alpha (1 - beta)``, near -1), and its state
wanders from the reference's in a sound program too (0.02 at the second
layer, 0.4 at the twelfth, once 1.2: my chip runs, PR 32).

Everything else is ``drivers/serve.py``'s own: the engine's build, the
token comparison, the lead-in and the window.

    python3 chipbench/drivers/serve_held.py --workload <cell> --seeds 1,2,3 [--engine '{"kv_dtype": "fp8"}' | --state-arena bfloat16]

prints the comparison's numbers a seed, as ``calibrate.py`` does for the
accepted drivers (it tells a serving driver by the name ``serve``).  The two
storage controls: ``--engine '{"kv_dtype": "fp8"}'`` is an option of the
engine's; ``--state-arena bfloat16`` is planted here (the engine has no
option for the state's storage: it sets ``StatePool.STATE_DTYPE`` before the
engine is built, and the programs follow the arena they are handed).  With
``--witness-layers N`` the program runs in float32 at a depth of ``N`` (set
``JAX_DEFAULT_MATMUL_PRECISION=highest`` beside it): what the numbers read when
the program computes as the reference does.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import common, traffic  # noqa: E402

serve = common.load_module("drivers", "serve")
build, measure = serve.build, serve.measure


def held_check(ctx: dict, st: dict) -> dict:
    eng, hf, arch, seed = st["engine"], ctx["config"], ctx["arch"], ctx["seed"]
    spec = ctx["mix"]["check"]
    vocab, pad = hf["vocab_size"], spec["reference_pad"]
    clients = [serve.Client(traffic.Req(30_000_000 + i, p, n))
               for i, (p, n) in enumerate(spec["requests"])]
    for c in clients:
        serve.submit(eng, c, seed, vocab)
    # served together and stopped together, every request still running
    stop = min(n for _, n in spec["requests"]) - spec["held_tokens_before_end"]
    while max(len(c.tokens) for c in clients) < stop:
        eng.step()
    square = {name: [] for name in ("state", "k", "v")}   # a layer: [sum of squares of the difference, of the reference]
    tokens = []

    def add(name, layer, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        floor = 0.0 if name == "state" else spec["kv_large_rms"] * np.sqrt(np.mean(want ** 2))
        large = np.abs(want) > floor
        if layer == len(square[name]):
            square[name].append(np.zeros(2))
        square[name][layer] += [np.sum((got - want)[large] ** 2, dtype=np.float64),
                                np.sum(want[large] ** 2, dtype=np.float64)]

    for c in clients:
        # to the host at once: beside the arenas the chip has room for one layer of the reference
        held = jax.device_get(eng.held(c.handle))
        n, r = held["tokens"], c.req
        fed = np.concatenate([traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab),
                              np.asarray(c.tokens, np.int32)])[:n]
        padded = np.zeros(-(-n // pad) * pad, np.int32)
        padded[:n] = fed
        tokens.append(n)
        seen = {"state": 0, "kv": 0}
        with jax.default_device(ctx["devices"][0]):
            for kind, want in arch.ref_caches(hf, st["params"], jnp.asarray(padded), n):
                layer = seen[kind]
                seen[kind] += 1
                if kind == "state":
                    add("state", layer, held["state"][layer], want)
                else:
                    add("k", layer, held["k"][layer], want[0])
                    add("v", layer, held["v"][layer], want[1])
    serve.drain(eng, clients)
    rel = {name: [float(np.sqrt(d / w)) for d, w in layers] for name, layers in square.items()}
    out = {"state_rel_err": rel["state"][0], "kv_rel_err": max(rel["k"][0], rel["v"][0]),
           "kv_rel_err_max": max(rel["k"] + rel["v"])}
    out.update({k + "_limit": spec[k + "_limit"] for k in list(out)})
    ok = (all(out[k] <= out[k + "_limit"] for k in ("state_rel_err", "kv_rel_err", "kv_rel_err_max"))
          and all(c.handle.result(drive=False).finish_reason == "length" for c in clients))
    return {**out, "held_rel_err_by_layer": rel, "held_tokens": tokens,
            "state_arena": eng.stats()["state"]["dtype"], "held_ok": bool(ok)}


def check(ctx: dict, st: dict) -> dict:
    chk = serve.check(ctx, st)
    held = held_check(ctx, st)
    return {**chk, **held, "ok": bool(chk["ok"] and held["held_ok"])}


def run(ctx: dict) -> dict:
    st = build(ctx)
    out = measure(ctx, st, check(ctx, st))
    st["engine"].shutdown(drain=False)
    return out


if __name__ == "__main__":
    import argparse

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--state-arena", default="", help="the control: the state arena's storage, planted in the pool")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-layers", type=int, default=0)
    args = ap.parse_args()
    if args.state_arena:
        from thunder_tpu.serving.kv_pool import StatePool

        StatePool.STATE_DTYPE = jnp.dtype(args.state_arena)
    if args.witness_layers:
        import functools
        import types

        opened = calibrate.context

        def in_float32(a, seed):
            ctx = opened(a, seed)
            ctx["config"]["num_hidden_layers"] = args.witness_layers
            arch = ctx["arch"]
            ctx["arch"] = types.SimpleNamespace(**{**vars(arch), "make_params": functools.partial(
                arch.make_params, dtype=jnp.float32)})
            return ctx

        calibrate.context = in_float32
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
