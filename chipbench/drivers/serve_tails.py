"""``drivers/serve.py`` with one more comparison in ``correct``, for a model
that keeps a conv tail a request beside its paged K/V: what the engine's
caches hold of a request, against what the reference holds.

A served token's logit (``serve.check``) has passed every layer and the head,
and only a quarter of this model's layers read the K/V arena at all: a cache
stored one precision lower than the configuration states moves the tokens by
about what the seeds do.  So this driver also serves the check's requests
again, stops them short of their end, reads what the engine holds of each
(``engine.held``: the K/V of its blocks, the lane-packed rows taken apart, and
its slot's conv tails) and compares it, layer by layer, with what the
reference holds after the same tokens (``arch.ref_caches``), as a relative
error over the requests.  Three numbers are held to limits: the first conv
layer's tail (its input is the embedding, the same numbers on both sides, so
its error is the program's own rounding: a narrower tail fails it); the
shallowest full_attention layer's keys and values on their large elements
(over ``kv_large_rms`` times the layer's root mean square: a narrower storage
rounds each element in proportion to its size, the rounding carried in from
the layers before lands on all alike; ``drivers/serve_held.py`` has the
argument); and the largest such error of any full_attention layer, which
another request's blocks, another layer's rows or a head's lanes swapped with
its neighbour's fail at once.  The deeper tails are printed and held to
nothing.

Everything else is ``drivers/serve.py``'s own: the engine's build, the token
comparison, the lead-in and the window.

    python3 chipbench/drivers/serve_tails.py --workload <cell> --seeds 1,2,3 [--engine '{"kv_dtype": "fp8"}' | --tail-store float8_e4m3fn | --engine '{"quantized": true}']

prints the comparison's numbers a seed, as ``calibrate.py`` does for the
accepted drivers (it tells a serving driver by the name ``serve``).  The
tails' storage control is planted here, not an option of the engine's:
``--tail-store float8_e4m3fn`` wraps ``models.generate.shortconv_mixer`` so
that every new tail is rounded to that dtype (and back) before it is kept,
which is what an fp8 tail arena would hold; it must be planted before the
process builds its first engine (built programs are cached).
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
    square = {name: [] for name in ("conv", "k", "v")}    # a layer: [sum of squares of the difference, of the reference]
    tokens = []

    def add(name, layer, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        floor = 0.0 if name == "conv" else spec["kv_large_rms"] * np.sqrt(np.mean(want ** 2))
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
        seen = {"conv": 0, "kv": 0}
        with jax.default_device(ctx["devices"][0]):
            for kind, want in arch.ref_caches(hf, st["params"], jnp.asarray(padded), n):
                layer = seen[kind]
                seen[kind] += 1
                if kind == "conv":
                    add("conv", layer, held["conv"][layer], want)
                else:
                    add("k", layer, held["k"][layer], want[0])
                    add("v", layer, held["v"][layer], want[1])
    serve.drain(eng, clients)
    rel = {name: [float(np.sqrt(d / w)) for d, w in layers] for name, layers in square.items()}
    out = {"tail_rel_err": rel["conv"][0], "kv_rel_err": max(rel["k"][0], rel["v"][0]),
           "kv_rel_err_max": max(rel["k"] + rel["v"])}
    out.update({k + "_limit": spec[k + "_limit"] for k in list(out)})
    ok = (all(out[k] <= out[k + "_limit"] for k in ("tail_rel_err", "kv_rel_err", "kv_rel_err_max"))
          and all(c.handle.result(drive=False).finish_reason == "length" for c in clients))
    attn = eng.stats()["attn"]
    return {**out, "held_rel_err_by_layer": rel, "held_tokens": tokens,
            "decode_path": attn["path"], "lane_pack": attn["lane_pack"], "held_ok": bool(ok)}


def check(ctx: dict, st: dict) -> dict:
    chk = serve.check(ctx, st)
    held = held_check(ctx, st)
    return {**chk, **held, "ok": bool(chk["ok"] and held["held_ok"])}


def run(ctx: dict) -> dict:
    st = build(ctx)
    out = measure(ctx, st, check(ctx, st))
    st["engine"].shutdown(drain=False)
    return out


def plant_tail_store(dtype_name: str) -> None:
    """The control: every tail a conv layer keeps, rounded to ``dtype_name`` and back."""
    from thunder_tpu.models import generate
    from thunder_tpu.serving import paged_attention

    store, mixer = jnp.dtype(dtype_name), generate.shortconv_mixer

    def narrow(*args, **kw):
        y, tail = mixer(*args, **kw)
        return y, tail.astype(store).astype(tail.dtype)

    generate.shortconv_mixer = paged_attention.shortconv_mixer = narrow


if __name__ == "__main__":
    import argparse

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--tail-store", default="", help="the control: the dtype every kept tail is rounded to")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.tail_store:
        plant_tail_store(args.tail_store)
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
