"""``drivers/serve.py`` with one more comparison in ``correct``, for a model
whose cache is one latent a token a layer: what the engine's arena holds of a
request, against what the reference holds.

The token comparison (``serve.check``) passes every layer and the head; a
cache stored one precision lower than the configuration states moves it by
about what the seeds do.  So this driver also serves the check's requests
again, stops them short of their end, reads the rows the engine holds of each
(``engine.held``: ``latent (L, tokens, kv_lora_rank + qk_rope_head_dim)``, the
normed latent and the rotated key) and compares them, layer by layer, with
what the reference computes for the same tokens (``arch.ref_latents``), as a
relative error over the requests.  The first layer's rows have the same input
on both sides (the embedding), so their error is the program's own rounding:
that number is held to a limit that a narrower storage fails.  The largest
error of any layer is held to a limit that another request's blocks, or
another layer's rows, fail at once.

Everything else is ``drivers/serve.py``'s own: the engine's build, the token
comparison, the lead-in and the window.

    python3 chipbench/drivers/serve_latent.py --workload <cell> --seeds 1,2,3 [--latent-store float8_e4m3fn | --engine '{"quantized": true}']

prints the comparison's numbers a seed, as ``calibrate.py`` does for the
accepted drivers (it tells a serving driver by the name ``serve``).  The
storage control is planted here, not an option of the engine's:
``--latent-store float8_e4m3fn`` wraps ``models.generate.mla_latent`` so that
every row is rounded to that dtype (and back) before it is attended or
written, which is what an fp8 arena without scales would hold.
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
    square: list = []          # a layer: [sum of squares of the difference, of the reference]
    tokens = []
    for c in clients:
        held = jax.device_get(eng.held(c.handle))
        n, r = held["tokens"], c.req
        fed = np.concatenate([traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab),
                              np.asarray(c.tokens, np.int32)])[:n]
        padded = np.zeros(-(-n // pad) * pad, np.int32)
        padded[:n] = fed
        tokens.append(n)
        with jax.default_device(ctx["devices"][0]):
            for layer, want in enumerate(arch.ref_latents(hf, st["params"], jnp.asarray(padded), n)):
                got, want = np.asarray(held["latent"][layer], np.float32), np.asarray(want, np.float32)
                if layer == len(square):
                    square.append(np.zeros(2))
                square[layer] += [np.sum((got - want) ** 2, dtype=np.float64), np.sum(want ** 2, dtype=np.float64)]
    serve.drain(eng, clients)
    rel = [float(np.sqrt(d / w)) for d, w in square]
    out = {"latent_rel_err": rel[0], "latent_rel_err_max": max(rel)}
    out.update({k + "_limit": spec[k + "_limit"] for k in list(out)})
    ok = (all(out[k] <= out[k + "_limit"] for k in ("latent_rel_err", "latent_rel_err_max"))
          and all(c.handle.result(drive=False).finish_reason == "length" for c in clients))
    occ = eng.stats()["pool_occupancy"]
    return {**out, "latent_rel_err_by_layer": rel, "held_tokens": tokens, "arena_kind": occ.get("kind"),
            "token_bytes": [occ.get("token_bytes_counted"), occ.get("token_bytes_laid_out")],
            "held_ok": bool(ok)}


def check(ctx: dict, st: dict) -> dict:
    chk = serve.check(ctx, st)
    held = held_check(ctx, st)
    return {**chk, **held, "ok": bool(chk["ok"] and held["held_ok"])}


def run(ctx: dict) -> dict:
    st = build(ctx)
    out = measure(ctx, st, check(ctx, st))
    st["engine"].shutdown(drain=False)
    return out


def plant_latent_store(dtype_name: str) -> None:
    """The control: every row a latent layer makes is rounded to ``dtype_name``
    and back before anything reads it."""
    from thunder_tpu.models import generate

    made, store = generate.mla_latent, jnp.dtype(dtype_name)

    def rounded(*args, **kwargs):
        rows = made(*args, **kwargs)
        return rows.astype(store).astype(rows.dtype)

    generate.mla_latent = rounded


if __name__ == "__main__":
    import argparse

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (a control)")
    ap.add_argument("--latent-store", default="", help="the control: the dtype every latent row is rounded to")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.latent_store:
        plant_latent_store(args.latent_store)
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
