"""``drivers/serve.py`` with one more comparison in ``correct``: what the
engine's caches hold of a request, against what the reference holds, **by the
kinds of layer the architecture names**.

A served token's logit (``serve.check``) has passed every layer and the head,
and most of a hybrid model's layers never read the paged K/V at all: a cache
stored one precision lower than the configuration states moves the tokens by
about what the seeds do.  So this driver also serves the check's requests
again, stops them short of their end, reads what the engine holds of each
(``engine.held``) and compares it, layer by layer, with what the reference
holds after the same tokens (``arch.ref_caches``: ``(kind, arrays)`` a layer, in
the model's order), as a relative error over the requests.  ``KINDS`` says, for
a kind of layer, which of ``engine.held``'s arrays are its and how they lie
beside the reference's; a kind that keeps nothing (a gated memory unit, cross
attention on another layer's K and V) yields ``None`` and is passed over.  What
is held to a limit is, of every kind the model has, the *shallowest* layer's
arrays (the first layer's input is the embedding, the same numbers on both
sides, so its error is the program's own rounding and a narrower storage fails
it; deeper layers carry the rounding of the layers before them, which a limit
cannot tell from a fault), and over every layer that keeps keys and values the
largest error, which another request's blocks, another layer's rows or a head
pair's lanes swapped fail at once.  Keys and values are compared on their
large elements (over ``kv_large_rms`` times the layer's root mean square: a
narrower storage rounds each element in proportion to its size, the rounding
carried in from the layers before lands on all alike; ``drivers/serve_held.py``
has the argument).  The limits' names in the mix's ``check``:
``<name>_rel_err_limit`` for each name ``KINDS`` reports a kind under, where the
model has a layer of it, and ``kv_rel_err_max_limit``.

Everything else is ``drivers/serve.py``'s own: the engine's build, the token
comparison, the lead-in and the window.

    python3 chipbench/drivers/serve_kinds.py --workload <cell> --seeds 1,2,3 [--engine '{"quantized": true}' | --state-arena bfloat16 | --kv-store float8_e4m3fn] [--witness-layers 4]

prints the comparison's numbers a seed, as ``calibrate.py`` does for the
accepted drivers (it tells a serving driver by the name ``serve``).  Two storage
controls are planted here, not options of the engine's: ``--state-arena
bfloat16`` sets ``StatePool.STATE_DTYPE`` before the pool is built; ``--kv-store
float8_e4m3fn`` wraps ``models.generate.diff_attention`` so that every key and
value a layer projects is rounded to that dtype's exponent and mantissa bits
(``lax.reduce_precision``) before it is attended or kept, which is what an fp8
K/V arena would hold (the engine refuses ``kv_dtype`` for per-kind caches).  Both before the process builds its first
engine (built programs are cached).  ``--witness-layers N`` runs the program in
float32 at a depth of ``N`` (with ``JAX_DEFAULT_MATMUL_PRECISION=highest``): it
reads what the reference reads.
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

# a kind of layer -> (its arrays in ``engine.held``, in the order ``arch.ref_caches`` gives them;
#                     the name its shallowest layer's error is reported under;
#                     whether its arrays are keys and values, compared on their large elements)
KINDS = {
    "ssm": (("state", "conv"), "state", False),
    "linear_attention": (("state", "conv"), "state", False),
    "conv": (("conv",), "tail", False),
    "sliding_attention": (("k_ring", "v_ring"), "ring", True),
    "full_attention": (("k", "v"), "kv", True),
}
# the reference keeps the equations' layout; the engine's differs here
TO_REFERENCE = {("ssm", "state"): lambda a: np.swapaxes(a, -1, -2)}     # (N, d) on the chip, (d, N) in the equations


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
    square: dict = {}      # (kind, array's name) -> a layer of that kind: [sum of squares of the difference, of the reference]
    tokens = []

    def add(kind, name, layer, got, want, large_only):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        floor = spec["kv_large_rms"] * np.sqrt(np.mean(want ** 2)) if large_only else 0.0
        large = np.abs(want) > floor
        layers = square.setdefault((kind, name), [])
        if layer == len(layers):
            layers.append(np.zeros(2))
        layers[layer] += [np.sum((got - want)[large] ** 2, dtype=np.float64),
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
        seen: dict = {}
        with jax.default_device(ctx["devices"][0]):
            for kind, want in arch.ref_caches(hf, st["params"], jnp.asarray(padded), n):
                if want is None:
                    continue
                names, _, large_only = KINDS[kind]
                layer = seen[kind] = seen.get(kind, -1) + 1
                for name, w in zip(names, want):
                    got = TO_REFERENCE.get((kind, name), lambda a: a)(held[name][layer])
                    add(kind, name, layer, got, w, large_only)
    serve.drain(eng, clients)
    rel = {f"{kind}.{name}": [float(np.sqrt(d / w)) for d, w in layers] for (kind, name), layers in square.items()}
    out = {}
    for kind, (names, report, large_only) in KINDS.items():
        first = [rel[f"{kind}.{name}"][0] for name in (names if large_only else names[:1]) if f"{kind}.{name}" in rel]
        if first:
            out[report + "_rel_err"] = max(first)
    out["kv_rel_err_max"] = max(e for key, errs in rel.items() if KINDS[key.split(".")[0]][2] for e in errs)
    out.update({k + "_limit": spec[k + "_limit"] for k in list(out)})
    ok = (all(out[k] <= out[k + "_limit"] for k in list(out) if not k.endswith("_limit"))
          and all(c.handle.result(drive=False).finish_reason == "length" for c in clients))
    stats = eng.stats()
    return {**out, "held_rel_err_by_layer": rel, "held_tokens": tokens, "decode_path": stats["attn"]["path"],
            "lane_pack": stats["attn"]["lane_pack"], "state_arena": stats["pool_occupancy"]["state"]["dtype"],
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


def plant_kv_store(dtype_name: str) -> None:
    """The control: every key and value an attention layer projects, rounded to ``dtype_name`` and back."""
    from thunder_tpu.models import generate
    from thunder_tpu.serving import paged_attention

    store, mixer = jnp.finfo(jnp.dtype(dtype_name)), generate.diff_attention

    def narrow(ap, x, layer, cfg, attend, *, lin=generate._linear, **kw):
        def rounded(a, w, b=None):
            # ``reduce_precision``, not a cast there and back: XLA may drop such a pair (it did, for
            # the one layer whose keys go no further than the cache and an einsum; chip call 2, PR 41)
            y = lin(a, w, b)
            return jax.lax.reduce_precision(y, store.nexp, store.nmant) if w is ap.get("wk") or w is ap.get("wv") else y

        return mixer(ap, x, layer, cfg, attend, lin=rounded, **kw)

    generate.diff_attention = paged_attention.diff_attention = narrow


if __name__ == "__main__":
    import argparse

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (the control)")
    ap.add_argument("--state-arena", default="", help="the control: the state arena's storage, planted in the pool")
    ap.add_argument("--kv-store", default="", help="the control: the dtype every kept key and value is rounded to")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness-layers", type=int, default=0)
    args = ap.parse_args()
    if args.state_arena:
        from thunder_tpu.serving.kv_pool import StatePool

        StatePool.STATE_DTYPE = jnp.dtype(args.state_arena)
    if args.kv_store:
        plant_kv_store(args.kv_store)
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
