"""Time the expert share's grouped product alone on the chip, at the shapes the cells run.

    python3 tools/moe_tune.py [--check] [--shapes lfm2_decode,lfm2_prefill] [--vmem-mib 32,48]

One line a shape, a product (``fc``: rows x ``(C, I)``; ``proj``: rows x
``(I, C)``) and a routing (``even``: every held expert the same rows; ``skew``:
top-k of sigmoid scores under a bias a held expert drawn N(0, 0.1^2), as the
LFM2 cell draws ``expert_bias``): ms a call of the operations named
``moe_grouped_mm`` from a device trace, GB/s against the held experts' weights
and the used rows read once and the product written once, TF/s of the used
rows, the tiles used of the wave's, the most tiles a group, and what
``pallasex.gmm_schedule`` says the product was laid out as.  The shapes: a
decode step and a 2,048-token prefill of ``lfm2moe-serve-1chip.offline-wide``
(32 experts of ``2048 x 1792``, top-4; 256 rows in tiles of 64, 2,048 in tiles
of 128), a decode step and an 8,192-token prefill of
``axk1-serve-1chip.offline-longctx`` (12 of 192 experts of ``7168 x 2048``,
top-8; 64 rows in tiles of 16), and the forward and the rows' gradient of
``qwen3next-train-1chip.seq8k-x2`` (32 of 512 experts of ``2048 x 512``,
top-10, 16,384 tokens).  A wave past the first is timed where the routing
fills it, as the model runs it.  ``--vmem-mib`` times the kernel again with
``pallasex._gmm_vmem_cap`` at each value (which block the rule derives from
it is on the line).  To compare kernels, put each variant in
a tree of its own under ``_checkout/`` with this file in it and run the tool in
each, all in one call.  ``--check`` first compares the compiled kernel with
``lax.ragged_dot`` at every shape.  Needs a TPU; exits non-zero without one,
or if the check fails."""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu._platform import device_info
from thunder_tpu.executors import jaxex
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models.generate import moe_row_tile

REPS = 5
# tokens a call, experts a token, held and all experts, C, I, transposed (the rows' gradient: rows x w^T)
SHAPES = {
    "lfm2_decode": dict(tokens=256, k=4, held=32, total=32, C=2048, I=1792),
    "lfm2_prefill": dict(tokens=2048, k=4, held=32, total=32, C=2048, I=1792),
    "axk1_decode": dict(tokens=64, k=8, held=12, total=192, C=7168, I=2048),
    "axk1_prefill": dict(tokens=8192, k=8, held=12, total=192, C=7168, I=2048),
    "hybrid_forward": dict(tokens=16384, k=10, held=32, total=512, C=2048, I=512, tile=128),
    "hybrid_transposed": dict(tokens=16384, k=10, held=32, total=512, C=2048, I=512, tile=128, transposed=True),
}


def routing(tokens, k, held, total, skew: bool, seed=0):
    """``top_idx (tokens, k)`` over ``total`` experts: exactly even, or the
    top-k of sigmoid scores under a bias an expert."""
    rng = np.random.default_rng(seed)
    if not skew:
        return ((np.arange(tokens)[:, None] * k + np.arange(k)[None, :]) % total).astype(np.int32)
    scores = 1.0 / (1.0 + np.exp(-rng.normal(0.0, 0.9, (tokens, total))))
    return np.argsort(-(scores + rng.normal(0.0, 0.1, total)[None, :]), axis=1)[:, :k].astype(np.int32)


def waves(tokens, k, held, total, skew, tile=None, **_):
    """The waves of the sorted buffer that hold rows: ``(tile_group, tiles_used (1,))``
    each, the tile's rows, and the rows routed a held expert."""
    tile = tile or moe_row_tile(tokens * k / total)
    wave_tiles = jaxex.moe_wave_tiles(tokens * k, held, total, tile)
    plan = jaxex.moe_plan(jnp.asarray(routing(tokens, k, held, total, skew)), 0, held, tile, wave_tiles)
    out = []
    for w in range(plan["tile_group"].shape[0] // wave_tiles):
        _, tg, used = jaxex.moe_wave_rows(plan, w, tile, wave_tiles)
        if int(used) > 0:
            out.append((tg, used.reshape(1)))
    return out, tile, np.asarray(plan["cnt"])


def operands(C, I, held, product: str, transposed=False, rows=0, dtype=jnp.bfloat16, **_):
    """``x (rows, K)`` and ``w`` as the product takes them; ``(K, N)``."""
    K, N = (C, I) if product == "fc" else (I, C)
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    w = (jax.random.normal(kw, (held, K, N)) * 0.05).astype(dtype)
    if transposed:          # the gradient of the rows: dy (rows, N) x w^T
        K, N = N, K
    return jax.random.normal(kx, (rows, K)).astype(dtype), w, (K, N)


def check(names) -> float:
    worst = 0.0
    for name in names:
        shape = SHAPES[name]
        ws, tile, _ = waves(skew=True, **shape)
        tg, used = ws[0]
        for product in ("fc", "proj"):
            x, w, _ = operands(product=product, rows=tg.shape[0] * tile, **shape)
            t = bool(shape.get("transposed"))
            got = px.grouped_mm(x, w, tg, used, t)
            if got is None:
                sys.exit(f"moe_tune: the kernel declined {name} {product}")
            fast, jaxex._grouped_mm_fast_path = jaxex._grouped_mm_fast_path, None
            try:
                want = jaxex._grouped_mm_impl(x, w, tg, used, t).astype(jnp.float32)
            finally:
                jaxex._grouped_mm_fast_path = fast
            err = float(jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))
            worst = max(worst, err)
            print(f"check {name:18s} {product:4s} relative error {err:.6f}", flush=True)
    return worst


def time_shape(name, skew: bool):
    from tools.flash_tune import kernel_ms

    shape = SHAPES[name]
    ws, tile, cnt = waves(skew=skew, **shape)
    t = bool(shape.get("transposed"))
    for product in ("fc", "proj"):
        x, w, (K, N) = operands(product=product, rows=ws[0][0].shape[0] * tile, **shape)
        call = jax.jit(lambda x_, w_, tg, used: px.grouped_mm(x_, w_, tg, used, t))
        run = lambda: jax.block_until_ready([call(x, w, tg, used) for tg, used in ws])   # noqa: E731, B023
        run()
        ms = kernel_ms(run, REPS)
        own = sum(v for n, v in ms.items() if n.startswith("moe_grouped_mm"))
        used = sum(int(u[0]) for _, u in ws)
        groups = int((cnt > 0).sum())
        least = (groups * K * N + used * tile * (K + N)) * x.dtype.itemsize
        schedule = dict(getattr(px, "gmm_schedule", {}))       # a parent's tree has none
        print(f"{name:18s} {product:4s} {'skew' if skew else 'even'}  {own:7.3f} ms  {least / own / 1e6:6.1f} GB/s "
              f"{2 * used * tile * K * N / own / 1e9:6.1f} TF/s  rows x ({K}, {N}) in tiles of {tile}: {used} of "
              f"{len(ws)} x {ws[0][0].shape[0]} tiles used, at most {int(-(-cnt.max() // tile))} a group ({groups} groups)  "
              f"beside it {sum(ms.values()) - own:.3f}  {schedule}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--vmem-mib", default="", help="comma-separated values of pallasex._gmm_vmem_cap to time beside the device's")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"moe_tune: times the kernel on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    names = [n for n in args.shapes.split(",") if n]
    if args.check and check(names) > 0.01:       # bfloat16 results of a float32 sum: a rounding of the last place
        sys.exit("moe_tune: the compiled kernel disagrees with lax.ragged_dot")
    for mib in [None] + [int(m) for m in args.vmem_mib.split(",") if m]:
        if mib is not None:
            px._gmm_vmem_cap = lambda mib=mib: mib << 20
            px._moe_grouped_mm.clear_cache()
            print(f"_gmm_vmem_cap = {mib} MiB", flush=True)
        for name in names:
            for skew in (False, True):
                time_shape(name, skew)


if __name__ == "__main__":
    main()
