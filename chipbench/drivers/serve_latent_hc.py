"""``drivers/serve_latent.py`` for a latent-attention model that holds whole expert
layers under hyper-connections: the same build, the same comparison (``serve.check``
plus the rows the engine holds of a request against ``arch.ref_latents``), the same
lead-in and window, with two things more.

**The counts.**  The engine's sums of how a decode step's rows fell on the held
experts (``engine.stats()["moe"]``: ``row_sums``), read after the check and after
the window and put beside what ``measure`` kept of the engine's stats
(``counters``: ``stats0``, ``stats1``, from which ``serve._slim`` drops them), as
``drivers/serve_window.py`` does; they span the lead-in's decode steps too.  The
readers ``layer_metrics/expert_rows_per_step.nemoserve.py`` and
``experts_hit_share.nemoserve.py`` take the window's own from the two.  That is why
the configuration names this driver and not ``serve_latent``: the comparison that
decides ``correct`` is that driver's, unchanged.

**The controls**, planted here and never options of the program.  Each wraps
``models.generate.hc_maps`` (the one place the dense cache's forward and the paged
server's compute a hyper-connection's three maps), before the process builds its
first engine, since built programs are cached:

- ``--hc-control sinkhorn1``: one Sinkhorn iteration where the configuration says
  20 (``H_res``'s columns sum to one within 0.3, not 1e-4);
- ``--hc-control static``: the token's own part dropped (``alpha`` 0: every token
  gets the maps of the biases alone);
- ``--hc-control bfloat16``: the flattened norm, the three products, the sigmoids
  and the Sinkhorn iterations in bfloat16 (a copy of the program's lines in that
  dtype; the maps are handed back in float32, so the mixing itself is the program's);
- ``--latent-store float8_e4m3fn``: ``serve_latent.py``'s storage control.

    python3 chipbench/drivers/serve_latent_hc.py --workload <cell> --seeds 1,2,3 [--hc-control sinkhorn1|static|bfloat16 | --latent-store float8_e4m3fn | --engine '{"quantized": true}']

prints the comparison's numbers a seed, as ``serve_latent.py``'s own command does.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

latent = common.load_module("drivers", "serve_latent")
serve = latent.serve
build, measure, check = latent.build, latent.measure, latent.check


def counts(stats: dict) -> dict:
    """Where the engine counts them, the expert layers' sums."""
    return {"moe": stats["moe"]} if "moe" in stats else {}


def run(ctx: dict) -> dict:
    st = build(ctx)
    chk = check(ctx, st)
    before = counts(st["engine"].stats())
    out = measure(ctx, st, chk)
    out["counters"]["stats0"].update(before)
    out["counters"]["stats1"].update(counts(st["engine"].stats()))
    st["engine"].shutdown(drain=False)
    return out


def plant_hc_control(which: str) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from thunder_tpu.models import generate

    maps = generate.hc_maps

    def sinkhorn1(hp, x, cfg):
        return maps(hp, x, dataclasses.replace(cfg, hc_sinkhorn_iters=1))

    def static(hp, x, cfg):
        return maps({**hp, "alpha": jnp.zeros_like(hp["alpha"])}, x, cfg)

    def bfloat16(hp, x, cfg):
        b16 = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
        n, C = x.shape[1], x.shape[3]
        xb, eps = b16(x), b16(cfg.hc_eps)
        r = jax.lax.rsqrt(b16(jnp.mean((xb * xb).astype(jnp.float32), axis=(1, 3))) + eps)
        phi = b16(hp["phi"] * hp["norm"]).reshape(-1, n, C)
        raw = b16(sum(jnp.einsum("btc,mc->mbt", xb[:, j], phi[:, j]) for j in range(n))) * r
        a, b = b16(hp["alpha"]), b16(hp["bias"])[:, None, None]
        m = jnp.exp(jnp.clip(a[2] * raw[2 * n:] + b[2 * n:], *cfg.hc_res_clamp)).reshape(n, n, *raw.shape[1:])
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        out = (jax.nn.sigmoid(a[0] * raw[:n] + b[:n]), 2 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n]), m)
        assert all(o.dtype == jnp.bfloat16 for o in out), [o.dtype for o in out]
        return tuple(o.astype(jnp.float32) for o in out)

    generate.hc_maps = {"sinkhorn1": sinkhorn1, "static": static, "bfloat16": bfloat16}[which]


if __name__ == "__main__":
    import argparse

    from chipbench import calibrate

    ap = argparse.ArgumentParser(description="The comparison's numbers a seed, one set-up.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--engine", default="", help="JSON of engine options to override (a control)")
    ap.add_argument("--latent-store", default="", help="the control: the dtype every latent row is rounded to")
    ap.add_argument("--hc-control", default="", choices=("", "sinkhorn1", "static", "bfloat16"),
                    help="the control: a planted fault of the hyper-connection's maps")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.latent_store:
        latent.plant_latent_store(args.latent_store)
    if args.hc_control:
        plant_hc_control(args.hc_control)
    calibrate.check_serve(args, sys.modules[__name__], [int(s) for s in args.seeds.split(",")])
