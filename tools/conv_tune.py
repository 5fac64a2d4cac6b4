"""Time the causal depthwise conv of the DeltaNet layers alone on the chip, at the hybrid cell's shape.

    python3 tools/conv_tune.py [--check] [--tiles 512x1024x64,256x1024x32]

One line a form, forward and backward: ms a call and GB/s of the least bytes
(x in and out; g, x in and dx out) for x ``(2, 8192, 8192)`` bfloat16, four
taps, SiLU: the XLA form with the activation inside (``jaxex._causal_conv1d_xla``,
the fallback), the two operations the model used to trace (the conv rounded to
bfloat16, then ``silu``, each with its own backward), XLA's own grouped conv
(``lax.conv_general_dilated(feature_group_count=C)``, the control: would less
code do?), and the kernel pair ``causal_conv1d_fwd`` / ``causal_conv1d_bwd`` by
name from a device trace, at the tiles derived from the shape and at each of
``--tiles`` (``tT x tC x rows of a loop step``).  ``--check`` first compares the
compiled kernels with the XLA form (bfloat16 and float32 operands, a ragged
last tile among them).  Needs a TPU; exits non-zero without one, or if the
check fails."""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp

from thunder_tpu._platform import device_info
from thunder_tpu.executors import jaxex
from thunder_tpu.executors import pallasex as px
from tools.flash_tune import REPS, kernel_ms

# B, T, C, K: two sequences of the hybrid cell, q | k | v of a DeltaNet layer side by side
SHAPE = (2, 8192, 8192, 4)
ACT = "silu"


def operands(B, T, C, K, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x, g = (jax.random.normal(k, (B, T, C)).astype(dtype) for k in ks[:2])
    return g, x, (jax.random.normal(ks[2], (C, K)) * 0.5).astype(dtype)


def two_ops(x, w):
    """What ``gated_delta_net`` traced before the prim took its activation."""
    return jax.nn.silu(jaxex._causal_conv1d_xla(x, w))


def two_ops_backward(g, x, w):
    y = jaxex._causal_conv1d_xla(x, w)           # made again, rounded to x's dtype
    gy = jax.vjp(jax.nn.silu, y)[1](g)[0]
    return jaxex._causal_conv1d_backward_xla(gy, x, w)


def grouped_conv(x, w):
    K = w.shape[1]
    # float32 operands (the conversions fuse): the transpose of a mixed-precision conv is refused
    y = jax.lax.conv_general_dilated(x.astype(jnp.float32), jnp.transpose(w.astype(jnp.float32))[:, None, :], (1,),
                                     [(K - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
                                     feature_group_count=x.shape[2])
    return jax.nn.silu(y).astype(x.dtype)


def grouped_conv_backward(g, x, w):
    return jax.vjp(grouped_conv, x, w)[1](g)


def check():
    worst = 0.0
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))   # noqa: E731
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    for dtype, (B, T, C, K) in ((jnp.bfloat16, (2, 2048, 1024, 4)), (jnp.float32, (1, 1040, 384, 3)),
                                (jnp.bfloat16, (1, 2560, 11520, 4))):
        g, x, w = operands(B, T, C, K, dtype)
        got = (px.causal_conv1d(x, w, ACT), *px.causal_conv1d_backward(g, x, w, ACT))
        if got[0] is None:
            sys.exit("conv_tune: the kernels declined a shape they should take")
        want = (jaxex._causal_conv1d_xla(x, w, ACT), *jaxex._causal_conv1d_backward_xla(g, x, w, ACT))
        for what, a, b in zip(("out", "dx", "dw"), got, want):
            worst = max(worst, rel(a, b))
            print(f"check {jnp.dtype(dtype).name:8s} {(B, T, C, K)} {what:3s} relative error {rel(a, b):.6f}", flush=True)
    return worst


def time_pair(label, fwd, bwd, args, names=None):
    """ms a call of ``fwd(x, w)`` and ``bwd(g, x, w)``: every device operation
    they start, or those whose name starts with ``names``."""
    g, x, w = args
    fwd, bwd = jax.jit(fwd), jax.jit(bwd)
    jax.block_until_ready((fwd(x, w), bwd(g, x, w)))
    nbytes = x.size * x.dtype.itemsize
    out = []
    for k, (run, least) in enumerate(((lambda: jax.block_until_ready(fwd(x, w)), 2 * nbytes),
                                      (lambda: jax.block_until_ready(bwd(g, x, w)), 3 * nbytes))):
        ms = kernel_ms(run, REPS)
        own = sum(t for n, t in ms.items() if names is None or n.startswith(names[k]))
        out.append(f"{'fwd' if k == 0 else 'bwd'} {own:7.3f} ms {least / own / 1e6:6.1f} GB/s"
                   f" (beside it {sum(ms.values()) - own:.3f})")
    print(f"{label:28s} {'   '.join(out)}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--tiles", default="", help="comma-separated tTxtCxrows to time beside the derived tiles")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"conv_tune: times the kernels on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    if args.check and check() > 0.01:   # bfloat16 results: a rounding of the last place
        sys.exit("conv_tune: the compiled kernels disagree with the XLA form")
    ops = operands(*SHAPE)
    time_pair("xla, activation inside", lambda x, w: jaxex._causal_conv1d_xla(x, w, ACT),
              lambda g, x, w: jaxex._causal_conv1d_backward_xla(g, x, w, ACT), ops)
    time_pair("xla, conv then silu (parent)", two_ops, two_ops_backward, ops)
    time_pair("lax.conv_general_dilated", grouped_conv, grouped_conv_backward, ops)
    derived = px._conv_tiles(SHAPE[1], SHAPE[2], 2)
    for tiles in [derived] + [tuple(int(n) for n in t.split("x")) for t in args.tiles.split(",") if t]:
        label = f"kernels {'x'.join(map(str, tiles))}{' (derived)' if tiles == derived else ''}"
        try:
            time_pair(label, lambda x, w: px._conv_fwd(x, w, activation=ACT, tiles=tiles),
                      lambda g, x, w: px._conv_bwd(g, x, w, activation=ACT, tiles=tiles), ops,
                      names=("causal_conv1d_fwd", "causal_conv1d_bwd"))
        except Exception as e:   # a tile Mosaic refuses (VMEM) is a line of the table, not the end of it
            if tiles == derived:
                raise
            print(f"{label:28s} refused: {str(e).splitlines()[0][:160]}", flush=True)
    px.causal_conv1d(ops[1], ops[2], ACT)
    print(f"schedule {px.conv_schedule}", flush=True)


if __name__ == "__main__":
    main()
