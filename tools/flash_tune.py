"""Time the three flash kernels alone on the chip, at the benchmark cells' shapes.

    python3 tools/flash_tune.py [--shapes mistral,hybrid] [--blocks 512x512,512x256] [--check]
    python3 tools/flash_tune.py --serve

For each shape and each (BQ, BK) (none given: what ``pallasex._block``
derives), one line: ms a call of ``_flash_fwd``, ``_flash_bwd_dq`` and
``_flash_bwd_dkv`` by name from a device trace of five forward and five
backward calls, and of whatever else XLA runs beside them in the backward
program (``delta``, a sum over the group's heads).  ``--check`` first compares
out, dq, dk, dv with the float32 reference at T 2048 (compiled kernels, not
the interpreter).  ``--serve``: the forward kernel alone as a whole prompt's
prefill calls it in the two serve cells (``generate._attn_with_cache`` at a
static position 0), a line a prefill bucket, beside the form it replaced: the
same prompt scored in float32 against every slot of the request's table.
Needs a TPU; exits non-zero if a geometry failed."""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import trace
from thunder_tpu._platform import device_info
from thunder_tpu.executors import pallasex as px
from thunder_tpu.executors.jaxex import _sdpa_backward_reference, _sdpa_reference

# B, H, G, T, hs, window: one sequence of the Mistral train cell; the two of
# the hybrid cell's gated attention layer
SHAPES = {"mistral": (1, 32, 8, 8192, 128, 4096), "hybrid": (2, 16, 2, 8192, 256, None)}
# H, G, hs, window, slots of the request's table, prefill buckets: a full-attention
# layer of ``offline-batch`` (Mistral-7B) and of ``offline-longgen`` (Olmo-Hybrid-7B)
SERVE = {"mistral": (32, 8, 128, 4096, 3584, (1024, 2048, 3072)),
         "olmo-hybrid": (30, 30, 128, None, 3328, (1024, 2048, 2560))}
REPS = 5


def operands(B, H, G, T, hs, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda n: (B * n, T, hs)   # noqa: E731
    q, g = (jax.random.normal(k, shape(H), dtype) for k in ks[:2])
    k, v = (jax.random.normal(k, shape(G), dtype) for k in ks[2:])
    return q, k, v, g


def kernel_ms(run, reps):
    """ms a call of every device operation ``run()`` starts, by name."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            run()
        jax.profiler.stop_trace()
        ops = trace.load(trace.find_xplane(d)).top_ops(n=100)
    return {name: seconds * 1e3 / reps for name, seconds in ops}


def time_shape(name, blocks):
    B, H, G, T, hs, window = SHAPES[name]
    q, k, v, g = operands(B, H, G, T, hs)
    scale = 1.0 / np.sqrt(hs)
    fwd = lambda: px._flash_fwd(q, k, v, None, True, scale, H, G, None, 1, window)   # noqa: E731
    out, lse = jax.block_until_ready(fwd())
    bwd = lambda: px._flash_bwd(g, q, k, v, out, lse, None, True, scale, H, G, None, 1, window)   # noqa: E731
    jax.block_until_ready(bwd())
    ms = kernel_ms(lambda: jax.block_until_ready((fwd(), bwd())), REPS)
    three = [ms.pop(n, float("nan")) for n in ("_flash_fwd", "_flash_bwd_dq", "_flash_bwd_dkv")]
    rest = ", ".join(f"{n} {t:.3f}" for n, t in sorted(ms.items(), key=lambda kv: -kv[1])[:4])
    print(f"{name:8s} {blocks or 'derived':>9s}: fwd {three[0]:7.3f}  dq {three[1]:7.3f}  dkv {three[2]:7.3f}"
          f"  sum {sum(three):7.3f} ms   beside them: {rest}   schedule {px.flash_schedule}", flush=True)


def table_form(q, kt, vt, window):
    """What a prompt at a traced position 0 costs ``_attn_with_cache``: K/V of
    the whole table broadcast to the query heads, float32 scores against every
    slot, mask, softmax, the second product."""
    from thunder_tpu.models.generate import _expand_groups

    T, Tc = q.shape[2], kt.shape[2]
    kk, vv = _expand_groups(kt, vt, q.shape[1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk, preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    row, col = jnp.arange(T)[None, None, :, None], jnp.arange(Tc)[None, None, None, :]
    keep = col <= row if window is None else jnp.logical_and(col <= row, col > row - window)
    w = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vv)


def time_serve(name):
    H, G, hs, window, table, buckets = SERVE[name]
    for T in buckets:
        q, k, v, _ = (x.reshape(1, -1, T, hs) for x in operands(1, H, G, T, hs))
        kt, vt = (jnp.pad(x, ((0, 0), (0, 0), (0, table - T), (0, 0))) for x in (k, v))
        bind = window if window is not None and T > window else None      # as _attn_with_cache passes it
        flash = jax.jit(lambda q, k, v: px.flash_sdpa(q, k, v, None, True, 1.0 / np.sqrt(hs), bind)[0])
        dense = jax.jit(lambda q, kt, vt: table_form(q, kt, vt, window))
        a, b = jax.block_until_ready((flash(q, k, v), dense(q, kt, vt)))
        err = float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))
        schedule = dict(px.flash_schedule)
        kernel = kernel_ms(lambda: jax.block_until_ready(flash(q, k, v)), REPS)
        form = kernel_ms(lambda: jax.block_until_ready(dense(q, kt, vt)), REPS)
        ops = ", ".join(f"{n} {t:.3f}" for n, t in sorted(form.items(), key=lambda kv: -kv[1])[:4])
        print(f"serve {name:11s} T {T:5d}: _flash_fwd {kernel.get('_flash_fwd', float('nan')):7.3f} ms a layer"
              f" (all ops {sum(kernel.values()):7.3f})   against a table of {table}: {sum(form.values()):7.3f} ms ({ops})"
              f"   they differ by {err:.5f}   schedule {schedule}", flush=True)


def check():
    """Compiled kernels against the float32 reference, T 2048, both cells' kinds."""
    worst = 0.0
    for name, (B, H, G, _, hs, window) in SHAPES.items():
        T, window = 2048, None if window is None else 1024
        q, k, v, g = (x.reshape(B, -1, T, hs) for x in operands(B, H, G, T, hs))
        scale = 1.0 / np.sqrt(hs)
        out, lse = px.flash_sdpa(q, k, v, None, True, scale, window)
        got = (out, *px.flash_sdpa_backward(g, q, k, v, out, lse, None, True, scale, window))
        f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
        oref, lref = _sdpa_reference(*f32[:3], None, True, scale, window)
        want = (oref, *_sdpa_backward_reference(f32[3], *f32[:3], oref, lref, None, True, scale, window))
        for what, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            err = float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
            worst = max(worst, err)
            print(f"check {name:8s} {what:3s} relative error {err:.5f}", flush=True)
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,hybrid")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--serve", action="store_true", help="the forward kernel at the serve cells' prefill buckets")
    ap.add_argument("--window", type=int, help="another window for the mistral shape (to place _block's rule)")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"flash_tune: times the kernels on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    if args.window:
        SHAPES["mistral"] = (*SHAPES["mistral"][:5], args.window)
    if args.check and check() > 0.02:   # bfloat16 operands: 0.003-0.006
        sys.exit("flash_tune: the compiled kernels disagree with the reference")
    if args.serve:
        for name in SERVE:
            time_serve(name)
        return
    failed = []
    for blocks in args.blocks.split(",") if args.blocks else [""]:
        for which in "QK":
            os.environ.pop(f"THUNDER_TPU_FLASH_B{which}", None)
        if blocks:
            os.environ["THUNDER_TPU_FLASH_BQ"], os.environ["THUNDER_TPU_FLASH_BK"] = blocks.split("x")
        jax.clear_caches()
        for name in args.shapes.split(","):
            try:
                time_shape(name, blocks)
            except Exception as e:  # a geometry Mosaic refuses is a result of the search
                failed.append((name, blocks))
                print(f"{name:8s} {blocks:>9s}: FAILED {type(e).__name__}: {str(e)[:300]}", flush=True)
    if failed:
        sys.exit(f"flash_tune: {len(failed)} geometries failed: {failed}")


if __name__ == "__main__":
    main()
