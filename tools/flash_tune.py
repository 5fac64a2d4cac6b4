"""Time the four flash kernels alone on the chip, at the benchmark cells' shapes.

    python3 tools/flash_tune.py [--shapes mistral,hybrid] [--blocks 512x512,512x256] [--check]
    python3 tools/flash_tune.py --serve [--cells trinity-mini]
    python3 tools/flash_tune.py --fit
    python3 tools/flash_tune.py --latent [--check]

For each shape and each (BQ, BK) (none given: what ``pallasex._flash_blocks``
derives): a line for ``_flash_fwd`` and a line for each form of the backward
pass, ``_flash_bwd`` (the one walk) and ``_flash_bwd_dq`` + ``_flash_bwd_dkv``
(the two kernels it replaced, kept for what the walk's sums do not fit): ms a
call by name from a device trace of five calls, the bytes the form keeps
resident in VMEM, its grid steps a KV group, and whatever else XLA runs beside
the kernels in the backward program (``delta``); then the one walk against
the pair, and which form ``pallasex._flash_bwd_form`` takes on this device.
``--check`` first compares
out, dq, dk, dv with the float32 reference at T 2048 and at T 2304, which no
derived block divides, and holds the backward pass's two forms to each
other, bit for bit (compiled kernels, not the interpreter).  ``--serve``:
the forward kernel alone as a whole prompt's prefill calls it in three serve
cells (``generate._attn_with_cache`` at a static position 0), a line a prefill
bucket and kind of layer: the derived block, the rows of its last block past
the end, ms a call and the share of the MXU's peak over the kept pairs; the
same in the largest block that divides the length (the rule before PR 49) and
padded with zeros to a whole block and sliced (the other form of a ragged
end); and, where a table's float32 scores fit the device, the form PR 33
replaced.  ``--fit``: the two constants of ``_flash_blocks``' model, a grid
step's us and a thousand listed pairs' ns, by least squares over Trinity-Mini's
layer at its buckets and three exact lengths, in blocks of 256, 512 and 1024.
``--latent``: a latent prompt's call at the two latent cells' heads and prefill
buckets (heads of 192 over values of 128), a line a bucket and three forms
each with its pads and slices inside the timed function: q/k and v as they
are, q/k padded to 256 beside v at 128, and all three padded to 256 with the
result sliced (the call before PR 54); us a call of everything the function
runs, of ``_flash_fwd`` alone, the matrix passes a 128 x 128 tile of a block,
and the first form's gain over the last.  ``--check`` holds the three to each
other and to the float32 reference at the first bucket.
Needs a TPU; exits non-zero if a geometry failed."""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, trace
from thunder_tpu._platform import device_info
from thunder_tpu.executors import pallasex as px
from thunder_tpu.executors.jaxex import _sdpa_backward_reference, _sdpa_reference

# B, H, G, T, hs, window: one sequence of the Mistral train cell; the two of
# the hybrid cell's gated attention layer
SHAPES = {"mistral": (1, 32, 8, 8192, 128, 4096), "hybrid": (2, 16, 2, 8192, 256, None)}
# H, G, hs, windows, slots of the request's table, prefill buckets: a full-attention
# layer of ``offline-batch`` (Mistral-7B) and of ``offline-longgen`` (Olmo-Hybrid-7B);
# a global and a window layer of ``offline-docqa`` (Trinity-Mini: one chip's 4 of 32
# heads a KV group; a table's float32 scores would not fit beside them)
SERVE = {"mistral": (32, 8, 128, (4096,), 3584, (1024, 2048, 3072)),
         "olmo-hybrid": (30, 30, 128, (None,), 3328, (1024, 2048, 2560)),
         "trinity-mini": (32, 4, 128, (None, 2048), None, (3840, 5888, 7936, 9984))}
# heads, (q/k, v) widths, prefill buckets: ``offline-digest`` (Xing4.0) and ``offline-longctx`` (A.X-K1)
LATENT = {"xing4": (32, (192, 128), (5120, 6656, 8192)), "axk1": (64, (192, 128), (4096, 6144, 8192))}
FIT_LENGTHS = (2560, 3584, 8192)     # beside Trinity-Mini's buckets: the other cells' longest, which a block divides
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


BWD_FORMS = {"one_walk": ("_flash_bwd",), "two_kernels": ("_flash_bwd_dq", "_flash_bwd_dkv")}


def time_shape(name, blocks):
    B, H, G, T, hs, window = SHAPES[name]
    q, k, v, g = operands(B, H, G, T, hs)
    scale = 1.0 / np.sqrt(hs)
    fwd = lambda: px._flash_fwd(q, k, v, None, True, scale, H, G, None, 1, window)   # noqa: E731
    out, lse = jax.block_until_ready(fwd())
    ms = kernel_ms(lambda: jax.block_until_ready(fwd()), REPS)
    head = f"{name:8s} {blocks or 'derived':>9s}:"
    print(f"{head} fwd {ms.pop('_flash_fwd', float('nan')):7.3f} ms", flush=True)
    took = {}
    for form, names in BWD_FORMS.items():
        bwd = lambda: px._flash_bwd(g, q, k, v, out, lse, None, True, scale, H, G, None, 1, window, form=form)   # noqa: E731
        try:
            jax.block_until_ready(bwd())
        except Exception as e:  # a form Mosaic refuses at this geometry is a result of the search
            print(f"{head} bwd {form:11s} FAILED {type(e).__name__}: {str(e)[:300]}", flush=True)
            continue
        schedule = dict(px.flash_schedule)
        ms = kernel_ms(lambda: jax.block_until_ready(bwd()), REPS)
        kernels = [ms.pop(n, float("nan")) for n in names]
        took[form] = sum(kernels)
        rest = ", ".join(f"{n} {t:.3f}" for n, t in sorted(ms.items(), key=lambda kv: -kv[1])[:4])
        print(f"{head} bwd {form:11s} " + "  ".join(f"{n} {t:7.3f}" for n, t in zip(names, kernels))
              + f"  sum {took[form]:7.3f} ms   resident {schedule['bwd_resident_bytes'] / 2**20:.1f} MiB,"
              f" {schedule['bwd_grid_steps']} steps a group   beside them: {rest}   schedule {schedule}", flush=True)
    if len(took) == 2:
        print(f"{head} one walk against the two kernels: {took['one_walk'] / took['two_kernels'] - 1:+.3f}", flush=True)
    rule = px._flash_bwd_form(px._flash_walk_bytes(T, T, *px._flash_blocks(q, k, 1, window), hs, q.dtype.itemsize))
    print(f"{head} the byte rule takes {rule} ({px._gmm_vmem_cap() / 2**20:.0f} MiB of VMEM to ask for)", flush=True)
    if rule not in took:
        raise RuntimeError(f"{rule}, the form the byte rule takes, did not run")


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


def set_blocks(blocks: str = ""):
    """``THUNDER_TPU_FLASH_BQ`` / ``_BK`` from "BQxBK", or neither; on a change the traces made before are dropped."""
    names = [f"THUNDER_TPU_FLASH_B{which}" for which in "QK"]
    before = [os.environ.pop(n, None) for n in names]
    after = blocks.split("x") if blocks else [None, None]
    os.environ.update({n: b for n, b in zip(names, after) if b})
    if before != after:
        jax.clear_caches()


def kept_pairs(T, window):
    """Pairs a causal call of length T keeps a head."""
    return int(np.minimum(np.arange(T) + 1, window or T).sum())


def divisor_block(T, hs, window):
    """The block before PR 49: the largest of 1024 (where ``_flash_blocks`` allows it), 512, 256, 128 that divides T."""
    wide = hs * 2 <= 512 and (window is None or window >= 2048)
    return next(b for b in (1024, 512, 256, 128)[0 if wide else 1:] if T % b == 0)


def flash_ms(q, k, v, bind, blocks=""):
    """ms a call of ``_flash_fwd`` as ``flash_sdpa`` builds it (``blocks``: forced), its output and its schedule."""
    set_blocks(blocks)
    hs = q.shape[-1]
    flash = jax.jit(lambda q, k, v: px.flash_sdpa(q, k, v, None, True, 1.0 / np.sqrt(hs), bind)[0])
    out = jax.block_until_ready(flash(q, k, v))
    schedule = dict(px.flash_schedule)
    ms = kernel_ms(lambda: jax.block_until_ready(flash(q, k, v)), REPS)
    set_blocks()
    return ms, out, schedule


def rel_err(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))


def time_serve(name):
    H, G, hs, windows, table, buckets = SERVE[name]
    peak = common.peaks(device_info()["kind"])["bf16_flops_per_sec"]
    share = lambda T, bind, ms: 4 * kept_pairs(T, bind) * hs * H / (ms * 1e-3) / peak   # noqa: E731
    for T, window in ((T, w) for T in buckets for w in windows):
        q, k, v, _ = (x.reshape(1, -1, T, hs) for x in operands(1, H, G, T, hs))
        bind = window if window is not None and T > window else None      # as _attn_with_cache passes it
        kernel, a, schedule = flash_ms(q, k, v, bind)
        ms = kernel.get("_flash_fwd", float("nan"))
        line = (f"serve {name:12s} T {T:5d} window {bind}: block {schedule['block_q']}x{schedule['block_k']}"
                f" tail_rows {schedule['tail_rows']} steps {schedule['grid_steps']}: _flash_fwd {ms:7.3f} ms a layer"
                f" (all ops {sum(kernel.values()):7.3f}), {share(T, bind, ms):.3f} of the peak over kept pairs")
        if schedule["tail_rows"]:
            old = divisor_block(T, hs, bind)
            was, b, _ = flash_ms(q, k, v, bind, f"{old}x{old}")
            was = was.get("_flash_fwd", float("nan"))
            line += f"   in blocks of {old}: {was:7.3f} ms, {share(T, bind, was):.3f} (they differ by {rel_err(a, b):.5f})"
            # the other form of a ragged end: zeros up to a whole block, the kernel at that length, a slice
            more = ((0, 0), (0, 0), (0, schedule["tail_rows"]), (0, 0))
            padded = jax.jit(lambda q, k, v: px.flash_sdpa(
                *(jnp.pad(x, more) for x in (q, k, v)), None, True, 1.0 / np.sqrt(hs), bind)[0][:, :, :T])
            b = jax.block_until_ready(padded(q, k, v))
            form = kernel_ms(lambda: jax.block_until_ready(padded(q, k, v)), REPS)
            line += (f"   padded and sliced: {sum(form.values()):7.3f} ms, _flash_fwd {form.get('_flash_fwd', float('nan')):7.3f}"
                     f" (they differ by {rel_err(a, b):.5f})")
        if table is not None:
            kt, vt = (jnp.pad(x, ((0, 0), (0, 0), (0, table - T), (0, 0))) for x in (k, v))
            dense = jax.jit(lambda q, kt, vt: table_form(q, kt, vt, window))
            b = jax.block_until_ready(dense(q, kt, vt))
            form = kernel_ms(lambda: jax.block_until_ready(dense(q, kt, vt)), REPS)
            ops = ", ".join(f"{n} {t:.3f}" for n, t in sorted(form.items(), key=lambda kv: -kv[1])[:4])
            line += f"   against a table of {table}: {sum(form.values()):7.3f} ms ({ops})   they differ by {rel_err(a, b):.5f}"
        print(line, flush=True)


def latent_forms(H, hs, hv):
    """name -> (fn(q, k, v) -> out (H, T, hv), kernel widths): the three forms of a latent prompt's call."""
    wide = px._pad128(hs)
    pad = lambda x, w: px._pad_hs(x, x.shape[-1], w)   # noqa: E731
    call = lambda q, k, v: px._flash_fwd(q, k, v, None, True, hs ** -0.5, H, H, None, 1)[0]   # noqa: E731
    return {
        "as they are": (call, (hs, hv)),
        f"q/k at {wide}": (lambda q, k, v: call(pad(q, wide), pad(k, wide), v), (wide, hv)),
        # generate._mla_with_cache's pad to q's width, _fwd_local's to whole tiles, and the two slices back
        f"all at {wide}": (lambda q, k, v: call(pad(q, wide), pad(k, wide), pad(pad(v, hs), wide))[..., :hs][..., :hv],
                           (wide, wide)),
    }


def time_latent(name, check):
    H, (hs, hv), buckets = LATENT[name]
    forms = latent_forms(H, hs, hv)
    first, *_, last = forms
    for n, T in enumerate(buckets):
        ks = jax.random.split(jax.random.PRNGKey(T), 3)
        q, k = (jax.random.normal(key, (H, T, hs), jnp.bfloat16) for key in ks[:2])
        v = jax.random.normal(ks[2], (H, T, hv), jnp.bfloat16)
        outs, total = {}, {}
        for form, (fn, (wqk, wv)) in forms.items():
            head = f"latent {name:5s} heads {H} T {T:5d} ({hs} | {hv}) {form:12s}"
            try:
                jitted = jax.jit(fn)
                outs[form] = jax.block_until_ready(jitted(q, k, v))
                schedule = dict(px.flash_schedule)
                us = {op: ms * 1e3 for op, ms in kernel_ms(lambda: jax.block_until_ready(jitted(q, k, v)), REPS).items()}
            except Exception as e:  # a width Mosaic refuses is a result of the search
                print(f"{head}: FAILED {type(e).__name__}: {str(e)[:300]}", flush=True)
                continue
            total[form] = sum(us.values())
            beside = ", ".join(f"{op} {t:.0f}" for op, t in sorted(us.items(), key=lambda kv: -kv[1]) if op != "_flash_fwd")
            print(f"{head} [{-(-wqk // 128)} + {-(-wv // 128)} passes a tile, block {schedule['block_q']}x{schedule['block_k']}]:"
                  f" {total[form]:8.0f} us a call, _flash_fwd {us.get('_flash_fwd', float('nan')):8.0f}"
                  f" (beside it: {beside or 'nothing'})", flush=True)
        for form in (f for f in total if last in total and f != last):
            print(f"latent {name:5s} heads {H} T {T:5d}: {form} against {last}: {total[form] / total[last] - 1:+.3f}", flush=True)
        if check and n == 0:
            want, _ = _sdpa_reference(*(x.astype(jnp.float32)[None] for x in (q, k, v)), None, True, hs ** -0.5)
            errs = {form: round(rel_err(out, want[0]), 5) for form, out in outs.items()}
            print(f"check latent {name} T {T}: relative error against the float32 reference {errs}; the forms differ by "
                  f"{max(rel_err(a, outs[first]) for a in outs.values()):.6f}", flush=True)
            if max(errs.values()) > 0.02:
                sys.exit("flash_tune: a latent form disagrees with the reference")


def fit():
    """``pallasex._FLASH_STEP_US`` and ``_FLASH_KPAIR_NS``: us a head of ``_flash_fwd`` at Trinity-Mini's layer
    against its schedule's steps and listed pairs, every length in blocks of 256, 512 and 1024, by least
    squares on the relative error (a short call counts as a long one)."""
    H, G, hs, windows, _, buckets = SERVE["trinity-mini"]
    rows = []
    for T in sorted(buckets + FIT_LENGTHS):
        q, k, v, _ = (x.reshape(1, -1, T, hs) for x in operands(1, H, G, T, hs))
        for window in windows:
            for block in (256, 512, 1024):
                kernel, _, schedule = flash_ms(q, k, v, window, f"{block}x{block}")
                us = kernel["_flash_fwd"] * 1e3 / H
                rows.append((schedule["grid_steps"], schedule["grid_steps"] * block * block / 1e3, us))
                print(f"fit T {T:5d} window {window} block {block:4d}: steps {rows[-1][0]:4d}, {rows[-1][1] / 1e3:7.2f} M pairs"
                      f" listed, {us:8.2f} us a head", flush=True)
    steps, kpairs, us = np.array(rows).T
    (a, b), *_ = np.linalg.lstsq(np.stack([steps / us, kpairs / us], 1), np.ones_like(us), rcond=None)
    off = (steps * a + kpairs * b) / us - 1
    print(f"fit: a grid step {a:.3f} us, a thousand listed pairs {b * 1e3:.3f} ns; the model is off by"
          f" {np.abs(off).mean():.3f} in the mean, {off.min():+.3f} to {off.max():+.3f}", flush=True)


def check():
    """Compiled kernels against the float32 reference, both cells' kinds, at T 2048 and at T 2304 with a
    ragged last block (the derived 512, and 1024)."""
    worst = 0.0
    for (name, (B, H, G, _, hs, window)), (T, blocks) in (
            (s, c) for s in SHAPES.items() for c in ((2048, ""), (2304, ""), (2304, "1024x1024"))):
        set_blocks(blocks)
        window = None if window is None else 1024
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
            print(f"check {name:8s} T {T} {what:3s} relative error {err:.5f}   schedule {px.flash_schedule}", flush=True)
        # the form the byte rule did not take, against the one it took
        took = px.flash_schedule["bwd_form"]
        other = next(f for f in BWD_FORMS if f != took)
        flat = lambda x: x.reshape(-1, T, hs)   # noqa: E731
        pair = px._flash_bwd(*(flat(x) for x in (g, q, k, v, out)), lse.reshape(-1, 1, T), None, True, scale, H, G, None, 1,
                             window, form=other)
        for what, a, b in zip(("dq", "dk", "dv"), got[1:], pair):
            a, b = flat(a).astype(jnp.float32), b.astype(jnp.float32)
            err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            worst = max(worst, err)
            differ = int(jnp.sum(a != b))
            print(f"check {name:8s} T {T} {what:3s} {took} against {other}: {'they differ' if differ else 'the same bits'},"
                  f" relative {err:.2e}, {differ} of {a.size} elements", flush=True)
    set_blocks()
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,hybrid")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--serve", action="store_true", help="the forward kernel at the serve cells' prefill buckets")
    ap.add_argument("--cells", default=",".join(SERVE), help="which of them")
    ap.add_argument("--fit", action="store_true", help="the two constants of _flash_blocks' model")
    ap.add_argument("--latent", action="store_true", help="a latent prompt's call, keys and values at their own widths or padded")
    ap.add_argument("--window", type=int, help="another window for the mistral shape (to place _flash_blocks' rule)")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"flash_tune: times the kernels on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    if args.window:
        SHAPES["mistral"] = (*SHAPES["mistral"][:5], args.window)
    if args.latent:
        for name in LATENT:
            time_latent(name, args.check)
        return
    if args.check and check() > 0.02:   # bfloat16 operands: 0.003-0.006
        sys.exit("flash_tune: the compiled kernels disagree with the reference")
    if args.fit:
        fit()
    if args.serve:
        for name in args.cells.split(","):
            time_serve(name)
    if args.fit or args.serve:
        return
    failed = []
    for blocks in args.blocks.split(",") if args.blocks else [""]:
        set_blocks(blocks)
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
