"""Time the expert share's grouped product alone on the chip, at the shapes the cells run.

    python3 tools/moe_tune.py [--check] [--shapes lfm2_decode,lfm2_prefill] [--vmem-mib 32,48]
    python3 tools/moe_tune.py --glue [--check] [--shapes lfm2_decode,lfm2_prefill]

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
it is on the line).  Since PR 60 a prompt's and the trainer's products copy
their own weights a group ahead (``gmm_schedule["weights_ahead"]``), and such a
shape is timed in both forms, the ``BlockSpec`` form (the kernel before PR 60,
which a decode step keeps) on a line of its own: ``bytes`` (the weights of the
groups hit, the used rows and the product once, at the HBM's peak), ``products``
(the used rows' at the MXU's peak) and the call, all in ms, so the bare piece is
the call less the larger of the two; the shapes then also hold a prompt of
``xing4-serve-1chip.offline-digest`` (8,192 tokens, 64 experts of ``3584 x
1024``, top-4), ``trinity-mini-serve-1chip.offline-docqa`` (9,984; 16 of 128 of
``2048 x 1024``, top-8), ``smallthinker-serve-1chip.offline-mixedlen`` (5,120;
64 of ``2560 x 768``, top-6) and ``nemotron3super-serve-1chip.offline-rollouts``
(3,584; 128 of 512 at the latent width, ``1024 x 2688``, top-22).  To compare
other kernels, put each variant in a tree of its own under ``_checkout/`` with
this file in it and run the tool in each, all in one call.  ``--check`` first
compares the compiled kernel with ``lax.ragged_dot`` at every shape, and the two
forms with each other bit for bit.  The check's and the timings' lines are
also kept in ``chiprun_out/moe_tune.txt`` (a call shows only the end of a long
output).

``--glue`` times the share outside its kernel instead, a part a line under the
skewed routing, the form before PR 43 (kept below) beside ``jaxex``'s: the
``plan`` (the sort and the first wave's ``row_src``; the new one with the
rows' weights, and with ``pos`` where the shapes gather by it), the ``dispatch``
(rows gathered by ``row_src``; before, the weights too, and both masked), the
``swiglu`` pass over the wave's rows (one form), the ``combine`` (a scatter-add
of the wave's rows before; then the XLA form the shapes choose, named on the
shape's first line, and ``moe_combine`` side by side, the kernel with the GB/s
of its one pass: the rows routed read and the token rows written once; where
``jaxex`` leaves the call to XLA, a decode step's, the kernel is timed all the
same), and the whole ``_moe_share`` with the part of it that is not
``moe_grouped_mm*``.  ``--glue`` also knows a prompt and a decode step of
``xing4-serve-1chip.offline-digest`` and Trinity-Mini's longest prompt.
``--check`` there holds both to the scatter-add's bits, the kernel's 16-bit sum
(the rows' gradient) with them.  Needs a TPU; exits non-zero without one, or if
a check fails."""
import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common
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
    "xing4_prefill": dict(tokens=8192, k=4, held=64, total=64, C=3584, I=1024),
    "trinity_prefill": dict(tokens=9984, k=8, held=16, total=128, C=2048, I=1024),
    "smallthinker_prefill": dict(tokens=5120, k=6, held=64, total=64, C=2560, I=768),
    "nemotron_prefill": dict(tokens=3584, k=22, held=128, total=512, C=1024, I=2688),
}
# what --glue times besides
GLUE_SHAPES = {
    "xing4_decode": dict(tokens=32, k=4, held=64, total=64, C=3584, I=1024),
    # where XLA's gather by pos and the kernel cross (jaxex._tokens_of_rows' rule): LFM2's longest bucket, Xing4's shortest
    "lfm2_prefill_3k": dict(tokens=3072, k=4, held=32, total=32, C=2048, I=1792),
    "xing4_prefill_5k": dict(tokens=5120, k=4, held=64, total=64, C=3584, I=1024),
}

KEPT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out", "moe_tune.txt")


def say(line: str):
    """A line of the kernel's own timings: printed, and kept where the chip tool brings it back whole."""
    print(line, flush=True)
    os.makedirs(os.path.dirname(KEPT), exist_ok=True)
    with open(KEPT, "a") as f:
        f.write(line + "\n")


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
    idx = jnp.asarray(routing(tokens, k, held, total, skew))
    plan = jaxex.moe_plan(idx, jnp.ones(idx.shape, jnp.float32), 0, held, tile, wave_tiles)
    out = []
    for w in range(plan["tile_group"].shape[0] // wave_tiles):
        *_, tg, used = jaxex.moe_wave_rows(plan, w, tile, wave_tiles)
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
            bits = ""
            if px.gmm_schedule.get("weights_ahead"):
                with blockspec_form():
                    same = bool(jnp.all(px.grouped_mm(x, w, tg, used, t) == got))
                bits = f"  the BlockSpec form's bits: {same}"
                worst = worst if same else float("inf")
            say(f"check {name:18s} {product:4s} relative error {err:.6f}{bits}")
    return worst


@contextlib.contextmanager
def blockspec_form():
    """``moe_grouped_mm`` as it was before PR 60 at every shape: the weights a ``BlockSpec`` operand."""
    blocks = px._gmm_blocks
    px._gmm_blocks = lambda *a: {**blocks(*a), "weights_ahead": 0}
    px._moe_grouped_mm.clear_cache()
    try:
        yield
    finally:
        px._gmm_blocks = blocks
        px._moe_grouped_mm.clear_cache()


def time_shape(name, skew: bool, device_kind: str):
    from tools.flash_tune import kernel_ms

    shape = SHAPES[name]
    ws, tile, cnt = waves(skew=skew, **shape)
    t = bool(shape.get("transposed"))
    peak = common.peaks(device_kind)
    flops_s, bytes_s = peak["bf16_flops_per_sec"], peak["hbm_bytes_per_sec"]
    for product in ("fc", "proj"):
        x, w, (K, N) = operands(product=product, rows=ws[0][0].shape[0] * tile, **shape)
        used = sum(int(u[0]) for _, u in ws)
        groups = int((cnt > 0).sum())
        least = (groups * K * N + used * tile * (K + N)) * x.dtype.itemsize
        flops = 2 * used * tile * K * N

        def line():
            call = jax.jit(lambda x_, w_, tg, used_: px.grouped_mm(x_, w_, tg, used_, t))
            run = lambda: jax.block_until_ready([call(x, w, tg, used_) for tg, used_ in ws])   # noqa: E731, B023
            run()
            ms = kernel_ms(run, REPS)
            own = sum(v for n, v in ms.items() if n.startswith("moe_grouped_mm"))
            schedule = dict(px.gmm_schedule)
            form = "ahead" if schedule.get("weights_ahead") else "blockspec"
            say(f"{name:20s} {product:4s} {'skew' if skew else 'even'} {form:9s} bytes {least / bytes_s * 1e3:7.3f}  products "
                f"{flops / flops_s * 1e3:7.3f}  call {own:7.3f} ms  {least / own / 1e6:6.1f} GB/s {flops / own / 1e9:6.1f} TF/s  "
                f"rows x ({K}, {N}) in tiles of {tile}: {used} of {len(ws)} x {ws[0][0].shape[0]} tiles used, at most "
                f"{int(-(-cnt.max() // tile))} a group ({groups} groups)  beside it {sum(ms.values()) - own:.3f}  {schedule}")
            return schedule

        if line().get("weights_ahead"):
            with blockspec_form():
                line()


# ---- the share outside its kernel (--glue) ----------------------------------------------

def _plan_before(top_idx, held, tile, wave_tiles):
    """``moe_plan`` and the first wave's ``moe_wave_rows`` as they stood before PR 43."""
    N, k = top_idx.shape
    A, i32 = N * k, jnp.int32
    wave = wave_tiles * tile
    R = -(-(A + held * (tile - 1)) // wave) * wave
    e = top_idx.reshape(A).astype(i32)
    key = jnp.where((e >= 0) & (e < held), e, held)
    skey, order = jax.lax.sort((key, jnp.arange(A, dtype=i32)), num_keys=1, is_stable=True)
    off = jnp.searchsorted(skey, jnp.arange(held + 1, dtype=i32)).astype(i32)
    cnt = off[1:] - off[:-1]
    padded = -(-cnt // tile) * tile
    pend = jnp.cumsum(padded)
    tile_group = jnp.minimum(jnp.searchsorted(pend, jnp.arange(R // tile, dtype=i32) * tile, side="right"), held - 1).astype(i32)
    tg = tile_group[:wave_tiles]
    used = jnp.clip((pend[-1] // tile).astype(i32), 0, wave_tiles)
    t = jnp.arange(wave_tiles, dtype=i32)
    within = (t * tile - (pend - padded)[tg])[:, None] + jnp.arange(tile, dtype=i32)[None, :]
    valid = (t < used)[:, None] & (within < cnt[tg][:, None])
    row_src = jnp.where(valid, order[jnp.clip(off[tg][:, None] + within, 0, A - 1)], -1)
    return row_src.reshape(wave), tg, used


def _dispatch_before(x, top_w, row_src):
    valid, a = row_src >= 0, jnp.maximum(row_src, 0)
    return (jnp.where(valid[:, None], jnp.take(x, a // top_w.shape[1], axis=0), 0),
            jnp.where(valid, jnp.take(top_w.reshape(-1), a), 0))


def _combine_before(yb, row_src, N, k):
    return jnp.zeros((N, yb.shape[1]), jnp.float32).at[jnp.maximum(row_src, 0) // k].add(yb.astype(jnp.float32))


def _ms(fn, *args):
    """ms a call of ``fn`` jitted alone: everything it starts on the device, and its largest operations."""
    from tools.flash_tune import kernel_ms

    call = jax.jit(fn)
    run = lambda: jax.block_until_ready(call(*args))    # noqa: E731
    run()
    ms = kernel_ms(run, REPS)
    return sum(ms.values()), ms


def _xla(fn):
    """``fn`` with ``moe_combine`` out of the way: the XLA form the shapes choose."""
    def run(*a):
        fast, jaxex._tokens_of_rows_fast_path = jaxex._tokens_of_rows_fast_path, None
        try:
            return fn(*a)
        finally:
            jaxex._tokens_of_rows_fast_path = fast
    return run


def glue_shape(name, check_bits: bool) -> bool:
    shape = {**SHAPES, **GLUE_SHAPES}[name]
    N, k, held, total, C, I = (shape[n] for n in ("tokens", "k", "held", "total", "C", "I"))
    tile = shape.get("tile") or moe_row_tile(N * k / total)
    wave_tiles = jaxex.moe_wave_tiles(N * k, held, total, tile)
    R = wave_tiles * tile
    idx = jnp.asarray(routing(N, k, held, total, True))
    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    x, yb = (jax.random.normal(kk, sh).astype(jnp.bfloat16) for kk, sh in zip(keys, ((N, C), (R, C))))
    h1, h2 = (jax.random.normal(kk, (R, I)).astype(jnp.bfloat16) for kk in keys[2:4])
    top_w = jax.random.uniform(keys[4], (N, k), jnp.float32)
    w1, w3 = ((jax.random.normal(kk, (held, C, I)) * 0.05).astype(jnp.bfloat16) for kk in keys[5:7])
    w2 = (jax.random.normal(keys[7], (held, I, C)) * 0.05).astype(jnp.bfloat16)
    static = (N, k, jnp.dtype(jnp.bfloat16), held)
    walked = jaxex._kernel_takes(static, R) and not px.combine_declines(N, R, wave_tiles, C, held, x.dtype, jnp.float32)

    def plan_now(idx_, top_w_):
        return jaxex.moe_wave_rows(jaxex.moe_plan(idx_, top_w_, 0, held, tile, wave_tiles), 0, tile, wave_tiles)

    row_src, pos, row_w, tg, used = jax.jit(plan_now)(idx, top_w)
    routed = int((row_src >= 0).sum())
    form = "scatter-add" if pos is None else "gather by pos"
    print(f"--- {name}: {N} tokens x {k} = {N * k} assignments on {held} of {total}, tiles of {tile}, a wave of {R} rows "
          f"({int(used)} of {wave_tiles} tiles used, {routed} rows routed); in XLA the tokens' rows come back by {form}; "
          f"jaxex gives the call to {'moe_combine' if walked else 'XLA'}", flush=True)
    parts = [
        ("plan", lambda: _ms(lambda i: _plan_before(i, held, tile, wave_tiles), idx), lambda: _ms(plan_now, idx, top_w)),
        ("dispatch", lambda: _ms(_dispatch_before, x, top_w, row_src),
         lambda: _ms(lambda *a: jaxex._dispatch(static, *a), x, top_w, row_src, pos, row_w, tg)),
        ("swiglu", lambda: _ms(lambda a, b, w: jax.nn.silu(a) * b * w[:, None].astype(a.dtype), h1, h2, jnp.ones((R,), jnp.float32)), None),
        ("combine", lambda: _ms(lambda y, r: _combine_before(y, r, N, k), jnp.where((row_src >= 0)[:, None], yb, 0), row_src),
         lambda: _ms(_xla(lambda *a: jaxex._combine(static, *a)), yb, row_src, pos, tg)),
    ]
    for part, before, now in parts:
        b, _ = before()
        n, ops = now() if now else (b, {})
        top = ", ".join(f"{o} {t * 1e3:.0f}" for o, t in sorted(ops.items(), key=lambda kv: -kv[1])[:3])
        print(f"{name:18s} {part:9s} before {b * 1e3:8.1f} us   now {n * 1e3:8.1f} us   [{top}]", flush=True)
    for out in (jnp.float32, jnp.bfloat16):     # the share's sum, and the sum of the rows' gradient
        kern, ops = _ms(lambda y, r, t, out=out: px.combine(y, r, t, N, k, held, out), yb, row_src, tg)
        own = sum(t for o, t in ops.items() if o.startswith("moe_combine"))
        least = routed * C * yb.dtype.itemsize + N * C * jnp.dtype(out).itemsize
        print(f"{name:18s} {'combine':9s} moe_combine to {jnp.dtype(out).name:8s} {kern * 1e3:8.1f} us ({own * 1e3:.1f} its own, the rows' list beside it)   "
              f"{least / own / 1e6:6.1f} GB/s of one pass over {least / 1e6:.1f} MB   {dict(px.combine_schedule)}", flush=True)
    whole, ops = _ms(lambda *a: jaxex._moe_share(*a, 0, total, tile), x, idx, top_w, w1, w3, w2)
    kernel = sum(t for o, t in ops.items() if o.startswith("moe_grouped_mm"))
    print(f"{name:18s} {'share':9s} whole {whole * 1e3:8.1f} us, moe_grouped_mm {kernel * 1e3:8.1f}, beside it {(whole - kernel) * 1e3:8.1f}", flush=True)
    if not check_bits:
        return True
    same = True
    for out in (jnp.float32, jnp.bfloat16):
        want = jax.jit(lambda y, r, out=out: jnp.zeros((N, C), out).at[jnp.maximum(r, 0) // k].add(y.astype(out)))(
            jnp.where((row_src >= 0)[:, None], yb, 0), row_src)
        xla = jax.jit(_xla(lambda y, r, p, t, out=out: jaxex._tokens_of_rows(y, r, p, t, static, out)))(yb, row_src, pos, tg)
        got = jax.jit(lambda y, r, t, out=out: px.combine(y, r, t, N, k, held, out))(yb, row_src, tg)
        bits = (bool(jnp.all(want == xla)), bool(jnp.all(want == got)))
        print(f"check {name:18s} a {jnp.dtype(out).name} sum has the scatter-add's bits: XLA's form {bits[0]}, moe_combine {bits[1]}", flush=True)
        same &= all(bits) if out == jnp.float32 else bits[1] or not bits[0]
    return same


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--glue", action="store_true", help="time the share's parts outside the kernel, the form before PR 43 beside the new")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--vmem-mib", default="", help="comma-separated values of pallasex._gmm_vmem_cap to time beside the device's")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"moe_tune: times the kernel on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    if args.glue and args.shapes == ",".join(SHAPES):
        args.shapes = ",".join([*SHAPES, *GLUE_SHAPES])
    names = [n for n in args.shapes.split(",") if n]
    if args.glue:
        same = [glue_shape(n, args.check) for n in names if not SHAPES.get(n, {}).get("transposed")]
        sys.exit(0 if all(same) else "moe_tune: the combine disagrees with the scatter-add it replaced")
    if args.check and check(names) > 0.01:       # bfloat16 results of a float32 sum: a rounding of the last place
        sys.exit("moe_tune: the compiled kernel disagrees with lax.ragged_dot")
    for mib in [None] + [int(m) for m in args.vmem_mib.split(",") if m]:
        if mib is not None:
            px._gmm_vmem_cap = lambda mib=mib: mib << 20
            px._moe_grouped_mm.clear_cache()
            print(f"_gmm_vmem_cap = {mib} MiB", flush=True)
        for name in names:
            for skew in (False, True):
                time_shape(name, skew, device["kind"])


if __name__ == "__main__":
    main()
