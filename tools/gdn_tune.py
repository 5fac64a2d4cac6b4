"""Time the two kernels of the chunked gated delta rule alone on the chip, at the hybrid cell's shape.

    python3 tools/gdn_tune.py [--check] [--serve]

``--serve`` times what a server runs instead, at Olmo-Hybrid's heads (30 of 96
and 192): ``gdn_chunk_fwd`` from a state to a state at every prefill bucket
(T 1024, 2048, 2560, one sequence) and ``gdn_decode_step`` on a 33-slot,
12-layer arena as the pool lays it out (a row's heads side by side, ``(96,
5760)``) at 32 rows, its ms a call with the GB/s of the state's bytes as
counted and as the chip's tiles hold them; with ``--check`` both against the
float32 recurrence first, and the step against ``gdn_step_math`` a head at a
time, bit for bit.  One line: ms a call of ``gdn_chunk_fwd`` and ``gdn_chunk_bwd`` by name from a
device trace of five forward and five backward calls (B 2, 16 key and 32 value
heads of 128, T 8192, bfloat16), of whatever else XLA runs beside them (the
cumulative log-decay, the sum over the heads of a key head), and
``pallasex.gdn_schedule``.  ``--check`` first compares o, the saved states and
the five gradients with the float32 recurrence, token by token, at T 1024
(compiled kernels, not the interpreter; bfloat16 and float32 operands).  Needs
a TPU; exits non-zero without one, or if the check fails."""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp

from thunder_tpu._platform import device_info
from thunder_tpu.core.prims import GDN_CHUNK, gdn_state_stride
from thunder_tpu.executors import pallasex as px
from thunder_tpu.serving.kv_pool import pack_state_heads, tiled_bytes, unpack_state_heads
from tools.flash_tune import REPS, kernel_ms

# B, Hk, Hv, T, dk, dv: the two sequences of the hybrid cell's DeltaNet layers
SHAPE = (2, 16, 32, 8192, 128, 128)


def operands(B, Hk, Hv, T, dk, dv, dtype=jnp.bfloat16):
    """Unit keys, queries scaled as the model's, a decay of about 0.6 a token."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)   # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, Hk, T, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, Hk, T, dk)))
    v, do = (jax.random.normal(key, (B, Hv, T, dv)) for key in ks[2:4])
    g = -jax.nn.softplus(jax.random.normal(ks[4], (B, Hv, T)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, Hv, T)))
    return do.astype(dtype), q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def recurrence(q, k, v, g, beta):
    """The rule token by token in float32: o and the state before every token."""
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def head(q, k, v, g, b):
        def step(S, x):
            qt, kt, vt, gt, bt = x
            S0 = S
            S = S * jnp.exp(gt)
            S = S + jnp.outer(kt, (vt - S.T @ kt) * bt)
            return S, (S.T @ qt, S0)
        return jax.lax.scan(step, jnp.zeros((k.shape[-1], v.shape[-1])), (q, k, v, g, b))[1]

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def check():
    worst = 0.0
    T = 1024
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))   # noqa: E731
    for dtype in (jnp.bfloat16, jnp.float32):
        do, *ops = operands(1, 4, 8, T, *SHAPE[4:], dtype=dtype)
        o, states = px.gdn_chunk(*ops)
        got = (o, states, *px.gdn_chunk_backward(do, *ops, states))
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in ops]
            oref, every = recurrence(*f32)
            grads = jax.grad(lambda *a: jnp.sum(recurrence(*a)[0] * do.astype(jnp.float32)), argnums=(0, 1, 2, 3, 4))(*f32)
        want = (oref, every[:, :, ::gdn_state_stride(T)], *grads)
        for what, a, b in zip(("o", "states", "dq", "dk", "dv", "dg", "dbeta"), got, want):
            worst = max(worst, rel(a, b))
            print(f"check {jnp.dtype(dtype).name:8s} {what:6s} relative error {rel(a, b):.6f}", flush=True)
    return worst


# Hk, Hv, dk, dv, rows, arena slots, arena layers, prefill buckets: the serving cell's linear-attention layers
SERVE = (30, 30, 96, 192, 32, 33, 12, (1024, 2048, 2560))


def recurrence_from(q, k, v, g, beta, h0):
    """o and the last state from ``h0``, token by token in float32."""
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def head(q, k, v, g, b, S0):
        def step(S, x):
            qt, kt, vt, gt, bt = x
            S = S * jnp.exp(gt)
            S = S + jnp.outer(kt, (vt - S.T @ kt) * bt)
            return S, S.T @ qt
        return jax.lax.scan(step, S0, (q, k, v, g, b))

    last, o = jax.vmap(jax.vmap(head))(q, k, v, g, beta, h0)
    return o, last


def serve(check: bool):
    Hk, Hv, dk, dv, rows, slots, layers, buckets = SERVE
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))   # noqa: E731
    key = jax.random.PRNGKey(1)
    heads = jax.random.normal(key, (slots, layers, Hv, dk, dv)) * 0.1     # a matrix a head, as the dense cache keeps it
    arena = pack_state_heads(heads)                                       # (slots, layers, dk, Hv dv): the pool's
    table = jnp.arange(1, rows + 1, dtype=jnp.int32)
    if check:
        _, q, k, v, g, beta = operands(1, Hk, Hv, 1024, dk, dv)
        beta, h0 = 2.0 * beta, heads[1:2, 0]
        o, last = px.gdn_chunk_state(q, k, v, g, beta, h0)
        with jax.default_matmul_precision("highest"):
            want = recurrence_from(*(x.astype(jnp.float32) for x in (q, k, v, g, beta)), h0)
            tok = [x[:, :, 0] for x in operands(rows, Hk, Hv, 1, dk, dv)[1:]]
            tok[4] = 2.0 * tok[4]
            so, sa = px.gdn_decode_step(arena, table, *tok, layer=3)
            sa = unpack_state_heads(sa, Hv)
            swant = recurrence_from(*(x.astype(jnp.float32)[:, :, None] for x in tok), heads[table, 3])
        worst = max(rel(o, want[0]), rel(last, want[1]), rel(so, swant[0][:, :, 0]), rel(sa[table, 3], swant[1]))
        print(f"check serve: scan o {rel(o, want[0]):.6f} last state {rel(last, want[1]):.6f}; "
              f"step o {rel(so, swant[0][:, :, 0]):.6f} state {rel(sa[table, 3], swant[1]):.6f}", flush=True)
        if worst > 0.02:
            sys.exit("gdn_tune: the compiled serving kernels disagree with the recurrence")
        # the bits: a head alone is a group of the whole width, which is ``gdn_step_math`` on its (dk, dv) tile
        # as Mosaic compiles it; side by side with its neighbours a head has to read the same.  Beside it XLA's
        # own compile of the same function (the dense cache's step), which may sum along dk in another order.
        q_, k_, v_, g_, b_ = tok
        alone = [px.gdn_decode_step(heads[:, :, h], table, q_[:, h:h + 1], k_[:, h:h + 1], v_[:, h:h + 1], g_[:, h:h + 1],
                                    b_[:, h:h + 1], layer=3) for h in range(Hv)]
        same_o = bool(jnp.array_equal(so, jnp.concatenate([a[0] for a in alone], axis=1)))
        same_s = bool(jnp.array_equal(sa, jnp.stack([a[1] for a in alone], axis=2)))
        f32 = jnp.float32
        col = lambda a: a.astype(f32)[..., None]  # noqa: E731
        row = lambda a: jnp.broadcast_to(a.astype(f32)[..., None, None], (rows, Hv, 1, dv))  # noqa: E731
        xo, xs = jax.jit(jax.vmap(jax.vmap(px.gdn_step_math)))(
            heads[table, 3], col(k_), col(q_), v_.astype(f32)[:, :, None], row(jnp.exp(g_)), row(b_))
        print(f"check serve: step against gdn_step_math a head at a time: o the same bits {same_o}, state the same bits "
              f"{same_s}; against XLA's compile of it: o {bool(jnp.array_equal(so, xo[:, :, 0].astype(so.dtype)))} "
              f"state {bool(jnp.array_equal(sa[table, 3], xs))} (largest difference "
              f"{float(jnp.max(jnp.abs(sa[table, 3] - xs))):.3g})", flush=True)
        if not (same_o and same_s):
            sys.exit("gdn_tune: a head beside its neighbours does not keep the bits it has alone")
    for T in buckets:
        _, *ops = operands(1, Hk, Hv, T, dk, dv)
        fwd = jax.jit(px.gdn_chunk_state)
        jax.block_until_ready(fwd(*ops, arena[1:2, 0]))
        ms = kernel_ms(lambda: jax.block_until_ready(fwd(*ops, arena[1:2, 0])), REPS)
        own = sum(t for n, t in ms.items() if n.startswith("gdn_chunk_fwd"))
        print(f"serve T {T}: gdn_chunk_fwd {own:7.3f} ms a call; beside it {sum(ms.values()) - own:.3f}", flush=True)
    tok = [x[:, :, 0] for x in operands(rows, Hk, Hv, 1, dk, dv)[1:]]
    step = jax.jit(lambda a, *t: px.gdn_decode_step(a, table, *t, layer=3), donate_argnums=(0,))
    box = [arena]

    def once():
        _, box[0] = jax.block_until_ready(step(box[0], *tok))

    once()
    ms = kernel_ms(once, REPS)
    own = sum(t for n, t in ms.items() if n.startswith("gdn_decode_step"))
    counted = rows * 2 * Hv * dk * dv * 4
    laid_out = rows * 2 * tiled_bytes(arena.shape[2:], arena.dtype)
    print(f"serve step, {rows} rows, a row {arena.shape[2:]}: gdn_decode_step {own:7.3f} ms a call ({counted / own / 1e6:.1f} "
          f"GB/s of state as counted, {laid_out / own / 1e6:.1f} as laid out: {laid_out / counted:.3f} of the count); "
          f"beside it {sum(ms.values()) - own:.3f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--serve", action="store_true")
    args = ap.parse_args()
    device = device_info()
    if device["platform"] != "tpu":
        sys.exit(f"gdn_tune: times the kernels on a device and needs a TPU; jax found "
                 f"{device['platform']!r} ({device['kind']}).  Nothing was measured.")
    print(device, flush=True)
    if args.serve:
        return serve(args.check)
    if args.check and check() > 0.02:   # bfloat16 operands: a few thousandths
        sys.exit("gdn_tune: the compiled kernels disagree with the recurrence")
    do, *ops = operands(*SHAPE)
    fwd = jax.jit(lambda *a: px.gdn_chunk(*a, GDN_CHUNK))
    bwd = jax.jit(lambda *a: px.gdn_chunk_backward(*a, GDN_CHUNK))
    _, states = jax.block_until_ready(fwd(*ops))
    jax.block_until_ready(bwd(do, *ops, states))
    ms = kernel_ms(lambda: jax.block_until_ready((fwd(*ops), bwd(do, *ops, states))), REPS)
    two = [sum(t for n, t in ms.items() if n.startswith(name)) for name in ("gdn_chunk_fwd", "gdn_chunk_bwd")]
    rest = {n: t for n, t in ms.items() if not n.startswith("gdn_chunk")}
    beside = ", ".join(f"{n} {t:.3f}" for n, t in sorted(rest.items(), key=lambda kv: -kv[1])[:5])
    print(f"T {SHAPE[3]}: gdn_chunk_fwd {two[0]:7.3f}  gdn_chunk_bwd {two[1]:7.3f}  sum {sum(two):7.3f} ms a call;"
          f"  beside them {sum(rest.values()):.3f}: {beside}   schedule {px.gdn_schedule}", flush=True)


if __name__ == "__main__":
    main()
