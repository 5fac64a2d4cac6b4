"""Every serving Pallas kernel against the jnp program it replaces.

The engine-level differential tests (served tokens with the kernels == with
their XLA forms == solo ``generate()``) run the kernels under the Pallas
interpreter, which accepts block shapes and ops the TPU compiler refuses and
proves nothing about what the compiled kernel computes.  :func:`run_checks`
calls each kernel directly on random arenas and compares with the dense
cache's own building blocks (``kv_pool.gather_dense`` / ``scatter_token`` /
``scatter_blocks``, their ``quant`` twins, ``generate._lora_delta``), so the
same code is the CPU test (interpreted, tiny shapes) and the on-chip check
(``chip_smoke.py``, Mistral-7B widths).  The two attention entries' XLA form
(``pallasex.paged_attn_xla``: what they take where Pallas is off) is held to
the same reference in a row of its own beside each kernel's.

Tolerances, by kind of check:

- ``attn`` (decode / verify): the kernel multiplies at the compute dtype
  with float32 accumulation and rounds the probabilities to the compute
  dtype before the value product, then rounds the output; the reference is
  float32 throughout on the same stored bytes.  Two roundings of relative
  size ``eps`` on values of magnitude <= ~4 (unit-normal V): ``8 * eps``
  absolute, i.e. 6e-2 for bfloat16 and 1e-6 for float32 (measured on the
  v5e at Mistral-7B widths: 8e-3 decode, 1.3e-2 verify).
- ``write`` (token / chunk, unquantized): a copy; stored bytes must be equal.
- ``write_q`` (fused quantize-on-write): same ops as ``quantize_kv``, but the
  division is the kernel's, and its last bit can move a value across a
  rounding boundary.  At most 1e-3 of the stored bytes may differ, scales
  must agree to 1e-6 relative.
- ``lora``: both sides round each product to the compute dtype; they may
  differ by one rounding, ``2 * eps`` relative to the largest delta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.executors import pallasex as px
from thunder_tpu.models.generate import _lora_delta
from thunder_tpu.serving.kv_pool import (
    dest_for_pos,
    gather_dense,
    scatter_blocks,
    scatter_token,
)
from thunder_tpu.serving.quant import (
    gather_dense_q,
    quantize_kv,
    scatter_blocks_q,
    scatter_token_q,
)

__all__ = ["run_checks"]


def _ref_attend(q, kd, vd, fresh_k, fresh_v, pos, window):
    """float32 attention of ``q`` (B, nh, T, hs) at positions ``pos + t``
    over one layer's gathered dense cache ``kd``/``vd`` (B, ng, cap, hs; slots
    ``< pos`` are the committed prefix) plus the chunk's own ``fresh`` keys
    (B, ng, T, hs), causally — the mask the kernels fuse."""
    B, nh, T, hs = q.shape
    ng, cap = kd.shape[1], kd.shape[2]
    f32 = jnp.float32
    k = jnp.concatenate([kd, fresh_k], axis=2).astype(f32)        # (B, ng, cap+T, hs)
    v = jnp.concatenate([vd, fresh_v], axis=2).astype(f32)
    k, v = (jnp.repeat(x, nh // ng, axis=1) for x in (k, v))
    s = jnp.einsum("bhtd,bhkd->bhtk", q.astype(f32), k) / np.sqrt(hs)
    t = jnp.arange(T)
    kpos = jnp.concatenate([                                      # (B, cap+T) global key positions
        jnp.broadcast_to(jnp.arange(cap), (B, cap)), pos[:, None] + t[None, :]], axis=1)
    old = jnp.arange(cap + T)[None, :] < cap
    qpos = pos[:, None] + t[None, :]                              # (B, T)
    keep = jnp.where(old[:, None, :], kpos[:, None, :] < pos[:, None, None],
                     kpos[:, None, :] <= qpos[:, :, None])
    if window is not None:
        keep = keep & (kpos[:, None, :] > qpos[:, :, None] - window)
    s = jnp.where(keep[:, None], s, -jnp.inf)
    return jnp.einsum("bhtk,bhkd->bhtd", jax.nn.softmax(s, axis=-1), v)


def _eps(dtype) -> float:
    return float(jnp.finfo(dtype).eps)


def _mismatch(a, b) -> float:
    return float(jnp.mean((a != b).astype(jnp.float32)))


def _rel(a, b) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def run_checks(*, n_head, n_query_groups, head_size, block_size, n_layer=2,
               batch=4, table_width=6, chunk=None, dtype=jnp.bfloat16,
               window=None, seed=0) -> list[dict]:
    """Runs every kernel once at the given widths; returns one row per check:
    ``{"kernel", "err", "tol", "ok"}``.  ``window`` adds the sliding-window
    decode case; ``chunk`` is the verify / chunked-prefill query width
    (default: two blocks)."""
    nh, ng, hs, bs, L, B, nbb = (n_head, n_query_groups, head_size, block_size,
                                 n_layer, batch, table_width)
    T = chunk if chunk is not None else 2 * bs
    nb = 1 + B * nbb                                   # block 0 is the sink
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    rnd = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)
    layer = L - 1
    eps = _eps(dtype)
    rows: list[dict] = []

    def row(kernel, err, tol):
        rows.append({"kernel": kernel, "err": float(err), "tol": float(tol),
                     "ok": bool(err <= tol)})

    # each request owns a disjoint run of blocks; request i holds pos[i] tokens
    tables = (1 + jnp.arange(B * nbb, dtype=jnp.int32)).reshape(B, nbb)
    cap = nbb * bs
    pos = jnp.asarray(np.linspace(1, cap - T - 1, B).astype(np.int32))
    k_arena, v_arena = rnd(nb, L, ng, bs, hs), rnd(nb, L, ng, bs, hs)
    stores = {"": (k_arena, v_arena, None, None)}
    for name, storage in (("int8", jnp.int8), ("fp8", jnp.float8_e4m3fn)):
        (kq, ks), (vq, vs) = quantize_kv(k_arena, storage), quantize_kv(v_arena, storage)
        stores[name] = (kq, vq, ks, vs)

    def dense(store):
        ka, va, ks, vs = stores[store]
        if ks is None:
            kd, vd = gather_dense(ka, va, tables)
        else:
            kd, vd = gather_dense_q(ka, va, ks, vs, tables, dtype)
        return kd[layer], vd[layer]                    # (B, ng, cap, hs)

    # attention: decode (T = 1) per storage and window, verify (T) per storage
    q1, fk1, fv1 = rnd(B, nh, hs), rnd(B, ng, hs), rnd(B, ng, hs)
    qT, fkT, fvT = rnd(B, nh, T, hs), rnd(B, ng, T, hs), rnd(B, ng, T, hs)
    cases = [("", None), ("int8", None), ("fp8", None)]
    if window is not None:
        cases.append(("", window))
    for store, w in cases:
        ka, va, ks, vs = stores[store]
        kd, vd = dense(store)
        xla = jax.jit(functools.partial(px.paged_attn_xla, layer=layer, window=w))
        got = jax.jit(functools.partial(px.paged_attn_decode, layer=layer, window=w))(
            q1, ka, va, fk1, fv1, tables, pos, k_scale=ks, v_scale=vs)
        ref = _ref_attend(q1[:, :, None], kd, vd, fk1[:, :, None], fv1[:, :, None], pos, w)
        tag = "_".join(x for x in (store, "window" if w else "") if x)
        tag = "/" + tag if tag else ""
        row(f"paged_attn_decode{tag}", jnp.max(jnp.abs(got.astype(jnp.float32) - ref[:, :, 0])), 8 * eps)
        got = xla(q1[:, :, None], ka, va, fk1[:, :, None], fv1[:, :, None], tables, pos, k_scale=ks, v_scale=vs)
        row(f"paged_attn_xla/decode{tag}", jnp.max(jnp.abs(got.astype(jnp.float32) - ref)), 8 * eps)
        if w is None:
            got = jax.jit(functools.partial(px.paged_attn_verify, layer=layer))(
                qT, ka, va, fkT, fvT, tables, pos, k_scale=ks, v_scale=vs)
            ref = _ref_attend(qT, kd, vd, fkT, fvT, pos, None)
            row(f"paged_attn_verify{tag}", jnp.max(jnp.abs(got.astype(jnp.float32) - ref)), 8 * eps)
            got = xla(qT, ka, va, fkT, fvT, tables, pos, k_scale=ks, v_scale=vs)
            row(f"paged_attn_xla/verify{tag}", jnp.max(jnp.abs(got.astype(jnp.float32) - ref)), 8 * eps)

    # token writes: plain, keep-masked at a chunk offset, rank-4 scale arena
    vals = rnd(B, L, ng, hs)
    live = jnp.ones((B,), bool)
    blk, slot = dest_for_pos(tables, pos, live, block_size=bs)
    write = jax.jit(functools.partial(px.paged_token_write, block_size=bs))
    row("paged_token_write",
        _mismatch(write(k_arena, vals, tables, pos), scatter_token(k_arena, vals, blk, slot)), 0)
    svals = jax.random.uniform(next(keys), (B, L, ng), jnp.float32)
    ks = stores["int8"][2]
    row("paged_token_write/scales",
        _mismatch(write(ks, svals, tables, pos), ks.at[blk, :, :, slot].set(svals)), 0)
    n_emit = jnp.asarray((np.arange(B) % 3).astype(np.int32))     # some rows rejected
    mblk, mslot = dest_for_pos(tables, pos + 1, 1 < n_emit, block_size=bs)
    got = jax.jit(functools.partial(px.paged_token_write, block_size=bs, offset=1))(
        k_arena, vals, tables, pos, n_emit=n_emit)
    row("paged_token_write/masked",
        _mismatch(got[1:], scatter_token(k_arena, vals, mblk, mslot)[1:]), 0)  # sink excluded

    # fused quantize-on-write, token and chunk
    dest = tables[0]
    cpos = jnp.asarray([bs], jnp.int32)                            # block-aligned chunk start
    nc = T // bs
    chunk_vals = rnd(nc, L, ng, bs, hs)
    chunk_dest = jnp.zeros((nbb,), jnp.int32).at[1:1 + nc].set(dest[1:1 + nc])
    chunk_dense = chunk_vals.transpose(1, 2, 0, 3, 4).reshape(L, 1, ng, nc * bs, hs)
    chunk_table = chunk_dest[1:1 + nc]
    row("paged_chunk_write",
        _mismatch(
            jax.jit(functools.partial(px.paged_chunk_write, block_size=bs))(
                k_arena, chunk_vals, chunk_dest, cpos),
            scatter_blocks(k_arena, chunk_dense, chunk_table)), 0)
    for name in ("int8", "fp8"):
        kq, _, ks, _ = stores[name]
        ga, gs = jax.jit(functools.partial(px.paged_token_write_fused, block_size=bs))(
            kq, ks, vals, tables, pos)
        ra, rs = scatter_token_q(kq, ks, vals, blk, slot)
        row(f"paged_token_write_fused/{name}", _mismatch(ga, ra), 1e-3)
        row(f"paged_token_write_fused/{name}/scales", _rel(gs, rs), 1e-6)
        ga, gs, ge = jax.jit(functools.partial(px.paged_chunk_write_fused, block_size=bs))(
            kq, ks, chunk_vals, chunk_dest, cpos)
        ra, rs, rerr = scatter_blocks_q(kq, ks, chunk_dense, chunk_table)
        row(f"paged_chunk_write_fused/{name}", _mismatch(ga, ra), 1e-3)
        row(f"paged_chunk_write_fused/{name}/scales", _rel(gs, rs), 1e-6)
        gerr = jnp.sum(ge[..., 0]) / (jnp.sum(ge[..., 1]) + 1e-30)
        row(f"paged_chunk_write_fused/{name}/rel_err", abs(float(gerr) - float(rerr)), 1e-3)

    # fused LoRA delta, decode- and chunk-shaped
    C, r = nh * hs, 8
    for t in (1, T):
        x = rnd(B, t, C)
        a = rnd(B, r, C) * 0.05
        b = rnd(B, ng * hs, r) * 0.05
        got = jax.jit(functools.partial(px.lora_delta_fused, scaling=2.0))(x, a, b)
        row(f"lora_delta_fused/T{t}", _rel(got, _lora_delta(x, a, b, 2.0)), 2 * eps)
    return rows
