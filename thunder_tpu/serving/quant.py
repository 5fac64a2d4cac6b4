"""Quantized (int8 / fp8-e4m3) KV block storage for the paged serving pool.

Pool capacity is the admission-control bottleneck of the serving subsystem,
and capacity is bytes: every block stored at full ``dtype`` width caps how
many requests can be resident at once.  This module stores the K/V arenas
in a **1-byte storage dtype** — ``int8`` (symmetric absmax) or
``float8_e4m3fn`` (absmax-scaled to the e4m3 dynamic range; ``fp8``) — with
a float32 scale arena at **per-block-slot, per-head** granularity — one
symmetric absmax scale for each ``(block, layer, kv_group, slot)``
coordinate, i.e. an absmax over the ``head_size`` values of one token's K
(or V) for one head:

- ``quantize_kv``: symmetric absmax int8 over the last (``hs``) dim —
  deterministic per token, so a request's stored KV never depends on what
  else shares the batch (the serving bit-exactness contract survives);
- ``scatter_token_q`` / ``scatter_blocks_q``: quantize-on-scatter — the
  exact K/V computed by the step is quantized once at write time (decode
  picks the *freshly computed* values, never a dequantized round trip, so
  there is no requantization drift across steps);
- ``gather_dense_q``: dequant-on-gather back into the dense
  :func:`models.generate.cache_shape` layout ``forward_with_cache``
  consumes, in the pool's compute dtype.

Capacity math: a stored slot-head costs ``hs`` bytes (int8 or fp8) plus 4
bytes of scale instead of ``hs * itemsize`` — ``hs*4 / (hs+4)`` more blocks
per arena byte vs a float32 pool (3.2x at ``hs=16``, 3.76x at ``hs=64``;
``tests/test_serving_invariants.py`` holds the admitted-concurrency multiple
on a live engine).  int8
and fp8 cost identical bytes; they differ only in error shape.

Error model: absmax int8 keeps ~2 decimal digits; expect ~1e-2 relative
error on the stored KV (the ``serving.kv_quant.rel_err`` gauge reports the
measured value per prefill).  fp8 e4m3 has 3 mantissa bits (~3e-2 relative
per element) but a sign-magnitude float grid, so small-magnitude values
keep relative precision where int8's uniform grid loses them.  Greedy
tokens match the full-precision cache whenever logit margins exceed that
noise — the tiny-llama greedy differential tests assert exact argmax-token
parity for both storage dtypes.

In mesh mode the scale arenas shard by the same
``distributed.kv_cache_spec`` rule as the data arenas (heads dim at axis 2
in both layouts), so no new placement rule is introduced.
"""
from __future__ import annotations

import jax.numpy as jnp

from thunder_tpu.models.generate import kv_block_shape

__all__ = [
    "resolve_kv_dtype",
    "is_quantized_kv",
    "quantize_kv",
    "dequantize_kv",
    "gather_dense_q",
    "scatter_token_q",
    "scatter_blocks_q",
    "arena_block_bytes",
    "blocks_for_arena_bytes",
]

_SINK = 0  # kv_pool.SINK_BLOCK (not imported: kv_pool imports this module)

# fp8 storage is gated on the jax build actually shipping the dtype (the
# ml_dtypes extended-float set); older builds fall back to a clear error
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)
_FP8_ALIASES = ("fp8", "e4m3", "float8_e4m3fn")


def _qmax(storage) -> float:
    """Largest representable magnitude of a quantized storage dtype — the
    absmax scale divisor (127 for int8, 448 for fp8 e4m3)."""
    storage = jnp.dtype(storage)
    if storage == jnp.dtype(jnp.int8):
        return 127.0
    return float(jnp.finfo(storage).max)          # 448.0 for e4m3fn


def resolve_kv_dtype(kv_dtype, dtype):
    """Storage dtype of the block arenas: ``None`` keeps today's behavior
    (store at the compute ``dtype``); ``"int8"``/``jnp.int8`` selects the
    int8 quantized path; ``"fp8"``/``"e4m3"``/``jnp.float8_e4m3fn`` the
    fp8 one.  Any other storage dtype is rejected — silent float truncation
    is exactly what this module replaces."""
    if kv_dtype is None:
        return jnp.dtype(dtype)
    if isinstance(kv_dtype, str) and kv_dtype.lower() in _FP8_ALIASES:
        if _FP8_DTYPE is None:
            raise ValueError(
                "kv_dtype='fp8' requires a jax build with float8_e4m3fn "
                "(jax.numpy.float8_e4m3fn is missing here)"
            )
        return jnp.dtype(_FP8_DTYPE)
    kd = jnp.dtype(kv_dtype)
    if kd == jnp.dtype(jnp.int8):
        return kd
    if _FP8_DTYPE is not None and kd == jnp.dtype(_FP8_DTYPE):
        return kd
    if kd == jnp.dtype(dtype):
        return kd
    raise ValueError(
        f"unsupported kv_dtype {kv_dtype!r}: use None (store at the compute "
        f"dtype {jnp.dtype(dtype)}), 'int8', or 'fp8' (quantized block "
        f"storage)"
    )


def is_quantized_kv(kv_dtype, dtype) -> bool:
    """Whether a resolved storage dtype takes the quantize/scale-arena path
    (1-byte storage that is NOT the compute dtype itself)."""
    kd = jnp.dtype(kv_dtype)
    if kd == jnp.dtype(dtype):
        return False
    if kd == jnp.dtype(jnp.int8):
        return True
    return _FP8_DTYPE is not None and kd == jnp.dtype(_FP8_DTYPE)


def quantize_kv(x, storage=jnp.int8):
    """Symmetric absmax quantization over the last (``hs``) dim into
    ``storage`` (int8: round-and-clip to ±127; fp8 e4m3: scale the absmax
    onto ±448 and let the cast round).

    Returns ``(q, scale)`` with ``q`` in ``storage`` shaped like ``x`` and
    ``scale`` float32 shaped ``x.shape[:-1]``.  All-zero rows get scale 1.0
    (exact).  Deterministic per token either way, so a request's stored KV
    never depends on batch composition.  Pure jnp; call inside jit."""
    storage = jnp.dtype(storage)
    qmax = _qmax(storage)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax == 0.0, 1.0, amax / qmax)
    if storage == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(xf / scale[..., None]), -qmax, qmax).astype(storage)
    else:
        # the scaled max lands exactly on ±qmax (representable in e4m3);
        # the cast rounds everything else to the nearest fp8 grid point
        q = (xf / scale[..., None]).astype(storage)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (up to rounding)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def gather_dense_q(k_arena, v_arena, k_scale, v_scale, tables, dtype):
    """Quantized twin of :func:`kv_pool.gather_dense`: reassembles dense
    caches from quantized (int8 or fp8) block arenas, dequantizing into
    ``dtype``.

    ``tables``: (B, nb) int32 physical-block ids (sink-padded).  Returns
    ``k, v`` of shape (L, B, ng, nb*bs, hs) — the layout
    ``forward_with_cache`` consumes.  Pure jnp; call inside jit."""

    def one(arena, scale):
        g = jnp.take(arena, tables, axis=0)        # (B, nb, L, ng, bs, hs) int8
        s = jnp.take(scale, tables, axis=0)        # (B, nb, L, ng, bs) f32
        x = g.astype(jnp.float32) * s[..., None]
        x = x.transpose(2, 0, 3, 1, 4, 5)          # (L, B, ng, nb, bs, hs)
        L, B, ng, nb, bs, hs = x.shape
        return x.reshape(L, B, ng, nb * bs, hs).astype(dtype)

    return one(k_arena, k_scale), one(v_arena, v_scale)


def scatter_token_q(arena, scale_arena, new_kv, dest_block, dest_slot):
    """Quantized twin of :func:`kv_pool.scatter_token`: quantizes one
    token's K (or V) per batch row and writes value + scale.

    ``new_kv``: (B, L, ng, hs) in compute dtype; ``dest_block``/``dest_slot``:
    (B,) int32 (sink-routed for padding rows).  The storage dtype comes from
    the arena itself (int8 or fp8).  Pure jnp; call inside jit on donated
    arenas."""
    q, s = quantize_kv(new_kv, arena.dtype)        # (B, L, ng, hs) / (B, L, ng)
    arena = arena.at[dest_block, :, :, dest_slot, :].set(q)
    scale_arena = scale_arena.at[dest_block, :, :, dest_slot].set(s)
    return arena, scale_arena


def scatter_blocks_q(arena, scale_arena, dense, dest_table):
    """Quantized twin of :func:`kv_pool.scatter_blocks`: quantizes a
    request's dense cache block-by-block and writes values + scales.

    ``dense``: (L, 1, ng, nb*bs, hs) float (B=1 prefill layout);
    ``dest_table``: (nb,) int32 — sink entries absorb padding.  Returns
    ``(arena, scale_arena, rel_err)`` where ``rel_err`` is the measured
    quantization error over the actually-written (non-sink) blocks:
    ``sum|dq - x| / sum|x|`` — the per-prefill value behind the
    ``serving.kv_quant.rel_err`` gauge."""
    if not jnp.issubdtype(dense.dtype, jnp.floating):
        from thunder_tpu.serving.kv_pool import ArenaMismatchError

        raise ArenaMismatchError(
            "scatter", "dtype", "floating source", jnp.dtype(dense.dtype),
            msg=f"scatter_blocks_q quantizes a float dense cache into a "
                f"quantized arena; got source dtype {jnp.dtype(dense.dtype)}",
        )
    L, B, ng, cap, hs = dense.shape
    bs = arena.shape[3]
    blocks = dense[:, 0].reshape(L, ng, cap // bs, bs, hs).transpose(2, 0, 1, 3, 4)
    q, s = quantize_kv(blocks, arena.dtype)        # (nb, L, ng, bs, hs) / (nb, L, ng, bs)
    dq = q.astype(jnp.float32) * s[..., None]
    xf = blocks.astype(jnp.float32)
    m = (dest_table != _SINK).astype(jnp.float32)[:, None, None, None, None]
    rel_err = jnp.sum(jnp.abs(dq - xf) * m) / (jnp.sum(jnp.abs(xf) * m) + 1e-30)
    arena = arena.at[dest_table].set(q)
    scale_arena = scale_arena.at[dest_table].set(s)
    return arena, scale_arena, rel_err


#
# capacity math (host-side; the admission-accounting-in-bytes helpers)
#


def arena_block_bytes(cfg, block_size: int, dtype, kv_dtype=None) -> int:
    """Bytes ONE pool block costs across both (K+V) arenas, including the
    scale arenas on the int8 path — the unit of byte-based capacity math."""
    L, ng, bs, hs = kv_block_shape(cfg, block_size)
    storage = resolve_kv_dtype(kv_dtype, dtype)
    per_side = L * ng * bs * hs * storage.itemsize
    if is_quantized_kv(storage, dtype):
        per_side += L * ng * bs * 4                # float32 scale per slot-head
    return 2 * per_side


def blocks_for_arena_bytes(cfg, block_size: int, budget_bytes: int, dtype,
                           kv_dtype=None) -> int:
    """Total blocks (sink included) an arena-byte budget affords — the
    equal-bytes pool sizing."""
    bb = arena_block_bytes(cfg, block_size, dtype, kv_dtype)
    return max(int(budget_bytes) // bb, 2)
