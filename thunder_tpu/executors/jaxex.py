"""The default JAX operator executor: every prim → a jax.numpy/lax call.

Capability analog of the reference's ``thunder/executors/torchex.py`` (the
always-on operator executor mapping prims to ``torch.*``); here prims map to
JAX ops, which also serve as the single source of truth for the XLA fusion
executor's region evaluation (``thunder_tpu/executors/xlaex.py``).
"""
from __future__ import annotations

import functools
from numbers import Number
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from thunder_tpu.core import dtypes
from thunder_tpu.core.devices import Device, to_jax_device
from thunder_tpu.core.prims import GDN_CHUNK, MOE_ROW_TILE, PrimIDs, gdn_state_stride, prim_lookup
from thunder_tpu.extend import OperatorExecutor, add_always_executor, add_default_executor, register_executor

__all__ = ["ex", "jax_ex", "get_prim_impl", "prim_impls"]


def _jd(d) -> Any:
    """thunder dtype → jax dtype."""
    return dtypes.to_jax_dtype(d)


def _key_for(key, offset: int):
    return jax.random.fold_in(key, offset)


#
# Implementations, keyed by PrimIDs.  Signatures match the prim metas exactly.
#

prim_impls: dict[PrimIDs, Callable] = {}


def impl(pid: PrimIDs):
    def deco(fn):
        prim_impls[pid] = fn
        return fn

    return deco


# Elementwise unary
_unary_jax = {
    PrimIDs.ABS: jnp.abs,
    PrimIDs.ACOS: jnp.arccos,
    PrimIDs.ACOSH: jnp.arccosh,
    PrimIDs.ASIN: jnp.arcsin,
    PrimIDs.ASINH: jnp.arcsinh,
    PrimIDs.ATAN: jnp.arctan,
    PrimIDs.ATANH: jnp.arctanh,
    PrimIDs.BITWISE_NOT: jnp.bitwise_not,
    PrimIDs.CEIL: jnp.ceil,
    PrimIDs.COS: jnp.cos,
    PrimIDs.COSH: jnp.cosh,
    PrimIDs.ERF: jax.lax.erf,
    PrimIDs.ERFC: jax.lax.erfc,
    PrimIDs.ERFINV: jax.lax.erf_inv,
    PrimIDs.EXP: jnp.exp,
    PrimIDs.EXP2: jnp.exp2,
    PrimIDs.EXPM1: jnp.expm1,
    PrimIDs.FLOOR: jnp.floor,
    PrimIDs.ISFINITE: jnp.isfinite,
    PrimIDs.ISINF: jnp.isinf,
    PrimIDs.ISNAN: jnp.isnan,
    PrimIDs.LOG: jnp.log,
    PrimIDs.LOG10: jnp.log10,
    PrimIDs.LOG1P: jnp.log1p,
    PrimIDs.LOG2: jnp.log2,
    PrimIDs.NEG: jnp.negative,
    PrimIDs.ROUND: jnp.round,
    PrimIDs.RSQRT: jax.lax.rsqrt,
    PrimIDs.SIGN: jnp.sign,
    PrimIDs.SIGNBIT: jnp.signbit,
    PrimIDs.SIN: jnp.sin,
    PrimIDs.SINH: jnp.sinh,
    PrimIDs.SQRT: jnp.sqrt,
    PrimIDs.TAN: jnp.tan,
    PrimIDs.TANH: jnp.tanh,
    PrimIDs.TRUNC: jnp.trunc,
    PrimIDs.REAL: jnp.real,
    PrimIDs.IMAG: jnp.imag,
}
for _pid, _fn in _unary_jax.items():
    prim_impls[_pid] = _fn


@impl(PrimIDs.DIGAMMA)
def _digamma_impl(a):
    from jax.scipy.special import digamma

    return digamma(a)


@impl(PrimIDs.LGAMMA)
def _lgamma_impl(a):
    from jax.scipy.special import gammaln

    return gammaln(a)


@impl(PrimIDs.RECIPROCAL)
def _reciprocal_impl(a):
    return jnp.reciprocal(a)


# Elementwise binary
_binary_jax = {
    PrimIDs.ADD: jnp.add,
    PrimIDs.ATAN2: jnp.arctan2,
    PrimIDs.BITWISE_AND: jnp.bitwise_and,
    PrimIDs.BITWISE_OR: jnp.bitwise_or,
    PrimIDs.BITWISE_XOR: jnp.bitwise_xor,
    PrimIDs.SHIFT_LEFT: jnp.left_shift,
    PrimIDs.SHIFT_RIGHT: jnp.right_shift,
    PrimIDs.COPYSIGN: jnp.copysign,
    PrimIDs.EQ: jnp.equal,
    PrimIDs.FMOD: jnp.fmod,
    PrimIDs.GE: jnp.greater_equal,
    PrimIDs.GT: jnp.greater,
    PrimIDs.LE: jnp.less_equal,
    PrimIDs.LT: jnp.less,
    PrimIDs.MAXIMUM: jnp.maximum,
    PrimIDs.MINIMUM: jnp.minimum,
    PrimIDs.MUL: jnp.multiply,
    PrimIDs.NE: jnp.not_equal,
    PrimIDs.NEXTAFTER: jnp.nextafter,
    PrimIDs.POW: jnp.power,
    PrimIDs.REMAINDER: jnp.remainder,
    PrimIDs.SUB: jnp.subtract,
}
for _pid, _fn in _binary_jax.items():
    prim_impls[_pid] = _fn


@impl(PrimIDs.DIV)
def _div_impl(a, b):
    if jnp.issubdtype(jnp.result_type(a), jnp.integer) or jnp.issubdtype(jnp.result_type(a), jnp.bool_):
        # C-style truncation division for exact types (matches reference prims.div)
        return jax.lax.div(a, b)
    return jnp.true_divide(a, b)


@impl(PrimIDs.WHERE)
def _where_impl(pred, a, b):
    return jnp.where(pred, a, b)


@impl(PrimIDs.CLAMP)
def _clamp_impl(a, min, max):
    return jnp.clip(a, min, max)


# Data movement
@impl(PrimIDs.CONVERT_ELEMENT_TYPE)
def _convert_element_type_impl(a, dtype):
    return a.astype(_jd(dtype))


@impl(PrimIDs.DEVICE_PUT)
def _device_put_impl(a, device):
    return jax.device_put(a, to_jax_device(device))


@impl(PrimIDs.ITEM)
def _item_impl(a):
    return a.reshape(()).item() if not isinstance(a, jax.core.Tracer) else a.reshape(())


@impl(PrimIDs.COPY_)
def _copy__impl(a, b):
    return jnp.asarray(b, dtype=a.dtype)


# Creation
@impl(PrimIDs.FULL)
def _full_impl(shape, fill_value, *, device, dtype):
    return jnp.full(tuple(int(s) for s in shape), fill_value, dtype=_jd(dtype))


@impl(PrimIDs.IOTA)
def _iota_impl(length, *, start, step, device, dtype):
    return start + step * jnp.arange(int(length), dtype=_jd(dtype))


@impl(PrimIDs.UNIFORM)
def _uniform_impl(shape, minval, maxval, *, device, dtype, key, offset):
    return jax.random.uniform(
        _key_for(key, offset), tuple(int(s) for s in shape), dtype=_jd(dtype), minval=minval, maxval=maxval
    )


@impl(PrimIDs.RANDN)
def _randn_impl(shape, *, device, dtype, key, offset):
    return jax.random.normal(_key_for(key, offset), tuple(int(s) for s in shape), dtype=_jd(dtype))


@impl(PrimIDs.RANDINT)
def _randint_impl(shape, low, high, *, device, dtype, key, offset):
    return jax.random.randint(_key_for(key, offset), tuple(int(s) for s in shape), low, high, dtype=_jd(dtype))


@impl(PrimIDs.MULTINOMIAL)
def _multinomial_impl(a, num_samples, replacement, *, key, offset):
    k = _key_for(key, offset)
    logits = jnp.log(a)
    if a.ndim == 1:
        return jax.random.categorical(k, logits, shape=(num_samples,)).astype(jnp.int32)
    return jax.random.categorical(k, logits[:, None, :], axis=-1, shape=(a.shape[0], num_samples)).astype(jnp.int32)


# Shape
@impl(PrimIDs.BROADCAST_IN_DIM)
def _broadcast_in_dim_impl(a, shape, broadcast_dimensions):
    return jax.lax.broadcast_in_dim(a, tuple(int(s) for s in shape), tuple(int(d) for d in broadcast_dimensions))


@impl(PrimIDs.CAT)
def _cat_impl(tensors, dim):
    return jnp.concatenate(list(tensors), axis=int(dim))


@impl(PrimIDs.FLIP)
def _flip_impl(a, dims):
    return jnp.flip(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.RESHAPE)
def _reshape_impl(a, shape):
    return jnp.reshape(a, tuple(int(s) for s in shape))


@impl(PrimIDs.SLICE)
def _slice_impl(a, start_indices, end_indices, strides=None):
    if strides is None:
        strides = [1] * a.ndim
    return jax.lax.slice(
        a, tuple(int(s) for s in start_indices), tuple(int(e) for e in end_indices), tuple(int(s) for s in strides)
    )


@impl(PrimIDs.SQUEEZE)
def _squeeze_impl(a, dims):
    return jnp.squeeze(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.TRANSPOSE)
def _transpose_impl(a, permutation):
    return jnp.transpose(a, tuple(int(p) for p in permutation))


@impl(PrimIDs.UNFOLD)
def _unfold_impl(a, dim, size, step):
    dim, size, step = int(dim), int(size), int(step)
    n_windows = (a.shape[dim] - size) // step + 1
    idx = jnp.arange(n_windows)[:, None] * step + jnp.arange(size)[None, :]
    out = jnp.take(a, idx, axis=dim)  # (..., n_windows, size, ...) at dim
    return jnp.moveaxis(out, dim + 1, -1)


@impl(PrimIDs.PAD)
def _pad_impl(a, padding_value, padding_config):
    pv = jnp.asarray(padding_value, dtype=a.dtype)
    return jax.lax.pad(a, pv, [(int(lo), int(hi), int(i)) for lo, hi, i in padding_config])


# Reductions
@impl(PrimIDs.AMAX)
def _amax_impl(a, dims):
    return jnp.max(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.AMIN)
def _amin_impl(a, dims):
    return jnp.min(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.PROD)
def _prod_impl(a, dims):
    return jnp.prod(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.SUM)
def _sum_impl(a, dims):
    return jnp.sum(a, axis=tuple(int(d) for d in dims))


@impl(PrimIDs.VAR)
def _var_impl(a, dims, *, correction):
    return jnp.var(a, axis=tuple(int(d) for d in dims), ddof=correction)


@impl(PrimIDs.VAR_MEAN)
def _var_mean_impl(a, dims, *, correction):
    axis = tuple(int(d) for d in dims)
    return jnp.var(a, axis=axis, ddof=correction), jnp.mean(a, axis=axis)


@impl(PrimIDs.ARGMAX)
def _argmax_impl(a, dim):
    return jnp.argmax(a, axis=None if dim is None else int(dim)).astype(jnp.int32)


@impl(PrimIDs.ARGMIN)
def _argmin_impl(a, dim):
    return jnp.argmin(a, axis=None if dim is None else int(dim)).astype(jnp.int32)


@impl(PrimIDs.TOPK)
def _topk_impl(a, k, dim, largest, sorted):
    dim = int(dim)
    moved = jnp.moveaxis(a, dim, -1)
    if not largest:
        values, indices = jax.lax.top_k(-moved, int(k))
        values = -values
    else:
        values, indices = jax.lax.top_k(moved, int(k))
    return jnp.moveaxis(values, -1, dim), jnp.moveaxis(indices.astype(jnp.int32), -1, dim)


@impl(PrimIDs.SORT)
def _sort_impl(a, dim, descending):
    dim = int(dim)
    key = -a if descending else a
    indices = jnp.argsort(key, axis=dim).astype(jnp.int32)
    values = jnp.take_along_axis(a, indices, axis=dim)
    return values, indices


@impl(PrimIDs.ARGSORT)
def _argsort_impl(a, dim, descending):
    key = -a if descending else a
    return jnp.argsort(key, axis=int(dim)).astype(jnp.int32)


@impl(PrimIDs.CUMSUM)
def _cumsum_impl(a, dim):
    return jnp.cumsum(a, axis=int(dim))


@impl(PrimIDs.CUMPROD)
def _cumprod_impl(a, dim):
    return jnp.cumprod(a, axis=int(dim))


# Scatter/gather
@impl(PrimIDs.TAKE)
def _take_impl(a, indices, dim):
    return jnp.take(a, indices, axis=int(dim))


@impl(PrimIDs.TAKE_ALONG_AXIS)
def _take_along_axis_impl(a, indices, dim):
    return jnp.take_along_axis(a, indices, axis=int(dim))


@impl(PrimIDs.GATHER)
def _gather_impl(a, indices, dim):
    return jnp.take_along_axis(a, indices, axis=int(dim))


@impl(PrimIDs.INDEX_ADD)
def _index_add_impl(a, indices, value, dim):
    dim = int(dim)
    idx = tuple(indices if i == dim else slice(None) for i in range(a.ndim))
    return a.at[idx].add(value)


@impl(PrimIDs.INDEX_PUT)
def _index_put_impl(a, indices, values, accumulate):
    idx = tuple(indices)
    if accumulate:
        return a.at[idx].add(values)
    return a.at[idx].set(values)


@impl(PrimIDs.SCATTER_ADD)
def _scatter_add_impl(a, indices, value, dim):
    dim = int(dim)
    grids = jnp.meshgrid(*[jnp.arange(s) for s in indices.shape], indexing="ij")
    grids[dim] = indices
    v = value
    if v.shape != indices.shape:
        v = v[tuple(slice(0, s) for s in indices.shape)]
    return a.at[tuple(grids)].add(v)


# Linear algebra / NN
@impl(PrimIDs.MATMUL)
def _matmul_impl(a, b):
    return jnp.matmul(a, b)


@impl(PrimIDs.LINEAR)
def _linear_impl(a, w, bias):
    out = jax.lax.dot_general(a, w, (((a.ndim - 1,), (1,)), ((), ())))
    if bias is not None:
        out = out + bias
    return out


@impl(PrimIDs.EMBEDDING)
def _embedding_impl(indices, weight, *, padding_idx=None):
    return jnp.take(weight, indices, axis=0)


@impl(PrimIDs.EMBEDDING_BACKWARD)
def _embedding_backward_impl(grad, indices, num_weights, padding_idx):
    num_weights = int(num_weights)
    flat_idx = indices.reshape(-1)
    flat_grad = grad.reshape(-1, grad.shape[-1])
    from thunder_tpu.executors.pallasex import _mesh_var

    def onehot_matmul():
        oh = (flat_idx[:, None] == jnp.arange(num_weights)[None, :])
        return jax.lax.dot_general(
            oh.astype(flat_grad.dtype), flat_grad,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).astype(grad.dtype)

    mesh = _mesh_var.get()
    if mesh is not None and mesh.size > 1:
        # One-hot matmul instead of scatter-add under a multi-device mesh:
        # the (V, N)·(N, C) contraction partitions like any other matmul
        # (data-sharded N → grad all-reduce) and rides the MXU.  XLA's
        # scatter partitioner on this pattern either replicates the whole
        # (N, C) update matrix (spmd_partitioner.cc:652 "involuntary full
        # rematerialization" when the vocab dim is sharded) or produces a
        # numerically WRONG sum (measured 5e-2 vs an f64 reference when the
        # embd dim is sharded).
        out = onehot_matmul()
    else:
        out = jnp.zeros((num_weights, grad.shape[-1]), dtype=grad.dtype)
        out = out.at[flat_idx].add(flat_grad)
    if padding_idx is not None and padding_idx >= 0:
        out = out.at[int(padding_idx)].set(0)
    return out


@impl(PrimIDs.ONE_HOT)
def _one_hot_impl(indices, num_classes):
    return jax.nn.one_hot(indices, int(num_classes), dtype=jnp.int32)


@impl(PrimIDs.EINSUM)
def _einsum_impl(spec, *operands):
    return jnp.einsum(spec, *operands)


@impl(PrimIDs.REDUCE_WINDOW)
def _reduce_window_impl(a, kind, window, strides, padding):
    n = len(window)
    lead = a.ndim - n
    window_dims = (1,) * lead + tuple(int(w) for w in window)
    window_strides = (1,) * lead + tuple(int(s) for s in strides)
    pads = [(0, 0)] * lead + [(int(lo), int(hi)) for lo, hi in padding]
    # plain-scalar inits keep lax on the monoid (reduce_window_max/sum) path,
    # which is the differentiable one
    if kind == "max":
        init = -float("inf") if jnp.issubdtype(a.dtype, jnp.floating) else int(jnp.iinfo(a.dtype).min)
        return jax.lax.reduce_window(a, init, jax.lax.max, window_dims, window_strides, pads)
    return jax.lax.reduce_window(a, 0 if jnp.issubdtype(a.dtype, jnp.integer) else 0.0, jax.lax.add, window_dims, window_strides, pads)


@impl(PrimIDs.RESIZE)
def _resize_impl(a, shape, method):
    _method = {"bilinear": "linear", "trilinear": "linear", "bicubic": "cubic"}.get(method, method)
    return jax.image.resize(a, tuple(int(s) for s in shape), method=_method, antialias=False)


@impl(PrimIDs.CONVOLUTION)
def _convolution_impl(a, weight, bias, stride, padding, dilation, transposed, output_padding, groups):
    ndim = a.ndim - 2
    dn = jax.lax.conv_dimension_numbers(
        a.shape,
        weight.shape,
        (
            ("NCHW"[: 2 + ndim] if ndim <= 2 else "NCDHW"),
            ("OIHW"[: 2 + ndim] if ndim <= 2 else "OIDHW"),
            ("NCHW"[: 2 + ndim] if ndim <= 2 else "NCDHW"),
        ),
    )
    out = jax.lax.conv_general_dilated(
        a,
        weight,
        window_strides=tuple(int(s) for s in stride),
        padding=[(int(p), int(p)) for p in padding],
        rhs_dilation=tuple(int(d) for d in dilation),
        dimension_numbers=dn,
        feature_group_count=int(groups),
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


# Fused attention.  The reference impls below are the jnp decomposition
# (numerically the flash algorithm's result, materializing the score matrix);
# the Pallas executor (pallasex.py) installs blockwise flash kernels into
# these hooks so every execution path — claimed traces, XLA fusion regions,
# and the distributed TrainStep's trace evaluation — dispatches to them when
# the shapes/backend qualify.
_sdpa_fast_path: Callable | None = None  # (q, k, v, mask, causal, scale) -> (out, lse) or None
_sdpa_bwd_fast_path: Callable | None = None


def _gqa_expand(q, k, v):
    """Expand grouped K/V heads to q's head count for the decomposed path
    (the fused kernels index groups natively instead — pallasex.py)."""
    if q.shape[:-2] == k.shape[:-2]:
        return k, v, 1
    rep = q.shape[-3] // k.shape[-3]
    return jnp.repeat(k, rep, axis=-3), jnp.repeat(v, rep, axis=-3), rep


def _band(Tq, Tk, window):
    """Causal(+sliding-window) boolean mask: row i attends cols in
    (i-window, i] — top-left aligned like the torch decomposition."""
    cm = jnp.tril(jnp.ones((Tq, Tk), dtype=bool))
    if window is not None:
        row = jnp.arange(Tq)[:, None]
        col = jnp.arange(Tk)[None, :]
        cm = cm & (col > row - window)
    return cm


def _sdpa_reference(q, k, v, mask, causal, scale, window=None):
    k, v, _ = _gqa_expand(q, k, v)
    s = jnp.einsum("...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)
    if causal:
        s = jnp.where(_band(q.shape[-2], k.shape[-2], window), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)
    return out.astype(q.dtype), lse


@impl(PrimIDs.SDPA)
def _sdpa_impl(q, k, v, mask, causal, scale, window=None):
    if _sdpa_fast_path is not None:
        res = _sdpa_fast_path(q, k, v, mask, causal, scale, window)
        if res is not None:
            return res
    return _sdpa_reference(q, k, v, mask, causal, scale, window)


def _sdpa_backward_reference(g, q, k, v, out, lse, mask, causal, scale, window=None):
    kx, vx, rep = _gqa_expand(q, k, v)
    s = jnp.einsum("...qd,...kd->...qk", q, kx, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)
    if causal:
        s = jnp.where(_band(q.shape[-2], kx.shape[-2], window), s, -jnp.inf)
    p = jnp.exp(s - lse[..., None])  # (..., Tq, Tk) f32
    dv = jnp.einsum("...qk,...qd->...kd", p, g.astype(jnp.float32))
    dp = jnp.einsum("...qd,...kd->...qk", g, vx, preferred_element_type=jnp.float32)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("...qk,...kd->...qd", ds, kx.astype(jnp.float32))
    dk = jnp.einsum("...qk,...qd->...kd", ds, q.astype(jnp.float32))
    if rep > 1:  # sum the expanded-head grads back onto the shared KV groups
        G = k.shape[-3]
        dk = dk.reshape(*dk.shape[:-3], G, rep, *dk.shape[-2:]).sum(axis=-3)
        dv = dv.reshape(*dv.shape[:-3], G, rep, *dv.shape[-2:]).sum(axis=-3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@impl(PrimIDs.SDPA_BACKWARD)
def _sdpa_backward_impl(g, q, k, v, out, lse, mask, causal, scale, window=None):
    if _sdpa_bwd_fast_path is not None:
        res = _sdpa_bwd_fast_path(g, q, k, v, out, lse, mask, causal, scale, window)
        if res is not None:
            return res
    return _sdpa_backward_reference(g, q, k, v, out, lse, mask, causal, scale, window)


_ce_fast_path: Callable | None = None  # installed by pallasex (fused CE kernel)


def _cross_entropy_fwd_reference(logits, target):
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, target[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return lse - picked, lse


@impl(PrimIDs.CROSS_ENTROPY_FWD)
def _cross_entropy_fwd_impl(logits, target):
    if _ce_fast_path is not None:
        res = _ce_fast_path(logits, target)
        if res is not None:
            return res
    return _cross_entropy_fwd_reference(logits, target)


def _flce_chunk(V: int, desired: int = 8192) -> int:
    """Vocab chunk for the fused linear+CE scan: the largest MXU-friendly
    slab ≤ ``desired`` that DIVIDES ``V`` — divisibility is load-bearing, a
    non-divisor would silently drop the tail vocab rows from the softmax."""
    for c in (8192, 4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if c <= desired and V % c == 0:
            return c
    return V


def _flce_partials(h, w, tgt, global_off, CH):
    """Online-logsumexp partials of ``h @ w.T`` scanned over vocab chunks of
    size ``CH`` (must divide ``w.shape[0]``).  ``global_off`` is ``w``'s
    offset in the full vocab (nonzero for a vocab shard, see
    distributed/vocab_parallel.py).  Returns float32 (N,) ``(m, s, tl)``:
    running max, normalizer at ``m``, and the target logit (0 when the
    target id falls outside this ``w``)."""
    N = h.shape[0]
    V = w.shape[0]
    n_chunks = V // CH

    def body(carry, c):
        m, s, tl = carry
        off = c * CH
        wc = jax.lax.dynamic_slice_in_dim(w, off, CH, axis=0)
        lg = jax.lax.dot_general(h, wc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (N, CH)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(lg - m_new[:, None]), axis=-1)
        gcol = global_off + off
        in_chunk = jnp.logical_and(tgt >= gcol, tgt < gcol + CH)
        idx = jnp.clip(tgt - gcol, 0, CH - 1)
        cand = jnp.take_along_axis(lg, idx[:, None], axis=1)[:, 0]
        tl = jnp.where(in_chunk, cand, tl)
        return (m_new, s, tl), None

    init = (
        jnp.full((N,), -jnp.inf, dtype=jnp.float32),
        jnp.zeros((N,), dtype=jnp.float32),
        jnp.zeros((N,), dtype=jnp.float32),
    )
    (m, s, tl), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return m, s, tl


@impl(PrimIDs.FUSED_LINEAR_CE)
def _fused_linear_ce_impl(h, w, target, ignore_index=-100):
    """Online-logsumexp CE over vocab chunks of ``h @ w.T`` — the (N, V)
    logits never exist in HBM; peak extra memory is one (N, CH) slab."""
    V = w.shape[0]
    tgt = target.astype(jnp.int32)
    m, s, tl = _flce_partials(h, w, tgt, 0, _flce_chunk(V))
    lse = m + jnp.log(s)
    losses = jnp.where(tgt != ignore_index, lse - tl, 0.0)
    return losses, lse


@impl(PrimIDs.FUSED_LINEAR_CE_BACKWARD)
def _fused_linear_ce_backward_impl(g, h, w, target, lse, ignore_index=-100):
    """dh/dw from chunked softmax recompute: ds_c = (p_c - onehot_c) * g."""
    N, C = h.shape
    V = w.shape[0]
    CH = _flce_chunk(V)
    n_chunks = V // CH
    tgt = target.astype(jnp.int32)
    gg = jnp.where(tgt != ignore_index, g.astype(jnp.float32), 0.0)

    def body(dh, c):
        off = c * CH
        wc = jax.lax.dynamic_slice_in_dim(w, off, CH, axis=0)
        lg = jax.lax.dot_general(h, wc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        p = jnp.exp(lg - lse[:, None])  # (N, CH)
        col = off + jnp.arange(CH)
        oh = (tgt[:, None] == col[None, :]).astype(jnp.float32)
        ds = (p - oh) * gg[:, None]
        dh = dh + jax.lax.dot_general(ds, wc.astype(jnp.float32), (((1,), (0,)), ((), ())))
        dwc = jax.lax.dot_general(ds, h.astype(jnp.float32), (((0,), (0,)), ((), ())))
        return dh, dwc.astype(w.dtype)

    dh, dwcs = jax.lax.scan(body, jnp.zeros((N, C), dtype=jnp.float32), jnp.arange(n_chunks))
    dw = dwcs.reshape(V, C)
    return dh.astype(h.dtype), dw


# Chunked gated delta rule.  ``_gdn_chunked`` is the plain XLA decomposition
# (everything that does not depend on the state is batched over all chunks;
# a ``lax.scan`` over chunks carries the state through three small batched
# products a step, inside a scan over the blocks whose starting states the
# prim returns).  It is the executor of last resort and, with its
# derivative (``_gdn_chunked_backward``), the oracle of the Pallas
# ``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` kernels, which pallasex.py installs
# into the two hooks below.
_gdn_fast_path: Callable | None = None  # (q, k, v, g, beta, chunk) -> (o, states) or None
_gdn_bwd_fast_path: Callable | None = None  # (do, q, k, v, g, beta, states, chunk) -> five gradients or None
_gdn_state_fast_path: Callable | None = None  # (q, k, v, g, beta, h0, chunk) -> (o, last state) or None


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A (..., C, C)``: with
    ``B = -A`` nilpotent, ``(I + B)(I + B^2)(I + B^4)...`` is the whole
    Neumann series after ``log2 C`` squarings -- products only, no
    row-by-row substitution, so it runs on the MXU and differentiates."""
    C = A.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    P = -A
    T = jnp.eye(C, dtype=A.dtype) + P
    n = 1
    while 2 * n < C:
        P = jnp.matmul(P, P, precision=hi)
        T = T + jnp.matmul(T, P, precision=hi)
        n *= 2
    return T


def _gdn_chunked(q, k, v, g, beta, chunk):
    """q, k ``(B, Hk, T, dk)``, v ``(B, Hv, T, dv)``, g, beta ``(B, Hv, T)``
    -> o ``(B, Hv, T, dv)`` and the float32 state at the start of every block
    of ``gdn_state_stride(T)`` tokens, ``(B, Hv, blocks, dk, dv)``.  T is
    padded to whole chunks with tokens that leave the state alone (g = 0,
    beta = 0)."""
    return _gdn_chunked_from(q, k, v, g, beta, chunk)[:2]


def gdn_chunk_state(q, k, v, g, beta, h0, chunk=GDN_CHUNK):
    """The scan as the server runs it: from the float32 state ``h0 (B, Hv, dk,
    dv)`` to ``(o, the state after the last token)``, through the Pallas
    kernel where it takes the shapes, else the XLA decomposition."""
    if _gdn_state_fast_path is not None:
        res = _gdn_state_fast_path(q, k, v, g, beta, h0, chunk)
        if res is not None:
            return res
    o, _, last = _gdn_chunked_from(q, k, v, g, beta, chunk, h0)
    return o, last.astype(h0.dtype)


def _gdn_chunked_from(q, k, v, g, beta, chunk, h0=None):
    """:func:`_gdn_chunked` from the state ``h0`` (zeros where None), with the
    state after the last token as a third result."""
    B, Hk, T, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    rep = Hv // Hk
    C = int(chunk)
    pad = (-T) % C
    f32 = jnp.float32
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))) for t in (g, beta))
    n = (T + pad) // C
    qc = jnp.repeat(q, rep, axis=1).reshape(B, Hv, n, C, dk)
    kc = jnp.repeat(k, rep, axis=1).reshape(B, Hv, n, C, dk)
    vc = v.reshape(B, Hv, n, C, dv)
    bc = beta.astype(f32).reshape(B, Hv, n, C, 1)
    G = jnp.cumsum(g.astype(f32).reshape(B, Hv, n, C), axis=-1)       # log decay since the chunk's start
    low = jnp.tril(jnp.ones((C, C), bool))
    # decay from token j to token i of a chunk, j <= i; masked before the exp
    M = jnp.exp(jnp.where(low, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("...ik,...jk->...ij", kc, kc, preferred_element_type=f32)
    Tm = _unit_lower_inverse(jnp.where(jnp.tril(low, -1), bc * M * kk, 0.0))
    eG = jnp.exp(G)[..., None]
    U = jnp.einsum("...ij,...jd->...id", Tm, bc * vc.astype(f32))     # d_t with an empty state
    W = jnp.einsum("...ij,...jk->...ik", Tm, bc * eG * kc.astype(f32))  # what the carried state takes off it
    QK = M * jnp.einsum("...ik,...jk->...ij", qc, kc, preferred_element_type=f32)
    Qd = eG * qc.astype(f32)
    Glast = G[..., -1]
    Kd = jnp.exp(Glast[..., None] - G)[..., None] * kc.astype(f32)

    def step(S, xs):
        U_, W_, QK_, Qd_, Kd_, gl = xs
        D = U_ - jnp.einsum("bhik,bhkd->bhid", W_, S)
        o = jnp.einsum("bhik,bhkd->bhid", Qd_, S) + jnp.einsum("bhij,bhjd->bhid", QK_, D)
        S = jnp.exp(gl)[..., None, None] * S + jnp.einsum("bhik,bhid->bhkd", Kd_, D)
        return S, o

    def block(S, xs):
        S_next, o = jax.lax.scan(jax.checkpoint(step), S, xs)
        return S_next, (o, S)

    nb = T // gdn_state_stride(T)                                      # 1 where the stride is the sequence
    lead = lambda a: jnp.moveaxis(a, 2, 0).reshape(nb, n // nb, *a.shape[:2], *a.shape[3:])  # noqa: E731 -- blocks, chunks first
    first = jnp.zeros((B, Hv, dk, dv), f32) if h0 is None else h0.astype(f32)
    last, (o, states) = jax.lax.scan(block, first, tuple(lead(a) for a in (U, W, QK, Qd, Kd, Glast)))
    o = jnp.moveaxis(o.reshape(n, B, Hv, C, dv), 0, 2).reshape(B, Hv, T + pad, dv)
    return o[:, :, :T].astype(v.dtype), jnp.moveaxis(states, 0, 2), last


@impl(PrimIDs.GDN_CHUNK)
def _gdn_chunk_impl(q, k, v, g, beta, chunk=GDN_CHUNK):
    if _gdn_fast_path is not None:
        res = _gdn_fast_path(q, k, v, g, beta, chunk)
        if res is not None:
            return res
    return _gdn_chunked(q, k, v, g, beta, chunk)


def _gdn_chunked_backward(do, q, k, v, g, beta, chunk):
    """The XLA chunked form differentiated: the forward scan again (each
    chunk's step checkpointed, so only the carried states are kept), then
    the backward scan.  The oracle: it takes no saved states."""
    _, vjp = jax.vjp(lambda *a: _gdn_chunked(*a, chunk)[0], q, k, v, g, beta)
    return vjp(do)


@impl(PrimIDs.GDN_CHUNK_BACKWARD)
def _gdn_chunk_backward_impl(do, q, k, v, g, beta, states, chunk=GDN_CHUNK):
    if _gdn_bwd_fast_path is not None:
        res = _gdn_bwd_fast_path(do, q, k, v, g, beta, states, chunk)
        if res is not None:
            return res
    return _gdn_chunked_backward(do, q, k, v, g, beta, chunk)


@impl(PrimIDs.OPTIMIZATION_BARRIER)
def _optimization_barrier_impl(*tensors):
    return tuple(jax.lax.optimization_barrier(tensors))


# The causal depthwise conv of the DeltaNet layers with its activation
# (pallasex installs ``causal_conv1d_fwd`` / ``causal_conv1d_bwd``: one pass over
# HBM each way).  The XLA forms below are the fallback and the tests' oracle:
# XLA reads each shifted window of the padded float32 copy again.
_causal_conv_fast_path: Callable | None = None       # (x, w, activation) -> out or None
_causal_conv_bwd_fast_path: Callable | None = None   # (g, x, w, activation) -> (dx, dw) or None


def _shifted(xp, T, K):
    """The K windows of T steps of a sequence padded by K - 1 steps."""
    return [xp[:, j:j + T] for j in range(K)]


def _causal_conv_taps(x, K):
    """``x`` in float32 as each tap sees it: tap j the token K - 1 - j back."""
    return _shifted(jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32), x.shape[1], K)


def _causal_conv1d_xla(x, w, activation=None):
    wf = w.astype(jnp.float32)
    y = sum(tap * wf[:, j] for j, tap in enumerate(_causal_conv_taps(x, w.shape[1])))
    return (jax.nn.silu(y) if activation == "silu" else y).astype(x.dtype)


@impl(PrimIDs.CAUSAL_CONV1D)
def _causal_conv1d_impl(x, w, activation=None):
    if _causal_conv_fast_path is not None:
        res = _causal_conv_fast_path(x, w, activation)
        if res is not None:
            return res
    return _causal_conv1d_xla(x, w, activation)


def _causal_conv1d_backward_xla(g, x, w, activation=None):
    T, K = x.shape[1], w.shape[1]
    gf, wf = g.astype(jnp.float32), w.astype(jnp.float32)
    taps = _causal_conv_taps(x, K)
    if activation == "silu":
        # the float32 sum again, never rounded: silu'(y) = s (1 + y (1 - s)), s = sigmoid(y)
        y = sum(tap * wf[:, j] for j, tap in enumerate(taps))
        s = jax.nn.sigmoid(y)
        gf = gf * s * (1.0 + y * (1.0 - s))
    # tap j weighs the token K - 1 - j steps back, so its gradient comes from that many steps on
    ahead = _shifted(jnp.pad(gf, ((0, 0), (0, K - 1), (0, 0))), T, K)
    dx = sum(ahead[K - 1 - j] * wf[:, j] for j in range(K))
    dw = jnp.stack([jnp.sum(gf * tap, axis=(0, 1)) for tap in taps], axis=1)
    return dx.astype(x.dtype), dw.astype(w.dtype)


@impl(PrimIDs.CAUSAL_CONV1D_BACKWARD)
def _causal_conv1d_backward_impl(g, x, w, activation=None):
    if _causal_conv_bwd_fast_path is not None:
        res = _causal_conv_bwd_fast_path(g, x, w, activation)
        if res is not None:
            return res
    return _causal_conv1d_backward_xla(g, x, w, activation)


# Grouped matrix products over rows sorted by group, each group padded to
# whole row tiles, the group sizes known only at run time inside static shapes
# (``lax.ragged_dot`` here; pallasex installs ``moe_grouped_mm*``), and an
# expert layer's share made of them.  ``tile_group (R / tile,)`` names each
# tile's group, ``tiles_used (1,)`` how many tiles hold rows; the time follows
# the tiles used.
_grouped_mm_fast_path: Callable | None = None      # (x, w, tile_group, tiles_used, transpose_w) -> out or None
_grouped_mm_dw_fast_path: Callable | None = None   # (x, dy, tile_group, tiles_used, groups) -> dw or None
_tokens_of_rows_fast_path: Callable | None = None  # (vb, row_src, tile_group, N, k, groups, dtype) -> token rows or None


def _group_sizes(tile_group, tiles_used, rows, groups):
    tile = rows // tile_group.shape[0]
    used = jnp.arange(tile_group.shape[0]) < tiles_used[0]
    return tile * jnp.sum((tile_group[:, None] == jnp.arange(groups)[None, :]) & used[:, None], axis=0,
                          dtype=jnp.int32)


def _grouped_mm_impl(x, w, tile_group, tiles_used, transpose_w=False):
    """``out[tile t] = x[tile t] @ w[tile_group[t]]`` for the used tiles, zero
    after; ``x (R, K)``, ``w (G, K, N)`` (``(G, N, K)`` with ``transpose_w``)."""
    if _grouped_mm_fast_path is not None:
        res = _grouped_mm_fast_path(x, w, tile_group, tiles_used, transpose_w)
        if res is not None:
            return res
    gs = _group_sizes(tile_group, tiles_used, x.shape[0], w.shape[0])
    out = jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transpose_w else w, gs)
    return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(gs))[:, None], out, 0).astype(x.dtype)


def _grouped_mm_dw_impl(x, dy, tile_group, tiles_used, groups):
    """``dw[g] = sum over the used tiles t of group g of x[tile t]^T @ dy[tile
    t]``, ``(groups, K, N)``; a group without rows gets zeros."""
    if _grouped_mm_dw_fast_path is not None:
        res = _grouped_mm_dw_fast_path(x, dy, tile_group, tiles_used, groups)
        if res is not None:
            return res
    gs = _group_sizes(tile_group, tiles_used, x.shape[0], groups)
    keep = (jnp.arange(x.shape[0]) < jnp.sum(gs))[:, None]
    w0 = jnp.zeros((groups, x.shape[1], dy.shape[1]), x.dtype)
    _, vjp = jax.vjp(lambda w_: jax.lax.ragged_dot(jnp.where(keep, x, 0), w_, gs), w0)
    return vjp(jnp.where(keep, dy, 0).astype(x.dtype))[0]


# The expert share.  Every (token, slot) assignment may fall on a held expert,
# so the sorted buffer's worst case is N * k rows; what a step really routes
# here is about held / all of that.  The rows are therefore worked through in
# *waves* sized for an even routing (``moe_wave_tiles``): the first always, the
# others only where the routing filled the one before (``lax.cond``), so memory
# and time follow the rows routed and nothing is ever dropped.
#
# The buffer is a partial permutation of the assignments, kept both ways:
# ``row_src[r]`` is the assignment row ``r`` holds, ``pos[n, s]`` the row
# assignment ``(n, s)`` landed in.  A wave's rows are a gather by ``row_src``
# (``_dispatch``), and the tokens take their results back by a gather by
# ``pos`` and a sum over ``k`` (``_combine``): no padding row is read back, so
# none needs a zero on the way in.  Differentiated, each is the other: the
# rows' gradient is gathered by ``pos``, the results' by ``row_src``, and the
# two index arrays are all either keeps.
#
# XLA's scatter-add walks its update rows one after another, at five to ten
# times what a gathered row costs (46-137 ns against 9-13 at a width of 2048 on
# a v5e; ``tools/moe_tune.py --glue``), so the gather wins wherever the
# assignments are not many times a wave's rows: every shape of a share that
# holds a fair part of the experts, and any decode step.  Where a few of many
# experts are held and the rows are many (a long prompt, the trainer's step:
# ten assignments for a buffer row) the gather would walk ten rows to find
# one and keep ``(k, N, C)`` of them, so there the tokens are still added to
# (``pos`` is ``None``).  Both forms add a token's rows in buffer order, so
# they agree to the bit and so does a token alone with a token in a batch.
#
# Where pallasex takes the call (``moe_combine``, through
# ``_tokens_of_rows_fast_path``: rows of whole lane tiles, one device) neither
# form runs: the kernel walks the routed rows a tile of tokens at a time (by
# ``row_src`` and the row tiles' groups: it needs no ``pos``) and reads the
# buffer once, a sublane tile of rows at a time, adding a token's rows in the
# same order to the same bits, at 40-50 ns a row of 2,048 to 3,584 numbers and
# 110 at 7,168 whatever the shapes (a v5e; ``tools/moe_tune.py --glue``).  It is
# asked where that beats XLA's form (``_kernel_takes``): wherever the tokens
# would be added to, bar a call of few tokens; and where they would gather, from
# 16 k assignments on, where XLA keeps the ``(k, N, C)`` rows it gathered and
# adds them in further passes (71 ns a row at Xing4.0's prompts of 5,120 and
# 8,192 tokens; LFM2's of 2,048 and 3,072, which it still gathers and adds in
# one fusion or two, cost 9 and 24, and a decode step's rows 3-12 us a call
# against the kernel's 13-55 with its first chunk a group).
_ROWS_GATHERED_A_ROW_SCATTERED = 4
_TOKENS_A_KERNEL_CALL = 512
_ASSIGNMENTS_GATHERED_A_KERNEL_CALL = 16384


def moe_wave_tiles(assignments: int, held: int, total: int, tile: int) -> int:
    """Tiles of one wave: the rows an even routing sends to ``held`` of
    ``total`` experts and an eighth more, a tile of padding a group, in eights."""
    even = assignments * held / total / tile
    return max(8, -(-int(1.125 * even + held) // 8) * 8)


def moe_plan(top_idx, top_w, first: int, held: int, tile: int, wave_tiles: int) -> dict:
    """Sorts the ``(N, k)`` choices that fall on experts ``[first, first +
    held)`` by expert, each group padded to whole ``tile``-row tiles, into a
    buffer of ``R`` rows: the worst case the shapes allow, in whole waves.
    One stable sort lays the buffer out: the flat assignments ``n * k + s``
    under their expert's key, and after them ``tile - 1`` fillers a group, as
    many under the group's key as pad it to whole tiles and the rest, like the
    assignments on other experts, under a key past the last; the sort carries
    the assignments' weights ``top_w (N, k)`` along, since a gather of scalars
    costs nanoseconds an element.  Returns int32 ``row_src (R,)`` (the
    assignment a row holds, -1 for padding and past the rows routed), ``row_w
    (R,)`` (its weight, 0 there; a constant to differentiation, see
    ``_dispatch``), ``cnt (held,)`` (each group's rows), ``tile_group (R /
    tile,)``, ``tiles_used ()`` and ``pos (N, k)``: the buffer row each
    assignment landed in (the sort's inverse, by a second sort), -1 where its
    expert is not held; ``None`` where the shapes leave the tokens to be added
    to (many assignments a buffer row: see above)."""
    N, k = top_idx.shape
    A = N * k
    wave = wave_tiles * tile
    R = -(-(A + held * (tile - 1)) // wave) * wave
    i32 = jnp.int32
    e = top_idx.reshape(A).astype(i32) - first
    key = jnp.where((e >= 0) & (e < held), e, held)
    groups = jnp.arange(held, dtype=i32)
    cnt = jnp.sum(key[:, None] == groups[None, :], axis=0, dtype=i32)
    short = -cnt % tile                                        # rows that pad a group to whole tiles
    fill = jnp.where(jnp.arange(tile - 1, dtype=i32)[None, :] < short[:, None], groups[:, None], held).reshape(-1)
    L = A + fill.shape[0]                                      # what is sorted; the buffer's tail past it is padding
    keys = jnp.concatenate([key, fill])
    src = jnp.concatenate([jnp.arange(A, dtype=i32), jnp.full(fill.shape, A, i32)])
    w = jnp.pad(jax.lax.stop_gradient(top_w).reshape(A), (0, L - A))
    skey, ssrc, sw = jax.lax.sort((keys, src, w), num_keys=1, is_stable=True)
    routed = (skey < held) & (ssrc < A)
    row_src = jnp.pad(jnp.where(routed, ssrc, -1), (0, R - L), constant_values=-1)
    row_w = jnp.pad(jnp.where(routed, sw, 0), (0, R - L))
    tile_group = jnp.minimum(jnp.pad(skey, (0, R - L), constant_values=held)[::tile], held - 1)
    pos = None
    if A <= _ROWS_GATHERED_A_ROW_SCATTERED * wave:
        _, at = jax.lax.sort((ssrc, jnp.arange(L, dtype=i32)), num_keys=1, is_stable=True)
        pos = jnp.where(key < held, at[:A], -1).reshape(N, k)   # the fillers sort past the assignments
    return {"row_src": row_src, "row_w": row_w, "cnt": cnt, "tile_group": tile_group,
            "tiles_used": (jnp.sum(cnt + short) // tile).astype(i32), "pos": pos}


def moe_wave_rows(plan: dict, w: int, tile: int, wave_tiles: int):
    """Wave ``w`` of the sorted buffer: its ``row_src``, ``pos`` counted from
    its first row (-1 for an assignment that lies in another wave; ``None`` as
    the plan's), its ``row_w``, its tiles' groups and how many of them are used."""
    T = wave_tiles
    rows = slice(w * T * tile, (w + 1) * T * tile)
    pos = plan["pos"]
    if pos is not None:
        pos = pos - rows.start
        pos = jnp.where((pos >= 0) & (pos < T * tile), pos, -1)
    return (plan["row_src"][rows], pos, plan["row_w"][rows], plan["tile_group"][w * T:(w + 1) * T],
            jnp.clip(plan["tiles_used"] - w * T, 0, T))


@jax.custom_vjp
def _gmm(x, w, tile_group, tiles_used):
    return _grouped_mm_impl(x, w, tile_group, tiles_used, False)


def _gmm_bwd(res, g):
    x, w, tile_group, tiles_used = res
    return (_grouped_mm_impl(g, w, tile_group, tiles_used, True),
            _grouped_mm_dw_impl(x, g, tile_group, tiles_used, w.shape[0]), None, None)


_gmm.defvjp(lambda x, w, tg, tu: (_gmm(x, w, tg, tu), (x, w, tg, tu)), _gmm_bwd)


def _take_rows(v, idx):
    """``v[idx]`` along axis 0 for indices known to lie inside (no select on
    an index's validity behind the gather)."""
    return v.at[idx].get(mode="promise_in_bounds")


def _rows_of_tokens(v, row_src, k, mask: bool):
    """Buffer rows from token rows ``v (N, C)``: row ``r`` is that of token
    ``row_src[r] // k``; a padding row is zero with ``mask``, else some token's."""
    rows = _take_rows(v, jnp.maximum(row_src, 0) // k)
    return jnp.where((row_src >= 0)[:, None], rows, 0) if mask else rows


def _kernel_takes(static, R: int) -> bool:
    """Whether a wave of ``R`` rows back to ``static``'s tokens is the kernel's
    to take (where there is one): from ``N``, ``k`` and which form XLA would
    run, as ``moe_plan`` chooses it."""
    N, k = static[:2]
    if N * k > _ROWS_GATHERED_A_ROW_SCATTERED * R:
        return N >= _TOKENS_A_KERNEL_CALL
    return N * k >= _ASSIGNMENTS_GATHERED_A_KERNEL_CALL


def _tokens_of_rows(vb, row_src, pos, tile_group, static, dtype):
    """Token rows ``(N, C)`` from buffer rows ``vb (R, C)``: the sum in
    ``dtype`` of the rows a token's assignments landed in, taken in buffer
    order whichever form the shapes chose.  By ``pos``: one gather of ``(k,
    N)`` rows, slot-major so that a slot's rows lie together, added a slot at a
    time, a slot without a row here adding zero.  Without: the rows added to
    their tokens, padding rows as zeros.  Or neither, where a kernel takes the
    call (``tile_group``: the group of each of the wave's row tiles)."""
    N, k, _, groups = static
    if _tokens_of_rows_fast_path is not None and _kernel_takes(static, vb.shape[0]):
        res = _tokens_of_rows_fast_path(vb, row_src, tile_group, N, k, groups, dtype)
        if res is not None:
            return res
    y = jnp.zeros((N, vb.shape[1]), dtype)
    if pos is None:
        return y.at[jnp.maximum(row_src, 0) // k].add(jnp.where((row_src >= 0)[:, None], vb, 0).astype(dtype))
    pos = jnp.sort(pos, axis=1).T                             # buffer order: by expert, as a scatter adds them
    rows = _take_rows(vb, jnp.maximum(pos, 0))
    for s in range(k):
        y = y + jnp.where((pos[s] >= 0)[:, None], rows[s], 0).astype(dtype)
    return y


# ``static`` of the two below: (tokens N, slots k, the rows' dtype, the groups held)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(static, x, top_w, row_src, pos, row_w, tile_group):
    """A wave's rows ``xb (R, C)`` and their weights ``wb (R,)``: the rows
    gathered from the tokens by ``row_src`` (a padding row holds some token's,
    since nothing reads its product back), the weights as the plan's sort
    laid ``top_w`` out (``row_w``).  Their gradients are gathered by ``pos``,
    the weights' into ``top_w``."""
    return _rows_of_tokens(x, row_src, static[1], False), row_w.astype(top_w.dtype)


def _dispatch_bwd(static, res, g):
    N, k = static[:2]
    row_src, pos, tile_group = res
    dxb, dwb = g
    dx = _tokens_of_rows(dxb, row_src, pos, tile_group, static, dxb.dtype)
    if pos is None:
        dw = jnp.zeros((N * k,), dwb.dtype).at[jnp.maximum(row_src, 0)].add(jnp.where(row_src >= 0, dwb, 0))
    else:
        dw = jnp.where(pos >= 0, _take_rows(dwb, jnp.maximum(pos, 0)), 0)
    return dx, dw.reshape(N, k), None, None, None, None


def _dispatch_fwd(static, x, top_w, row_src, pos, row_w, tile_group):
    return _dispatch(static, x, top_w, row_src, pos, row_w, tile_group), (row_src, pos, tile_group)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(static, yb, row_src, pos, tile_group):
    """What a wave adds to the tokens, float32 ``(N, C)``: each token's rows
    of ``yb (R, C)`` summed.  Its gradient is gathered by ``row_src``, zero on
    the padding rows: through it every gradient of the wave is zero there."""
    return _tokens_of_rows(yb, row_src, pos, tile_group, static, jnp.float32)


def _combine_fwd(static, yb, row_src, pos, tile_group):
    return _combine(static, yb, row_src, pos, tile_group), row_src


def _combine_bwd(static, row_src, g):
    return _rows_of_tokens(g, row_src, static[1], True).astype(static[2]), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _moe_wave(gate, x, top_w, fc_1, fc_2, proj, row_src, pos, row_w, tile_group, tiles_used):
    """One wave of the sorted buffer: its rows gathered from their tokens
    (by ``row_src``), through the experts' SwiGLU as grouped products,
    weighted, and gathered back by their tokens (by ``pos``) and summed in
    float32.  ``row_src``, ``pos``, ``row_w`` and ``tile_group`` are the wave's.
    ``fc_2`` None (the server's ungated experts): ``relu(. fc_1)^2`` in the SwiGLU's place.
    ``gate``: the gated experts' activation, ``jax.nn.silu`` or the server's ``jax.nn.relu``."""
    static = (*top_w.shape, jnp.dtype(x.dtype), fc_1.shape[0])
    xb, wb = _dispatch(static, x, top_w, row_src, pos, row_w, tile_group)
    used = tiles_used.reshape(1)
    if fc_2 is None:        # ungated experts: W2 relu(W1 x)^2
        h = jnp.square(jax.nn.relu(_gmm(xb, fc_1, tile_group, used)))
    else:
        h = gate(_gmm(xb, fc_1, tile_group, used)) * _gmm(xb, fc_2, tile_group, used)
    yb = _gmm(h * wb[:, None].astype(h.dtype), proj, tile_group, used)
    return _combine(static, yb, row_src, pos, tile_group)


def _run_wave(static, plan, w, *operands):
    tile, wave_tiles, _, gate = static
    return _moe_wave(gate, *operands, *moe_wave_rows(plan, w, tile, wave_tiles))


def _waves_after_the_first(static, plan, *operands):
    """Waves 1.. of the buffer, each only where the routing reached it."""
    _, wave_tiles, n_waves, _ = static
    y = jnp.zeros(operands[0].shape, jnp.float32)
    for w in range(1, n_waves):
        y = jax.lax.cond(plan["tiles_used"] > w * wave_tiles,
                         lambda y_, *a, w=w: y_ + _run_wave(static, plan, w, *a), lambda y_, *a: y_, y, *operands)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _overflow(static, plan, *operands):
    """What the rows past the first wave add: nothing, and at no cost, unless
    the routing filled it.  Differentiated by hand so that the branch not
    taken makes zeros for the five gradients only (left to ``jax.vjp``, a
    ``cond`` hands every residual of every later wave out of both branches:
    gigabytes of zeros a layer in the step that never needs them)."""
    return jax.lax.cond(plan["tiles_used"] > static[1], lambda *a: _waves_after_the_first(static, plan, *a),
                        lambda *a: jnp.zeros(a[0].shape, jnp.float32), *operands)


def _overflow_bwd(static, res, g):
    plan, operands = res

    def taken(g_, *a):
        return jax.vjp(lambda *a_: _waves_after_the_first(static, plan, *a_), *a)[1](g_)

    grads = jax.lax.cond(plan["tiles_used"] > static[1], taken,
                         lambda g_, *a: tuple(jnp.zeros_like(o) for o in a), g, *operands)
    return (None, *grads)


_overflow.defvjp(lambda static, plan, *operands: (_overflow(static, plan, *operands), (plan, operands)),
                 _overflow_bwd)


def _moe_share_planned(x, top_idx, top_w, fc_1, fc_2, proj, first, total, tile, gate=jax.nn.silu):
    """The held experts' part of the layer and the plan it was computed by
    (``moe_plan``: the server reads its ``cnt``).  ``fc_2`` None: ungated experts
    of two matrices (the server's alone: undifferentiated).  ``gate``: the gated
    experts' activation, ``jax.nn.silu`` (SwiGLU) or ``jax.nn.relu`` (the server's gated ReLU)."""
    held = fc_1.shape[0]
    wave_tiles = moe_wave_tiles(top_idx.size, held, total, tile)
    plan = moe_plan(top_idx, top_w, first, held, tile, wave_tiles)
    static = (tile, wave_tiles, plan["tile_group"].shape[0] // wave_tiles, gate)
    operands = (x, top_w, fc_1, fc_2, proj)
    y = _run_wave(static, plan, 0, *operands)
    if static[2] > 1:
        y = y + _overflow(static, plan, *operands)
    return y.astype(x.dtype), plan


@impl(PrimIDs.MOE_EXPERT_SHARE)
def _moe_share(x, top_idx, top_w, fc_1, fc_2, proj, first, total, tile=MOE_ROW_TILE):
    return _moe_share_planned(x, top_idx, top_w, fc_1, fc_2, proj, first, total, tile)[0]


@impl(PrimIDs.MOE_EXPERT_SHARE_BACKWARD)
def _moe_expert_share_backward_impl(dy, x, top_idx, top_w, fc_1, fc_2, proj, first, total, tile=MOE_ROW_TILE):
    _, vjp = jax.vjp(lambda *a: _moe_share(a[0], top_idx, *a[1:], first, total, tile), x, top_w, fc_1, fc_2, proj)
    return vjp(dy)


def get_prim_impl(pid: PrimIDs) -> Callable | None:
    return prim_impls.get(pid)


#
# The executor object: registers an eager implementation for every prim above.
# These claimed symbols are also fusible by the XLA fusion executor (they are
# pure jax-traceable callables), marked via _xla_fusible.
#

ex = OperatorExecutor("jax", version=jax.__version__)
register_executor(ex)

for _pid, _impl_fn in list(prim_impls.items()):
    _prim_sym = prim_lookup[_pid]
    _op = ex.register_operator(f"jax_{_prim_sym.name}", like=_prim_sym, fn=_impl_fn)
    _op._xla_fusible = True
    _op._prim_id = _pid
    ex.register_implementation(_pid, _op)

jax_ex = ex

add_default_executor(ex)
add_always_executor(ex)
