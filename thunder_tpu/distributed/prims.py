"""Distributed communication primitives.

Capability analog of the reference's ``thunder/distributed/prims.py:13-26``
(ALL_GATHER, ALL_REDUCE, BROADCAST, REDUCE_SCATTER, SYNCHRONIZE, WAIT, ...),
re-designed for TPU:

- collectives are *named-axis* operations (``axis_name`` over a
  ``jax.sharding.Mesh``), not process-group calls: inside ``shard_map`` or
  ``pjit`` they lower to XLA collectives riding ICI/DCN;
- there are no Future proxies or wait-sorting passes — XLA's latency-hiding
  scheduler overlaps collectives with compute, so ``wait`` is an identity
  kept only for API parity (reference FutureTensorProxy, proxies.py:1064);
- axis sizes are static (trace-time) values, matching XLA's static-shape
  compilation model.
"""
from __future__ import annotations

import sys
from enum import Enum, auto, unique
from numbers import Number

from thunder_tpu.core.baseutils import check
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.symbol import Symbol

_this_module = sys.modules[__name__]
__print_name__ = "dist_prims"

__all__ = [
    "DistPrimIDs",
    "DistributedReduceOps",
    "all_gather",
    "all_reduce",
    "reduce_scatter",
    "broadcast",
    "ppermute",
    "all_to_all",
    "axis_index",
    "wait",
    "synchronize",
    "shard_map_compat",
]


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.  Every shard_map in
    the repo goes through here, so that choice lives in one place: the
    per-shard bodies close over collectives the checker cannot see through."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@unique
class DistPrimIDs(Enum):
    ALL_GATHER = auto()
    ALL_REDUCE = auto()
    REDUCE_SCATTER = auto()
    BROADCAST = auto()
    PPERMUTE = auto()
    ALL_TO_ALL = auto()
    AXIS_INDEX = auto()
    WAIT = auto()
    SYNCHRONIZE = auto()


class DistributedReduceOps(Enum):
    """Reduction ops (reference prims.py:31-40 supports SUM only; we add the
    full lattice XLA provides)."""

    SUM = auto()
    MEAN = auto()
    MAX = auto()
    MIN = auto()


def _make_dist_prim(id: DistPrimIDs, name: str, meta):
    sym = Symbol(name=name, meta=meta, id=id, is_prim=True, module=_this_module)
    return sym


def _like(a: TensorProxy, shape=None) -> TensorProxy:
    return TensorProxy(
        shape=tuple(shape if shape is not None else a.shape),
        device=a.device,
        dtype=a.dtype,
        requires_grad=False,
    )


#
# meta functions (shape/dtype rules; all axis sizes static)
#


def _all_gather_meta(a: TensorProxy, axis_name, axis_size: int, dim: int = 0, tiled: bool = True):
    check(isinstance(axis_size, (int, Number)) and axis_size >= 1, lambda: f"bad axis_size {axis_size}")
    shape = list(a.shape)
    if tiled:
        shape[dim] = shape[dim] * int(axis_size)
    else:
        shape.insert(0, int(axis_size))
    return _like(a, shape)


def _all_reduce_meta(a: TensorProxy, axis_name, op: DistributedReduceOps = DistributedReduceOps.SUM):
    return _like(a)


def _reduce_scatter_meta(
    a: TensorProxy, axis_name, axis_size: int, dim: int = 0, op: DistributedReduceOps = DistributedReduceOps.SUM
):
    shape = list(a.shape)
    check(
        shape[dim] % int(axis_size) == 0,
        lambda: f"reduce_scatter dim {dim} (={shape[dim]}) not divisible by axis size {axis_size}",
    )
    shape[dim] = shape[dim] // int(axis_size)
    return _like(a, shape)


def _broadcast_meta(a: TensorProxy, axis_name, root: int = 0):
    return _like(a)


def _ppermute_meta(a: TensorProxy, axis_name, perm):
    return _like(a)


def _all_to_all_meta(a: TensorProxy, axis_name, axis_size: int, split_dim: int, concat_dim: int):
    shape = list(a.shape)
    check(shape[split_dim] % int(axis_size) == 0, lambda: f"all_to_all split dim not divisible by {axis_size}")
    shape[split_dim] = shape[split_dim] // int(axis_size)
    shape[concat_dim] = shape[concat_dim] * int(axis_size)
    return _like(a, shape)


def _axis_index_meta(axis_name):
    from thunder_tpu.core import dtypes
    from thunder_tpu.core.devices import cpu

    return TensorProxy(shape=(), device=cpu, dtype=dtypes.int32, requires_grad=False)


def _wait_meta(a: TensorProxy):
    return _like(a)


def _synchronize_meta(a: TensorProxy, axis_name, axis_size: int = 1, sharded: bool = False, dim: int = 0):
    if sharded:
        return _all_gather_meta(a, axis_name, axis_size, dim=dim, tiled=True)
    return _like(a)


all_gather = _make_dist_prim(DistPrimIDs.ALL_GATHER, "all_gather", _all_gather_meta)
all_reduce = _make_dist_prim(DistPrimIDs.ALL_REDUCE, "all_reduce", _all_reduce_meta)
reduce_scatter = _make_dist_prim(DistPrimIDs.REDUCE_SCATTER, "reduce_scatter", _reduce_scatter_meta)
broadcast = _make_dist_prim(DistPrimIDs.BROADCAST, "broadcast", _broadcast_meta)
ppermute = _make_dist_prim(DistPrimIDs.PPERMUTE, "ppermute", _ppermute_meta)
all_to_all = _make_dist_prim(DistPrimIDs.ALL_TO_ALL, "all_to_all", _all_to_all_meta)
axis_index = _make_dist_prim(DistPrimIDs.AXIS_INDEX, "axis_index", _axis_index_meta)
wait = _make_dist_prim(DistPrimIDs.WAIT, "wait", _wait_meta)
synchronize = _make_dist_prim(DistPrimIDs.SYNCHRONIZE, "synchronize", _synchronize_meta)


#
# JAX implementations (valid inside shard_map/pjit over a Mesh)
#


def _register_impls():
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors.jaxex import impl

    @impl(DistPrimIDs.ALL_GATHER)
    def _all_gather_impl(a, axis_name, axis_size, dim=0, tiled=True):
        return jax.lax.all_gather(a, axis_name, axis=dim, tiled=tiled)

    @impl(DistPrimIDs.ALL_REDUCE)
    def _all_reduce_impl(a, axis_name, op=DistributedReduceOps.SUM):
        if op is DistributedReduceOps.SUM:
            return jax.lax.psum(a, axis_name)
        if op is DistributedReduceOps.MEAN:
            return jax.lax.pmean(a, axis_name)
        if op is DistributedReduceOps.MAX:
            return jax.lax.pmax(a, axis_name)
        if op is DistributedReduceOps.MIN:
            return jax.lax.pmin(a, axis_name)
        raise ValueError(f"Unknown reduce op {op}")

    @impl(DistPrimIDs.REDUCE_SCATTER)
    def _reduce_scatter_impl(a, axis_name, axis_size, dim=0, op=DistributedReduceOps.SUM):
        check(
            op in (DistributedReduceOps.SUM, DistributedReduceOps.MEAN),
            lambda: "reduce_scatter supports SUM/MEAN",
        )
        out = jax.lax.psum_scatter(a, axis_name, scatter_dimension=dim, tiled=True)
        if op is DistributedReduceOps.MEAN:
            out = out / axis_size
        return out

    @impl(DistPrimIDs.BROADCAST)
    def _broadcast_impl(a, axis_name, root=0):
        idx = jax.lax.axis_index(axis_name)
        return jax.lax.psum(jnp.where(idx == root, a, jnp.zeros_like(a)), axis_name)

    @impl(DistPrimIDs.PPERMUTE)
    def _ppermute_impl(a, axis_name, perm):
        return jax.lax.ppermute(a, axis_name, perm=[tuple(p) for p in perm])

    @impl(DistPrimIDs.ALL_TO_ALL)
    def _all_to_all_impl(a, axis_name, axis_size, split_dim, concat_dim):
        return jax.lax.all_to_all(a, axis_name, split_axis=split_dim, concat_axis=concat_dim, tiled=True)

    @impl(DistPrimIDs.AXIS_INDEX)
    def _axis_index_impl(axis_name):
        return jax.lax.axis_index(axis_name)

    @impl(DistPrimIDs.WAIT)
    def _wait_impl(a):
        # XLA handles async scheduling; identity for API parity
        return a

    @impl(DistPrimIDs.SYNCHRONIZE)
    def _synchronize_impl(a, axis_name, axis_size=1, sharded=False, dim=0):
        if sharded:
            return jax.lax.all_gather(a, axis_name, axis=dim, tiled=True)
        return a


_register_impls()
