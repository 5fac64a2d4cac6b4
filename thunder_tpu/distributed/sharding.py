"""Mesh construction and parameter-sharding rules.

This is where the reference's DDP/FSDP mechanics (``thunder/distributed/
__init__.py:103,321`` — in-place dim-0 shards, DDPType tags, bucketing)
become TPU-idiomatic: a parallelism strategy is a *pytree of
``NamedSharding``s* over a ``jax.sharding.Mesh``.  XLA's SPMD partitioner
then inserts the all_gathers (FSDP param use), reduce_scatters (FSDP grad),
and all_reduces (DDP grad) automatically and overlaps them with compute —
replacing the reference's pack/unpack bucketing prims and wait-sorting
passes (``distributed/utils.py:14-220``).

Axis convention (the scaling-book recipe):
- ``dp``    pure data parallel (params replicated)
- ``fsdp``  data parallel with ZeRO param/grad/opt-state sharding (dim-0)
- ``tp``    megatron-style tensor parallel within attention/MLP blocks
The global batch is sharded over (``dp``, ``fsdp``); weights over
(``tp``, ``fsdp``) per the rules below.
"""
from __future__ import annotations

import math
import re
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "batch_spec",
    "kv_cache_spec",
    "fsdp_shardings",
    "ddp_shardings",
    "llama_shardings",
    "apply_shardings",
    "init_sharded",
    "ShardingRules",
]


def make_mesh(axis_sizes: dict[str, int] | None = None, *, devices=None) -> Mesh:
    """Builds a Mesh from ``{axis_name: size}``.  A size of -1 absorbs the
    remaining devices (like a reshape).  Default: all devices on one ``dp``
    axis.  Axis order matters on real hardware: the innermost axis maps to
    the fastest ICI links, so put ``tp`` last."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    if axis_sizes is None:
        axis_sizes = {"dp": n}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    n_auto = sum(1 for s in sizes if s == -1)
    if n_auto:
        fixed = math.prod(s for s in sizes if s != -1)
        auto = n // fixed
        sizes = [s if s != -1 else auto for s in sizes]
    assert math.prod(sizes) == n, f"mesh {dict(zip(names, sizes))} != {n} devices"
    return Mesh(devices.reshape(sizes), tuple(names))


def batch_spec(mesh: Mesh) -> P:
    """Batch-dim sharding: over every data-parallel axis present (dp, fsdp)."""
    axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def kv_cache_spec(cfg, mesh: Mesh | None, *, axis: str = "tp") -> P:
    """PartitionSpec for a KV cache/arena with the **heads dim at axis 2**:
    the dense ``(L, B, n_query_groups, T, hs)`` layout of
    ``models.generate.cache_shape`` AND the paged serving arena
    ``(num_blocks, L, n_query_groups, block_size, hs)`` — one rule so
    serving and ``generate()`` can never disagree on how KV bytes shard.
    The int8 pool's float32 scale arenas
    ``(num_blocks, L, n_query_groups, block_size)`` keep the heads dim at
    axis 2 as well, so this spec is a valid prefix for them too: all four
    serving arrays place with the ONE rule.  The arena under a mesh is always
    a head a row: the pool's lane-packed layout for heads under 128
    (``(num_blocks, L, n_query_groups / P, block_size, 128)``,
    ``PagedKVPool.lane_pack``) folds ``P`` heads into axis 4, which this rule
    would split across, so ``PagedKVPool`` packs on one device only.

    Heads split over ``axis`` (tensor-parallel: each device holds its
    query groups' cache, attention stays device-local, only the output
    projection reduces).  Falls back to full replication (``P()``) when
    the mesh is absent, the axis is missing/trivial, or ``axis`` does not
    divide ``n_query_groups`` — same degrade-don't-error policy as
    :func:`ShardingRules` via ``_prune_spec``.
    """
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return P()
    if getattr(cfg, "latent", False):
        # a latent cache's axis 2 is one row for all heads: nothing to split
        # (the serving engine refuses a mesh for such a model, with the reason)
        return P()
    if cfg.n_query_groups % mesh.shape[axis] != 0:
        return P()
    return P(None, None, axis)


def _divisible(dim_size: int, mesh: Mesh, axes) -> bool:
    if axes is None:
        return True
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    k = math.prod(mesh.shape[a] for a in axes)
    return dim_size % k == 0


def _prune_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drops sharding on axes that don't exist in the mesh, are trivial
    (size 1), or don't divide the dimension — so tiny test configs and odd
    shapes degrade to replication instead of erroring."""
    out = []
    for i, axes in enumerate(spec):
        if i >= len(shape):  # spec longer than rank: extra entries degrade too
            break
        if axes is None:
            out.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        axes_t = tuple(a for a in axes_t if a in mesh.axis_names and mesh.shape[a] > 1)
        if not axes_t or not _divisible(shape[i], mesh, axes_t):
            out.append(None)
        elif len(axes_t) == 1:
            out.append(axes_t[0])
        else:
            out.append(axes_t)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


class ShardingRules:
    """Path-pattern → PartitionSpec rules (first match wins).

    Paths are '/'-joined pytree key paths, e.g. ``blocks/3/attn/wq``.
    Patterns are regexes matched with ``re.search``.
    """

    def __init__(self, rules: Sequence[tuple[str, P]], default: P = P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec_for(self, path: str, shape, mesh: Mesh) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return _prune_spec(spec, shape, mesh)
        return _prune_spec(self.default, shape, mesh)

    def shardings(self, params, mesh: Mesh):
        def to_path(kp) -> str:
            parts = []
            for k in kp:
                if hasattr(k, "key"):
                    parts.append(str(k.key))
                elif hasattr(k, "idx"):
                    parts.append(str(k.idx))
                else:
                    parts.append(str(k))
            return "/".join(parts)

        return jax.tree_util.tree_map_with_path(
            lambda kp, x: NamedSharding(mesh, self.spec_for(to_path(kp), x.shape, mesh)), params
        )


def ddp_shardings(params, mesh: Mesh):
    """DDP (reference ddp(), distributed/__init__.py:103): params replicated;
    grad all-reduce falls out of batch sharding under pjit."""
    return jax.tree_util.tree_map(lambda x: NamedSharding(mesh, P()), params)


def fsdp_shardings(params, mesh: Mesh, *, axis: str = "fsdp", min_size: int = 2**10):
    """FSDP/ZeRO (reference fsdp(), distributed/__init__.py:321): every
    param's dim-0 sharded over ``axis``; small/indivisible params stay
    replicated (the reference shards unconditionally because NCCL gathers are
    explicit; XLA prefers replicating tiny tensors)."""

    def leaf(x):
        if x.ndim >= 1 and x.size >= min_size and _divisible(x.shape[0], mesh, axis):
            return NamedSharding(mesh, _prune_spec(P(axis), x.shape, mesh))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(leaf, params)


# Megatron-style TP + ZeRO FSDP rules for the llama param pytree
# (thunder_tpu/models/llama.py layout)
_LLAMA_RULES = [
    # attention: wq/wk/wv split heads (dim0=out_features) over tp, fsdp on dim1
    (r"attn/w[qkv]$", P("tp", "fsdp")),
    # wo: row-parallel (dim1=in_features over tp)
    (r"attn/wo$", P("fsdp", "tp")),
    # MLP: up/gate column-parallel; down row-parallel
    (r"mlp/fc(_[12])?$", P("tp", "fsdp")),
    (r"mlp/proj$", P("fsdp", "tp")),
    # embeddings / head: vocab dim over tp, embd over fsdp.  Do NOT shard
    # the embd (feature) dim instead: XLA SPMD mis-partitions the embedding
    # gather/scatter on a feature-sharded table — measured on the 8-device
    # mesh: P(None, "tp") corrupts even the FORWARD loss (5.5664 vs 5.5758),
    # P(None, ("tp", "fsdp")) corrupts the wte grad by 5e-2 abs.  Vocab
    # sharding is exact (grad diff 2e-8 vs single-device); its backward
    # scatter hazard is retired by computing the embedding grad as a
    # one-hot matmul under a mesh (jaxex._embedding_backward_impl).
    (r"^wte$", P("tp", "fsdp")),
    (r"^lm_head$", P("tp", "fsdp")),
    # norm scales: replicated (tiny)
    (r"norm|ln_f", P()),
]

llama_rules = ShardingRules(_LLAMA_RULES, default=P("fsdp"))


def llama_shardings(params, mesh: Mesh):
    """Combined TP(+SP-ready) × FSDP × DP shardings for the llama family."""
    return llama_rules.shardings(params, mesh)


def _place_no_alias(x, s):
    """``device_put`` that never aliases the source buffer.

    When the source device is in the target sharding, ``jax.device_put``
    zero-copies the same-device shard (observed on jax 0.9 CPU and
    single-device placements).  A later donation of the placed array — the
    TrainStep default — would then silently delete the *user's original*
    array too.  Detect the alias and break it with an explicit copy; the
    copy is transient and only made when aliasing actually occurred.
    """
    def _ptrs(a) -> set:
        try:
            return {sh.data.unsafe_buffer_pointer() for sh in a.addressable_shards}
        except Exception:
            return set()  # backends without buffer pointers

    y = jax.device_put(x, s)
    if isinstance(x, jax.Array) and (y is x or _ptrs(x) & _ptrs(y)):
        # sharding-preserving copy: never gathers (a plain jnp.array copy
        # would materialize sharded params unsharded — OOM at scale)
        y = jax.jit(jnp.copy, out_shardings=s)(x)
    return y


def apply_shardings(tree, shardings):
    """Places a pytree onto devices per a matching pytree of shardings."""
    return jax.tree_util.tree_map(_place_no_alias, tree, shardings)


def init_sharded(init_fn: Callable[[], Any], shardings_fn: Callable[[Any], Any]):
    """Builds a pytree that is born placed: ``init_fn()`` runs under
    ``jax.jit`` with ``out_shardings = shardings_fn(shapes)``, where
    ``shapes`` is its ``jax.eval_shape`` result (any ``*_shardings`` rule
    here accepts it).  Each device materializes only its own shards, so a
    model larger than one chip can be initialised; building every leaf
    eagerly and moving it with :func:`apply_shardings` puts the whole tree
    on the default device first.  The PRNG is partitionable — same bits per
    key whatever the sharding — so the values are the eager call's, up to
    the last-ulp rounding of a fused program."""
    return jax.jit(init_fn, out_shardings=shardings_fn(jax.eval_shape(init_fn)))()
