"""Mesh-parallel serving: the SPMD layer under the continuous-batching engine.

The engine's math (gather → ``forward_with_cache`` → scatter, see
:mod:`thunder_tpu.serving.engine`) is already pure jnp inside ``jax.jit``;
this module supplies everything needed to run those bucket programs SPMD
over a :class:`jax.sharding.Mesh`:

- the **arena sharding**: the paged K/V arenas
  ``(num_blocks, L, n_query_groups, block_size, hs)`` carry a
  ``NamedSharding`` splitting the KV-heads dim over ``tp`` — the same
  :func:`thunder_tpu.distributed.kv_cache_spec` rule the dense
  ``generate()`` cache uses (heads dim at axis 2 in both layouts), so each
  device holds only its heads' blocks while the host-side allocator
  (free list, refcounts, prefix index) is untouched; the int8 pool's
  float32 scale arenas keep the heads dim at axis 2 too, so the one spec
  places them as a pytree prefix;
- **explicit program shardings**: per-bucket prefill/decode programs get
  ``in_shardings``/``out_shardings`` (params as placed, arenas per the
  arena sharding, every host-built table/token array replicated), with
  ``donate_argnums`` preserved so arena updates stay in place *per shard*;
- a **mesh fingerprint** extending the module-level program-cache key, so
  programs compile once per (mesh, bucket) and engines on the same mesh
  share them while a different device set never aliases a stale program;
- **observability**: per-shard arena bytes and the collective count of one
  compiled decode program (from its optimized-HLO text), surfaced through
  ``engine.stats()["mesh"]``, the flight-recorder snapshot, and
  ``serving.mesh.*`` registry gauges.

Attention under this sharding is Megatron-style: per-head score/value work
is device-local (q heads and KV groups co-shard), the output projection
all-reduces, and the vocab-sharded head resolves sampling with one small
collective — exactly the placement ``distributed.tp_fsdp`` gives the
params, which is the default when ``tt.serve(..., mesh=...)`` is called
without explicit ``shardings``.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from thunder_tpu.distributed.sharding import apply_shardings, kv_cache_spec, llama_shardings

__all__ = [
    "mesh_fingerprint",
    "arena_sharding",
    "split_mesh",
    "place_params",
    "program_shardings",
    "collective_counts",
    "per_shard_bytes",
]


def mesh_fingerprint(mesh: Mesh | None) -> tuple | None:
    """Hashable identity of a mesh for program-cache keys: axis names,
    axis sizes, and the concrete device ids in mesh order.  Two mesh
    objects over the same devices in the same layout fingerprint equal
    (their compiled programs are interchangeable); the same shape over a
    different device set does not."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def split_mesh(mesh: Mesh, *, axis: str = "dp") -> list[Mesh]:
    """Splits ``mesh`` along its ``axis`` dimension into one submesh per
    index — the device-set side of data-parallel serving replication.

    Each returned submesh keeps every *other* axis of the parent (so a
    ``(dp=2, tp=2)`` mesh yields two 2-device ``("tp",)`` meshes whose
    engines stay TP-sharded), in the parent's device order.  A mesh whose
    only axis is ``axis`` degrades each slice to a single-device ``("tp",)``
    mesh of size 1 — every sharding rule (:func:`kv_cache_spec`,
    ``llama_shardings``) degrades to replicated on a trivial axis, so the
    per-replica engine runs effectively unsharded while still carrying a
    distinct :func:`mesh_fingerprint` (its own device id), which keeps each
    replica's compiled programs from aliasing another device's placement.

    Works unchanged for a ``dist.multihost.hybrid_mesh`` whose leading
    (DCN) axis is the replica axis: each slice is then one ICI-connected
    device block.  Multi-host caveat: the *router* that consumes these
    submeshes is host-local — run it on process 0 only (single-process
    serving is the documented fallback; see ``serving.router``)."""
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {axis!r} axis to split on (axes: {mesh.axis_names})"
        )
    rest = tuple(a for a in mesh.axis_names if a != axis)
    idx = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, idx, 0)
    out = []
    for i in range(devs.shape[0]):
        sub = devs[i]
        if rest:
            out.append(Mesh(sub, rest))
        else:
            # a dp-only mesh: each slice is one device (indexing the object
            # array yields the bare Device), kept as a trivial ("tp",) mesh
            # so every axis-keyed rule degrades cleanly
            out.append(Mesh(np.array([sub], dtype=object), ("tp",)))
    return out


def arena_sharding(cfg, mesh: Mesh, *, axis: str = "tp") -> NamedSharding:
    """NamedSharding of the paged K/V arenas: heads-over-``axis`` via the
    shared :func:`kv_cache_spec` rule (the arena keeps the heads dim at
    axis 2 just like the dense cache, so one spec serves both layouts);
    replicated when the rule degrades.  Re-prefill recovery reuses this
    same sharding when it rebuilds arenas (``PagedKVPool._zeros`` allocates
    shard-local through it), so a recovered mesh engine keeps the exact
    placement the bucket programs were compiled against."""
    return NamedSharding(mesh, kv_cache_spec(cfg, mesh, axis=axis))


def place_params(params, mesh: Mesh, shardings=None):
    """Places ``params`` on the mesh once, at engine construction.

    ``shardings`` is a pytree of ``NamedSharding``s (from
    ``distributed.llama_shardings`` / ``fsdp_shardings`` / custom rules);
    ``None`` defaults to the llama TP×FSDP rules — the placement
    ``distributed.tp_fsdp`` uses, which is what the differential parity
    guarantee is tested against.  Already-placed params are a no-op
    (``apply_shardings`` never aliases, so donation stays safe)."""
    if shardings is None:
        shardings = llama_shardings(params, mesh)
    return apply_shardings(params, shardings)


def program_shardings(kind: str, params, mesh: Mesh, arena_sh: NamedSharding,
                      *, draft_params=None, draft_arena_sh=None) -> dict:
    """``in_shardings``/``out_shardings`` for a bucket program.

    Everything the host builds per step (token/pos/table/dest arrays, PRNG
    keys, LoRA factor arenas + slot indices) is replicated — small next to
    the arenas; params keep their placement; the arena pytree carries
    ``arena_sh`` as a *prefix* sharding in AND out so the donated update is
    shard-local (no resharding between steps).  The one
    ``kv_cache_spec``-derived sharding covers the whole arena dict: the
    int8 path's float32 scale arenas keep the heads dim at axis 2 just
    like the data arenas, so the spec applies to both ranks.

    Argument orders match ``ServingEngine._build_prefill`` /
    ``_build_prefill_chunk`` / ``_build_decode_paged`` exactly:

    - prefill: ``(params, toks, pos, n_real, arenas, table, dest, key,
      lora, slot)`` → ``(tok, arenas, key, qerr)``
    - prefill_fresh: prefill's row without ``pos`` and ``table``
    - prefill_chunk: ``(params, toks, pos, arenas, table, dest, lora,
      slot)`` → ``(arenas, qerr)``
    - decode_paged:  ``(params, toks, pos, tables, arenas, keys, lora,
      slots)`` → ``(nxt, new_keys, new_pos, arenas)`` (write destinations are
      derived in-program from ``tables``/``pos``, and the returned device
      outputs chain into the next step's inputs); inside the program the
      paged kernels run under ``shard_map`` with heads-local specs matching
      ``arena_sh``

    Donation composes with the async engine's deferred materialization:
    the returned arena pytree carries the same per-shard sharding in and
    out, so while the host defers ``np.asarray`` on the small replicated
    outputs (tokens/keys), the donated shard-local arena buffers chain
    directly into the next dispatched program — no reshard, no gather,
    whether or not anything has materialized yet.
    """
    repl = NamedSharding(mesh, P())
    param_sh = jax.tree_util.tree_map(lambda x: x.sharding, params)
    if kind == "prefill":
        return dict(
            in_shardings=(param_sh, repl, repl, repl, arena_sh, repl, repl, repl, repl, repl),
            out_shardings=(repl, arena_sh, repl, repl),
        )
    if kind == "prefill_fresh":
        return dict(
            in_shardings=(param_sh, repl, repl, arena_sh, repl, repl, repl, repl),
            out_shardings=(repl, arena_sh, repl, repl),
        )
    if kind in ("prefill_chunk", "prefill_chunk_paged"):
        # the paged chunk kind keeps the exact gather-chunk signature, so
        # it shares the row (inside the program the kernels run under
        # shard_map with heads-local specs matching ``arena_sh``)
        return dict(
            in_shardings=(param_sh, repl, repl, arena_sh, repl, repl, repl, repl),
            out_shardings=(arena_sh, repl),
        )
    if kind == "decode_paged":
        return dict(
            in_shardings=(param_sh, repl, repl, repl, arena_sh, repl, repl, repl),
            out_shardings=(repl, repl, repl, arena_sh),
        )
    # the speculative lane (serving.speculative): draft params/arena carry
    # their own placements; the host-built chunk arrays stay replicated
    dparam_sh = jax.tree_util.tree_map(lambda x: x.sharding, draft_params)
    if kind == "spec_prefill":
        # (params, dparams, toks, pos, n_real, arenas, darenas, table,
        #  dest, key, lora, slot) -> (tok, arenas, darenas, key, qerr)
        return dict(
            in_shardings=(param_sh, dparam_sh, repl, repl, repl, arena_sh,
                          draft_arena_sh, repl, repl, repl, repl, repl),
            out_shardings=(repl, arena_sh, draft_arena_sh, repl, repl),
        )
    if kind == "spec_prefill_chunk":
        # (params, dparams, toks, pos, arenas, darenas, table, dest, lora,
        #  slot) -> (arenas, darenas, qerr)
        return dict(
            in_shardings=(param_sh, dparam_sh, repl, repl, arena_sh,
                          draft_arena_sh, repl, repl, repl, repl),
            out_shardings=(arena_sh, draft_arena_sh, repl),
        )
    if kind == "draft_decode":
        # (dparams, toks, pos, tables, darenas, keys)
        #   -> (drafts, q_rows, keys_mid, darenas)
        return dict(
            in_shardings=(dparam_sh, repl, repl, repl, draft_arena_sh, repl),
            out_shardings=(repl, repl, repl, draft_arena_sh),
        )
    assert kind == "verify_paged", kind
    # (params, toks, pos, tables, arenas, drafts, q_rows, keys, lora,
    #  slots) -> (emitted, n_emit, y, new_keys, new_pos, arenas)
    return dict(
        in_shardings=(param_sh, repl, repl, repl, arena_sh, repl, repl,
                      repl, repl, repl),
        out_shardings=(repl, repl, repl, repl, repl, arena_sh),
    )


# HLO collective ops XLA's SPMD partitioner inserts (both sync and -start
# async forms); counted from one compiled program's optimized HLO
_COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def collective_counts(prog, *example_args) -> dict[str, int]:
    """Collective-op census of one jitted bucket program, from the
    optimized HLO of an AOT lowering at ``example_args``
    (ShapeDtypeStructs suffice — the program's own ``in_shardings`` drive
    the partitioner).  One extra XLA compile; callers cache the result per
    (mesh, static-config)."""
    structs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example_args
    )
    txt = prog.lower(*structs).compile().as_text()
    counts = {}
    for op in _COLLECTIVE_OPS:
        n = len(re.findall(rf"\b{op}(?:-start)?\(", txt))
        if n:
            counts[op] = n
    counts["total"] = sum(counts.values())
    return counts


def per_shard_bytes(arena) -> int:
    """Bytes of one device's shard of an arena array (the quantity that
    must fit a single chip's HBM — the whole point of mesh serving)."""
    shards = getattr(arena, "addressable_shards", None)
    if not shards:
        return int(arena.nbytes)
    return max(int(s.data.nbytes) for s in shards)
