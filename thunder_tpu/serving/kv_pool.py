"""Paged KV-cache pool: a preallocated block arena + free-list allocator.

vLLM's PagedAttention insight, recast for the XLA static-shape world: instead
of one contiguous per-request cache, all requests share one arena of
fixed-size **blocks** — ``(num_blocks, L, n_query_groups, block_size, hs)``
for K and V each (per-block geometry from
:func:`models.generate.kv_block_shape`, so a gather over a request's block
table reassembles exactly the dense :func:`models.generate.cache_shape`
layout that ``forward_with_cache`` already consumes).  A head size that
divides 128 is stored **lane-dense**: ``P = 128 // hs`` consecutive KV heads of
a token side by side in one 128-lane row, ``(num_blocks, L, n_query_groups / P,
block_size, 128)`` (``PagedKVPool.lane_pack``), because the chip would hold a
narrower last axis padded to 128 lanes (twice the bytes at a head of 64) and
the decode kernel's walk cannot copy a slice of such a row.  Fragmentation is
bounded to one partial block per request, admission control becomes a free-
block count, and finished/expired requests return their blocks in O(blocks).

Design points:

- **Physical block 0 is a reserved garbage sink.**  Every compiled serving
  program is static-shape: padding rows in a bucketed batch and
  not-yet-reached table slots still need *some* valid physical index to
  read from / write to.  They all point at block 0, whose contents are never
  attended (the positional keep-mask excludes them), so no dynamic shapes
  and no masked scatters are ever needed.
- **Reference counting** enables prefix sharing: two requests with the same
  block-aligned prompt prefix map their leading table entries to the same
  physical blocks (``share``), and a block returns to the free list only
  when its last owner releases it.
- **Quantized block storage** (``kv_dtype="int8"`` or ``"fp8"``): the
  arenas store 1-byte values plus a float32 scale arena at per-block-slot,
  per-head granularity (:mod:`thunder_tpu.serving.quant`) —
  ~``hs*itemsize/(hs+4)``× the resident requests per arena byte, with
  quantize-on-scatter and dequant-on-gather inside the jitted programs.
- **Chunk scatter granularity**: a prefill piece (whole prompt, shared-
  prefix suffix, or one chunk of a chunked prefill) writes only the block
  range its tokens cover — :func:`chunk_tables` builds the sink-padded
  gather/scatter tables for any ``[pos, pos + n)`` token window, so the
  prefill and chunked-prefill lanes share one granularity rule.
- The pool owns only the *allocator* state (host-side, O(num_blocks) ints)
  and the arena arrays.  All array movement (gather/scatter) is pure
  jnp code in :mod:`thunder_tpu.serving.engine`'s jitted bucket programs,
  which donate the arenas so updates stay in place.
- Sliding-window models keep the plain positional layout (slot = position);
  the window shows up as the keep-mask band plus **early block release**:
  once every position in a block has slid out of the window, the scheduler
  frees it and the table entry falls back to the sink block.
"""
from __future__ import annotations

import functools
import math
from collections import deque
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.models.generate import kv_block_shape, kv_lane_pack, ring_block_shape, ring_blocks
from thunder_tpu.serving.quant import is_quantized_kv, resolve_kv_dtype

__all__ = ["PoolExhaustedError", "ArenaMismatchError", "PagedKVPool", "StatePool",
           "PrefixIndex", "chunk_tables", "dest_for_pos", "gather_state", "scatter_state", "gather_rows", "pack_lanes",
           "pack_state_heads", "unpack_state_heads", "tiled_bytes",
           "ring_tables", "ring_dest", "RING_ARENAS", "OCCUPANCY_WINDOW"]

SINK_BLOCK = 0  # reserved physical block for padding/expired table entries

OCCUPANCY_WINDOW = 128  # samples retained in the occupancy timeline ring


class PoolExhaustedError(RuntimeError):
    """Raised by :meth:`PagedKVPool.alloc` when fewer free blocks remain
    than requested.  Admission control catches this to queue the request."""


class ArenaMismatchError(ValueError):
    """An arena (or arena write) does not match the pool's geometry
    (shape/dtype) or placement (sharding).  Caught at the swap/scatter, not
    steps later as garbage KV.

    Attributes: ``arena`` ("k" | "v" | "k_scale" | "v_scale" | "scatter"),
    ``field`` ("shape" | "dtype" | "sharding"), ``expected``, ``got``."""

    def __init__(self, arena: str, field: str, expected, got, *, msg: str | None = None):
        self.arena = arena
        self.field = field
        self.expected = expected
        self.got = got
        super().__init__(
            msg if msg is not None else (
                f"refusing to install {arena}-arena with mismatched {field}: "
                f"program returned {got!r}, pool expects {expected!r} — the "
                f"producing bucket program is writing a different arena "
                f"geometry/placement than this pool owns"
            )
        )


SINK_SLOT = 0   # reserved state slot for padding rows, as block 0 is for table entries
RING_ARENAS = ("k_ring", "v_ring")   # the sliding_attention layers' K and V, a ring of blocks a state slot


class StatePool:
    """What a model's linear_attention or conv layers keep a request, beside
    its KV blocks: slot-indexed arenas of what ``generate.state_shapes`` names.
    linear_attention: the delta rule's states, ``state (slots +
    1, L_lin, dk, nv dv)`` in float32 (``STATE_DTYPE``: no option of the
    engine's, a deployment holds what the configuration states): a row's value
    heads side by side, head ``h``'s ``(dk, dv)`` matrix in columns ``[h dv, (h +
    1) dv)`` with the key on the sublanes (:func:`pack_state_heads`;
    ``state_heads`` = ``nv``).  The layout is the pool's, as ``lane_pack`` is the
    K/V arena's: the chip pads an array's last axis to whole 128-lane tiles, so
    a head of 192 a row would lie as 256 (a third more bytes a slot and a decode
    step), and thirty of them side by side are 45 tiles exactly; the dense cache
    keeps ``(L_lin, B, nv, dk, dv)`` and :func:`gather_state` / :func:`scatter_state`
    cross between the two.  And the conv's last inputs, ``conv (slots + 1, L_lin,
    K - 1, channels)`` at the compute dtype.  conv (a gated short convolution): the
    tails ``conv (slots + 1, L_conv, conv_kernel - 1, n_embd)`` at the compute
    dtype, and no ``state``.  ssm (a selective scan): ``state (slots + 1, L_ssm,
    ssm_state, ssm_inner)`` in float32 and ``conv (slots + 1, L_ssm, K - 1,
    ssm_inner)``.  A model with sliding_attention layers keeps their K and V
    here too, a table a layer kind: a slot owns a **ring** of ``ring_blocks`` =
    ``ceil(layer_window / block_size) + 1`` blocks of ``k_ring`` / ``v_ring``
    (``((slots + 1) * ring_blocks, L_ring, ng / P, block_size, P hs)``, laid out
    as the paged arenas are), block ``i`` of the sequence in entry ``i %
    ring_blocks`` of the slot's ring (:func:`ring_tables`), overwritten when its
    last token has left the window: the window kind's reservation is the ring,
    whatever the request's length, where the other kind's is the whole length
    in blocks of the paged arenas.  A request leases one slot at admission
    and gives it back when it finishes; slot 0 is the garbage sink padding
    rows of a static-shape program point at.  The arrays travel in the KV
    pool's ``arenas`` pytree (donated beside K and V, rebuilt with them); a
    leased slot's contents are whatever its last owner left: the prompt's
    first piece starts from zeros in the program, not here."""

    STATE_DTYPE = jnp.float32

    def __init__(self, cfg, slots: int, dtype, *, block_size: int = 16, lane_pack: int = 1):
        from thunder_tpu.models.generate import state_shapes

        if slots < 1:
            raise ValueError(f"state_slots must be >= 1, got {slots}")
        shapes = state_shapes(cfg, slots + 1)
        # slot-major: a row's state is one contiguous slab a kernel can name by its slot
        self.shapes = {k: (v[1], v[0], *v[2:]) for k, v in shapes.items()}
        # the delta rule's state, a matrix a value head: the heads side by side on the lanes
        # (0: another kind's state, or none: the arena's rows are the dense cache's)
        self.state_heads = 0
        if len(self.shapes.get("state", ())) == 5:
            n, L, nv, dk, dv = self.shapes["state"]
            self.state_heads, self.shapes["state"] = nv, (n, L, dk, nv * dv)
        self.ring_blocks = ring_blocks(cfg, block_size)
        if self.ring_blocks:
            ring = ((slots + 1) * self.ring_blocks, *ring_block_shape(cfg, block_size, lane_pack))
            self.shapes.update({name: ring for name in RING_ARENAS})
        self.dtypes = {k: jnp.dtype(self.STATE_DTYPE if k == "state" else dtype) for k in self.shapes}
        self.num_slots = int(slots)
        self.layers = len(cfg.state_layers)
        self._free: list[int] = list(range(self.num_slots, SINK_SLOT, -1))   # pop() -> lowest id
        self._free_low_water = len(self._free)
        self.rebuild()

    def rebuild(self) -> None:
        self._arenas = {k: jnp.zeros(shape, self.dtypes[k]) for k, shape in self.shapes.items()}

    @property
    def arenas(self) -> dict:
        """``{"state", "conv"}`` for linear_attention layers, ``{"conv"}`` for conv layers."""
        return dict(self._arenas)

    @property
    def state(self):
        """The delta rule's state arena, or None for a model of conv layers."""
        return self._arenas.get("state")

    @property
    def conv(self):
        return self._arenas["conv"]

    def install(self, arenas: dict) -> None:
        """The arenas a donated program returned, in place of the ones it took."""
        self._arenas = {k: arenas[k] for k in self.shapes}

    @property
    def free_low_water(self) -> int:
        """Fewest free slots ever observed."""
        return self._free_low_water

    @property
    def leased(self) -> int:
        return self.num_slots - len(self._free)

    def can_lease(self) -> bool:
        return bool(self._free)

    def lease(self) -> int:
        if not self._free:
            raise PoolExhaustedError(f"no free state slot of {self.num_slots}")
        slot = self._free.pop()
        self._free_low_water = min(self._free_low_water, len(self._free))
        return slot

    def free(self, slot: int) -> None:
        if slot == SINK_SLOT:
            return
        if slot in self._free or not 0 < slot <= self.num_slots:
            raise ValueError(f"double free of state slot {slot}")
        self._free.append(slot)

    def slot_bytes(self) -> int:
        """Bytes one slot costs across its arenas: the unit beside
        ``block_bytes`` in byte-based admission."""
        return self.arena_bytes() // (self.num_slots + 1)

    def arena_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self._arenas.values())

    def laid_out_bytes(self, name: str | None = None) -> int:
        """Bytes the chip holds for the arenas (for ``name`` alone), their last two
        axes in whole tiles (:func:`tiled_bytes`): what ``arena_bytes`` counts, and the padding."""
        return sum(tiled_bytes(a.shape, a.dtype) for k, a in self._arenas.items() if name in (None, k))

    def slot_rows(self, slot: int) -> dict:
        """What ``slot`` holds, the rings apart, a layer a row as the dense cache keeps it:
        the delta rule's heads a matrix each again, ``state (L_lin, nv, dk, dv)``."""
        rows = {name: arena[slot] for name, arena in self._arenas.items()
                if name not in RING_ARENAS and not (name == "state" and self.state_heads)}
        if self.state_heads:
            rows["state"] = slot_state_heads(self._arenas["state"], slot, heads=self.state_heads)
        return rows

    def ring_bytes(self) -> int:
        """Bytes of the ring arenas (0 without sliding_attention layers)."""
        return sum(int(self._arenas[name].nbytes) for name in RING_ARENAS if name in self._arenas)

    def snapshot(self) -> dict:
        """``dtype`` is the recurrent state's storage where there is one, else the tails'.
        ``ring_*``: the window kind's table, a ring a slot; its fill is the slots leased,
        its bytes a slot's rings (``ring_slot_bytes``) and the leased slots' (``ring_leased_bytes``).
        ``arena_bytes`` / ``slot_bytes`` are the elements as counted, ``arena_laid_out_bytes`` /
        ``slot_laid_out_bytes`` what the chip's tiles hold for them (:func:`tiled_bytes`)."""
        a_ring = self.ring_bytes() // (self.num_slots + 1)
        ring = ({"ring_blocks": self.ring_blocks, "ring_arena_bytes": self.ring_bytes(), "ring_slot_bytes": a_ring,
                 "ring_leased_bytes": self.leased * a_ring,
                 "ring_fill_frac": self.leased / self.num_slots} if self.ring_blocks else {})
        # a pool of rings alone (an ordinary decoder's window layers) has neither: its dtype is the rings'
        tails = self.dtypes.get("conv", self.dtypes.get(RING_ARENAS[0]))
        laid_out = self.laid_out_bytes()
        return {**ring, "slots": self.num_slots, "leased": self.leased,
                "free_low_water": self._free_low_water, "arena_bytes": self.arena_bytes(),
                "slot_bytes": self.slot_bytes(), "arena_laid_out_bytes": laid_out,
                "slot_laid_out_bytes": laid_out // (self.num_slots + 1),
                "layers": self.layers, "arenas": sorted(self.shapes),
                "dtype": str(self.dtypes.get("state", tails)), "conv_dtype": str(tails),
                "fill_frac": self.leased / self.num_slots}


class PagedKVPool:
    """Block arena + free-list allocator + per-block reference counts.

    ``dtype`` is the **compute** dtype the model consumes (what
    ``gather_dense*`` hands ``forward_with_cache``); ``kv_dtype`` selects
    the **storage** dtype — ``None`` stores at ``dtype`` (full-width),
    ``"int8"`` stores quantized blocks plus float32 scale arenas of shape
    ``(num_blocks, L, n_query_groups, block_size)``.

    With ``mesh``, the arenas carry a ``NamedSharding`` splitting the
    KV-heads dim over ``axis`` (the shared ``distributed.kv_cache_spec``
    rule; the scale arenas keep the heads dim at axis 2 too, so ONE rule
    places all four arrays) — the *bytes* live sharded across the mesh
    while every allocator decision (free list, refcounts, prefix sharing)
    stays host-side and identical to the single-device pool."""

    def __init__(self, cfg, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                 *, kv_dtype=None, mesh=None, axis: str = "tp",
                 state_slots: int | None = None, lane_pack: int | None = None):
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (block 0 is the sink), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.cfg = cfg
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype                              # compute/dequant dtype
        self.kv_dtype = resolve_kv_dtype(kv_dtype, dtype)  # storage dtype
        self.quantized_kv = is_quantized_kv(self.kv_dtype, dtype)
        self.mesh = mesh
        # a latent-attention model keeps ONE arena, ``arenas["latent"]``: a
        # row is a token's latent and rotated key, read by every head; it
        # lives in ``k_arena`` (``v_arena`` is None), so the allocator, the
        # tables and the chunk writers above see blocks as they always did
        self.latent = bool(getattr(cfg, "latent", False))
        if self.latent and (self.quantized_kv or mesh is not None):
            raise ValueError("a latent arena has no quantised storage and no layout under a mesh")
        # KV heads side by side in a 128-lane row (``generate.kv_lane_pack``):
        # where the head size divides 128, for an arena stored at the compute
        # dtype on one device.  A quantised arena keeps a head a row (its scale
        # is a head's) and so does a sharded one (``kv_cache_spec`` splits the
        # heads axis); ``lane_pack=1`` asks for that layout outright
        auto = 1 if (self.quantized_kv or mesh is not None) else kv_lane_pack(cfg)
        self.lane_pack = auto if lane_pack is None else int(lane_pack)
        if self.lane_pack not in (1, auto):
            raise ValueError(f"lane_pack={lane_pack}: this config and storage pack {auto} KV heads a row, or 1")
        shape = (self.num_blocks, *kv_block_shape(cfg, self.block_size, self.lane_pack))
        self._arena_shape = shape
        self._scale_shape = shape[:-1]                  # absmax over hs
        if mesh is not None:
            from thunder_tpu.serving.mesh import arena_sharding

            self.arena_sharding = arena_sharding(cfg, mesh, axis=axis)
            # shard-local allocation: no device ever materializes the full
            # arena (the whole point — a model/cache too big for one chip).
            # The spec (heads at axis 2) is a valid prefix for the rank-4
            # scale arenas too, so one sharding object places everything.
        else:
            self.arena_sharding = None

        # two kinds of cache in one manager: a model with linear_attention
        # layers keeps a state slot a request beside its blocks (the K/V
        # arenas' layer axis then holds the full-attention layers only)
        self.state = None
        if getattr(cfg, "keeps_slot", False):
            if state_slots is None:
                raise ValueError("a config with linear_attention, conv, ssm, mamba2 or sliding_attention layers "
                                 "needs state_slots=")
            if self.quantized_kv and getattr(cfg, "ring_layers", ()):
                raise ValueError("the ring arenas of sliding_attention layers have no quantised storage")
            self.state = StatePool(cfg, state_slots, dtype, block_size=self.block_size, lane_pack=self.lane_pack)
        # independent buffers (no copy traffic between K and V updates)
        self.k_arena = self._zeros(shape, self.kv_dtype)
        self.v_arena = None if self.latent else self._zeros(shape, self.kv_dtype)
        if self.quantized_kv:
            self.k_scale = self._zeros(self._scale_shape, jnp.float32)
            self.v_scale = self._zeros(self._scale_shape, jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        # outgoing donated arena handles, parked until their consumer
        # completes (see set_arenas/release_retired)
        self._retired: list = []
        # block 0 is permanently leased to the sink
        self._refcount = np.zeros(self.num_blocks, dtype=np.int32)
        self._refcount[SINK_BLOCK] = 1
        self._free: list[int] = list(range(self.num_blocks - 1, SINK_BLOCK, -1))  # pop() -> lowest id
        # capacity-exhaustion post-mortems need the floor, not the current
        # value: the low-water mark survives into the flight-recorder dump
        self._free_low_water = len(self._free)
        # occupancy timeline: bounded ring of (free, shared, leased) triples
        # sampled at each harvest — the low-water mark alone hides spikes
        self._occ_ring: deque = deque(maxlen=OCCUPANCY_WINDOW)

    #
    # allocator
    #

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_usable(self) -> int:
        """Allocatable blocks (arena minus the sink)."""
        return self.num_blocks - 1

    @property
    def free_blocks_low_water(self) -> int:
        """Fewest free blocks ever observed (capacity headroom floor)."""
        return self._free_low_water

    def utilization(self) -> float:
        """Fraction of usable blocks currently leased."""
        return 1.0 - self.num_free / self.num_usable

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache slots."""
        return -(-max(int(n_tokens), 0) // self.block_size)

    def can_alloc(self, n: int) -> bool:
        return n <= self.num_free

    def alloc(self, n: int) -> list[int]:
        """Leases ``n`` blocks (refcount 1 each); raises
        :class:`PoolExhaustedError` without side effects when short."""
        if n > self.num_free:
            raise PoolExhaustedError(
                f"need {n} blocks, {self.num_free} free of {self.num_usable}"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refcount[b] = 1
        self._free_low_water = min(self._free_low_water, len(self._free))
        return out

    def share(self, blocks: Sequence[int]) -> list[int]:
        """Increments the refcount of already-leased ``blocks`` (prefix
        sharing: the new owner's table points at the same physical blocks).
        Returns the same ids for convenience."""
        for b in blocks:
            if b == SINK_BLOCK:
                continue
            if self._refcount[b] <= 0:
                raise ValueError(f"block {b} is not leased; cannot share")
            self._refcount[b] += 1
        return list(blocks)

    def free(self, blocks: Sequence[int]) -> int:
        """Releases one reference on each block; blocks whose count reaches
        zero return to the free list.  Returns how many became free."""
        released = 0
        for b in blocks:
            if b == SINK_BLOCK:
                continue
            if self._refcount[b] <= 0:
                raise ValueError(f"double free of block {b}")
            self._refcount[b] -= 1
            if self._refcount[b] == 0:
                self._free.append(b)
                released += 1
        return released

    def refcount(self, block: int) -> int:
        return int(self._refcount[block])

    def sample_occupancy(self) -> tuple[int, int, int]:
        """Append one ``(free, shared, leased)`` sample to the bounded
        occupancy ring (the engine calls this once per harvest) and
        return it.  O(num_blocks) numpy scan; ring stays O(1) memory."""
        counts = self._refcount[SINK_BLOCK + 1:]
        sample = (self.num_free, int((counts > 1).sum()),
                  int((counts > 0).sum()))
        self._occ_ring.append(sample)
        return sample

    def occupancy_timeline(self) -> list[tuple[int, int, int]]:
        """The retained ``(free, shared, leased)`` samples, oldest first
        (at most :data:`OCCUPANCY_WINDOW` — spikes between crashes stay
        visible, unlike the low-water scalar alone)."""
        return list(self._occ_ring)

    def occupancy_snapshot(self) -> dict:
        """Summary of the timeline for ``stats()``: sample count, window,
        the latest triple, and the peak leased-block count observed."""
        tl = self._occ_ring
        return {
            "window": OCCUPANCY_WINDOW,
            "samples": len(tl),
            "last": tl[-1] if tl else None,
            "peak_leased": max((s[2] for s in tl), default=0),
            "occupancy_frac": self.utilization(),
            **self.kind_snapshot(),
            **({"state": self.state.snapshot()} if self.state is not None else {}),
        }

    def kind_snapshot(self) -> dict:
        """What a block's row is (``kv``: keys and values a head; ``latent``:
        one latent a token, all heads) and a token's bytes over all layers:
        as counted from the model's widths, and as the chip lays the arena's
        rows out: whole 128-lane tiles (a latent row is padded to them in the
        arena's own shape; a K/V row of a head under 128 that is not
        lane-packed, by the chip).  ``lane_pack``: KV heads a row."""
        cfg = self.cfg
        item = jnp.dtype(self.kv_dtype).itemsize
        if self.latent:
            counted = cfg.n_layer * cfg.latent_width * item
        else:
            counted = 2 * cfg.paged_kv_slabs * cfg.n_query_groups * cfg.head_size * item
        lanes = self._arena_shape[-1]
        laid_out = (1 if self.latent else 2) * int(np.prod(self._arena_shape[1:-1])) * (-(-lanes // 128) * 128) * item
        if self.quantized_kv:       # the two scale arenas, as counted
            laid_out += 2 * int(np.prod(self._scale_shape[1:])) * 4
        return {"kind": "latent" if self.latent else "kv", "token_bytes_counted": counted,
                "token_bytes_laid_out": laid_out // self.block_size, "lane_pack": self.lane_pack,
                "slabs": self._arena_shape[1]}

    def state_snapshot(self) -> dict:
        """Allocator state for the flight recorder: occupancy plus the
        free-list/sharing breakdown (the paged-pool notion of
        fragmentation is how lease references spread over blocks)."""
        counts = self._refcount[SINK_BLOCK + 1:]
        snap = {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "num_free": self.num_free,
            "free_blocks_low_water": self._free_low_water,
            "utilization": self.utilization(),
            "leased_blocks": int((counts > 0).sum()),
            "shared_blocks": int((counts > 1).sum()),
            "lease_refs": int(counts.sum()),
            "kv_dtype": str(self.kv_dtype),
            "arena_bytes": self.arena_bytes(),
            **self.kind_snapshot(),
            "occupancy_timeline": [list(s) for s in self._occ_ring],
            **({"state": self.state.snapshot()} if self.state is not None else {}),
        }
        if self.arena_sharding is not None:
            snap["arena_spec"] = str(self.arena_sharding.spec)
            snap["arena_shard_bytes"] = self.per_shard_bytes()
        return snap

    #
    # arena geometry helpers (pure; the jitted programs in engine.py close
    # over these shapes)
    #

    def capacity_tokens(self, n_blocks: int) -> int:
        return n_blocks * self.block_size

    def dense_shape(self, B: int, n_blocks: int) -> tuple[int, ...]:
        _, ng, bs, hs = kv_block_shape(self.cfg, self.block_size)   # the dense cache packs nothing
        return (self.cfg.kv_slabs, B, ng, n_blocks * bs, hs)        # and holds every layer that keeps K and V, a pass

    def block_bytes(self) -> int:
        """Bytes one block costs across all arenas (K+V data, plus the
        scale arenas on the quantized path) — the unit of byte-based
        admission/capacity accounting."""
        total = int(self.k_arena.nbytes) + (0 if self.latent else int(self.v_arena.nbytes))
        if self.quantized_kv:
            total += int(self.k_scale.nbytes) + int(self.v_scale.nbytes)
        return total // self.num_blocks

    def arena_bytes(self) -> int:
        """Total bytes of every arena array this pool owns."""
        return self.block_bytes() * self.num_blocks

    def per_shard_bytes(self) -> int:
        """Bytes of ONE K arena on one device (what a chip's HBM must
        hold; ×2 for K+V).  Equals ``k_arena.nbytes`` unsharded."""
        from thunder_tpu.serving.mesh import per_shard_bytes

        return per_shard_bytes(self.k_arena)

    @property
    def arenas(self) -> dict:
        """The arena pytree a bucket program takes (and returns donated):
        ``{"k", "v"}`` plus ``{"k_scale", "v_scale"}`` on the int8 path;
        ``{"latent"}`` alone for a latent-attention model."""
        if self.latent:
            return {"latent": self.k_arena}
        out = {"k": self.k_arena, "v": self.v_arena}
        if self.quantized_kv:
            out["k_scale"] = self.k_scale
            out["v_scale"] = self.v_scale
        if self.state is not None:
            out.update(self.state.arenas)
        return out

    def _check_arena(self, name: str, new: jax.Array) -> None:
        scale = name.endswith("_scale")
        want_shape = self._scale_shape if scale else self._arena_shape
        want_dtype = jnp.dtype(jnp.float32) if scale else jnp.dtype(self.kv_dtype)
        if self.state is not None and name in self.state.shapes:
            want_shape, want_dtype = self.state.shapes[name], self.state.dtypes[name]
        if tuple(new.shape) != want_shape:
            raise ArenaMismatchError(name, "shape", want_shape, tuple(new.shape))
        if new.dtype != want_dtype:
            raise ArenaMismatchError(name, "dtype", want_dtype, new.dtype)
        if self.arena_sharding is not None:
            got = getattr(new, "sharding", None)
            ok = got is not None and (
                got == self.arena_sharding
                or self.arena_sharding.is_equivalent_to(got, new.ndim)
            )
            if not ok:
                raise ArenaMismatchError(name, "sharding", self.arena_sharding, got)

    def set_arenas(self, arenas: dict) -> None:
        """Installs the arena pytree a donated program returned (in-place
        update).  Validates geometry, dtype, and (mesh mode) sharding
        first: a buggy program's mismatched arena would otherwise surface
        steps later as garbage KV — :class:`ArenaMismatchError` names the
        offending arena at the swap instead."""
        expected = set(self.arenas)
        if set(arenas) != expected:
            raise ArenaMismatchError(
                "arenas", "shape", sorted(expected), sorted(arenas),
                msg=f"program returned arena keys {sorted(arenas)}, pool "
                    f"expects {sorted(expected)} (kv_dtype={self.kv_dtype})",
            )
        for name, arr in arenas.items():
            self._check_arena(name, arr)
        # park the outgoing handles instead of letting them die here:
        # dropping the LAST reference to a jax Array that was DONATED to a
        # still-running execution blocks the host until that execution
        # completes — measured ~the full device step, i.e. it silently
        # serializes the async engine's overlap.  The engine calls
        # release_retired() at harvest, when the consumer has finished and
        # the deref costs microseconds.
        self._retired.append((self.k_arena, self.v_arena,
                              self.k_scale, self.v_scale,
                              *(self.state.arenas.values() if self.state is not None else ())))
        if self.state is not None:
            self.state.install(arenas)
        if self.latent:
            self.k_arena = arenas["latent"]
            return
        self.k_arena = arenas["k"]
        self.v_arena = arenas["v"]
        if self.quantized_kv:
            self.k_scale = arenas["k_scale"]
            self.v_scale = arenas["v_scale"]

    @property
    def n_retired(self) -> int:
        """How many donations' handles are parked: the mark an engine takes
        before a dispatch whose handles must outlive the next release."""
        return len(self._retired)

    def release_retired(self, upto: int | None = None) -> None:
        """Drops the parked donated-arena handles (cheap once their
        consuming executions have completed — call after materializing any
        later output of the same device stream).  ``upto`` keeps everything
        parked after that mark (:attr:`n_retired` when it was taken): the
        handles a program still on the device is consuming."""
        del self._retired[:upto]

    def _zeros(self, shp: tuple, dt) -> jax.Array:
        """A zeroed arena buffer, shard-local under a mesh (no device ever
        materializes the full arena)."""
        if self.mesh is not None:
            return jax.jit(
                lambda: jnp.zeros(shp, dtype=dt), out_shardings=self.arena_sharding
            )()
        return jnp.zeros(shp, dtype=dt)

    def rebuild_arenas(self) -> None:
        """Replaces the device arenas with fresh zeroed buffers, dropping
        whatever the old handles held (re-prefill recovery: the KV content
        is soft state the engine rebuilds by replaying known tokens).
        Allocator state — block tables, refcounts, prefix sharing, the
        free list — is host-side and survives untouched; under a mesh the
        new buffers come up with the same shard-local placement."""
        self._retired.clear()
        self.k_arena = self._zeros(self._arena_shape, self.kv_dtype)
        self.v_arena = None if self.latent else self._zeros(self._arena_shape, self.kv_dtype)
        if self.quantized_kv:
            self.k_scale = self._zeros(self._scale_shape, jnp.float32)
            self.v_scale = self._zeros(self._scale_shape, jnp.float32)
        if self.state is not None:
            self.state.rebuild()      # slots stay leased; the replay rebuilds what they held

    def update_arenas(self, k_arena: jax.Array, v_arena: jax.Array,
                      k_scale: jax.Array | None = None,
                      v_scale: jax.Array | None = None) -> None:
        """Positional convenience over :meth:`set_arenas` (kept for the
        pre-quantization call sites and tests)."""
        arenas = {"k": k_arena, "v": v_arena}
        if k_scale is not None or v_scale is not None:
            arenas["k_scale"] = k_scale
            arenas["v_scale"] = v_scale
        self.set_arenas(arenas)


class PrefixIndex:
    """Block-aligned prompt-prefix → ``(owner rid, block ids)`` map — the
    prefix-sharing lookup structure one engine (one pool) owns.

    Liveness is delegated: every query takes an ``alive(hit) -> bool``
    callback (the engine checks that the owner is still running and every
    snapshot block id is still the live table entry), so the index itself
    stays a pure pool-side structure with no scheduler dependency — which
    is what lets the dp router read it from outside the engine.

    Two lookup flavors with different side-effect contracts:

    - :meth:`find` — the engine's admission-path lookup: counts into
      ``lookups``/``hits`` and scrubs stale entries as it walks (sharing a
      stale snapshot would lease dead block ids);
    - :meth:`probe` — the router's affinity query: **non-mutating** (no
      counter bumps, no scrubbing), because a routing decision must not
      perturb the engine's prefix-share hit-rate accounting or race its
      scrub with an admission happening on the same step.
    """

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self._index: dict[tuple, tuple[int, tuple[int, ...]]] = {}
        self.lookups = 0
        self.hits = 0

    def __len__(self) -> int:
        return len(self._index)

    def find(self, prompt: np.ndarray, alive) -> list[int]:
        """Longest block-aligned prefix of ``prompt`` with a live owner:
        the shared block ids (the last prompt token always re-prefills, so
        the share is capped one token short of the full prompt), or ``[]``.
        Counts the lookup and deletes stale entries encountered."""
        self.lookups += 1
        bs = self.block_size
        max_share = ((int(prompt.shape[0]) - 1) // bs) * bs
        for k in range(max_share, 0, -bs):
            key = tuple(prompt[:k].tolist())
            hit = self._index.get(key)
            if hit is None:
                continue
            if alive(hit):
                self.hits += 1
                return list(hit[1])
            # stale snapshot (the owner's blocks were freed or sunk, e.g. by
            # sliding-window expiry): sharing it would lease dead block ids
            del self._index[key]
        return []

    def probe(self, prompt, alive) -> int:
        """Longest *alive* shared-prefix length in tokens (0 on miss),
        without touching counters or scrubbing — the router's read-only
        affinity question: "how much of this prompt is already resident
        here?"."""
        prompt = np.asarray(prompt).reshape(-1)
        bs = self.block_size
        max_share = ((int(prompt.shape[0]) - 1) // bs) * bs
        for k in range(max_share, 0, -bs):
            hit = self._index.get(tuple(prompt[:k].tolist()))
            if hit is not None and alive(hit):
                return k
        return 0

    def register(self, rid: int, prompt: np.ndarray, block_table,
                 alive, *, upto: int | None = None, full: bool = False) -> None:
        """Registers every block-aligned prefix of ``prompt`` (owner
        ``rid``).  ``upto`` bounds registration to tokens already written
        (a chunked prefill registers after each piece); live entries are
        never displaced — first writer wins while it stays alive.

        ``full=True`` lifts the one-token-short cap: a *running* request
        must keep its last prompt token for its own re-prefill, but a
        parked session sequence is complete and fully written, so every
        covered block is shareable (turn k+1's prompt is strictly longer,
        which is what the ``find`` cap already guarantees per-query)."""
        bs = self.block_size
        n = int(prompt.shape[0])
        limit = n if upto is None else min(upto, n)
        hi = (limit // bs) * bs if full else min(
            (limit // bs) * bs, ((n - 1) // bs) * bs)
        toks = prompt.tolist()
        for k in range(bs, hi + 1, bs):
            key = tuple(toks[:k])
            cur = self._index.get(key)
            if cur is None or not alive(cur):
                self._index[key] = (rid, tuple(block_table[: k // bs]))

    def unregister(self, rid: int) -> None:
        """Drops every entry owned by ``rid`` (called before its blocks
        free, so no later request can share just-released ids)."""
        if self._index:
            stale = [k for k, (r, _) in self._index.items() if r == rid]
            for k in stale:
                del self._index[k]


def chunk_tables(block_table, pos: int, n_tokens: int, nbb: int,
                 block_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side gather/scatter tables for one prefill piece at **chunk
    granularity**.

    A prefill piece (a whole prompt, a shared-prefix suffix, or one chunk
    of a chunked prefill) computes K/V for the ``n_tokens`` positions at
    ``[pos, pos + n_tokens)`` of a request holding ``block_table``.
    Returns ``(table, dest)`` int32 arrays of width ``nbb`` (the padded
    program table width):

    - ``table`` — the gather side: every leased block, sink-padded to
      ``nbb``, so the dense window the program reassembles covers the
      already-written prefix (earlier chunks / shared blocks);
    - ``dest`` — the scatter side: only the block range
      ``[pos // bs, ceil((pos + n_tokens) / bs))`` this piece writes;
      every other entry (shared prefix, earlier chunks, bucket padding
      beyond the leased table) routes to the sink block, so a piece never
      writes blocks another piece owns the write for.  ``n_tokens`` may be
      the *padded* bucket width: trailing padding that spills into leased
      future-decode blocks writes garbage that decode overwrites slot by
      slot before ever attending it (the same invariant padding always
      relied on).
    """
    bs = block_size
    table = np.full(nbb, SINK_BLOCK, dtype=np.int32)
    table[: len(block_table)] = block_table
    dest = np.full(nbb, SINK_BLOCK, dtype=np.int32)
    lo, hi = pos // bs, min(len(block_table), -(-(pos + n_tokens) // bs))
    dest[lo:hi] = block_table[lo:hi]
    return table, dest


def tiled_bytes(shape, dtype) -> int:
    """Bytes of an array as the chip lays it out: its last two axes in whole
    tiles of ``(8, 128)`` 32-bit elements (``(16, 128)`` at 16 bits, ``(32, 128)``
    at 8), the other axes as they are."""
    item = jnp.dtype(dtype).itemsize
    *lead, r, c = shape
    sub = 8 * max(1, 4 // item)
    return math.prod(lead) * (-(-r // sub) * sub) * (-(-c // 128) * 128) * item


def pack_state_heads(x):
    """``x (..., nv, dk, dv)``, a matrix a value head, as the state arena's rows
    ``(..., dk, nv dv)``: the heads side by side, head ``h`` in columns ``[h dv, (h
    + 1) dv)``."""
    *lead, nv, dk, dv = x.shape
    return jnp.swapaxes(x, -3, -2).reshape(*lead, dk, nv * dv)


def unpack_state_heads(x, heads: int):
    """The inverse of :func:`pack_state_heads`: ``(..., dk, nv dv)`` to ``(..., nv, dk, dv)``."""
    *lead, dk, W = x.shape
    return jnp.swapaxes(x.reshape(*lead, dk, heads, W // heads), -3, -2)


@functools.partial(jax.jit, static_argnames="heads")
def slot_state_heads(arena, slot, *, heads: int):
    """One slot's rows of the delta rule's state arena, a matrix a head: ``(L_lin,
    nv, dk, dv)``.  One program, where the same lines run eagerly are four
    (``StatePool.slot_rows``, for ``engine.held``, which a benchmark reads that counts the programs a run built)."""
    return unpack_state_heads(arena[slot], heads)


def gather_state(arenas, slots, fresh, state_heads: int = 0):
    """The rows' recurrent state in the dense cache's layout
    (``generate.state_shapes``): ``{"conv": (L_lin, B, K - 1, channels),
    "state": (L_lin, B, nv, dk, dv)}`` from the slot-major arenas, zeros for
    a row that is ``fresh`` (``(B,)`` bool: its sequence starts here, and the
    slot still holds its last owner's); ``conv`` alone for a model of conv
    layers.  ``state_heads`` (``StatePool.state_heads``): the state arena's rows
    hold that many value heads side by side, and come apart here.  Pure jnp;
    call inside jit."""
    def one(name):
        rows = jnp.take(arenas[name], slots, axis=0)                 # (B, L_lin, ...)
        rows = jnp.where(fresh.reshape((-1,) + (1,) * (rows.ndim - 1)), jnp.zeros_like(rows), rows)
        if name == "state" and state_heads:
            rows = unpack_state_heads(rows, state_heads)
        return jnp.swapaxes(rows, 0, 1)
    return {name: one(name) for name in ("conv", "state") if name in arenas}


def scatter_state(arenas, cache, slots, state_heads: int = 0):
    """The inverse of :func:`gather_state`: the rows' new state back to their
    slots (padding rows all write the sink, slot 0), the delta rule's heads
    side by side again where the arena keeps ``state_heads`` of them a row.
    Returns the arenas written."""
    def one(name):
        rows = jnp.swapaxes(cache[name], 0, 1)
        if name == "state" and state_heads:
            rows = pack_state_heads(rows)
        return arenas[name].at[slots].set(rows.astype(arenas[name].dtype))
    return {name: one(name) for name in ("conv", "state") if name in arenas}


def ring_tables(slots, n_ring: int, width: int):
    """The sliding_attention layers' block tables, from the rows' state slots
    ``(B,)``: ``(B, width)`` int32 over the ring arenas, entry ``i`` the block
    that holds block ``i`` of the sequence *while it is in the window*: ``slots *
    n_ring + i % n_ring``.  An entry behind the window names a block that now
    holds a later one: the window's mask never reaches it.  Slot 0's ring is the
    sink (its block 0 the ring arenas' block 0).  Pure jnp; call inside jit."""
    return slots[:, None] * n_ring + (jnp.arange(width, dtype=jnp.int32) % n_ring)[None, :]


def ring_dest(slot, n_real, n: int, n_ring: int, block_size: int):
    """Where a whole prompt's last blocks go in its ring: of the ``n`` blocks
    that end with the one holding token ``n_real - 1`` (``n <= n_ring``), the
    first's index in the sequence (clipped at 0) and their ``(n,)`` destinations
    in the ring arenas, the sink for a block past the last real token.  Pure
    jnp; call inside jit."""
    last = (n_real - 1) // block_size
    start = jnp.maximum(last - (n - 1), 0)
    i = start + jnp.arange(n, dtype=jnp.int32)
    return start, jnp.where(i <= last, slot * n_ring + i % n_ring, SINK_BLOCK)


def gather_rows(arena, tables, lane_pack: int = 1):
    """One arena's blocks as a dense cache: ``tables`` (B, nb) int32
    physical-block ids (sink-padded) to (L, B, ng, nb*bs, hs), the
    :func:`cache_shape` layout ``forward_with_cache`` consumes (a latent
    arena: ng 1, hs the row's width).  ``lane_pack`` P: the arena's rows hold P
    KV heads side by side (``PagedKVPool.lane_pack``), and come apart here.
    Pure jnp; call inside jit."""
    g = jnp.take(arena, tables, axis=0)        # (B, nb, L, ng, bs, hs)
    B, nb, L, ng, bs, hs = g.shape
    if lane_pack > 1:                          # (.., ng / P, bs, P hs) -> (.., ng / P, P, bs, hs)
        g = g.reshape(B, nb, L, ng, bs, lane_pack, hs // lane_pack).transpose(0, 1, 2, 3, 5, 4, 6)
        ng, hs = ng * lane_pack, hs // lane_pack
        g = g.reshape(B, nb, L, ng, bs, hs)
    g = g.transpose(2, 0, 3, 1, 4, 5)          # (L, B, ng, nb, bs, hs)
    return g.reshape(L, B, ng, nb * bs, hs)


def gather_dense(k_arena, v_arena, tables, lane_pack: int = 1):
    """Reassembles the dense K and V caches from block tables
    (:func:`gather_rows` of each)."""
    return gather_rows(k_arena, tables, lane_pack), gather_rows(v_arena, tables, lane_pack)


def pack_lanes(x, lanes: int):
    """``x (..., ng, hs)`` as a lane-packed arena's rows ``(..., ng hs / lanes,
    lanes)``: consecutive KV heads side by side, a reshape.  ``x`` as it is
    where ``hs == lanes``."""
    if x.shape[-1] == lanes:
        return x
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1] // lanes, lanes)


def dest_for_pos(tables, pos, live, *, block_size):
    """In-program scatter destination for a token write at ``pos``, with a
    per-row liveness keep-mask.

    ``tables``: (B, nb) int32 (sink-padded); ``pos``/``live``: (B,).  Live
    rows advance through their own table as ``pos`` crosses block
    boundaries (``tables[b, pos // bs]`` — the full table is leased at
    admission, so every entry the walk can reach is owned); dead rows route
    to ``(SINK_BLOCK, 0)``, where a write is garbage the sink absorbs.  Pure jnp; call inside
    jit.  ``take_along_axis`` clamps an out-of-range block index to the
    row's last (sink-padded) entry, matching the single-step derivation."""
    blk = jnp.take_along_axis(tables, (pos // block_size)[:, None], axis=1)[:, 0]
    return (jnp.where(live, blk, SINK_BLOCK),
            jnp.where(live, pos % block_size, 0))


def scatter_token(arena, new_kv, dest_block, dest_slot):
    """Writes one token's K (or V) per batch row back into the arena.

    ``new_kv``: (B, L, ng, hs); ``dest_block``/``dest_slot``: (B,) int32
    (sink-routed for padding rows); a lane-packed arena takes them as its
    rows (:func:`pack_lanes`).  Pure jnp; call inside jit on a donated
    arena.  The source dtype must already match the arena (int8 arenas go
    through :func:`quant.scatter_token_q` instead)."""
    if jnp.dtype(new_kv.dtype) != jnp.dtype(arena.dtype):
        raise ArenaMismatchError(
            "scatter", "dtype", jnp.dtype(arena.dtype), jnp.dtype(new_kv.dtype),
            msg=f"scatter_token source dtype {jnp.dtype(new_kv.dtype)} != arena "
                f"dtype {jnp.dtype(arena.dtype)} — route int8 arenas through "
                f"quant.scatter_token_q; anything else is a silent truncation",
        )
    return arena.at[dest_block, :, :, dest_slot, :].set(pack_lanes(new_kv, arena.shape[-1]))


def scatter_blocks(arena, dense, dest_table):
    """Writes a request's dense cache back into the arena block-by-block.

    ``dense``: (L, 1, ng, nb*bs, hs) (B=1 prefill layout); ``dest_table``:
    (nb,) int32 — entries equal to the sink absorb padding/garbage blocks.
    Duplicate sink entries are benign (last write wins into garbage).

    The source dtype must match the arena exactly: the pre-quantization
    code silently ``astype``'d here, which would truncate an f32 cache into
    a narrower arena without a trace — now any mismatch raises
    :class:`ArenaMismatchError` at trace time, and int8 arenas route
    through the explicit quantize path (:func:`quant.scatter_blocks_q`)."""
    if jnp.dtype(dense.dtype) != jnp.dtype(arena.dtype):
        raise ArenaMismatchError(
            "scatter", "dtype", jnp.dtype(arena.dtype), jnp.dtype(dense.dtype),
            msg=f"scatter_blocks source dtype {jnp.dtype(dense.dtype)} != arena "
                f"dtype {jnp.dtype(arena.dtype)} — route int8 arenas through "
                f"quant.scatter_blocks_q; anything else is a silent truncation",
        )
    L, B, ng, cap, hs = dense.shape
    bs = arena.shape[3]
    blocks = dense[:, 0].reshape(L, ng, cap // bs, bs, hs).transpose(2, 0, 1, 3, 4)
    if arena.shape[-1] != hs:       # a lane-packed arena: (nb, L, ng / P, bs, P hs)
        blocks = pack_lanes(blocks.transpose(0, 1, 3, 2, 4), arena.shape[-1]).transpose(0, 1, 3, 2, 4)
    return arena.at[dest_table].set(blocks)
