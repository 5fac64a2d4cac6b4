"""Pallas TPU kernel executor: hand-written flash attention.

Capability analog of the reference's fused-attention executors
(``thunder/executors/sdpaex.py:240``, ``cudnnex.py:380`` — explicit fwd/bwd
operator symbols with checkers and a grad transform), re-designed for TPU:

- the kernels are blockwise **flash attention** over a sequential Pallas grid
  (TPU grids execute in order, so VMEM scratch accumulators carry the online
  softmax state across KV blocks — the TPU-idiomatic replacement for CUDA
  thread-block reductions);
- the backward consumes ``(q, k, v, out, lse, delta)`` and recomputes scores
  blockwise, so saved residuals stay O(T) instead of the O(T²) probability
  matrix — this is what lets long sequences train in HBM;
- registration is twofold: an ``OperatorExecutor`` that claims
  ``PrimIDs.SDPA``/``SDPA_BACKWARD`` in the executor pipeline, plus fast-path
  hooks installed into ``jaxex`` so XLA fusion regions and the distributed
  TrainStep's trace evaluation dispatch to the same kernels.

On non-TPU backends the kernels can run via the Pallas interpreter
(``THUNDER_TPU_PALLAS_INTERPRET=1``) for testing; otherwise dispatch falls
back to the jnp reference implementation.  That is also what happens when
jax could not reach a TPU and settled for the CPU, so a program that means to
run on the chip checks ``jax.devices()[0].platform`` itself
(``chip_smoke.py``, ``chipbench/run.py``, ``tools/flash_tune.py``).  The interpreter accepts block
shapes and ops Mosaic refuses: ``tests/test_pallas_tpu_lowering.py`` lowers
every kernel here for the TPU.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
import os
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from thunder_tpu.core.prims import CAUSAL_CONV_ACTIVATIONS, GDN_CHUNK, MOE_ROW_TILE, PrimIDs, gdn_state_stride, prim_lookup
from thunder_tpu.extend import OperatorExecutor, add_default_executor, register_executor

__all__ = [
    "ex", "pallas_ex", "flash_sdpa", "flash_sdpa_backward",
    "paged_attn_decode", "paged_token_write", "paged_available", "paged_head_size_ok", "mla_paged_decode",
    "gdn_chunk", "gdn_chunk_state", "gdn_decode_step", "grouped_mm", "grouped_mm_dw", "causal_conv1d", "causal_conv1d_backward", "hc_mix",
]

# exp(MASK_VALUE - lse) underflows to 0 without the inf-inf NaN hazard of -inf
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Sharded dispatch: a bare pallas_call has no SPMD partitioning rule, so
# GSPMD would replicate it inside a multi-device pjit (all-gathering sharded
# q/k/v onto every chip).  Multi-device program builders (distributed.
# TrainStep) publish their Mesh here, and the dispatchers wrap the kernels in
# ``jax.shard_map`` over the mesh's batch/head axes — heads and batch are
# embarrassingly parallel for attention, so the per-shard kernel is exactly
# the single-device kernel on the local shard.
_mesh_var = contextvars.ContextVar("pallas_mesh", default=None)


@contextlib.contextmanager
def mesh_context(mesh):
    """Activates ``mesh`` for Pallas SPMD dispatch during tracing."""
    tok = _mesh_var.set(mesh)
    try:
        yield
    finally:
        _mesh_var.reset(tok)


# dispatch counters (trace-time): how often the kernels were claimed, and via
# which path — introspection for tests and examine()
stats = {"direct": 0, "sharded": 0}


def _pallas_available() -> bool:
    if os.environ.get("THUNDER_TPU_DISABLE_PALLAS", "") == "1":
        return False
    if jax.default_backend() == "tpu":
        return True
    return os.environ.get("THUNDER_TPU_PALLAS_INTERPRET", "") == "1"


def _enabled() -> bool:
    return _pallas_available()


def _env_block(which: str) -> int:
    """``THUNDER_TPU_FLASH_BQ`` / ``THUNDER_TPU_FLASH_BK``: the q/kv axis's
    block for ``tools/flash_tune.py`` (a multiple of 128; it need not divide
    the length), 0 where unset.  Read at trace time: call
    ``jax.clear_caches()`` after changing them."""
    try:
        b = int(os.environ.get(f"THUNDER_TPU_FLASH_B{which}") or 0)
    except ValueError:
        return 0
    return b if b > 0 and b % 128 == 0 else 0


# What a flash call costs a head: a grid step, whatever it holds, and a
# thousand pairs of a listed block.  ``tools/flash_tune.py --fit`` on one v5e
# (PR 49, call 2): ``_flash_fwd`` at 32 heads over 4 of 128, seven lengths from
# 2560 to 9984 with and without a window of 2048, in blocks of 256, 512 and
# 1024; the 42 readings lie within 9% of the line, 4.4% in the mean.
_FLASH_STEP_US, _FLASH_KPAIR_NS = 0.95, 3.22


def _flash_blocks(q, k, mq: int, window: int | None, causal: bool = True) -> tuple[int, int]:
    """``(BQ, BK)`` of a flash call on q ``(BH, Tq, hs)`` and k ``(BG, Tk, hs)``.

    One size for both, of 512, 256, 128, and 1024 where it fits VMEM (a row of
    ``hs`` elements within 512 bytes, within 256 under a window, whose second
    compare is one more tile; no mask block a query row: what Mosaic compiles
    for a v5e) and the causal band is at least two of them wide.
    Neither length need be a multiple of it: the schedule's last block is
    ragged (``_band_blocks``).  Taken is the size with the least modelled time
    a head, ``steps * _FLASH_STEP_US + pairs listed * _FLASH_KPAIR_NS``, both
    counted from the blocks ``_band_blocks`` lists: wide blocks save steps (a
    step costs the same whatever it holds, and the forward kernel rescales
    its accumulator once a step), narrow ones the pairs that a band's edges
    and a ragged end waste.  A step costs what 295 thousand pairs do, so from
    a few blocks on the widest allowed wins: 9984 goes in ten blocks of 1024
    with 256 rows of the last past the end (in blocks of 256, its largest
    divisor, the same call takes four times as long: PERF.md, PR 49), 2560 in
    three with 512 past it (12% under five of 512), 128 and 256 in one of their own."""
    Tq, Tk = q.shape[1], k.shape[1]
    row = q.shape[2] * q.dtype.itemsize
    wide = mq == 1 and (row <= 512 if window is None else row <= 256 and window >= 2048)

    def us(b):
        bq, bk = _env_block("Q") or b, _env_block("K") or b
        run, _ = _band_blocks(Tq, Tk, bq, bk, causal, window)
        steps = int(np.maximum(run.sum(axis=1), 1).sum())     # a row that keeps no pair still gets a block
        return steps * _FLASH_STEP_US + steps * bq * bk * _FLASH_KPAIR_NS * 1e-6, bq, bk

    return min(us(b) for b in (1024, 512, 256, 128)[0 if wide else 1:])[1:]


def _pad128(hs: int) -> int:
    return -(-hs // 128) * 128


def _gqa_rep(q_shape, k_shape) -> int | None:
    """Heads-per-KV-group, or None if the shapes aren't kernel-compatible.

    1 = plain MHA.  GQA (q ``(..., H, Tq, hs)``, k/v ``(..., G, Tk, hs)``)
    is handled natively: the kernels' K/V BlockSpec index maps gather the
    group's block for each q head, so K/V are never expanded in HBM —
    the H/G× KV-bandwidth saving is the point of GQA (reference leans on
    aten's enable_gqa, sdpaex.py:240)."""
    if q_shape[:-2] == k_shape[:-2]:
        return 1
    if len(q_shape) < 3 or q_shape[:-3] != k_shape[:-3]:
        return None
    H, G = q_shape[-3], k_shape[-3]
    if G <= 0 or H % G != 0:
        return None
    return H // G


def _canon_mask(mask_shape, q_shape, k_shape):
    """Classify an additive mask for blockwise loading.

    Returns ``(mode, mq)`` — mode names how the mask's (flattened) leading
    dim indexes against the kernel's flat batch×head grid axis — or None if
    the layout isn't expressible as a BlockSpec index map:

    - ``shared``: broadcast over all batch dims (e.g. a (Tq, Tk) ALiBi bias)
    - ``batch``: per-batch, head-broadcast — the HF padding-mask layout
      (B, 1, 1|Tq, Tk); index = flat // H
    - ``head``: per-head, batch-broadcast (1, H, ., .); index = flat % H
    - ``full``: every batch×head has its own slice; index = flat

    ``mq`` is 1 (row-broadcast: the whole mask is O(Tk) per batch — padding
    masks stay O(T) in HBM) or Tq.
    """
    *qb, Tq, _ = q_shape
    Tk = k_shape[-2]
    if len(mask_shape) > len(qb) + 2:
        return None
    ms = (1,) * (len(qb) + 2 - len(mask_shape)) + tuple(mask_shape)
    if ms[-1] != Tk:
        return None
    mq = ms[-2]
    if mq not in (1, Tq):
        return None
    mb = ms[:-2]
    for md, qd in zip(mb, qb):
        if md not in (1, qd):
            return None
    if all(md == 1 for md in mb):
        return ("shared", mq)
    if all(md == qd for md, qd in zip(mb, qb)):
        return ("full", mq)
    if len(mb) == 2 and mb[1] == 1:
        return ("batch", mq)
    if len(mb) == 2 and mb[0] == 1:
        return ("head", mq)
    return None


def _supported(q_shape, k_shape, v_shape, dtype, causal, mask_shape=None, window=None, own_v_width=False) -> bool:
    if window is not None and (not causal or int(window) <= 0):
        return False
    *_, Tq, hs = q_shape
    Tk = k_shape[-2]
    # the forward kernel takes v (and writes out) at a width of its own
    # (``own_v_width``: a latent prompt's heads of 192 over values of 128);
    # the backward kernels assume one head dim for q/k/v
    if v_shape[-1] != hs and not own_v_width:
        return False
    if _gqa_rep(q_shape, k_shape) is None:
        return False
    if k_shape[:-2] != v_shape[:-2]:
        return False
    # head sizes that aren't lane-aligned (e.g. 64) run zero-padded to 128
    if max(_pad128(hs), _pad128(v_shape[-1])) > 512:
        return False
    if Tq % 128 or Tk % 128:
        return False
    # causal with Tq != Tk uses top-left alignment (torch/aten convention):
    # the kernels index rows/cols globally, so no extra restriction
    # full K and V blocks + f32 accumulators must fit VMEM comfortably
    if str(dtype) not in ("bfloat16", "float32"):
        return False
    if mask_shape is not None and _canon_mask(mask_shape, q_shape, k_shape) is None:
        return False
    return True


#
# The flash kernels.
#
# One mechanism for all four: a grid step is a block of the score matrix
# that holds at least one kept pair.  ``_flash_schedule`` lists those blocks
# from ``(Tq, Tk, BQ, BK, causal, window)`` at trace time; the kernels take
# the list by scalar prefetch, their grid is ``(heads, blocks listed)`` and
# every index map reads its block's ``(i, j)`` from the list.  A block outside
# the causal band is no step and no copy.  A non-causal call, ``Tq != Tk``
# (top-left alignment) and a band narrower than a block are the same
# function: the list is the full rectangle, the shifted triangle, the
# diagonal.
#
# Each entry carries three flags.  ``first``/``last`` bracket the blocks that
# share an accumulator (a row of blocks for ``_flash_fwd``/``_flash_bwd_dq``;
# for ``_flash_bwd_dkv`` a column, walked once for each of the group's ``rep``
# query heads, so dk and dv leave the kernel summed over the group in
# float32; for ``_flash_bwd`` a query head's whole walk).  ``edge`` marks a
# block that also holds a masked pair: the block on the diagonal and the one
# on the window's far edge.  Only those build the iotas, compares and select;
# every other block holds kept pairs alone.  A user's additive mask is added
# in every block.
#
# The backward pass is one kernel, ``_flash_bwd``, one walk of the blocks
# (PR 63): a block makes ``s^T``, ``p^T``, ``dp^T`` and ``ds^T`` once and from
# them ``dv += p^T g``, ``dk += ds^T q`` and ``dq += ds k``, five products
# where ``_flash_bwd_dq`` and ``_flash_bwd_dkv`` together spend seven (and two
# sets of exponentials, compares and loads of ``lse`` and ``delta``).  Its
# order is ``_flash_walk``'s: a KV group a program of the grid's first axis,
# the group's query heads outermost, a head's columns, a column's kept rows.
# The sums that cross blocks stay in VMEM in float32: dq of the head being
# walked, ``(Tq, hs)``, added to at rows ``i BQ`` and written once, cast, at
# the head's last block; dk and dv of the group, ``2 x (Tk, hs)``, added to at
# rows ``j BK`` through all ``rep`` heads and written once at the group's
# end.  Nothing is read back from HBM to be added to, and every row of dq, dk
# and dv receives its terms in the order the two kernels give them (heads,
# then rows, for a column; columns ascending for a row): on the chip the
# result is theirs to the bit (``tools/flash_tune.py --check``).  K and V are
# fetched once a head, not once a group, which the products hide: columns
# outermost with all ``rep`` heads' dq resident read 7.06 ms for 6.93 at the
# Mistral cell's call and does not fit at heads of 256 (PERF.md, PR 63).
# ``ds k`` contracts the transposed tile over its first axis; Mosaic turns
# the tile on the XLU, idle otherwise, and the product costs what the others
# do.  The kernel states its VMEM limit from its own bytes
# (``_flash_walk_bytes``: the sums and the three output blocks, 24 MiB at the
# Mistral train cell's call, 48 at the hybrid cell's heads of 256, beside the
# 16 MiB of block tiles).  Where that does not fit what the device reports
# (``_flash_bwd_form``, by ``_gmm_vmem_cap``: 32,768 tokens at a head of 128
# on a v5e, 16,384 at 256), and in a compile for a device that reports
# nothing, the two kernels run as before.  ``stats["flash_bwd_one_walk"]`` /
# ``["flash_bwd_two_kernels"]`` count the calls traced in each form.
#
# ``lse`` and the backward pass's ``delta`` are lane-dense rows, ``(BH, 1,
# Tq)`` float32 in blocks of ``(1, 1, BQ)``: a ``(BQ, 1)`` block of a
# ``(BH, Tq, 1)`` array is a column of 128-lane tiles, 128 times its bytes in
# HBM and in every copy.  ``_flash_bwd`` and ``_flash_bwd_dkv`` compute the
# scores transposed (``k q^T``, a ``(BK, BQ)`` tile) so the rows broadcast as
# they arrive and ``dv += p^T g``, ``dk += ds^T q`` are plain products;
# ``_flash_fwd`` and ``_flash_bwd_dq`` transpose a column to a row, a row to a
# column, once a row of blocks.
#
# Blocks are ``_flash_blocks``': the size that costs least by its count of
# steps and of pairs listed, 1024 where that fits VMEM and the band.  At T 8192
# on one v5e (PERF.md, PR 29) the two backward kernels then run at 88-94% of
# the MXU's peak over the pairs their blocks hold, the forward kernel at 67%;
# the one walk at 94% at the Mistral train cell's call and 97% at the hybrid
# cell's (PERF.md, PR 63: 6.93 and 16.22 ms a call for 10.05 and 22.94).
#
# A length need not be a multiple of its block.  The list then ends in a
# ragged block, flagged ``_TAILQ`` (it reaches past ``Tq``) or ``_TAILK``
# (past ``Tk``): its copy brings the rows there are and leaves the rest of
# the buffer as it was.  A row past ``Tq`` is written nowhere, and in
# ``_flash_fwd`` / ``_flash_bwd_dq`` touches no other row; a row past ``Tk``
# likewise in ``_flash_bwd_dkv``.  The other way round the tail would reach a
# sum (``p = 0`` times a stale ``V`` row is NaN), so a kernel built over such a
# list has a third form of its body, run in the ragged blocks alone: the
# tail's operands selected to zero and the mask cut at the length
# (``_flash_bwd`` sums along both axes, so it zeroes both tails).  Where the
# blocks divide both lengths no entry carries the flag and the kernels hold
# no such form: they are what they were before there was one.
#

_FIRST, _LAST, _EDGE, _TAILQ, _TAILK = 1, 2, 4, 8, 16


def _band_blocks(Tq: int, Tk: int, BQ: int, BK: int, causal: bool, window: int | None):
    """Boolean grids over the blocks of the score matrix, ``ceil(Tq / BQ)`` by
    ``ceil(Tk / BK)``: ``run`` where block ``(i, j)`` (rows ``i*BQ ..``,
    columns ``j*BK ..``, as far as the lengths go) holds a kept pair,
    ``whole`` where it holds nothing else.  A pair is kept where ``0 <= row -
    col`` (causal) ``< window``."""
    nq, nk = -(-Tq // BQ), -(-Tk // BK)
    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    if not causal:
        run = np.ones((nq, nk), bool)
        return run, run
    # row - col over a block spans [lo, hi], every value taken
    lo, hi = i * BQ - (np.minimum(j * BK + BK, Tk) - 1), np.minimum(i * BQ + BQ, Tq) - 1 - j * BK
    top = np.inf if window is None else window - 1
    return (hi >= 0) & (lo <= top), (lo >= 0) & (hi <= top)


@functools.lru_cache(maxsize=None)
def _flash_schedule(Tq: int, Tk: int, BQ: int, BK: int, causal: bool, window: int | None,
                    by_column: bool = False, rep: int = 1):
    """The blocks a flash kernel visits, in order: int32 arrays ``(qi, kj,
    head, flag)``, one entry a grid step.

    Listed are the blocks with a kept pair, row by row (``by_column``: column
    by column, each column ``rep`` times with ``head`` counting up).  ``flag``
    has ``_FIRST``/``_LAST`` on a row's (column's) first and last entry,
    ``_EDGE`` where the block also holds a masked pair, ``_TAILQ``/``_TAILK``
    where it reaches past ``Tq``/``Tk``.  A row (column) without any kept pair
    still gets its nearest block, fully masked, so its output is written."""
    run, whole = _band_blocks(Tq, Tk, BQ, BK, causal, window)
    if by_column:
        run, whole = run.T, whole.T
    along, across = (BK, BQ) if by_column else (BQ, BK)
    entries = []
    for line in range(run.shape[0]):
        cross = np.flatnonzero(run[line])
        if not len(cross):
            cross = [min(line * along // across, run.shape[1] - 1)]
        walk = [(c, r) for r in range(rep) for c in cross]
        for n, (c, r) in enumerate(walk):
            i, j = (c, line) if by_column else (line, c)
            flag = (n == 0) * _FIRST | (n == len(walk) - 1) * _LAST | (not whole[line, c]) * _EDGE
            flag |= ((i + 1) * BQ > Tq) * _TAILQ | ((j + 1) * BK > Tk) * _TAILK
            entries.append((i, j, r, flag))
    out = tuple(np.array(a, dtype=np.int32) for a in zip(*entries))
    for a in out:   # cached: every caller gets these very arrays
        a.setflags(write=False)
    return out


# what the last flash call built visits a head (trace time): grid steps, the
# blocks among them with a kept pair, the edge blocks of a full row, the
# blocks' sizes and the rows of the last query block past ``Tq``; and, from the
# dispatcher that padded the operands, the widths the kernels run at
# (``head_qk``, ``head_v``) with the zeros added a row over q, k and v together
# (``lanes_padded``).  Not among ``stats``: readers sum and subtract those
# counters.
flash_schedule: dict[str, int | str] = {}


def _note_widths(hs: int, hv: int, hp: int, hvp: int):
    flash_schedule.update(head_qk=hp, head_v=hvp, lanes_padded=2 * (hp - hs) + hvp - hv)


def _note_schedule(Tq, Tk, BQ, BK, causal, window):
    qi, _, _, flag = _flash_schedule(Tq, Tk, BQ, BK, causal, window)
    run, _ = _band_blocks(Tq, Tk, BQ, BK, causal, window)
    edges = np.bincount(qi[(flag & _EDGE) != 0], minlength=run.shape[0])
    flash_schedule.clear()      # the widths are the dispatcher's to add: none of an earlier call's stay
    flash_schedule.update(
        grid_steps=len(qi),
        running_blocks=int(run.sum()),
        edge_blocks_a_full_row=int(edges[np.argmax(np.bincount(qi))]),
        block_q=BQ,
        block_k=BK,
        tail_rows=-Tq % BQ,
    )


def _keep(row0, col0, shape, rows_axis: int, window, causal: bool = True, rows=None, cols=None):
    """The kept pairs of an edge block whose first row and column are
    ``row0``/``col0``; ``rows_axis`` is the axis the rows run along.  In a
    ragged block ``rows``/``cols`` cut at a length.  None: every pair."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, rows_axis)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis)
    keeps = [row >= col] if causal else []
    if window is not None:
        keeps.append(col > row - window)
    keeps += [x < end for x, end in ((row, rows), (col, cols)) if end is not None]
    return functools.reduce(jnp.logical_and, keeps) if keeps else None


def _load(ref, ragged: bool, n, B: int, end: int, axis: int = 0):
    """A block's operand; in a ragged block (the ``n``-th of ``B`` along
    ``axis``) with everything from ``end`` on selected to zero: what the copy
    did not bring."""
    x = ref[0]
    if not ragged:
        return x
    return jnp.where(n * B + jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) < end, x, jnp.zeros_like(x))


def _on_edge(flag, causal: bool, body, tail: int = 0):
    """Run ``body(masked)``: with the band's mask on an edge block, without
    it on every other.  ``tail`` (``_TAILQ`` or ``_TAILK``, where the list has
    such blocks and the kernel must not read past that end): ``body(True,
    True)`` on those."""
    def inside():
        if not causal:
            body(False)
            return
        edge = (flag & _EDGE) != 0
        pl.when(edge)(lambda: body(True))
        pl.when(jnp.logical_not(edge))(lambda: body(False))

    if not tail:
        inside()
        return
    ragged = (flag & tail) != 0
    pl.when(ragged)(lambda: body(True, True))
    pl.when(jnp.logical_not(ragged))(inside)


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _fwd_kernel(qi_ref, kj_ref, flag_ref, *refs, BQ, BK, causal, scale, has_mask, window, Tq, Tk):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        mask_ref = None
    t = pl.program_id(1)
    i, j, flag = qi_ref[t], kj_ref[t], flag_ref[t]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def block(masked: bool, ragged: bool = False):
        v = _load(v_ref, ragged, j, BK, Tk)   # p is 0 on the columns past Tk, and 0 times what lies there need not be
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT, preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32)  # (1|BQ, BK) broadcasts
        if masked:
            keep = _keep(i * BQ, j * BK, (BQ, BK), 0, window, causal, cols=Tk if ragged else None)
            s = jnp.where(keep, s, _MASK_VALUE)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)

    _on_edge(flag, causal, block, _TAILK if Tk % BK else 0)

    @pl.when((flag & _LAST) != 0)
    def _finalize():
        o_ref[0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(l_s[...])).T


def _flash_specs(H: int, G: int, mode: str | None, mq: int, BQ: int, BK: int, hs: int, by_group: bool = False):
    """BlockSpecs of the flash kernels' operands: a q-shaped block, a
    k-shaped one, a row of ``lse``/``delta``, the additive mask's.  Index maps
    take ``(b, t, qi, kj, ...)``: the flat query head and the schedule's entry
    (``by_group``, for ``_flash_bwd_dkv``: the flat KV group, with the entry's
    ``head`` prefetched third)."""
    rep = H // G
    if by_group:
        qh = lambda b, t, p: b * rep + p[2][t]   # noqa: E731
        kh = lambda b, t, p: b                   # noqa: E731
    else:
        qh = lambda b, t, p: b                   # noqa: E731
        # flat q index b*H + h reads KV group h // rep: GQA without
        # expanding K/V in HBM (rep = 1: the identity)
        kh = lambda b, t, p: (b // H) * G + (b % H) // rep   # noqa: E731

    def mask_index(b, t, *p):
        f = qh(b, t, p)
        m = {"shared": 0, "batch": f // H, "head": f % H, "full": f}[mode]
        return (m, p[0][t] if mq > 1 else 0, p[1][t])

    return (
        pl.BlockSpec((1, BQ, hs), lambda b, t, *p: (qh(b, t, p), p[0][t], 0)),
        pl.BlockSpec((1, BK, hs), lambda b, t, *p: (kh(b, t, p), p[1][t], 0)),
        pl.BlockSpec((1, 1, BQ), lambda b, t, *p: (qh(b, t, p), 0, p[0][t])),
        pl.BlockSpec((1, BQ if mq > 1 else 1, BK), mask_index),
    )


def _flash_params(**more):
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), **more)}


@functools.partial(jax.jit, static_argnames=("causal", "scale", "H", "G", "mode", "mq", "window"))
def _flash_fwd(q, k, v, mask, causal: bool, scale: float, H: int, G: int, mode: str | None, mq: int,
               window: int | None = None):
    """q (BH, Tq, hs), k (BG, Tk, hs), v (BG, Tk, hv), mask (M, mq, Tk) f32 or
    None -> out (BH, Tq, hv), lse (BH, 1, Tq) f32.  ``H``/``G`` are the
    per-shard q/KV head counts (the flat-batch gather key for GQA);
    ``mode``/``mq`` classify the mask layout (see _canon_mask).  ``hv`` is a
    shape the body reads: v's blocks, the accumulator and the output are that
    wide, the score product ``hs``; where the two are equal the specs are."""
    BH, Tq, hs = q.shape
    Tk, hv = k.shape[1], v.shape[2]
    BQ, BK = _flash_blocks(q, k, mq, window, causal)
    has_mask = mask is not None
    qi, kj, _, flag = _flash_schedule(Tq, Tk, BQ, BK, causal, window)
    _note_schedule(Tq, Tk, BQ, BK, causal, window)
    q_spec, k_spec, row_spec, mask_spec = _flash_specs(H, G, mode, mq, BQ, BK, hs)
    o_spec, v_spec, _, _ = _flash_specs(H, G, mode, mq, BQ, BK, hv)
    in_specs, operands = [q_spec, k_spec, v_spec], [q, k, v]
    if has_mask:
        in_specs.append(mask_spec)
        operands.append(mask)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, BQ=BQ, BK=BK, causal=causal, scale=scale, has_mask=has_mask, window=window,
                          Tq=Tq, Tk=Tk),
        name="_flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(qi)),
            in_specs=in_specs,
            out_specs=[o_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((BQ, 1), jnp.float32),
                pltpu.VMEM((BQ, 1), jnp.float32),
                pltpu.VMEM((BQ, hv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, hv), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        interpret=_interpret(),
        **_flash_params(),
    )(qi, kj, flag, *operands)


#
# Backward kernels
#


def _bwd_dq_kernel(qi_ref, kj_ref, flag_ref, *refs, BQ, BK, causal, scale, has_mask, window, Tq, Tk):
    if has_mask:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, mask_ref, dq_ref, dq_s, lse_s, delta_s = refs
    else:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, dq_ref, dq_s, lse_s, delta_s = refs
        mask_ref = None
    t = pl.program_id(1)
    i, j, flag = qi_ref[t], kj_ref[t], flag_ref[t]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)
        lse_s[...] = lse_ref[0].T
        delta_s[...] = delta_ref[0].T

    def block(masked: bool, ragged: bool = False):
        k = _load(k_ref, ragged, j, BK, Tk)   # the columns past Tk: p, dp and ds are 0 there, and so is what meets them
        g = g_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT, preferred_element_type=jnp.float32) * scale
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32)
        p = jnp.exp(s - lse_s[...])  # (BQ, BK)
        if masked:
            keep = _keep(i * BQ, j * BK, (BQ, BK), 0, window, causal, cols=Tk if ragged else None)
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(g, _load(v_ref, ragged, j, BK, Tk), _NT, preferred_element_type=jnp.float32)  # (BQ, BK)
        ds = p * (dp - delta_s[...])
        dq_s[...] += scale * jax.lax.dot_general(ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _on_edge(flag, causal, block, _TAILK if Tk % BK else 0)

    @pl.when((flag & _LAST) != 0)
    def _finalize():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qi_ref, kj_ref, head_ref, flag_ref, *refs, BQ, BK, causal, scale, has_mask, window, Tq, Tk):
    del head_ref   # the index maps' alone
    if has_mask:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, mask_ref, dk_ref, dv_ref, dk_s, dv_s = refs
    else:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s = refs
        mask_ref = None
    t = pl.program_id(1)
    i, j, flag = qi_ref[t], kj_ref[t], flag_ref[t]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def block(masked: bool, ragged: bool = False):
        # everything transposed: tiles are (BK, BQ), lse and delta rows
        # the rows past Tq, columns here: q, g, lse and delta zero make p one and dp, ds zero there
        q = _load(q_ref, ragged, i, BQ, Tq)
        g = _load(g_ref, ragged, i, BQ, Tq)
        s = jax.lax.dot_general(k_ref[0], q, _NT, preferred_element_type=jnp.float32) * scale
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32).T  # (BK, 1|BQ) broadcasts
        p = jnp.exp(s - _load(lse_ref, ragged, i, BQ, Tq, 1))
        if masked:
            # past Tq only a mask's own rows reach p (one more (1024, 1024) compare would not fit VMEM at a wide head)
            cut = Tq if ragged and has_mask and mask_ref.shape[1] > 1 else None
            keep = _keep(i * BQ, j * BK, (BK, BQ), 1, window, causal, rows=cut)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
        dv_s[...] += jax.lax.dot_general(p.astype(g.dtype), g, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0], g, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - _load(delta_ref, ragged, i, BQ, Tq, 1))
        dk_s[...] += scale * jax.lax.dot_general(ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _on_edge(flag, causal, block, _TAILQ if Tq % BQ else 0)

    @pl.when((flag & _LAST) != 0)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_walk_kernel(qi_ref, kj_ref, head_ref, flag_ref, *refs, BQ, BK, causal, scale, has_mask, window, Tq, Tk):
    del head_ref   # the index maps' alone
    if has_mask:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, mask_ref, dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s = refs
    else:
        g_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s = refs
        mask_ref = None
    t = pl.program_id(1)
    i, j, flag = qi_ref[t], kj_ref[t], flag_ref[t]
    rows = pl.ds(pl.multiple_of(i * BQ, BQ), BQ)
    cols = pl.ds(pl.multiple_of(j * BK, BK), BK)

    @pl.when(t == 0)
    def _open_group():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when((flag & _FIRST) != 0)
    def _open_head():
        dq_s[...] = jnp.zeros_like(dq_s)

    def block(masked: bool, ragged: bool = False):
        # ``_bwd_dkv_kernel``'s tile, (BK, BQ), and its zeros past Tq; past Tk ``_bwd_dq_kernel``'s: k and v zero
        # there, so that what the copy did not bring reaches no row of dq
        past_q, past_k = ragged and Tq % BQ != 0, ragged and Tk % BK != 0
        q, g = _load(q_ref, past_q, i, BQ, Tq), _load(g_ref, past_q, i, BQ, Tq)
        k, v = _load(k_ref, past_k, j, BK, Tk), _load(v_ref, past_k, j, BK, Tk)
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32).T  # (BK, 1|BQ) broadcasts
        p = jnp.exp(s - _load(lse_ref, past_q, i, BQ, Tq, 1))
        if masked:
            # past Tq only a mask's own rows reach p; past Tk its columns do
            keep = _keep(i * BQ, j * BK, (BK, BQ), 1, window, causal,
                         rows=Tq if past_q and has_mask and mask_ref.shape[1] > 1 else None,
                         cols=Tk if past_k and has_mask else None)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
        dv_s[cols, :] += jax.lax.dot_general(p.astype(g.dtype), g, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, g, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - _load(delta_ref, past_q, i, BQ, Tq, 1))).astype(q.dtype)
        dk_s[cols, :] += scale * jax.lax.dot_general(ds, q, _NN, preferred_element_type=jnp.float32)
        dq_s[rows, :] += scale * jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

    _on_edge(flag, causal, block, (_TAILQ if Tq % BQ else 0) | (_TAILK if Tk % BK else 0))

    @pl.when((flag & _LAST) != 0)
    def _close_head():
        dq_ref[0] = dq_s[:Tq, :].astype(dq_ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _close_group():
        dk_ref[0] = dk_s[:Tk, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:Tk, :].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _flash_walk(Tq: int, Tk: int, BQ: int, BK: int, causal: bool, window: int | None, rep: int):
    """``_flash_schedule``'s third order, the one walk's: a KV group's query
    heads outermost, a head's columns, a column's kept rows.  ``_FIRST`` /
    ``_LAST`` bracket a head (its dq); the group's dk and dv open at the first
    entry and close at the last."""
    qi, kj, _, flag = _flash_schedule(Tq, Tk, BQ, BK, causal, window, by_column=True)
    flag = flag & ~(_FIRST | _LAST)
    flag[0] |= _FIRST
    flag[-1] |= _LAST
    out = (np.tile(qi, rep), np.tile(kj, rep), np.repeat(np.arange(rep, dtype=np.int32), len(qi)), np.tile(flag, rep))
    for a in out:
        a.setflags(write=False)
    return out


def _flash_walk_bytes(Tq: int, Tk: int, BQ: int, BK: int, hs: int, itemsize: int) -> int:
    """VMEM the one walk holds from block to block: dq of a head and dk, dv of
    the group in float32, whole blocks long, and the three output blocks they
    are cast into at a head's and the group's end (two buffers each, the
    pipeline's)."""
    return 4 * hs * (-(-Tq // BQ) * BQ + 2 * -(-Tk // BK) * BK) + 2 * itemsize * hs * (Tq + 2 * Tk)


def _flash_bwd_form(resident: int) -> str:
    """``one_walk`` where its sums fit the VMEM a kernel may ask for
    (``_gmm_vmem_cap``: what the device reports; the interpreter has none to
    fill) beside the block tiles ``_flash_blocks`` sized for the 16 MiB a
    kernel gets without asking; else ``two_kernels``."""
    return "one_walk" if _interpret() or resident + _GMM_VMEM_DEFAULT <= _gmm_vmem_cap() else "two_kernels"


@functools.partial(jax.jit, static_argnames=("causal", "scale", "H", "G", "mode", "mq", "window", "form"))
def _flash_bwd(g, q, k, v, out, lse, mask, causal: bool, scale: float, H: int, G: int, mode: str | None, mq: int,
               window: int | None = None, form: str | None = None):
    """g/q/out (BH, Tq, hs), k/v (BG, Tk, hs), lse (BH, 1, Tq);
    returns (dq (BH,...), dk, dv (BG,...)).

    ``_flash_bwd`` (``one_walk``) runs over the KV groups and walks a group's
    blocks once a query head, with dq of the head and dk, dv of the group
    summed in VMEM.  Where those sums do not fit (``_flash_bwd_form``; ``form``
    is the tests' and the tuning tool's to set), two kernels:
    ``_flash_bwd_dq`` runs over the flat query heads with K/V gathered by
    index map; ``_flash_bwd_dkv`` runs over the KV groups and walks each
    column of blocks once a query head of the group, so K/V are fetched once
    a column and dk/dv are summed in its float32 accumulators."""
    BH, Tq, hs = q.shape
    BG, Tk, _ = k.shape
    rep = H // G
    BQ, BK = _flash_blocks(q, k, mq, window, causal)
    has_mask = mask is not None
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(BH, 1, Tq)
    _note_schedule(Tq, Tk, BQ, BK, causal, window)
    static = dict(BQ=BQ, BK=BK, causal=causal, scale=scale, has_mask=has_mask, window=window, Tq=Tq, Tk=Tk)
    operands = [g, q, k, v, lse, delta] + ([mask] if has_mask else [])
    resident = _flash_walk_bytes(Tq, Tk, BQ, BK, hs, q.dtype.itemsize)
    form = form or _flash_bwd_form(resident)
    stats["flash_bwd_" + form] = stats.get("flash_bwd_" + form, 0) + 1        # a call site, at trace time

    def specs(by_group):
        q_spec, kv_spec, row_spec, mask_spec = _flash_specs(H, G, mode, mq, BQ, BK, hs, by_group)
        ins = [q_spec, q_spec, kv_spec, kv_spec, row_spec, row_spec] + ([mask_spec] if has_mask else [])
        return ins, q_spec, kv_spec

    if form == "one_walk":
        sched = _flash_walk(Tq, Tk, BQ, BK, causal, window, rep)
        flash_schedule.update(bwd_form=form, bwd_resident_bytes=resident, bwd_grid_steps=len(sched[0]))
        sums = lambda T, B: pltpu.VMEM((-(-T // B) * B, hs), jnp.float32)   # noqa: E731 - whole blocks long
        return pl.pallas_call(
            functools.partial(_bwd_walk_kernel, **static),
            name="_flash_bwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(BG, len(sched[0])),
                in_specs=specs(True)[0],
                out_specs=[
                    pl.BlockSpec((1, Tq, hs), lambda b, t, *p: (b * rep + p[2][t], 0, 0)),
                    *[pl.BlockSpec((1, Tk, hs), lambda b, t, *p: (b, 0, 0))] * 2,
                ],
                scratch_shapes=[sums(Tq, BQ), sums(Tk, BK), sums(Tk, BK)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((BH, Tq, hs), q.dtype),
                jax.ShapeDtypeStruct((BG, Tk, hs), k.dtype),
                jax.ShapeDtypeStruct((BG, Tk, hs), v.dtype),
            ],
            interpret=_interpret(),
            **_flash_params(vmem_limit_bytes=resident + _GMM_VMEM_DEFAULT),
        )(*sched, *operands)

    qi, kj, _, flag = _flash_schedule(Tq, Tk, BQ, BK, causal, window)
    dq_in, dq_out, _ = specs(False)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        name="_flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(qi)),
            in_specs=dq_in,
            out_specs=dq_out,
            scratch_shapes=[
                pltpu.VMEM((BQ, hs), jnp.float32),
                pltpu.VMEM((BQ, 1), jnp.float32),
                pltpu.VMEM((BQ, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, hs), q.dtype),
        interpret=_interpret(),
        **_flash_params(),
    )(qi, kj, flag, *operands)

    sched = _flash_schedule(Tq, Tk, BQ, BK, causal, window, by_column=True, rep=rep)
    flash_schedule.update(bwd_form=form, bwd_resident_bytes=0, bwd_grid_steps=rep * len(qi) + len(sched[0]))
    dkv_in, _, kv_out = specs(True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        name="_flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BG, len(sched[0])),
            in_specs=dkv_in,
            out_specs=[kv_out, kv_out],
            scratch_shapes=[
                pltpu.VMEM((BK, hs), jnp.float32),
                pltpu.VMEM((BK, hs), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BG, Tk, hs), k.dtype),
            jax.ShapeDtypeStruct((BG, Tk, hs), v.dtype),
        ],
        interpret=_interpret(),
        **_flash_params(),
    )(*sched, *operands)
    return dq, dk, dv


#
# Dispatchers (shape-polymorphic over leading batch dims)
#


def _pad_hs(x, hs, hp):
    if hs == hp:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, hp - hs)]
    return jnp.pad(x, widths)


def _local_geometry(q_shape, k_shape):
    """(BH, BG, H, G) for the flat-batch kernel grid, from LOCAL (per-shard)
    shapes — so head counts stay correct under tp sharding inside shard_map."""
    *qb, _, _ = q_shape
    *kb, _, _ = k_shape
    BH = 1
    for b in qb:
        BH *= b
    BG = 1
    for b in kb:
        BG *= b
    H = q_shape[-3] if len(q_shape) >= 3 else 1
    G = k_shape[-3] if len(k_shape) >= 3 else 1
    return BH, BG, H, G


def _canon_mask_operand(mask, q_shape, k_shape):
    """Canonicalize an additive mask to the kernels' (M, mq, Tk) f32 layout.
    Returns (mask3, mode, mq); (None, None, 1) when mask is None."""
    if mask is None:
        return None, None, 1
    mode, mq = _canon_mask(mask.shape, q_shape, k_shape)
    Tk = k_shape[-2]
    # broadcast dims are all 1, so the canonical form is a plain reshape
    return mask.reshape(-1, mq, Tk).astype(jnp.float32), mode, mq


def _fwd_local(q, k, v, mask, causal: bool, scale: float, window: int | None = None):
    """Single-device forward on concrete arrays: flatten batch, pad the head
    sizes, run.  ``mask`` is the original-rank additive mask or None.

    q/k go at their width and v at its own, never padded to the other's
    (zeros the score product and the weighted sum would multiply).  v is
    padded with zeros to whole lane tiles; so are q/k where the two widths are
    one (the call there ever was, a head of 64 padded to 128 included, and the
    one the backward kernels pad alike).  Beside a v of another width, q/k
    wider than a tile and a whole number of half tiles go as they are, a
    block's last dimension the array's whole width: at a latent prompt's 192
    over 128 the kernel takes the same time as at 256 and the two pads' writes
    are not made (``tools/flash_tune.py --latent``, PERF.md, PR 54: 6.79 ms a
    call for 7.63 at 32 heads and 8,192 tokens, 9.94 with all three at 256)."""
    *batch, Tq, hs = q.shape
    Tk, hv = k.shape[-2], v.shape[-1]
    as_it_is = hs != hv and hs > 128 and hs % 64 == 0
    hp, hvp = hs if as_it_is else _pad128(hs), _pad128(hv)
    BH, BG, H, G = _local_geometry(q.shape, k.shape)
    mask3, mode, mq = _canon_mask_operand(mask, q.shape, k.shape)
    out, lse = _flash_fwd(
        _pad_hs(q.reshape(BH, Tq, hs), hs, hp),
        _pad_hs(k.reshape(BG, Tk, hs), hs, hp),
        _pad_hs(v.reshape(BG, Tk, hv), hv, hvp),
        mask3,
        bool(causal), float(scale), H, G, mode, mq,
        window=None if window is None else int(window),
    )
    _note_widths(hs, hv, hp, hvp)
    return out[..., :hv].reshape(*batch, Tq, hv), lse.reshape(*batch, Tq)


def _bwd_local(g, q, k, v, out, lse, mask, causal: bool, scale: float, window: int | None = None):
    *batch, Tq, hs = q.shape
    Tk = k.shape[-2]
    hp = _pad128(hs)
    BH, BG, H, G = _local_geometry(q.shape, k.shape)
    mask3, mode, mq = _canon_mask_operand(mask, q.shape, k.shape)
    r3 = lambda x, T, n: _pad_hs(x.reshape(n, T, hs), hs, hp)
    dq, dk, dv = _flash_bwd(
        r3(g, Tq, BH), r3(q, Tq, BH), r3(k, Tk, BG), r3(v, Tk, BG), r3(out, Tq, BH),
        lse.reshape(BH, 1, Tq).astype(jnp.float32),
        mask3,
        bool(causal), float(scale), H, G, mode, mq,
        window=None if window is None else int(window),
    )
    _note_widths(hs, hs, hp, hp)
    return (
        dq[..., :hs].reshape(q.shape),
        dk[..., :hs].reshape(k.shape),
        dv[..., :hs].reshape(v.shape),
    )


def _qkv_spec(mesh, q_shape, k_shape):
    """PartitionSpec for (*batch, T, hs) operands: batch dim over the data
    axes, head dim over tp, T/hs replicated (sharding either is a kernel
    restructuring — ring attention — not a blockwise-local op)."""
    import math

    from jax.sharding import PartitionSpec as P

    rank = len(q_shape)
    spec = [None] * rank
    nbatch = rank - 2
    data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1)
    if nbatch >= 1 and data_axes:
        kdiv = math.prod(mesh.shape[a] for a in data_axes)
        if q_shape[0] % kdiv == 0 and k_shape[0] % kdiv == 0:
            spec[0] = data_axes if len(data_axes) > 1 else data_axes[0]
    if nbatch >= 2 and "tp" in mesh.axis_names and mesh.shape["tp"] > 1:
        tp = mesh.shape["tp"]
        if q_shape[1] % tp == 0 and k_shape[1] % tp == 0:
            spec[1] = "tp"
    return P(*spec)


def _concrete_multi_device(x) -> bool:
    """True iff ``x`` is a concrete array sharded across >1 device: a bare
    pallas_call on it would be GSPMD-replicated (all-gather + redundant
    compute), so dispatch declines outside a mesh context."""
    try:
        sh = getattr(x, "sharding", None)
        return sh is not None and len(sh.device_set) > 1
    except Exception:
        return False


def _dispatch(local_fn, operands, specs):
    """Shared dispatch policy for fwd/bwd.

    Inside a ``mesh_context`` with a multi-device mesh: run under
    ``jax.shard_map`` partitioned over batch (dp/fsdp) and head (tp) axes —
    distributed TrainSteps keep the flash kernels instead of falling back to
    the O(T²) reference (round-1 VERDICT weak #3).  If no dim is divisible
    by the mesh axes, decline (None): the jnp fallback shards as plain
    einsums, which beats replicating the kernel on every device.
    """
    mesh = _mesh_var.get()
    if mesh is not None and mesh.devices.size > 1:
        in_specs, out_specs = specs
        if not any(s is not None for spec in in_specs for s in tuple(spec)):
            return None
        stats["sharded"] += 1
        from thunder_tpu.distributed.prims import shard_map_compat

        return shard_map_compat(
            local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )(*operands)
    if any(_concrete_multi_device(x) for x in operands):
        return None
    stats["direct"] += 1
    return local_fn(*operands)


def _mask_shard_spec(mask, q_shape, k_shape, qkv_spec):
    """PartitionSpec for the mask under a sharded dispatch, or ``False`` when
    the mask layout can't ride the mesh (per-head masks against tp-sharded
    heads): the caller then declines and the jnp fallback shards as einsums."""
    from jax.sharding import PartitionSpec as P

    if mask is None:
        return None
    mode, _ = _canon_mask(mask.shape, q_shape, k_shape)
    if mode == "shared":
        return P(*(None,) * mask.ndim)
    if mode == "batch" and mask.ndim == 4 and len(tuple(qkv_spec)) > 0:
        # HF padding-mask layout (B, 1, 1|Tq, Tk): shard B like q's batch dim
        return P(tuple(qkv_spec)[0], None, None, None)
    return False


def flash_sdpa(q, k, v, mask, causal, scale, window=None):
    """Returns (out, lse) via the flash kernels, or None if unsupported.
    ``v`` may be narrower or wider than ``q``/``k``: out is as wide as it."""
    if not _enabled() or not _supported(
        q.shape, k.shape, v.shape, q.dtype, causal,
        mask.shape if mask is not None else None, window, own_v_width=True,
    ):
        return None
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_var.get()
    spec = _qkv_spec(mesh, q.shape, k.shape) if mesh is not None else P()
    lse_spec = P(*tuple(spec)[:-1])
    if mask is None:
        return _dispatch(
            lambda q, k, v: _fwd_local(q, k, v, None, bool(causal), float(scale), window),
            (q, k, v),
            (((spec,) * 3), (spec, lse_spec)),
        )
    mspec = _mask_shard_spec(mask, q.shape, k.shape, spec)
    if mspec is False and mesh is not None and mesh.devices.size > 1:
        return None
    return _dispatch(
        lambda q, k, v, m: _fwd_local(q, k, v, m, bool(causal), float(scale), window),
        (q, k, v, mask),
        ((spec, spec, spec, mspec), (spec, lse_spec)),
    )


def flash_sdpa_backward(g, q, k, v, out, lse, mask, causal, scale, window=None):
    """Returns (dq, dk, dv) via the flash kernels, or None if unsupported."""
    if not _enabled() or not _supported(
        q.shape, k.shape, v.shape, q.dtype, causal,
        mask.shape if mask is not None else None, window,
    ):
        return None
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_var.get()
    spec = _qkv_spec(mesh, q.shape, k.shape) if mesh is not None else P()
    lse_spec = P(*tuple(spec)[:-1])
    if mask is None:
        return _dispatch(
            lambda g, q, k, v, out, lse: _bwd_local(
                g, q, k, v, out, lse, None, bool(causal), float(scale), window),
            (g, q, k, v, out, lse),
            ((spec, spec, spec, spec, spec, lse_spec), (spec, spec, spec)),
        )
    mspec = _mask_shard_spec(mask, q.shape, k.shape, spec)
    if mspec is False and mesh is not None and mesh.devices.size > 1:
        return None
    return _dispatch(
        lambda g, q, k, v, out, lse, m: _bwd_local(
            g, q, k, v, out, lse, m, bool(causal), float(scale), window),
        (g, q, k, v, out, lse, mask),
        ((spec, spec, spec, spec, spec, lse_spec, mspec), (spec, spec, spec)),
    )


#
# Executor registration + jaxex fast-path hooks
#


def _sdpa_full(q, k, v, mask, causal, scale, window=None):
    res = flash_sdpa(q, k, v, mask, causal, scale, window)
    if res is None:  # checker raced with env change: stay correct
        from thunder_tpu.executors.jaxex import _sdpa_reference

        return _sdpa_reference(q, k, v, mask, causal, scale, window)
    return res


def _sdpa_backward_full(g, q, k, v, out, lse, mask, causal, scale, window=None):
    res = flash_sdpa_backward(g, q, k, v, out, lse, mask, causal, scale, window)
    if res is None:
        from thunder_tpu.executors.jaxex import _sdpa_backward_reference

        return _sdpa_backward_reference(g, q, k, v, out, lse, mask, causal, scale, window)
    return res


ex = OperatorExecutor("pallas", version=jax.__version__)
register_executor(ex)

_sdpa_op = ex.register_operator("pallas_sdpa", like=prim_lookup[PrimIDs.SDPA], fn=_sdpa_full)
_sdpa_bwd_op = ex.register_operator(
    "pallas_sdpa_backward", like=prim_lookup[PrimIDs.SDPA_BACKWARD], fn=_sdpa_backward_full
)


def _sdpa_checker(q, k, v, mask, causal, scale, window=None):
    # A v of another width is claimed only where no operand asks for a
    # gradient: ``_sdpa_bwd_checker`` keeps the one-width rule, so a forward
    # claimed in a differentiated trace is one whose backward is claimed too.
    # (jaxex's own ``sdpa`` still tries ``flash_sdpa`` on the arrays it is
    # given; its ``(out, lse)`` are what the reference backward reads.)
    trains = any(getattr(x, "requires_grad", False) for x in (q, k, v))
    return _enabled() and _supported(
        q.shape, k.shape, v.shape, q.dtype, causal,
        mask.shape if mask is not None else None, window, own_v_width=not trains,
    )


def _sdpa_bwd_checker(g, q, k, v, out, lse, mask, causal, scale, window=None):
    return _enabled() and _supported(
        q.shape, k.shape, v.shape, q.dtype, causal,
        mask.shape if mask is not None else None, window,
    )


ex.register_implementation(PrimIDs.SDPA, _sdpa_op, checker=_sdpa_checker)
ex.register_implementation(PrimIDs.SDPA_BACKWARD, _sdpa_bwd_op, checker=_sdpa_bwd_checker)

pallas_ex = ex
add_default_executor(ex)  # ahead of xla so the claiming pass prefers the kernels

#
# Fused cross-entropy kernel (the apex/triton-CE analog,
# reference apex_entropyex.py:15, triton_crossentropy_impl.py:18).
#
# One pass over the logits: the vocab dim is tiled along a sequential grid
# axis and VMEM scratch carries the online-logsumexp state (running max,
# rescaled sum) plus the picked target logit — so the (N, V) matrix is read
# from HBM exactly once and no (N, V) log-prob intermediate exists.
#


def _ce_kernel(logits_ref, tgt_ref, loss_ref, lse_ref, m_s, s_s, p_s, *, BN, BV):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK_VALUE)
        s_s[...] = jnp.zeros_like(s_s)
        p_s[...] = jnp.zeros_like(p_s)

    x = logits_ref[...].astype(jnp.float32)  # (BN, BV)
    t = tgt_ref[...]  # (BN, 1) int32

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    s_s[...] = s_s[...] * corr + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True)
    m_s[...] = m_new

    # the target logit: exactly one column hits across the whole vocab sweep;
    # accumulated in raw (unshifted) logit space so no rescaling is needed
    col = j * BV + jax.lax.broadcasted_iota(jnp.int32, (BN, BV), 1)
    hit = col == t
    p_s[...] = p_s[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finalize():
        lse = m_s[...] + jnp.log(s_s[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - p_s[...]


def _ce_blocks(n: int, v: int) -> tuple[int, int] | None:
    bn = next((b for b in (256, 128, 64, 32, 16, 8) if n % b == 0), None)
    if bn is None:
        return None
    # Widest lane-aligned (×128) divisor of v under a VMEM budget: wider
    # vocab tiles mean fewer grid steps and longer DMA bursts (32000 =
    # 128·250 admits BV=3200 where a power-of-two list stops at 256).
    budget = 4 * 1024 * 1024  # f32 block bytes; pallas double-buffers on top
    bv = None
    for k in range(min(v, 4096) // 128, 0, -1):
        b = k * 128
        if v % b == 0 and bn * b * 4 <= budget:
            bv = b
            break
    if bv is None:
        # no lane-aligned divisor: decline so the checker yields to XLA —
        # a sub-lane (64-wide) tile is structurally likely to lose, the
        # exact regression class the win-or-yield rule exists to prevent
        return None
    return bn, bv


@functools.partial(jax.jit, static_argnames=())
def _flash_ce(logits, target):
    """logits (N, V) float, target (N,) int -> (losses, lse), both (N,) f32."""
    N, V = logits.shape
    BN, BV = _ce_blocks(N, V)
    kernel = functools.partial(_ce_kernel, BN=BN, BV=BV)
    params = {}
    if not _interpret():
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    losses, lse = pl.pallas_call(
        kernel,
        name="flash_cross_entropy",
        grid=(N // BN, V // BV),
        in_specs=[
            pl.BlockSpec((BN, BV), lambda i, j: (i, j)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BN, 1), jnp.float32),
            pltpu.VMEM((BN, 1), jnp.float32),
            pltpu.VMEM((BN, 1), jnp.float32),
        ],
        interpret=_interpret(),
        **params,
    )(logits, target.astype(jnp.int32).reshape(N, 1))
    return losses[:, 0], lse[:, 0]


def _ce_supported(logits_shape, target_shape, logits_dtype) -> bool:
    if len(logits_shape) != 2 or len(target_shape) != 1:
        return False
    try:
        if jnp.dtype(logits_dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
            return False
    except TypeError:
        return False
    return _ce_blocks(int(logits_shape[0]), int(logits_shape[1])) is not None


def _ce_local(logits, target):
    """Per-shard CE: the kernel when the local shape tiles, else the jnp
    reference (still avoids cross-shard traffic under shard_map)."""
    if _ce_blocks(int(logits.shape[0]), int(logits.shape[1])) is None:
        from thunder_tpu.executors.jaxex import _cross_entropy_fwd_reference

        return _cross_entropy_fwd_reference(logits, target)
    return _flash_ce(logits, target)


def _ce_spec(mesh, n_rows: int):
    """Row-sharding spec over the data axes (rows are batch×time — locally
    independent, so CE shards embarrassingly)."""
    import math

    from jax.sharding import PartitionSpec as P

    data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1)
    if not data_axes:
        return None
    kdiv = math.prod(mesh.shape[a] for a in data_axes)
    if n_rows % kdiv != 0:
        return None
    return data_axes if len(data_axes) > 1 else data_axes[0]


def flash_cross_entropy(logits, target):
    """Returns (losses, lse) via the fused kernel, or None if unsupported.

    Under a ``mesh_context`` with a multi-device mesh the kernel runs
    shard_map-partitioned over the row (batch×time) dim — a bare pallas_call
    has no SPMD rule and would be GSPMD-replicated (every chip all-gathering
    the full (N, V) logits)."""
    if not _enabled() or not _ce_supported(logits.shape, target.shape, logits.dtype):
        return None
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_var.get()
    row = _ce_spec(mesh, int(logits.shape[0])) if mesh is not None else None
    return _dispatch(
        _ce_local,
        (logits, target),
        ((P(row, None), P(row)), (P(row), P(row))),
    )


# ---------------------------------------------------------------------------
# Paged-attention decode: flash-decoding over the serving KV block arena.
#
# The serving engine's decode step historically paid gather_dense/scatter —
# one full-cache copy per token per request — to reassemble the paged arena
# into the dense layout forward_with_cache wants.  These two kernels read and
# write the arena *in place*:
#
# - ``paged_attn_decode``: grid (request,), walked in order.  The arenas stay
#   in HBM (``pl.ANY``) and the kernel fetches them itself: the block table and
#   positions ride in as **scalar-prefetch** operands (the table first, then
#   the positions, ``chain`` and the layer: the trace readers find the call by
#   the first), and one grid step walks its request's table from the first
#   live block (0, or where the sliding window begins) to the last in *chunks*
#   of ``C`` consecutive entries.  A block's KV groups of one layer are
#   contiguous in the arena, so one DMA a table entry lands all ``ng`` groups,
#   ``(ng, bs, hs)``, in a slot of VMEM scratch; a chunk (``C * bs`` keys a
#   group) is attended with one dot batched over the groups while the next
#   chunk's copies are in flight (:func:`_arena_walk`; no gather primitive
#   anywhere in the program).  ``C`` follows from the shapes and a fixed VMEM
#   budget (:func:`paged_kv_chunk_blocks`).
#   The chunk loop is ``mla_paged_decode``'s (PR 47; what PR 35 measured is in
#   the comment block above ``_MLA_CHUNK_KEYS``: a copy's start costs the core
#   ~45 ns in a rolled loop and ~20 ns unrolled whatever it moves, and starts
#   overlap products only in straight-line code whose target the products do
#   not read).  The two slots are separate scratch arrays with static roles
#   (K, V and, quantised, their scale rows: a tuple a slot); the loop takes two
#   chunks a turn, attending one slot while the other's ``2 C`` copies are
#   started in the same basic block (unrolled, nothing conditional around them),
#   an odd last chunk once outside the loop; a slot's copies are awaited once an
#   array, by the whole buffer's bytes.  What is started beside a row's last
#   chunk is the first chunk of the next row that has one (``chain``, from
#   ``pos`` and the window in :func:`_paged_decode_call`), so a request finds
#   its first chunk in flight; grid step 0 starts the first such row's, the last
#   fetches its own last chunk again and waits for it; the slot a row begins in
#   rides in SMEM scratch.  Straight-line code is long, so the layer is an
#   operand and a program's layers of one form share one traced body.  With 8
#   and 16 KB slabs (two and four 128-lane groups a block) the walk was bound by
#   the starts, not the bytes: 0.29 and 0.52 of its roofline before, where 40
#   and 120 KB slabs read 0.83 and 0.88 (``PERF.md`` section 6, PR 47;
#   ``tools/paged_tune.py`` times the kernel alone and splits a chunk's time).
#   Online softmax runs across chunks in loop-carried values; the positional
#   keep-mask (strictly-older slots, optional sliding window) and the int8/fp8
#   dequant from the scale arenas are fused in-kernel; GQA is native (q
#   reshaped to (B, ng, rep, hs)).  A request pays for its own context: which
#   rows its neighbours are changes what is prefetched and when, never a
#   chunk's boundaries nor the order of its sums, and the width of the table
#   bucket does not enter its walk, so its output is the same bits alone, in
#   any batch and under any bucket.  The *fresh* token's K/V (this step's
#   projection, at the cache compute dtype — exactly what the dense path
#   would have written before attending) joins as the final online-softmax
#   term, so every row has at least one kept key and the quantized path
#   attends the diagonal at full precision, matching quantize-on-scatter
#   semantics bit-for-bit.  Compiled for the TPU the slab copies need arena
#   rows of whole 128-lane tiles: a head size of that, or a head size that
#   divides 128 in a lane-packed arena (``P = 128 // hs`` KV heads side by side
#   in a row, ``(num_blocks, L, ng / P, bs, 128)``: the walk then sees ``ng / P``
#   groups of ``P * rep`` query rows, a head's queries in its own lanes and
#   zeros in the others', :func:`_lane_packed_queries`).  Arena rows that are
#   neither take ``paged_attn_verify``'s per-block grid (:func:`_decode_by_blocks`).
# - ``paged_token_write``: the scatter_token replacement — one grid step per
#   request lands the fresh K/V (or its quantization scale) in its
#   ``table[pos // bs]``/``pos % bs`` arena slot via an aliased output
#   (``input_output_aliases``), so the update is in place and the decode
#   program stays scatter-free.
#
# The two attention entries choose their own form from what they can observe
# (:func:`paged_decode_path`): the kernel where Pallas is on (the TPU, or the
# interpreter a CPU opted into with ``THUNDER_TPU_PALLAS_INTERPRET=1``) and it
# takes the arena's rows, else :func:`paged_attn_xla`, the same attention in
# XLA.  The programs above them are the same either way; ``stats`` counts a
# call site by the form it took (``paged_walk``, ``paged_by_blocks``,
# ``paged_xla``), at trace time.
# ---------------------------------------------------------------------------


def paged_available() -> bool:
    """Whether the paged decode kernels can run here: Pallas enabled (TPU, or
    interpret mode opted in)."""
    return _pallas_available()


def paged_walk_lanes_ok(lanes: int) -> bool:
    """Whether :func:`paged_attn_decode`'s chunked walk can fetch arenas whose
    rows are ``lanes`` wide here.  Compiled for the TPU it copies ``(ng, bs,
    lanes)`` slabs out of the HBM arena itself, and Mosaic takes such a copy
    only where ``lanes`` is whole 128-lane tiles: it holds a narrower arena
    padded to 128 lanes and refuses the 64- or 96-lane slice of it ("Slice
    shape along dimension 4 must be aligned to tiling (128)"), whatever the
    form of the index.  Narrower rows are fetched a block a grid step through
    BlockSpecs (:func:`_decode_by_blocks`), which Mosaic takes at any width.
    The interpreter has no tiles and walks any width."""
    return _interpret() or lanes % 128 == 0


def paged_head_size_ok(hs: int) -> bool:
    """Whether a head of size ``hs`` can ride :func:`paged_attn_decode`'s
    chunked walk here: whole 128-lane tiles, or a size that divides 128, which
    the pool stores lane-packed (``kv_pool.PagedKVPool.lane_pack``: 128 // hs KV
    heads side by side in a row; 64, 32, 16 ...).  What the walk is handed
    decides (:func:`paged_walk_lanes_ok` of the arena's rows): a head of 96, a
    quantised arena of a narrow head (a head a row, its scale a head's) or KV
    heads that do not come in whole rows go a block a grid step."""
    return _interpret() or hs % 128 == 0 or (hs < 128 and 128 % hs == 0)


def paged_decode_path(lanes: int, window: int | None = None) -> str:
    """The form :func:`paged_attn_decode` takes here for arena rows ``lanes``
    wide under a sliding ``window``: ``"walk"`` (the chunked walk), ``"by_blocks"``
    (a block a grid step: rows Mosaic cannot slice), or ``"xla"``
    (:func:`paged_attn_xla`: Pallas is off, or such rows with a window, which
    the per-block kernel has not)."""
    if not _pallas_available():
        return "xla"
    if paged_walk_lanes_ok(lanes):
        return "walk"
    return "by_blocks" if window is None else "xla"


def paged_attn_xla(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos, *, layer,
                   k_scale=None, v_scale=None, window=None, packed_out=False):
    """:func:`paged_attn_verify` (and, at ``T`` = 1, :func:`paged_attn_decode`)
    in XLA, for a backend without Pallas and for the arenas the kernels do not
    take: ``layer``'s blocks of the rows gathered by the table (a quantised
    arena dequantised to the cache compute dtype, ``quant.gather_dense_q``;
    a lane-packed one's heads taken apart), the fresh rows put at ``[pos, pos +
    T)``, the causal keep mask with the sliding window, one softmax.  It is the
    dense cache's own attention (``generate.attend_dense``) on the dense cache's
    own operands, so what it serves is solo ``generate()``'s to the bit.

    ``q (B, nh, T, hs)``, ``fresh_k``/``fresh_v (B, ng, T, hs)``; the rest as the
    kernels take them, and the table holds the slots of ``[pos, pos + T)`` (the
    writer's, one call later).  Returns ``(B, nh, T, hs)``; ``packed_out`` (rows of a head
    pair, differential attention): the pair's two softmaxes over its K row, each
    weighing the whole V row (``generate.diff_attend_dense``), ``(B, ng / 2, 2
    rep, T, 2 hs)``."""
    from thunder_tpu.models.generate import attend_dense, diff_attend_dense, pair_rows
    from thunder_tpu.serving.kv_pool import gather_rows
    from thunder_tpu.serving.quant import gather_dense_q

    B, nh, T, hs = q.shape
    G, lanes = k_arena.shape[2], k_arena.shape[4]
    if isinstance(layer, int):
        one = lambda a: a[:, layer:layer + 1]  # noqa: E731
    else:       # a looped model's slab, traced (``llama.Config.kv_slab``)
        one = lambda a: jax.lax.dynamic_slice_in_dim(a, layer, 1, axis=1)  # noqa: E731
    if k_scale is not None:
        kd, vd = gather_dense_q(one(k_arena), one(v_arena), one(k_scale), one(v_scale), tables, fresh_k.dtype)
    else:
        P = 1 if packed_out else lanes // hs
        kd, vd = gather_rows(one(k_arena), tables, P), gather_rows(one(v_arena), tables, P)
    if packed_out:
        assert lanes == 2 * hs, (lanes, hs)
        fresh_k, fresh_v = pair_rows(fresh_k), pair_rows(fresh_v)
    put = jax.vmap(lambda rows, new, p: jax.lax.dynamic_update_slice_in_dim(rows, new, p, axis=1))
    kd, vd = put(kd[0], fresh_k.astype(kd.dtype), pos), put(vd[0], fresh_v.astype(vd.dtype), pos)
    j = jnp.arange(kd.shape[2])
    qpos = (pos[:, None] + jnp.arange(T))[:, :, None]                  # (B, T, 1)
    keep = j <= qpos
    if window is not None:
        keep = jnp.logical_and(keep, j > qpos - window)
    if packed_out:
        out = diff_attend_dense(q.reshape(B, G, 2, nh // (2 * G), T, hs), kd, vd, keep[:, None, None])
        return out.reshape(B, G, nh // G, T, lanes)
    return attend_dense(q, kd, vd, keep[:, None])


# VMEM that one chunk of the decode walk may hold: K and V of its table
# entries, in both slots.  And the most keys a chunk attends at once, which
# bounds the score tile (and the length of a chunk's unrolled starts) whatever
# the byte budget says.
_PAGED_CHUNK_BYTES = 2 * 1024 * 1024
_PAGED_CHUNK_KEYS = 512


def paged_kv_chunk_blocks(ng: int, bs: int, hs: int, itemsize: int) -> int:
    """``C``: how many consecutive table entries one step of
    :func:`paged_attn_decode`'s walk fetches and attends, from what the call
    sees — the arena's (local) KV groups, block size, head size and storage
    item size.  Not from the table's width nor the batch: a row's chunk
    boundaries, and with them the order of its sums, depend on the row alone."""
    if not paged_walk_lanes_ok(hs):
        return 1                                        # _decode_by_blocks
    per_block = 2 * 2 * ng * bs * hs * itemsize        # K and V, two slots
    return max(1, min(_PAGED_CHUNK_BYTES // per_block, _PAGED_CHUNK_KEYS // bs))


def _scale_column(row, bs, at=0):
    """Lanes ``[at, at + bs)`` of the dequant-scale row ``row`` (1, n) as a
    ``(bs, 1)`` column.  The row has a block's slots on lanes and the K/V tile
    wants them on sublanes: the masked lane-sum below is that transpose in
    ops Mosaic lowers at any ``bs``, and it is exact (one non-zero term per
    output)."""
    n = row.shape[1]
    pick = (jax.lax.broadcasted_iota(jnp.int32, (bs, n), 1)
            == jax.lax.broadcasted_iota(jnp.int32, (bs, n), 0) + at)
    return jnp.sum(jnp.where(pick, row, 0.0), axis=1, keepdims=True)


def _scale_rows(scale, layer):
    """One layer of a ``(num_blocks, L, ng, bs)`` scale arena as lane-dense
    rows ``(num_blocks, 1, n)``, ``n`` = ``ng * bs`` rounded up to 128 lanes:
    a block's scale for group ``g``, slot ``j`` sits at lane ``g * bs + j``.
    Mosaic cannot slice an HBM array whose last dim is not whole lane tiles,
    so the walk could not copy ``(ng, bs)`` slabs out of the arena itself; a
    slice, a reshape and a pad of one layer's scales (no gather) is what the
    kernel's row copies read instead."""
    nb, _, ng, bs = scale.shape
    rows = jax.lax.dynamic_index_in_dim(scale, layer, axis=1, keepdims=False).reshape(nb, 1, ng * bs)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, -(ng * bs) % 128)))


def _paged_dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _paged_start_chunk(tab_ref, arenas, bufs, sems, r, at, hi, *, layer, C, unroll=True):
    """Start the copies of row ``r``'s ``C`` table entries from ``at`` into one
    slot's buffers, as straight-line code (a loop unrolled where the kernel is
    lowered: traced once; the interpreter, which has nothing to overlap and
    would compile every copy as a slice and an update, keeps it rolled): an
    entry's ``(ng, bs, lanes)`` slab of K and of V and, quantised, its two
    scale rows.  The tail of a row's last chunk (entries ``>= hi``) fetches
    block ``hi - 1`` again: real arena bytes, masked as future ones."""
    def one(t, _):
        blk = tab_ref[r, jnp.minimum(at + t, hi - 1)]
        for n, (src, dst) in enumerate(zip(arenas, bufs)):
            src, dst = (src.at[blk, layer], dst.at[:, t]) if n < 2 else (src.at[blk], dst.at[t])
            pltpu.make_async_copy(src, dst, sems.at[n]).start()
    jax.lax.fori_loop(0, C, one, None, unroll=unroll)


def _paged_wait_chunk(bufs, sems, *, C):
    """Wait once an array for the bytes of the ``C`` copies started into it."""
    for n, buf in enumerate(bufs):
        whole = buf.at[:, pl.ds(0, C)] if n < 2 else buf.at[pl.ds(0, C)]
        pltpu.make_async_copy(whole, whole, sems.at[n]).wait()


def _arena_walk(tab_ref, pos_ref, chain_ref, i, q, arenas, bufs, dq, sem, par, *, layer, bs, C,
                window, cdtype, sm, parts, unroll):
    """Request ``i``'s online softmax over its strictly-older arena slots:
    ``(m, l, acc)`` for queries ``q`` (ng, rows, hs), before the fresh term.

    ``arenas``: the HBM refs ``(k, v)``, or ``(k, v, k_rows, v_rows)`` with
    the layer's :func:`_scale_rows`; ``bufs``: a tuple a slot of their VMEM
    chunk buffers, ``(ng, C, bs, hs)`` for K/V and ``(C, 1, n)`` for scale rows;
    ``dq`` (quantised): the two ``(ng, C, bs, hs)`` buffers the dequantised
    chunk is built in; ``sem``: DMA semaphores ``(2, len(arenas))``; ``par``:
    the slot this row's first chunk is in, SMEM ``(1,)``; ``parts``: the
    loop's product, chunk start and chunk wait (``tools/paged_tune.py`` takes
    one out).

    The walk covers table entries ``[lo, hi)``: ``hi`` is the first block with
    no slot ``< pos``, ``lo`` the block of the oldest slot the window keeps.
    Chunk ``c`` is entries ``lo + c*C ...``, counted from the row's own first
    live block; every chunk that runs starts at a live block and so keeps at
    least one slot: ``exp`` never sees an all-masked row.

    The loop is ``mla_paged_decode``'s (the comment block above
    ``_MLA_CHUNK_KEYS`` has what was measured): the slots are arrays with static
    roles and the loop takes two chunks a turn, one slot attended while the
    other's ``2 C`` copies (``4 C`` quantised) are started beside the products in
    the same straight-line code, nothing conditional around them, and awaited
    once an array.  What is started beside a row's last chunk is the first
    chunk of the next row that has one (``chain_ref[i]``; ``B``: none, and the
    row fetches its own last chunk again and waits for it), so a row finds its
    first chunk in flight, in the slot ``par`` names; grid step 0 starts the
    first such row's (``chain_ref[B]``).  The grid is walked in order."""
    dot, start_chunk, wait_chunk = parts
    quantized = len(arenas) == 4
    ng, rows, hs = q.shape
    B = pl.num_programs(0)
    p_i = pos_ref[i]

    def span(r):
        p = pos_ref[r]
        first = 0 if window is None else jnp.maximum(p - (window - 1), 0)
        lo = first // bs
        return lo, jnp.where(first < p, (p + bs - 1) // bs, lo)

    def start(r, c, span_r, k):
        start_chunk(tab_ref, arenas, bufs[k], sem.at[k], r, span_r[0] + c * C, span_r[1], layer=layer, C=C,
                    unroll=unroll)

    def wait(k):
        wait_chunk(bufs[0], sem.at[k], C=C)                # the slots' arrays are the same shapes

    lo, hi = span(i)
    n = (hi - lo + C - 1) // C
    nxt = chain_ref[i]
    last = nxt == B
    r_n = jnp.minimum(nxt, B - 1)
    lo_n, hi_n = span(r_n)

    @pl.when(i == 0)
    def _first():
        par[0] = 0
        first = chain_ref[B]

        @pl.when(first < B)
        def _():
            start(first, 0, span(first), 0)

    def ahead(c, k):
        # into slot k: this row's chunk c if it has one, else the first chunk of
        # the next row that has any; the last such row of the grid fetches its own
        # last chunk again (waited for below, never attended)
        own = jnp.logical_or(c < n, last)
        chunk = jnp.where(c < n, c, jnp.where(last, n - 1, 0))
        start(jnp.where(own, i, r_n), chunk, (jnp.where(own, lo, lo_n), jnp.where(own, hi, hi_n)), k)

    def dequant(k):
        # per table entry and group: (bs, hs) stored values times their
        # (bs, 1) scale column, rounded to the cache compute dtype exactly as
        # ``quant.gather_dense_q`` does
        def one(t, _):
            for x_buf, s_buf, d_buf in zip(bufs[k][:2], bufs[k][2:], dq):
                for g in range(ng):
                    col = _scale_column(s_buf[t], bs, at=g * bs)
                    d_buf[g, t] = (x_buf[g, t].astype(jnp.float32) * col).astype(cdtype)
        jax.lax.fori_loop(0, C, one, None)

    def tile(x):                                           # (ng, C, bs, hs)
        return x[...].reshape(ng, C * bs, hs).astype(q.dtype)

    def attend(c, k, carry):
        m_prev, l_prev, acc = carry
        if quantized:
            dequant(k)
        keys, vals = (tile(x) for x in (dq if quantized else bufs[k][:2]))
        s = dot(q, keys, (((2,), (2,)), ((0,), (0,)))) / sm    # (ng, rows, C*bs)
        posn = (lo + c * C) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, C * bs), 2)
        keep = posn < p_i                                  # strictly older: the
        if window is not None:                             # fresh token is the
            keep = jnp.logical_and(keep, posn > p_i - window)  # caller's final term
        s = jnp.where(keep, s, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=2, keepdims=True)
        acc = acc * corr + dot(p.astype(q.dtype), vals, (((2,), (1,)), ((0,), (0,))))
        return m_new, l_new, acc

    def walk(a, b):
        def pair(j, carry):
            c = 2 * j
            ahead(c + 1, b)
            wait(a)
            carry = attend(c, a, carry)
            ahead(c + 2, a)
            wait(b)
            return attend(c + 1, b, carry)

        def odd(carry):
            ahead(n, b)
            wait(a)
            return attend(n - 1, a, carry)

        carry = jax.lax.fori_loop(0, n // 2, pair, (
            jnp.full((ng, rows, 1), _MASK_VALUE, jnp.float32),
            jnp.zeros((ng, rows, 1), jnp.float32),
            jnp.zeros((ng, rows, hs), jnp.float32)))
        return jax.lax.cond(n % 2 == 1, odd, lambda carry: carry, carry)

    base = par[0]
    carry = jax.lax.cond(base == 0, lambda: walk(0, 1), lambda: walk(1, 0))

    @pl.when(n > 0)
    def _handed_over():
        spare = (base + n) % 2
        par[0] = spare

        @pl.when(last)
        def _():
            wait(spare)
    return carry


def _paged_kernel(tab_ref, pos_ref, chain_ref, layer_ref, q_ref, *rest, n_arenas, **walk):
    arenas, (fk_ref, fv_ref, o_ref), scratch = (
        rest[:n_arenas], rest[n_arenas:n_arenas + 3], rest[n_arenas + 3:])
    sm = walk["sm"]
    q = q_ref[0]                                           # (ng, rep, hs)
    m_prev, l_prev, acc = _arena_walk(
        tab_ref, pos_ref, chain_ref, pl.program_id(0), q, arenas,
        (scratch[:n_arenas], scratch[n_arenas:2 * n_arenas]), scratch[2 * n_arenas:-2], *scratch[-2:],
        layer=layer_ref[0], **walk)
    # the fresh token is one key: its score and value terms are written as
    # float32 multiply-and-sum, because Mosaic refuses the (rep, hs)·(1, hs)
    # dot_general for rep > 1.  The operands are rounded to q.dtype first, as
    # a matmul's would be, and a product of two such values is exact in
    # float32.
    qf = q.astype(jnp.float32)
    fk = fk_ref[0].astype(q.dtype).astype(jnp.float32)     # (ng, 1, hs)
    fv = fv_ref[0].astype(q.dtype).astype(jnp.float32)
    s_f = jnp.sum(qf * fk, axis=2, keepdims=True) / sm    # never masked
    m_new = jnp.maximum(m_prev, s_f)
    p = jnp.exp(s_f - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p
    acc = acc * corr + p.astype(q.dtype).astype(jnp.float32) * fv
    o_ref[0] = (acc / l_new).astype(o_ref.dtype)


def _ragged_step(i, j, nb):
    """``paged_attn_verify``'s block walk: clamp grid step ``j`` into request
    ``i``'s live block range.  Out-of-range steps (bucket padding past the
    request's last real block) re-map to the last live block, so consecutive
    grid steps hand the pipeline the *same* arena indices and it skips
    re-issuing the DMA.  The compute for those steps is ``pl.when``-skipped;
    this clamps the *fetch*."""
    return jnp.minimum(j, jnp.maximum(nb[i], 1) - 1)


def _lane_packed_queries(q, P: int):
    """Queries for a lane-packed arena: ``q (B, ng, rep, hs)`` to ``(B, ng / P,
    P * rep, P * hs)``.  Row ``j * rep + r`` of packed group ``g`` is query ``r``
    of KV head ``g * P + j`` in lanes ``[j hs, (j + 1) hs)`` and zeros in the
    other heads' lanes: the zeros cancel those heads' keys in ``q k^T``, so a
    row's scores are its own head's, exactly."""
    B, ng, rep, hs = q.shape
    own = jnp.eye(P, dtype=q.dtype)                                   # (head of the row, head of the lanes)
    packed = q.reshape(B, ng // P, P, rep, 1, hs) * own[None, None, :, None, :, None]
    return packed.reshape(B, ng // P, P * rep, P * hs)


def _lane_packed_outputs(o, P: int):
    """The inverse for the walk's output ``(B, ng / P, P * rep, P * hs)``: a
    row's own head's lanes of ``p v`` (the others hold what its weights make of
    the neighbouring heads' values, and are dropped) to ``(B, ng, rep, hs)``."""
    B, gp, rows, lanes = o.shape
    rep, hs = rows // P, lanes // P
    o = o.reshape(B, gp, P, rep, P, hs)
    own = jnp.stack([o[:, :, j, :, j] for j in range(P)], axis=2)     # (B, ng / P, P, rep, hs)
    return own.reshape(B, gp * P, rep, hs)


def paged_attn_decode(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos, *,
                      layer, k_scale=None, v_scale=None, window=None, packed_out=False):
    """Single-token attention straight off the KV block arena, one layer.

    ``q``: (B, nh, hs) queries at the compute dtype; ``k_arena``/``v_arena``:
    the FULL (num_blocks, L, ng, bs, hs) serving-pool arenas (storage dtype;
    int8/fp8 when quantized) — they stay in HBM and the kernel copies
    ``[block, layer]`` slabs out of them, so no per-layer arena slice (a
    full-arena copy) ever materializes; ``fresh_k``/``fresh_v``: (B, ng, hs)
    this step's projected K/V at the cache compute dtype (NOT yet in the
    arena — the caller lands them with :func:`paged_token_write`
    afterwards); ``tables``: (B, nbb) int32 sink-padded block tables;
    ``pos``: (B,) int32 global positions; ``k_scale``/``v_scale``:
    (num_blocks, L, ng, bs) float32 dequant scales (both or neither);
    ``window``: ``cfg.sliding_window``.  A **lane-packed** arena
    (``(num_blocks, L, ng / P, bs, P * hs)``, ``P`` KV heads of a token side by
    side in a row; told by its last axis against ``q``'s) is walked as ``ng /
    P`` groups of ``P * rep`` query rows (:func:`_lane_packed_queries`): the
    same kernel body, twice the matrix unit's passes at ``P`` = 2, half of them
    on zeros, the arena's bytes read once.  On the TPU the chunked walk needs
    the arena's rows to be whole 128-lane tiles (:func:`paged_walk_lanes_ok`);
    other arenas go a block a grid step (:func:`_decode_by_blocks`), and with a
    sliding window, or where Pallas is off, through :func:`paged_attn_xla`
    (:func:`paged_decode_path` says which).  ``bs`` = 8 and 16 both compile, at
    int8 and bfloat16.
    Returns (B, nh, hs) attention outputs at ``q.dtype``.  ``packed_out`` (a
    lane-packed arena only): the walk's rows whole, ``(B, ng / P, P * rep, P *
    hs)``: row ``j * rep + r`` of group ``g`` is query ``r`` of KV head ``g P + j``,
    its weights on *every* head's values of the row, side by side.  That is
    what differential attention sums (``models.generate.diff_attention``: both
    softmaxes of a head pair over one fetch of the pair's K and V row).
    """
    B, nh, hs = q.shape
    _, _L, ng, bs, lanes = k_arena.shape
    P = lanes // hs                                      # KV heads a row of the arena
    assert P * hs == lanes and (P == 1 or k_scale is None), (hs, lanes)
    rep = nh // (ng * P)
    assert rep * ng * P == nh, (nh, ng, P)
    path = paged_decode_path(lanes, window)
    if path == "by_blocks" and not isinstance(layer, int):
        path = "xla"        # a block a grid step takes the layer as a constant of its index maps; a traced one cannot be
    assert not packed_out or (P > 1 and path != "by_blocks"), "packed_out: a lane-packed arena, walked"
    stats["paged_" + path] = stats.get("paged_" + path, 0) + 1        # a call site, at trace time
    if path == "xla":
        out = paged_attn_xla(q[:, :, None], k_arena, v_arena, fresh_k[:, :, None], fresh_v[:, :, None], tables, pos,
                             layer=layer, k_scale=k_scale, v_scale=v_scale, window=window, packed_out=packed_out)
        return jnp.squeeze(out, -2)
    if path == "by_blocks":
        return _decode_by_blocks(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos,
                                 layer=layer, k_scale=k_scale, v_scale=v_scale)
    C = paged_kv_chunk_blocks(ng, bs, lanes, k_arena.dtype.itemsize)
    q = q.reshape(B, ng * P, rep, hs)
    if P > 1:       # the kernel sees heads of ``lanes``, ``P * rep`` query rows a group
        q = _lane_packed_queries(q, P)
        fresh_k, fresh_v = (x.reshape(B, ng, lanes) for x in (fresh_k, fresh_v))
    out = _paged_decode_call(
        tables, pos, jnp.full((1,), layer, jnp.int32), q,
        (k_arena, v_arena) if k_scale is None else (k_arena, v_arena, k_scale, v_scale),
        fresh_k[:, :, None, :], fresh_v[:, :, None, :], C=C, window=window, sm=float(np.sqrt(hs)),
        interpret=_interpret(), parts=(_paged_dot, _paged_start_chunk, _paged_wait_chunk))
    if packed_out:
        return out
    if P > 1:
        out = _lane_packed_outputs(out, P)
    return out.reshape(B, nh, -1)


@functools.partial(jax.jit, static_argnames=("C", "window", "sm", "interpret", "parts"))
def _paged_decode_call(tables, pos, layer, q, arenas, fresh_k, fresh_v, *, C, window, sm, interpret, parts):
    """The walk's call, one traced and lowered body for every layer of a
    program that shares its static arguments (the layer is an operand, as in
    ``_mla_decode_call``): its straight-line copies make it some thousand
    operations long.  ``arenas``: ``(k, v)`` or, quantised, ``(k, v, k_scale,
    v_scale)``; ``q (B, ng, rep, hs)`` and ``fresh_* (B, ng, 1, hs)`` as the
    kernel sees them (heads of the arena's rows).  ``chain`` (after ``tables``
    and ``pos``, which the trace readers find the call by): ``chain[i]`` is the
    next row after ``i`` with a cached token the window keeps (``B``: none),
    ``chain[B]`` the first such row."""
    B, ng, rep, hs = q.shape
    bs = arenas[0].shape[3]
    quantized = len(arenas) == 4
    first = 0 if window is None else jnp.maximum(pos - (window - 1), 0)
    after = jax.lax.cummin(jnp.where(first < pos, jnp.arange(B, dtype=jnp.int32), B), reverse=True)
    chain = jnp.concatenate([after[1:], jnp.full((1,), B, jnp.int32), after[:1]])
    if quantized:
        arenas = (*arenas[:2], _scale_rows(arenas[2], layer[0]), _scale_rows(arenas[3], layer[0]))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # (B, ng, 1, hs): a (1, 1, hs) block of the rank-3 array would tile
    # (ng, hs) by (1, hs), which Mosaic refuses
    fresh_spec = pl.BlockSpec((1, ng, 1, hs), lambda i, *_: (i, 0, 0, 0))
    q_spec = pl.BlockSpec((1, ng, rep, hs), lambda i, *_: (i, 0, 0, 0))
    slot = [pltpu.VMEM((ng, C, bs, hs), arenas[0].dtype)] * 2
    if quantized:
        slot += [pltpu.VMEM((C,) + arenas[2].shape[1:], jnp.float32)] * 2
    scratch = slot * 2 + ([pltpu.VMEM((ng, C, bs, hs), fresh_k.dtype)] * 2 if quantized else [])
    scratch += [pltpu.SemaphoreType.DMA((2, len(arenas))), pltpu.SMEM((1,), jnp.int32)]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_paged_kernel, n_arenas=len(arenas), bs=bs, C=C, window=window,
                          cdtype=fresh_k.dtype, sm=sm, parts=parts, unroll=not interpret),
        name="paged_attn_decode" + ("_quant" if quantized else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[q_spec] + [hbm] * len(arenas) + [fresh_spec, fresh_spec],
            out_specs=q_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, ng, rep, hs), q.dtype),
        interpret=interpret,
        **kwargs,
    )(tables, pos, chain, layer, q, *arenas, fresh_k, fresh_v)


def _decode_by_blocks(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos, **kw):
    """:func:`paged_attn_decode` for a head size the chunked walk cannot be
    compiled for (:func:`paged_head_size_ok`): the token is the first query
    of an 8-wide :func:`paged_attn_verify` chunk, whose ``(bs, hs)`` tiles
    arrive through BlockSpecs, a block and a KV group a grid step.  Query 0
    of a chunk sees the arena's strictly-older slots and fresh key 0 alone,
    which is the decode step's attention; the other seven rows are copies
    that fill a sublane tile (Mosaic refuses the one-key fresh dot for GQA)
    and are dropped."""
    def wide(x):
        return jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], 8, x.shape[2]))

    return paged_attn_verify(wide(q), k_arena, v_arena, wide(fresh_k), wide(fresh_v),
                             tables, pos, **kw)[:, :, 0]


def _token_dest(tab, p, ne, i, *, bs, offset):
    """Request ``i``'s destination ``(block, slot)`` for the token at chunk
    offset ``offset``, from the scalar-prefetch refs: ``tab[i, (pos +
    offset) // bs]`` / ``(pos + offset) % bs``, or sink block 0 slot 0 when
    the keep-mask ``offset < n_emit[i]`` rejects it.  Shared by the BlockSpec
    index maps (block) and the kernel bodies (slot)."""
    at = p[i] + offset
    blk, slot = tab[i, at // bs], at % bs
    if ne is not None:
        keep = offset < ne[i]
        blk, slot = jnp.where(keep, blk, 0), jnp.where(keep, slot, 0)
    return blk, slot


def _merge_slot(old, new, slot):
    """``old`` with row ``slot`` of its block_size dim (axis 1) replaced by
    ``new`` (size 1 there).  Mosaic takes neither a slot-granular block (the
    last two block dims must tile by (8, 128) or span the array's) nor a
    one-row dynamic store or DMA into a tiled dim, so a token lands as a
    whole-block select: read the block, replace one row, write it back."""
    row = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
    return jnp.where(row == slot, new, old)


def _token_write_kernel(*refs, bs, offset, masked):
    # refs = (tab, pos, n_emit?, arena, vals, out)
    tab_ref, pos_ref = refs[:2]
    ne_ref = refs[2] if masked else None
    a_ref, v_ref, o_ref = refs[-3:]
    _, slot = _token_dest(tab_ref, pos_ref, ne_ref, pl.program_id(0),
                          bs=bs, offset=offset)
    o_ref[0, 0] = _merge_slot(a_ref[0, 0], v_ref[0, 0], slot)


def _token_specs(arena, *, bs, offset):
    """Arena and token-value BlockSpecs over ``grid=(B, L)``: one (ng, bs[,
    hs]) block of one layer per step, so VMEM use does not grow with depth.
    Values ride with a size-1 block_size dim, (B, L, ng, 1[, hs]), which
    broadcasts against the block in :func:`_merge_slot`."""
    tail = (0,) * (arena.ndim - 3)

    def a_index(i, l, tab, p, *ne):
        blk, _ = _token_dest(tab, p, ne[0] if ne else None, i, bs=bs, offset=offset)
        return (blk, l, 0) + tail

    a_spec = pl.BlockSpec((1, 1) + arena.shape[2:], a_index)
    v_spec = pl.BlockSpec((1, 1, arena.shape[2], 1) + arena.shape[4:],
                          lambda i, l, *_: (i, l, 0) + tail)
    return a_spec, v_spec


def _token_call(name, kernel, prefetch, arenas, vals, in_specs, out_specs):
    B, L = vals[0].shape[:2]
    n = len(prefetch)
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n, grid=(B, L),
            in_specs=in_specs, out_specs=out_specs),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arenas],
        # arenas in == arenas out (in-place): unvisited blocks keep their bytes
        input_output_aliases={n + k: k for k in range(len(arenas))},
        interpret=_interpret(),
        **kwargs,
    )(*prefetch, *arenas, *vals)


def paged_token_write(arena, vals, tables, pos, *, block_size, n_emit=None,
                      offset=0, name="paged_token_write"):
    """In-place single-token arena write (the scatter_token replacement).

    ``arena``: (num_blocks, L, ng, bs, hs) K/V arena — or (num_blocks, L, ng,
    bs) scale arena; ``vals``: (B, L, ng, hs) (or (B, L, ng)) at the arena
    dtype — quantize *before* calling (``quant.quantize_kv``), so the stored
    values match scatter_token_q exactly.  A lane-packed arena (``(num_blocks,
    L, ng / P, bs, P * hs)``) takes the same ``vals``: a token's ``P``
    consecutive heads are one contiguous row, a reshape.  Each request's destination block
    (``tables[i, pos[i] // bs]``) is computed in the BlockSpec index map and
    its slot (``pos[i] % bs``) in the kernel, which rewrites that one block
    with the token's row replaced (:func:`_merge_slot`); the arena aliases
    the output, so untouched blocks keep their bytes and no scatter
    primitive appears in the program.  Padding rows (all-sink tables, pos 0)
    land in sink block 0, whose contents are never attended.

    Keep-masked form, for the speculative verify commit: with ``n_emit``
    (B,) int32 and a static chunk ``offset``, request ``i`` lands ``vals[i]``
    at slot ``pos[i] + offset`` iff ``offset < n_emit[i]``; rejected rows
    route to sink block 0 slot 0, so a rejected draft's KV stays invisible.

    ``name``: the kernel's name in a device trace (a latent arena's write is
    ``mla_latent_write``).
    """
    prefetch = (tables, pos) if n_emit is None else (
        tables, pos, n_emit.astype(jnp.int32))
    if arena.ndim == 5 and vals.shape[-1] != arena.shape[-1]:
        vals = vals.reshape(*vals.shape[:2], arena.shape[2], arena.shape[4])
    a_spec, v_spec = _token_specs(arena, bs=block_size, offset=offset)
    (out,) = _token_call(
        name + ("_masked" if n_emit is not None else ""),
        functools.partial(_token_write_kernel, bs=block_size, offset=offset,
                          masked=n_emit is not None),
        prefetch, (arena,), (jnp.expand_dims(vals, 3),),
        [a_spec, v_spec], [a_spec])
    return out


# ---------------------------------------------------------------------------
# ``mla_paged_decode``: one token's latent attention straight off the latent
# arena, in the absorbed form.  A grid step is a request: its table's blocks
# are copied out of the HBM arena once, a chunk of ``mla_chunk_keys`` rows at a
# time, and every head scores the same rows (one ``(nh, W) x (W, keys)``
# product) and sums the same rows (``(nh, keys) x (keys, dc)``): the arena's
# bytes are read once for all heads, where expanded keys and values would be
# ``nh (dn + dr + dv) / (dc + dr)`` times as many.  Online softmax across chunks
# in float32; the fresh token's row (this step's, not yet in the arena) is the
# last term, as in ``paged_attn_decode``.
#
# What the chunk loop is built around (PR 35, measured with tools/mla_tune.py):
# starting a block's copy costs the core about 20 ns whatever its size, a
# chunk's copies cost as long as its products, and the core overlaps the two
# only inside one basic block and only where it can see that the copies' target
# is not what the products read.  So
# - the two chunk buffers are two scratch arrays with static roles: the loop
#   takes two chunks a turn, attending one buffer while the other's copies are
#   started in the same straight-line code (no ``pl.when`` around them; a
#   ``fori_loop`` that the lowering unrolls: ``C`` is static), and a slot's
#   ``C`` copies are awaited once;
# - the matrix unit holds the rows and streams the queries in both products.
#   Holding the queries (64 fill half a 128-row pass) and streaming the rows
#   measured slower at 64 heads, so there is one form for every head count;
# - nothing is conditional in a turn: the copy started beside a row's last
#   chunk is the first chunk of the next row that has one (``chain``, from
#   ``pos`` in the wrapper), so a request finds its first chunk in flight,
#   started by its predecessor; grid step 0 starts the first such row's.  The
#   buffer a row begins in (the parity of all chunks before it) rides in SMEM
#   scratch and picks one of two instances of the walk; the grid is
#   ``"arbitrary"``: it is walked in order.  The last row with a cached token
#   has no successor: it fetches its own last chunk again and waits for it.
# A row's sums depend on the row alone: which rows its neighbours are changes
# what is prefetched and when, never a chunk's boundaries nor their order.
# Straight-line code is long (448 copy starts, twelve products): the layer is
# an operand and a program's layers share one traced body (``_mla_decode_call``).
# ---------------------------------------------------------------------------

_MLA_CHUNK_KEYS = 1024
_MLA_BUFFER_BYTES = 4 * 1024 * 1024   # both chunk buffers


def mla_chunk_keys(bs: int, W: int, itemsize: int) -> int:
    """Keys a step of :func:`mla_paged_decode`'s walk attends, from what the
    call sees: ``_MLA_CHUNK_KEYS`` (512 and 2,048 measured slower at 16, 64 and
    128 heads), fewer where two buffers of as many rows of ``W`` would pass
    ``_MLA_BUFFER_BYTES``, whole blocks of ``bs`` and one at least.  Not from the
    table's width nor the batch: a row's chunk boundaries depend on the row alone."""
    keys = min(_MLA_CHUNK_KEYS, _MLA_BUFFER_BYTES // (2 * W * itemsize))
    return max(1, keys // bs) * bs


def _mla_dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _mla_start_chunk(tab_ref, arena, buf, sem, r, c, hi, *, layer, C):
    """Start the ``C`` block copies of row ``r``'s chunk ``c`` into ``buf``, as
    straight-line code (a loop unrolled where the kernel is lowered: traced
    once).  The tail of a row's last chunk fetches block ``hi - 1`` again: real
    rows, masked as future ones."""
    def one(t, _):
        blk = tab_ref[r, jnp.minimum(c * C + t, hi - 1)]
        pltpu.make_async_copy(arena.at[blk, layer, 0], buf.at[t], sem).start()
    jax.lax.fori_loop(0, C, one, None, unroll=True)


def _mla_wait_chunk(buf, sem, *, C):
    """Wait once for the bytes of the ``C`` copies started into ``buf``."""
    pltpu.make_async_copy(buf.at[pl.ds(0, C)], buf.at[pl.ds(0, C)], sem).wait()


def _mla_decode_kernel(tab_ref, pos_ref, chain_ref, layer_ref, q_ref, arena, f_ref, o_ref, buf0, buf1, sem, par, *,
                       bs, C, dc, scale, parts):
    dot, start_chunk, wait_chunk = parts
    layer = layer_ref[0]
    i = pl.program_id(0)
    B = pl.num_programs(0)
    p_i = pos_ref[i]
    q = q_ref[0]                                           # (nh, W)
    nh, W = q.shape
    bufs = (buf0, buf1)

    def blocks(r):
        return (pos_ref[r] + bs - 1) // bs                 # table entries with a slot before pos

    def start(r, c, hi_r, k):
        start_chunk(tab_ref, arena, bufs[k], sem.at[k], r, c, hi_r, layer=layer, C=C)

    def wait(k):
        wait_chunk(buf0, sem.at[k], C=C)

    hi = blocks(i)
    n = (hi + C - 1) // C
    nxt = chain_ref[i]
    last = nxt == B
    r_n = jnp.minimum(nxt, B - 1)
    hi_n = blocks(r_n)

    @pl.when(i == 0)
    def _first():
        par[0] = 0
        first = chain_ref[B]

        @pl.when(first < B)
        def _():
            start(first, 0, blocks(first), 0)

    def ahead(c, k):
        # into buffer k: this row's chunk c if it has one, else the first chunk of
        # the next row that has any; the last such row of the grid fetches its own
        # last chunk again (waited for below, never attended)
        own = jnp.logical_or(c < n, last)
        chunk = jnp.where(c < n, c, jnp.where(last, n - 1, 0))
        start(jnp.where(own, i, r_n), chunk, jnp.where(own, hi, hi_n), k)

    def attend(c, k, carry):
        m_prev, l_prev, acc = carry
        rows = bufs[k][...].reshape(C * bs, W).astype(q.dtype)
        s = dot(q, rows, _NT) * scale                      # (nh, C * bs)
        posn = c * C * bs + jax.lax.broadcasted_iota(jnp.int32, (1, C * bs), 1)
        s = jnp.where(posn < p_i, s, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + dot(p.astype(q.dtype), rows[:, :dc], _NN)
        return m_new, l_new, acc

    def walk(a, b):
        def pair(j, carry):
            c = 2 * j
            ahead(c + 1, b)
            wait(a)
            carry = attend(c, a, carry)
            ahead(c + 2, a)
            wait(b)
            return attend(c + 1, b, carry)

        def odd(carry):
            ahead(n, b)
            wait(a)
            return attend(n - 1, a, carry)

        carry = jax.lax.fori_loop(0, n // 2, pair, (
            jnp.full((nh, 1), _MASK_VALUE, jnp.float32), jnp.zeros((nh, 1), jnp.float32),
            jnp.zeros((nh, dc), jnp.float32)))
        return jax.lax.cond(n % 2 == 1, odd, lambda carry: carry, carry)

    base = par[0]
    m_prev, l_prev, acc = jax.lax.cond(base == 0, lambda: walk(0, 1), lambda: walk(1, 0))

    @pl.when(n > 0)
    def _handed_over():
        spare = (base + n) % 2
        par[0] = spare

        @pl.when(last)
        def _():
            wait(spare)
    # the fresh row is one key: multiply-and-sum in float32 of operands rounded
    # to q's dtype, as a matmul's would be
    f = f_ref[0].astype(q.dtype).astype(jnp.float32)       # (1, W)
    s_f = jnp.sum(q.astype(jnp.float32) * f, axis=1, keepdims=True) * scale
    m_new = jnp.maximum(m_prev, s_f)
    p = jnp.exp(s_f - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p
    acc = acc * corr + p.astype(q.dtype).astype(jnp.float32) * f[:, :dc]
    o_ref[0] = (acc / l_new).astype(o_ref.dtype)


def _mla_decode_xla(q, arena, fresh, tables, pos, *, layer, dc, scale):
    """:func:`mla_paged_decode` in XLA, for a backend without Pallas: the
    rows' blocks gathered, the fresh row put at ``pos``, one softmax."""
    B, nbb = tables.shape
    bs, W = arena.shape[3], arena.shape[4]
    rows = jnp.take(arena[:, layer, 0], tables, axis=0).reshape(B, nbb * bs, W).astype(q.dtype)
    rows = jax.vmap(lambda r, f, p: jax.lax.dynamic_update_slice_in_dim(r, f[None], p, axis=0))(
        rows, fresh.astype(q.dtype), pos)
    s = jnp.einsum("bhw,bsw->bhs", q, rows, preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(nbb * bs)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsc->bhc", p, rows[..., :dc], preferred_element_type=jnp.float32).astype(q.dtype)


def mla_paged_decode(q, arena, fresh, tables, pos, *, layer: int, dc: int, scale: float):
    """One token a row of absorbed latent attention off the latent arena, one
    layer.  ``q (B, nh, W)``: the absorbed queries ``[q_nope W_k | q_rope | 0]``
    at the compute dtype; ``arena (num_blocks, L, 1, bs, W)``: the pool's
    latent arena, rows ``[c_kv (dc) | k_r | 0]``, ``W`` whole 128-lane tiles; it
    stays in HBM and a request's ``[block, layer]`` slabs are copied out once
    for all ``nh`` heads; ``fresh (B, W)``: this step's rows (not yet in the
    arena: the caller lands them with ``paged_token_write`` afterwards);
    ``tables (B, nbb)`` int32 sink-padded, ``pos (B,)`` int32.  Returns the
    heads' weighted latents ``(B, nh, dc)`` at ``q``'s dtype (the caller maps
    them to values with ``W_v``).  Without Pallas (a CPU that did not opt into
    the interpreter) the XLA form runs.

    The walk (the comment block above ``_MLA_CHUNK_KEYS``): the rows are held in
    the matrix unit and the queries streamed, at any head count; a chunk is
    ``C = mla_chunk_keys(bs, W, itemsize) // bs`` table entries, from the
    call's own shapes (1,024 keys at A.X-K1's: two buffers of 1.3 MB); a row's
    first chunk is its predecessor's to start, through ``chain``: ``chain[i]`` is
    the next row after ``i`` with a cached token (``B``: none) and ``chain[B]``
    the first such row."""
    B, nh, W = q.shape
    bs = arena.shape[3]
    assert arena.shape[2] == 1 and arena.shape[4] == W and W % 128 == 0 and dc % 128 == 0, (arena.shape, W, dc)
    if not _pallas_available():
        return _mla_decode_xla(q, arena, fresh, tables, pos, layer=layer, dc=dc, scale=scale)
    stats["mla_decode"] = stats.get("mla_decode", 0) + 1
    return _mla_decode_call(
        tables, pos, jnp.full((1,), layer, jnp.int32), q, arena, fresh[:, None, :],
        C=mla_chunk_keys(bs, W, arena.dtype.itemsize) // bs, dc=dc, scale=float(scale), interpret=_interpret(),
        parts=(_mla_dot, _mla_start_chunk, _mla_wait_chunk))


@functools.partial(jax.jit, static_argnames=("C", "dc", "scale", "interpret", "parts"))
def _mla_decode_call(tables, pos, layer, q, arena, fresh, *, C, dc, scale, interpret, parts):
    """The kernel's call, one traced and lowered body for every layer of a
    program (the layer is an operand): its straight-line copies make it some
    thousand operations long.  Everything the trace reads besides the operands
    is a static argument: the chunk, the interpreter, the loop's three parts
    (``tools/mla_tune.py`` takes one out)."""
    B, nh, W = q.shape
    bs = arena.shape[3]
    after = jax.lax.cummin(jnp.where(pos > 0, jnp.arange(B, dtype=jnp.int32), B), reverse=True)
    chain = jnp.concatenate([after[1:], jnp.full((1,), B, jnp.int32), after[:1]])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, bs=bs, C=C, dc=dc, scale=scale, parts=parts),
        name="mla_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[pl.BlockSpec((1, nh, W), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, 1, W), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, nh, dc), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((C, bs, W), arena.dtype), pltpu.VMEM((C, bs, W), arena.dtype),
                            pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, nh, dc), q.dtype),
        interpret=interpret,
        **kwargs,
    )(tables, pos, chain, layer, q, arena, fresh)


def _paged_verify_kernel(tab_ref, pos_ref, nb_ref, q_ref, k_ref, v_ref, *rest,
                         bs, T, quantized, cdtype, sm):
    """Multi-token-query variant of ``_paged_kernel`` for the speculative
    verify step — and, at T = chunk width, the chunked-prefill attention
    kernel (:func:`paged_attn_verify` docstring): T chunk queries per request
    share one pass over the arena blocks, with the causal intra-chunk mask
    folded into the final online-softmax term.  Queries ride flattened as
    (rep*T, hs) rows so the arena phase is the single-token kernel's math, a
    block and a KV group at a time, at a wider row count."""
    del nb_ref  # raggedness lives in the BlockSpec index maps
    if quantized:
        ks_ref, vs_ref, fk_ref, fv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        fk_ref, fv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    i, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb = pl.num_programs(2)
    p_i = pos_ref[i]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _dequant(x_ref, s_ref, dt):
        x = x_ref[0, 0, 0]                                 # (bs, hs) storage dtype
        if s_ref is not None:
            # the scale block is the whole (ng, bs) slab of one arena block
            # (the last two block dims must equal the array's): pick the row
            col = _scale_column(s_ref[0, 0, pl.ds(g, 1), :], bs)
            x = (x.astype(jnp.float32) * col).astype(cdtype)
        return x.astype(dt)

    def _online(s, v, dt):
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # arena phase: the arena holds only the committed strictly-older prefix
    # (rejected speculative slots are never written), so every chunk query —
    # at positions p_i .. p_i+T-1 — may see all slots < p_i and the keep-mask
    # is query-independent, exactly the single-token kernel's
    run = (j * bs) < p_i

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]                                    # (rep*T, hs)
        k = _dequant(k_ref, ks_ref, q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / sm                                             # (rep*T, bs)
        posn = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(posn < p_i, s, _MASK_VALUE)
        _online(s, _dequant(v_ref, vs_ref, q.dtype), q.dtype)

    @pl.when(j == nb - 1)
    def _finalize():
        q = q_ref[0, 0]
        rows = q.shape[0]                                  # rep * T
        fk = fk_ref[0, 0].astype(q.dtype)                  # (T, hs) at cdtype
        fv = fv_ref[0, 0].astype(q.dtype)
        s_f = jax.lax.dot_general(
            q, fk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / sm                                             # (rep*T, T)
        # causal intra-chunk mask: flattened row r is the query at chunk
        # offset t = r % T and sees fresh keys at offsets <= t; the diagonal
        # is always kept, so no row is ever all-masked
        t_of = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 0) % T
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        s_f = jnp.where(col <= t_of, s_f, _MASK_VALUE)
        _online(s_f, fv, q.dtype)
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attn_verify(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos, *,
                      layer, k_scale=None, v_scale=None):
    """Multi-token-query attention off the KV block arena, one layer — the
    speculative verify step's kernel (T = K+1) and, generalized to T = the
    chunk width, the chunked-prefill attention kernel (the arena keep-mask
    is query-independent either way: the arena holds only the committed
    strictly-older prefix, and the chunk's own keys fold in causally as the
    final online-softmax term).

    ``q``: (B, nh, T, hs) chunk queries at global positions
    ``[pos, pos+T)``; ``fresh_k``/``fresh_v``: (B, ng, T, hs) the chunk's own
    projected K/V at the cache compute dtype (not yet in the arena — the
    caller commits the accepted prefix with the keep-masked :func:`paged_token_write`,
    or the whole chunk with :func:`paged_chunk_write`, afterwards).
    Arena/scale/table/pos arguments as :func:`paged_attn_decode`; the grid
    is (request, kv-group, kv-block) with one ``(bs, hs)`` arena tile a step,
    fetched through BlockSpec index maps that :func:`_ragged_step` clamps to
    the request's live blocks.  Sliding-window models are rejected upstream
    (speculation needs full caches; a windowed model's prompt pieces take the
    gather chunk).  Where Pallas is off, :func:`paged_attn_xla`.  Returns (B, nh,
    T, hs) at ``q.dtype``.
    """
    B, nh, T, hs = q.shape
    num_blocks, _L, ng, bs, lanes = k_arena.shape
    if lanes != hs:
        raise NotImplementedError(
            f"paged_attn_verify: a lane-packed arena (rows of {lanes} lanes for heads of {hs}) has no "
            "multi-query kernel; build the pool with lane_pack=1")
    if not _pallas_available():
        return paged_attn_xla(q, k_arena, v_arena, fresh_k, fresh_v, tables, pos, layer=layer,
                              k_scale=k_scale, v_scale=v_scale)
    nbb = int(tables.shape[1])
    rep = nh // ng
    assert rep * ng == nh, (nh, ng)
    quantized = k_scale is not None
    # (B, nh, T, hs) -> (B, ng, rep*T, hs): nh splits as (ng, rep), then the
    # adjacent (rep, T) dims fold — row r = rep_idx*T + t
    qf = q.reshape(B, ng, rep * T, hs)
    n_blocks = ((pos + (bs - 1)) // bs).astype(jnp.int32)

    arena_spec = pl.BlockSpec(
        (1, 1, 1, bs, hs),
        lambda i, g, j, tab, p, nb: (tab[i, _ragged_step(i, j, nb)], layer, g, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, 1, ng, bs),                                # see _dequant
        lambda i, g, j, tab, p, nb: (tab[i, _ragged_step(i, j, nb)], layer, 0, 0))
    fresh_spec = pl.BlockSpec((1, 1, T, hs), lambda i, g, j, tab, p, nb: (i, g, 0, 0))
    q_spec = pl.BlockSpec((1, 1, rep * T, hs), lambda i, g, j, tab, p, nb: (i, g, 0, 0))

    in_specs = [q_spec, arena_spec, arena_spec]
    args = [qf, k_arena, v_arena]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
    in_specs += [fresh_spec, fresh_spec]
    args += [fresh_k, fresh_v]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, ng, nbb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rep * T, 1), jnp.float32),
            pltpu.VMEM((rep * T, 1), jnp.float32),
            pltpu.VMEM((rep * T, hs), jnp.float32),
        ],
    )
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(
            _paged_verify_kernel, bs=bs, T=T, quantized=quantized,
            cdtype=fresh_k.dtype, sm=float(np.sqrt(hs)),
        ),
        name="paged_attn_verify" + ("_quant" if quantized else ""),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, ng, rep * T, hs), q.dtype),
        interpret=_interpret(),
        **kwargs,
    )(tables, pos, n_blocks, *args)
    return out.reshape(B, nh, T, hs)


def _chunk_dest(c, dest_ref, pos_ref, *, bs):
    """Chunk-writer routing: grid step ``c`` writes the chunk's ``c``-th
    block, i.e. dest entry ``pos // bs + c``.  Entries past the table width
    (bucket padding spilling beyond the leased table) route to physical
    block 0 — the sink, whose bytes are never attended."""
    nbb = dest_ref.shape[0]
    idx = pos_ref[0] // bs + c
    return jnp.where(idx < nbb, dest_ref[jnp.minimum(idx, nbb - 1)], 0)


def _paged_chunk_write_kernel(dest_ref, pos_ref, a_ref, v_ref, o_ref):
    del dest_ref, pos_ref, a_ref  # routing happens in the BlockSpec index maps
    o_ref[0] = v_ref[0]


def paged_chunk_write(arena, vals, dest, pos, *, block_size):
    """In-place block-granule chunk write — the chunked-prefill
    ``scatter_blocks`` replacement.

    ``arena``: (num_blocks, L, ng, bs, hs) K/V arena; ``vals``: (nc, L, ng,
    bs, hs) the chunk's fresh K (or V) at the arena dtype, pre-folded to
    block granules (a pure reshape/transpose of the (1, L, ng, T, hs)
    forward output — no gather); ``dest``: (nbb,) int32 scatter table from
    :func:`serving.kv_pool.chunk_tables` (sink entries absorb everything
    outside the chunk's own block range); ``pos``: (1,) int32 chunk start
    (block-aligned — the paged chunk resolution guarantees it).  One grid
    step per chunk block lands a whole (L, ng, bs, hs) slab at
    ``dest[pos // bs + c]`` via the aliased output, so untouched blocks keep
    their bytes and no scatter primitive appears in the program.  Trailing
    bucket-padding slots write garbage exactly like the gather chunk's
    ``scatter_blocks`` — sunk, never attended, or overwritten before use.
    """
    bs = block_size
    nc, L, ng, _bs, hs = vals.shape
    assert _bs == arena.shape[3] == bs, (vals.shape, arena.shape, bs)
    route = functools.partial(_chunk_dest, bs=bs)
    a_spec = pl.BlockSpec(
        (1, L, ng, bs, hs), lambda c, dest, p: (route(c, dest, p), 0, 0, 0, 0))
    v_spec = pl.BlockSpec((1, L, ng, bs, hs), lambda c, dest, p: (c, 0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nc,),
        in_specs=[a_spec, v_spec],
        out_specs=a_spec,
    )
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        _paged_chunk_write_kernel,
        name="paged_chunk_write",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={2: 0},   # arena in == arena out (in-place)
        interpret=_interpret(),
        **kwargs,
    )(dest, pos, arena, vals)


def _qmax(storage) -> float:
    return 127.0 if storage == jnp.dtype(jnp.int8) else float(jnp.finfo(storage).max)


def _absmax_quant(x, qmax, storage):
    """The exact :func:`serving.quant.quantize_kv` math, in-kernel: float32
    absmax over the last (hs) dim, scale 1.0 for all-zero rows, int8
    round-and-clip / fp8 cast.  Same ops in the same order, so the stored
    bytes are bit-identical to the unfused quantize-then-write path.
    Returns ``(q, scale, xf)``; ``scale`` keeps the reduced dim at size 1."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / qmax)
    y = xf / scale
    if jnp.dtype(storage) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(storage)
    else:
        q = y.astype(storage)
    return q, scale, xf


def _paged_chunk_write_fused_kernel(dest_ref, pos_ref, a_ref, s_ref, v_ref,
                                    oa_ref, os_ref, oe_ref, *, bs, qmax):
    del a_ref, s_ref  # aliased outputs; routing happens in the index maps
    c = pl.program_id(0)
    q, scale, xf = _absmax_quant(v_ref[0], qmax, oa_ref.dtype)
    oa_ref[0] = q
    os_ref[0] = scale[..., 0]
    # masked quantization-error sums behind the serving.kv_quant.rel_err
    # gauge: only blocks actually written (non-sink dest) count, matching
    # scatter_blocks_q's mask
    nbb = dest_ref.shape[0]
    idx = pos_ref[0] // bs + c
    live = jnp.logical_and(idx < nbb, dest_ref[jnp.minimum(idx, nbb - 1)] != 0)
    m = live.astype(jnp.float32)
    dq = q.astype(jnp.float32) * scale
    # the two sums land at [0, 0] and [0, 1] of a zero (8, 128) tile; a
    # select on iotas, because Mosaic has no scatter for ``.at[].set``
    row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    oe_ref[0] = jnp.where(
        row != 0, 0.0,
        jnp.where(col == 0, jnp.sum(jnp.abs(dq - xf)) * m,
                  jnp.where(col == 1, jnp.sum(jnp.abs(xf)) * m, 0.0)))


def paged_chunk_write_fused(arena, scale_arena, vals, dest, pos, *, block_size):
    """Quantizing twin of :func:`paged_chunk_write` with the absmax
    quantize-on-write folded in (the Liger-style fused epilogue): ``vals``
    arrive at the *compute* dtype, the kernel computes the per-slot-head
    absmax scale and stores value + scale through two aliased outputs in ONE
    pallas_call — no standalone quantize op in the program.

    Returns ``(arena, scale_arena, err)`` where ``err`` is (nc, 8, 128)
    float32 with per-block masked error sums at ``[c, 0, 0]`` (|dq - x|) and
    ``[c, 0, 1]`` (|x|) — combine as ``sum / (sum + 1e-30)`` for the same
    rel_err figure ``scatter_blocks_q`` reports."""
    bs = block_size
    nc, L, ng, _bs, hs = vals.shape
    qmax = _qmax(arena.dtype)
    route = functools.partial(_chunk_dest, bs=bs)
    a_spec = pl.BlockSpec(
        (1, L, ng, bs, hs), lambda c, dest, p: (route(c, dest, p), 0, 0, 0, 0))
    s_spec = pl.BlockSpec(
        (1, L, ng, bs), lambda c, dest, p: (route(c, dest, p), 0, 0, 0))
    v_spec = pl.BlockSpec((1, L, ng, bs, hs), lambda c, dest, p: (c, 0, 0, 0, 0))
    e_spec = pl.BlockSpec((1, 8, 128), lambda c, dest, p: (c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nc,),
        in_specs=[a_spec, s_spec, v_spec],
        out_specs=[a_spec, s_spec, e_spec],
    )
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_paged_chunk_write_fused_kernel, bs=bs, qmax=qmax),
        name="paged_chunk_write_fused",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(arena.shape, arena.dtype),
            jax.ShapeDtypeStruct(scale_arena.shape, scale_arena.dtype),
            jax.ShapeDtypeStruct((nc, 8, 128), jnp.float32),
        ],
        input_output_aliases={2: 0, 3: 1},   # value + scale arenas in-place
        interpret=_interpret(),
        **kwargs,
    )(dest, pos, arena, scale_arena, vals)


def _token_write_fused_kernel(*refs, bs, offset, masked, qmax):
    # refs = (tab, pos, n_emit?, arena, scales, vals, vals_rows, out_a, out_s)
    tab_ref, pos_ref = refs[:2]
    ne_ref = refs[2] if masked else None
    a_ref, s_ref, v_ref, vr_ref, oa_ref, os_ref = refs[-6:]
    _, slot = _token_dest(tab_ref, pos_ref, ne_ref, pl.program_id(0),
                          bs=bs, offset=offset)
    # the value block keeps ng as a major dim, (ng, bs, hs), the scale block
    # has it on sublanes, (ng, bs): the same absmax runs once per layout —
    # identical scales — so no relayout is needed in between
    q, _, _ = _absmax_quant(v_ref[0, 0], qmax, oa_ref.dtype)      # (ng, 1, hs)
    _, scale, _ = _absmax_quant(vr_ref[0, 0], qmax, oa_ref.dtype)  # (ng, 1)
    oa_ref[0, 0] = _merge_slot(a_ref[0, 0], q, slot)
    os_ref[0, 0] = _merge_slot(s_ref[0, 0], scale, slot)


def paged_token_write_fused(arena, scale_arena, vals, tables, pos, *,
                            block_size, n_emit=None, offset=0):
    """Quantizing twin of :func:`paged_token_write` (keep-masked form
    included): ``vals`` (B, L, ng, hs) arrive at the compute dtype; the
    kernel runs the exact ``quantize_kv`` absmax math and lands value + scale
    through two aliased outputs in one pallas_call — the decode program's
    quantize-on-write with no standalone quantize op.
    Returns ``(arena, scale_arena)``."""
    prefetch = (tables, pos) if n_emit is None else (
        tables, pos, n_emit.astype(jnp.int32))
    a_spec, v_spec = _token_specs(arena, bs=block_size, offset=offset)
    s_spec, _ = _token_specs(scale_arena, bs=block_size, offset=offset)
    vr_spec = pl.BlockSpec((1, 1) + vals.shape[2:], lambda i, l, *_: (i, l, 0, 0))
    return _token_call(
        "paged_token_write_fused" + ("_masked" if n_emit is not None else ""),
        functools.partial(_token_write_fused_kernel, bs=block_size,
                          offset=offset, masked=n_emit is not None,
                          qmax=_qmax(arena.dtype)),
        prefetch, (arena, scale_arena), (jnp.expand_dims(vals, 3), vals),
        [a_spec, s_spec, v_spec, vr_spec], [a_spec, s_spec])


def _lora_delta_kernel(x_ref, a_ref, b_ref, o_ref, *, scaling):
    x = x_ref[0]                                       # (T, C)
    a = a_ref[0].astype(x.dtype)                       # (r, C)
    b = b_ref[0].astype(x.dtype)                       # (fout, r)
    # the MXU accumulates in float32 (Mosaic refuses any other accumulator);
    # rounding each product back to x.dtype is what the unfused einsums'
    # default accumulation does
    def mm(u, w):
        return jax.lax.dot_general(
            u, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)

    o_ref[0] = (mm(mm(x, a), b) * scaling).astype(o_ref.dtype)


def lora_delta_fused(x, a, b, scaling):
    """Fused per-request LoRA delta ``scaling * B(A(x))`` — one kernel call
    per target instead of two standalone HLO einsums (the Liger fused-
    epilogue pattern applied to the adapter path).  ``x``: (B, T, fin);
    ``a``: (B, r, fin); ``b``: (B, fout, r) → (B, T, fout), same dtype flow
    as ``models.generate._lora_delta`` (factors cast to ``x.dtype``, each
    product rounded to ``x.dtype``), so the delta matches the unfused twin.  Used
    by the meshless kernel path only — under a mesh the unfused einsums stay
    (a bare pallas_call has no SPMD rule)."""
    B, T, C = x.shape
    _, r, _ = a.shape
    _, fout, _ = b.shape
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    return pl.pallas_call(
        functools.partial(_lora_delta_kernel, scaling=scaling),
        name="lora_delta_fused",
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, T, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, r, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, fout, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, fout), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, fout), x.dtype),
        interpret=_interpret(),
        **kwargs,
    )(x, a, b)


# ---------------------------------------------------------------------------
# Chunked gated delta rule: ``gdn_chunk_fwd`` and ``gdn_chunk_bwd``.
#
# Grid (value head, block of NC chunks); the block axis is sequential and a
# VMEM scratch carries the head's (dk, dv) float32 state from block to block.
# A chunk of C tokens: the decay matrix M_ij = exp(G_i - G_j) (j <= i) from the
# within-chunk cumulative log-decay G, the strictly lower A = beta * M * K K^T,
# its unit-triangular inverse by squarings (products only), then five products
# against the carried state.  q and k come from their key head by index map
# (no repeat in HBM).
#
# A chunk is a chain of small dependent products (ten in the inverse alone,
# 64 rows each), and Mosaic runs the kernel body nearly as written: chunk after
# chunk, a call was one chain's latencies end to end.  So what the state does
# not enter is written for all of a block's chunks together, a product at a
# time (``_gdn_block_terms``), and the walks that do need the state (two
# dependent products a chunk, ``_gdn_state_walk`` and its mirror for the
# gradient) carry the work that hangs off them between their steps.  On one
# v5e (PERF.md, PR 31) that took a forward call from 11.0 to 6.2 ms and a
# backward call from 17.2 to 9.9; two chunks a product (a block-diagonal
# system of 128 rows) was slower than one (6.6, 13.2): once the chains overlap
# the array's passes bind, and the zero blocks cost theirs.
#
# ``G`` and ``beta`` reach the kernels a row a chunk, ``(BH, T/C, C)`` float32,
# and their gradients leave so: a ``(BH, T, 1)`` column is 128-lane tiles, 128
# times its bytes in HBM and in the copies XLA puts around the call.  A chunk's
# row becomes the column the algebra wants in the kernel (``_column``).
#
# Between the passes the forward kernel keeps the state each *block* starts
# from, ``(BH, T/TB, dk, dv)`` float32 (``prims.gdn_state_stride``: 67 MB a
# layer at B 2, 32 heads of 128, T 8192).  ``gdn_chunk_bwd`` walks the blocks
# from the last to the first with the gradient in the state carried the other
# way; in a block it first walks forward from the saved state, keeping each
# chunk's terms, ``D`` and starting state in VMEM, then backward.  No forward
# call runs in the backward pass.  Its oracle is the XLA
# chunked form differentiated (jaxex ``_gdn_chunked_backward``).
# ---------------------------------------------------------------------------

def _dot3(a, b, dims):
    """A float32 product in three bfloat16 passes on the MXU (``hi hi + hi lo
    + lo hi``: about 16 bits of each operand, between a GPU's TF32 and
    float32).  Mosaic has one pass and six (``HIGHEST``) and nothing between;
    the triangular inverse and what is multiplied by it need more than one
    and are a third of the kernel's time at six."""
    f32, bf = jnp.float32, jnp.bfloat16
    ah, bh = a.astype(bf), b.astype(bf)
    al, bl = (a - ah.astype(f32)).astype(bf), (b - bh.astype(f32)).astype(bf)
    d = functools.partial(jax.lax.dot_general, dimension_numbers=dims, preferred_element_type=f32)
    return d(ah, bh) + d(ah, bl) + d(al, bh)


_AB = (((1,), (0,)), ((), ()))       # a @ b
_AB_T = (((1,), (1,)), ((), ()))     # a @ b^T
_AT_B = (((0,), (0,)), ((), ()))     # a^T @ b


def _column(r, eye):
    """A chunk's row ``(1, C)`` as a column ``(C, 1)``: eight vregs and a lane
    reduction, once a chunk."""
    return jnp.sum(eye * r, axis=1, keepdims=True)


def _as_row(c, eye):
    """A column ``(C, 1)`` as a row ``(1, C)``."""
    return jnp.sum(eye * c, axis=0, keepdims=True)


def _gdn_masks(C: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return (row == col).astype(jnp.float32), row >= col, row > col


def _gdn_block_terms(k_ref, v_ref, g_ref, b_ref, C: int, NC: int, eye, low, strict):
    """What the state does not enter, for every chunk of a block, as lists over
    the chunks: the decay matrix ``M``, ``K K^T``, the unit-triangular inverse
    ``Tm = (I + beta M K K^T)^-1`` (by squarings, float32 in three passes:
    thirty of a chunk's MXU passes), ``beta V``, ``beta e^G K`` and the inverse
    applied to both (``U``, ``W``), with the factors of the state's update.
    Every product is written for all chunks before the next one: the chunks'
    chains (ten dependent products in the inverse alone) do not depend on each
    other, and Mosaic overlaps what is written side by side."""
    f32, dt = jnp.float32, v_ref.dtype
    mm = functools.partial(_dot3, dims=_AB)
    ch = range(NC)
    t = types.SimpleNamespace()
    t.k = [k_ref[0, c * C:(c + 1) * C, :] for c in ch]
    t.v = [v_ref[0, c * C:(c + 1) * C, :] for c in ch]
    t.Gr = [g_ref[0, c:c + 1, :] for c in ch]
    t.Gc = [_column(r, eye) for r in t.Gr]
    t.beta = [_column(b_ref[0, c:c + 1, :], eye) for c in ch]
    t.M = [jnp.exp(jnp.where(low, t.Gc[c] - t.Gr[c], _MASK_VALUE)) for c in ch]     # (C, C), 0 above the diagonal
    t.kk = [jax.lax.dot_general(t.k[c], t.k[c], _AB_T, preferred_element_type=f32) for c in ch]
    P = [-jnp.where(strict, t.beta[c] * t.M[c] * t.kk[c], 0.0) for c in ch]
    t.Tm = [eye + P[c] for c in ch]
    n = 1
    while 2 * n < C:                                                    # (I + P)(I + P^2)(I + P^4)...
        P = [mm(P[c], P[c]) for c in ch]
        t.Tm = [t.Tm[c] + mm(t.Tm[c], P[c]) for c in ch]
        n *= 2
    t.eG = [jnp.exp(t.Gc[c]) for c in ch]
    t.Ub = [t.beta[c] * t.v[c].astype(f32) for c in ch]
    t.Wb = [t.beta[c] * t.eG[c] * t.k[c].astype(f32) for c in ch]
    t.U = [mm(t.Tm[c], t.Ub[c]) for c in ch]
    t.W = [mm(t.Tm[c], t.Wb[c]).astype(dt) for c in ch]
    # the chunk's last log-decay scales the carried state and every key of the update
    t.glast = [t.Gc[c][C - 1:C, :] for c in ch]
    t.e_last = [jnp.exp(t.glast[c] - t.Gc[c]) for c in ch]                           # (C, 1)
    t.Kd = [t.e_last[c] * t.k[c].astype(f32) for c in ch]
    # (1, 1) -> (dk, 1) -> (dk, dv): Mosaic broadcasts one way at a time
    t.e_glast = [jnp.exp(jnp.broadcast_to(t.glast[c], (k_ref.shape[2], 1))) for c in ch]
    return t


def _gdn_state_walk(S, t, dt, beside):
    """The walk that needs the state, two dependent products a chunk: ``D = U
    - W S`` and ``S <- e^g S + Kd^T D``.  Returns each chunk's starting state,
    each chunk's ``D`` and the state after the block.  ``beside(c, S, D)`` is
    written between two steps: work that hangs off the chain and fills the
    time its products are in flight."""
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
    starts, Ds = [], []
    for c, (W, U, Kd, e_glast) in enumerate(zip(t.W, t.U, t.Kd, t.e_glast)):
        starts.append(S)
        Ds.append((U - dot(W, S.astype(dt), _AB)).astype(dt))
        S = e_glast * S + dot(Kd.astype(dt), Ds[-1], _AT_B)
        beside(c, starts[-1], Ds[-1])
    return starts, Ds, S


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *rest, C, NC, carry):
    """``carry`` (the server's call): one operand more, the state the sequence
    starts from, and one result more, the state it ends in; the trainer's call
    has neither and starts from zero."""
    f32, dt = jnp.float32, v_ref.dtype
    if carry:
        h0_ref, o_ref, st_ref, fin_ref, s_ref = rest
    else:
        o_ref, st_ref, s_ref = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = h0_ref[0].astype(f32) if carry else jnp.zeros_like(s_ref)

    st_ref[0, 0] = s_ref[...]          # the state this block starts from: what the backward pass keeps
    eye, low, strict = _gdn_masks(C)
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    t = _gdn_block_terms(k_ref, v_ref, g_ref, b_ref, C, NC, eye, low, strict)

    def read_out(c, S, D):
        q = q_ref[0, c * C:(c + 1) * C, :]
        QK = jnp.where(low, t.M[c] * dot(q, t.k[c], _AB_T), 0.0)
        o = dot((t.eG[c] * q.astype(f32)).astype(dt), S.astype(dt), _AB) + dot(QK.astype(dt), D, _AB)
        o_ref[0, c * C:(c + 1) * C, :] = o.astype(o_ref.dtype)

    _, _, s_ref[...] = _gdn_state_walk(s_ref[...], t, dt, beside=read_out)
    if carry:
        # the block stays in VMEM while its index stands still: what leaves is the last block's
        fin_ref[0] = s_ref[...].astype(fin_ref.dtype)


def _gdn_specs(Hk: int, Hv: int, TB: int, C: int, dk: int, dv: int, block):
    """BlockSpecs of the kernels' shared operands (q, k from their key head;
    v; the log-decay and beta, a row a chunk), with the block of a grid step
    chosen by ``block(i)`` (forward: i; backward: last first)."""
    rep, NC = Hv // Hk, TB // C
    kv_head = lambda b, i: ((b // Hv) * Hk + (b % Hv) // rep, block(i), 0)  # noqa: E731
    own = lambda b, i: (b, block(i), 0)  # noqa: E731
    return own, [pl.BlockSpec((1, TB, dk), kv_head), pl.BlockSpec((1, TB, dk), kv_head),
                 pl.BlockSpec((1, TB, dv), own), pl.BlockSpec((1, NC, C), own), pl.BlockSpec((1, NC, C), own)]


def _seq_params():
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))}


@functools.partial(jax.jit, static_argnames=("Hk", "Hv", "C", "TB"))
def _gdn_fwd(q, k, v, G, beta, Hk: int, Hv: int, C: int, TB: int, h0=None):
    """q, k (B*Hk, T, dk), v (B*Hv, T, dv), G, beta (B*Hv, T/C, C) f32 -> o
    (B*Hv, T, dv) and the float32 state before each block of ``TB`` tokens,
    (B*Hv, T/TB, dk, dv).  With ``h0 (B*Hv, dk, dv)`` the scan starts from it
    and a third result is the state after the last token, in ``h0``'s dtype."""
    BH, T, dv = v.shape
    dk = q.shape[-1]
    carry = h0 is not None
    own, in_specs = _gdn_specs(Hk, Hv, TB, C, dk, dv, lambda i: i)
    state = pl.BlockSpec((1, dk, dv), lambda b, i: (b, 0, 0))
    out_specs = [pl.BlockSpec((1, TB, dv), own), pl.BlockSpec((1, 1, dk, dv), lambda b, i: (b, i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, T, dv), v.dtype),
                 jax.ShapeDtypeStruct((BH, T // TB, dk, dv), jnp.float32)]
    if carry:
        in_specs, out_specs = in_specs + [state], out_specs + [state]
        out_shape = out_shape + [jax.ShapeDtypeStruct((BH, dk, dv), h0.dtype)]
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, C=C, NC=TB // C, carry=carry),
        name="gdn_chunk_fwd",
        grid=(BH, T // TB),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=_interpret(),
        **_seq_params(),
    )(q, k, v, G, beta, *((h0,) if carry else ()))


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, st_ref,
                    dq_ref, dk_ref, dv_ref, db_ref, dg_ref, ds_ref, *, C, NC):
    """One block of NC chunks.  Forward as ``gdn_chunk_fwd`` from the block's
    saved starting state (the terms, then the walk: each chunk's starting state
    and ``D`` stay in VMEM; the state's update is the one product this adds to
    what the pass needs anyway), with the products of the output's gradient
    that need no gradient in the state beside the walk.  Then the gradient in
    the state walks back, last chunk first, two dependent products a chunk,
    ``ds_ref`` carrying it in from the block after, with what hangs off it up
    to ``dT`` between the steps; the rest (through the inverse, and every
    gradient that leaves) is made for all chunks together.  Where each line
    stands was measured (PERF.md, PR 31): all of a chunk between two steps
    costs 12.8 ms a call, none of it 11.1, this 9.9."""
    f32, dt = jnp.float32, v_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    eye, low, strict = _gdn_masks(C)
    last_col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    ab, ab_t, at_b = _AB, _AB_T, _AT_B
    rows = lambda a: jnp.sum(a, axis=1, keepdims=True)  # noqa: E731 -- (C, n) -> (C, 1)
    total = lambda a: jnp.sum(rows(a), axis=0, keepdims=True)  # noqa: E731 -- -> (1, 1)
    ch = range(NC)
    each = lambda f, *lists: [f(*x) for x in zip(*lists)]  # noqa: E731 -- one line of the algebra, every chunk

    t = _gdn_block_terms(k_ref, v_ref, g_ref, b_ref, C, NC, eye, low, strict)
    q = [q_ref[0, c * C:(c + 1) * C, :] for c in ch]
    do = [do_ref[0, c * C:(c + 1) * C, :] for c in ch]
    Qd, QKraw, QKm, dD_out, dS_out, dQKm, dQd = ([None] * NC for _ in range(7))

    def beside(c, S, D):     # what the output's gradient gives without the gradient in the state
        Qd[c] = t.eG[c] * q[c].astype(f32)
        QKraw[c] = dot(q[c], t.k[c], ab_t)
        QKm[c] = jnp.where(low, t.M[c] * QKraw[c], 0.0)
        dD_out[c] = dot(QKm[c].astype(dt), do[c], at_b)
        dS_out[c] = dot(Qd[c].astype(dt), do[c], at_b)
        dQKm[c] = jnp.where(low, dot(do[c], D, ab_t), 0.0)
        dQd[c] = dot(do[c], S.astype(dt), ab_t)

    S0, Dd, _ = _gdn_state_walk(st_ref[0, 0], t, dt, beside=beside)
    k, v, beta, M, kk, Tm, eG, Ub, Wb, Wd, Kd = t.k, t.v, t.beta, t.M, t.kk, t.Tm, t.eG, t.Ub, t.Wb, t.W, t.Kd
    # the chain: through the read-out, the state's update and D = U - W S0
    dS, dD, dKd, dW, dUb, dWb, dT = ([None] * NC for _ in range(7))
    carried = ds_ref[...]
    for c in reversed(ch):
        dS[c] = carried                                                 # the gradient in the state after chunk c
        dD[c] = dD_out[c] + dot(Kd[c].astype(dt), carried.astype(dt), ab)
        carried = dS_out[c] + t.e_glast[c] * carried - dot(Wd[c], dD[c].astype(dt), at_b)
        dKd[c] = dot(Dd[c], dS[c].astype(dt), ab_t)
        dW[c] = -dot(dD[c].astype(dt), S0[c].astype(dt), ab_t)
        # through U = Tm Ub, W = Tm Wb
        dUb[c] = _dot3(Tm[c], dD[c], at_b)
        dWb[c] = _dot3(Tm[c], dW[c], at_b)
        dT[c] = _dot3(dD[c], Ub[c], ab_t) + _dot3(dW[c], Wb[c], ab_t)
    ds_ref[...] = carried
    # off the chain: through Tm = (I + A)^-1
    TdT = each(lambda Tm, dT: _dot3(Tm, dT, at_b), Tm, dT)
    dA = each(lambda TdT, Tm: -jnp.where(strict, _dot3(TdT, Tm, ab_t), 0.0), TdT, Tm)
    dKK = each(lambda dA, beta, M: (dA * beta * M).astype(dt), dA, beta, M)
    dMM = each(lambda dA, beta, kk, dQKm, QKraw, M: (dA * beta * kk + dQKm * QKraw) * M, dA, beta, kk, dQKm, QKraw, M)
    dQKraw = each(lambda dQKm, M: (dQKm * M).astype(dt), dQKm, M)
    for c in ch:
        sl = slice(c * C, (c + 1) * C)
        kf = k[c].astype(f32)
        dk_ref[0, sl, :] = (dot(dKK[c], k[c], ab) + dot(dKK[c], k[c], at_b) + dot(dQKraw[c], q[c], at_b)
                            + t.e_last[c] * dKd[c] + beta[c] * eG[c] * dWb[c]).astype(dk_ref.dtype)
        dq_ref[0, sl, :] = (dot(dQKraw[c], k[c], ab) + eG[c] * dQd[c]).astype(dq_ref.dtype)
        dv_ref[0, sl, :] = (beta[c] * dUb[c]).astype(dv_ref.dtype)
        db_ref[0, c:c + 1, :] = _as_row(
            rows(dA[c] * M[c] * kk[c]) + rows(dWb[c] * eG[c] * kf) + rows(dUb[c] * v[c].astype(f32)), eye)
        # G enters M as a column and as a row; the chunk's last log-decay also
        # scales the carried state and every key of the update
        d_col = rows(dMM[c]) + rows(dQd[c] * Qd[c]) - rows(dKd[c] * Kd[c]) + rows(dWb[c] * Wb[c])
        d_last = total(dKd[c] * Kd[c]) + total(dS[c] * (t.e_glast[c] * S0[c]))
        dg_ref[0, c:c + 1, :] = (_as_row(d_col, eye) - jnp.sum(dMM[c], axis=0, keepdims=True)
                                 + jnp.where(last_col, jnp.broadcast_to(d_last, (1, C)), 0.0))


@functools.partial(jax.jit, static_argnames=("Hk", "Hv", "C", "TB"))
def _gdn_bwd(do, q, k, v, G, beta, states, Hk: int, Hv: int, C: int, TB: int):
    """Gradients a value head from the state before each block, (B*Hv, T/TB,
    dk, dv) f32: dq, dk (B*Hv, T, dk) (to be summed over the heads that share
    a key head), dv, and dbeta and the gradient in the within-chunk cumulative
    log-decay, a row a chunk as they came: (B*Hv, T/C, C)."""
    BH, T, dv = v.shape
    dk = q.shape[-1]
    nb = T // TB
    own, in_specs = _gdn_specs(Hk, Hv, TB, C, dk, dv, lambda i: nb - 1 - i)
    row_spec, rows = pl.BlockSpec((1, TB // C, C), own), jax.ShapeDtypeStruct((BH, T // C, C), jnp.float32)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, C=C, NC=TB // C),
        name="gdn_chunk_bwd",
        grid=(BH, nb),
        in_specs=in_specs + [pl.BlockSpec((1, TB, dv), own),
                             pl.BlockSpec((1, 1, dk, dv), lambda b, i: (b, nb - 1 - i, 0, 0))],
        out_specs=[pl.BlockSpec((1, TB, dk), own), pl.BlockSpec((1, TB, dk), own), pl.BlockSpec((1, TB, dv), own),
                   row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, T, dk), q.dtype), jax.ShapeDtypeStruct((BH, T, dk), k.dtype),
                   jax.ShapeDtypeStruct((BH, T, dv), v.dtype), rows, rows],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=_interpret(),
        **_seq_params(),
    )(q, k, v, G, beta, do, states)


def _gdn_block_tokens(T: int, C: int) -> int:
    """Tokens a grid step, which is the stride of the saved states
    (``prims.gdn_state_stride``): whole chunks, and the chunk rows of ``Gr`` a
    whole sublane tile (8) or the whole array."""
    tb = gdn_state_stride(T)
    return tb if tb % C == 0 and ((tb // C) % 8 == 0 or tb == T) else 0


def _gdn_bwd_vmem_bytes(TB: int, C: int, dk: int, dv: int, itemsize: int) -> int:
    """About what ``gdn_chunk_bwd`` holds in VMEM for a block: between its walks
    every chunk's starting state, the gradient in it and the read-out's part of
    that gradient (float32 ``(dk, dv)``), six ``(C, C)`` terms and some eight
    float32 rows of ``C`` by a head; and the operands' double-buffered blocks.
    Against the 16 MiB of scoped VMEM Mosaic gives a v5e kernel this sits on
    the right side of what compiles: heads of 128 in float32 (7.9 MiB) and of
    256 in bfloat16 (14.8) do, 256 in float32 (18.3) and 384 (26.7) do not."""
    live = (TB // C) * 4 * (3 * dk * dv + 6 * C * C + 4 * C * (dk + dv))
    blocks = 2 * itemsize * TB * (4 * dk + 3 * dv) + 2 * 4 * dk * dv      # q, k, dq, dk; v, do, dv; the state
    return live + blocks


_GDN_VMEM_BUDGET = 16 << 20


def _gdn_lanes(d: int) -> int:
    """The width a head is handed to the compiled kernels at: whole 128-lane
    tiles.  Zero columns of q and k add nothing to a product over the head, and
    zero columns of v give zero columns of the state and of o, so the padding
    is exact; the interpreter takes any width."""
    return d if _interpret() else -(-d // 128) * 128


def _gdn_supported(q_shape, v_shape, dtype, chunk) -> bool:
    T = q_shape[-2]
    dk, dv = _gdn_lanes(q_shape[-1]), _gdn_lanes(v_shape[-1])
    TB = _gdn_block_tokens(T, chunk)
    if str(dtype) not in ("bfloat16", "float32") or TB == 0:
        return False
    if _gdn_bwd_vmem_bytes(TB, chunk, dk, dv, jnp.dtype(dtype).itemsize) > _GDN_VMEM_BUDGET:
        return False   # a block's chunks would not fit beside its operands
    return _interpret() or chunk % 8 == 0


def _gdn_dispatchable(q, k, v, chunk) -> bool:
    if not _enabled() or not _gdn_supported(q.shape, v.shape, v.dtype, int(chunk)):
        return False
    mesh = _mesh_var.get()
    # no sharded dispatch yet: the XLA decomposition partitions as plain products
    return not ((mesh is not None and mesh.devices.size > 1) or any(_concrete_multi_device(x) for x in (q, k, v)))


def _lane_pad(x, width: int):
    return x if x.shape[-1] == width else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _gdn_operands(q, k, v, g, beta, C: int):
    """The kernels' flat-batch operands: heads folded into the batch and
    padded to whole lane tiles (``_gdn_lanes``), the log-decay summed within
    each chunk, it and beta a row a chunk."""
    B, Hk, T, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    G = jnp.cumsum(g.astype(jnp.float32).reshape(B * Hv, T // C, C), axis=-1)
    return (_lane_pad(q.reshape(B * Hk, T, dk), _gdn_lanes(dk)), _lane_pad(k.reshape(B * Hk, T, dk), _gdn_lanes(dk)),
            _lane_pad(v.reshape(B * Hv, T, dv), _gdn_lanes(dv)),
            G, beta.astype(jnp.float32).reshape(B * Hv, T // C, C))


#: the last scan built, at trace time (a dict of its own, as ``flash_schedule``:
#: the readers of ``stats`` sum its values)
gdn_schedule: dict[str, int] = {}


def gdn_chunk(q, k, v, g, beta, chunk=GDN_CHUNK):
    """The chunked gated delta rule through ``gdn_chunk_fwd``: ``(o, states)``,
    or None where the shapes do not qualify (the XLA decomposition runs
    then)."""
    if not _gdn_dispatchable(q, k, v, chunk):
        return None
    stats["gdn"] = stats.get("gdn", 0) + 1
    B, Hk, T, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    C = int(chunk)
    TB = _gdn_block_tokens(T, C)
    gdn_schedule.update(state_stride_tokens=TB, states_saved_bytes=4 * B * Hv * (T // TB) * dk * dv,
                        forward_calls_in_backward=0, chunks_a_product=1)
    o, states = _gdn_fwd(*_gdn_operands(q, k, v, g, beta, C), Hk, Hv, C, TB)
    return o[..., :dv].reshape(v.shape), states[..., :dk, :dv].reshape(B, Hv, T // TB, dk, dv)


def gdn_chunk_state(q, k, v, g, beta, h0, chunk=GDN_CHUNK):
    """The same scan for the server: from the state ``h0 (B, Hv, dk, dv)`` (a
    prompt's first piece hands zeros) to ``(o, state after the last token)``,
    the state in ``h0``'s dtype; or None where the shapes do not qualify.  A
    token with ``g = 0`` and ``beta = 0`` leaves the state as it was: that is
    how a padded prompt's tail is told to do nothing."""
    if not _gdn_dispatchable(q, k, v, chunk):
        return None
    stats["gdn"] = stats.get("gdn", 0) + 1
    B, Hk, T, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    C = int(chunk)
    pk, pv = _gdn_lanes(dk), _gdn_lanes(dv)
    h0 = jnp.pad(h0.reshape(B * Hv, dk, dv), ((0, 0), (0, pk - dk), (0, pv - dv)))
    o, _, fin = _gdn_fwd(*_gdn_operands(q, k, v, g, beta, C), Hk, Hv, C, _gdn_block_tokens(T, C), h0=h0)
    return o[..., :dv].reshape(v.shape), fin[:, :dk, :dv].reshape(B, Hv, dk, dv)


def gdn_chunk_backward(do, q, k, v, g, beta, states, chunk=GDN_CHUNK):
    """Its backward pass: ``gdn_chunk_bwd`` from the last block to the first,
    each block's chunks rebuilt in VMEM from the block's saved state; or
    None."""
    if not _gdn_dispatchable(q, k, v, chunk):
        return None
    stats["gdn"] = stats.get("gdn", 0) + 1
    B, Hk, T, dk = q.shape
    Hv, dv = v.shape[1], v.shape[3]
    C, rep = int(chunk), Hv // Hk
    TB = _gdn_block_tokens(T, C)
    pk, pv = _gdn_lanes(dk), _gdn_lanes(dv)
    states = jnp.pad(states.reshape(B * Hv, T // TB, dk, dv), ((0, 0), (0, 0), (0, pk - dk), (0, pv - dv)))
    dq, dk_, dv_, db, dG = _gdn_bwd(
        _lane_pad(do.reshape(B * Hv, T, dv).astype(v.dtype), pv), *_gdn_operands(q, k, v, g, beta, C),
        states, Hk, Hv, C, TB)
    # q and k a key head: the heads that read it summed; the log-decay's gradient: back through the cumsum
    per_key = lambda d: d[..., :dk].astype(jnp.float32).reshape(B, Hk, rep, T, dk).sum(axis=2)  # noqa: E731
    dg = jnp.flip(jnp.cumsum(jnp.flip(dG, -1), axis=-1), -1).reshape(B, Hv, T)
    return (per_key(dq).astype(q.dtype), per_key(dk_).astype(k.dtype), dv_[..., :dv].reshape(v.shape),
            dg.astype(g.dtype), db.reshape(B, Hv, T).astype(beta.dtype))


# ---------------------------------------------------------------------------
# One delta-rule step a row, on the server's state arena: ``gdn_decode_step``.
#
# The arena lays a row's value heads side by side, ``(slots + 1, L_lin, dk, Hv
# dv)``: head ``h``'s ``(dk, dv)`` matrix in columns ``[h dv, (h + 1) dv)``, the key
# on the sublanes.  A last axis of one head pads to whole lane tiles where the
# chip holds it (192 lanes lie as 256: a third more bytes a step and a slot);
# thirty heads of 192 are 45 tiles exactly (``kv_pool.StatePool``).
#
# Grid (row); a row's state, ``(dk, Hv dv)`` of the arena's slot the
# scalar-prefetched table names, comes into VMEM once, takes the decay, the
# rank-one update and the read-out on the vector unit in float32 (products of
# one row: the MXU has nothing to win), and goes back to the same place: the
# arena aliases its result, so the other slots keep their bytes and the
# program holds no gather, update and scatter of 2 MB a row.  Padding rows
# name slot 0, the sink, which nobody reads.  q and k arrive a column a head,
# ``(rows, dk, Hk)``: a head's key is then a lane of a small block and
# broadcasts along the state's lanes without a transpose in the kernel; v, the
# decay and beta arrive a row a row, ``(rows, 1, Hv dv)``, a head's number on
# each of its lanes.  The body walks the row in groups of ``G`` heads whose
# width is whole lane tiles (``_gdn_group``: 2 heads at a ``dv`` of 192 or 64, 1 at
# 128; the whole width where the heads never end on a tile's edge, which is
# the tests' tiny shapes): a group's lanes take the key and query columns of
# their own heads by a lane mask, and then the step is ``gdn_step_math`` on the
# ``(dk, G dv)`` tile as it is on one head's: a lane's numbers depend on its own
# column alone, and the sums run along ``dk``.
#   a = exp(g);  kS = k^T S;  qS = q^T S;  d = beta (v - a kS)
#   S <- a S + k d^T;   o = a qS + (q . k) d      (= q^T of the new S)
# ---------------------------------------------------------------------------

def gdn_step_math(S, kc, qc, v, a, beta):
    """One head's step in float32: ``S (dk, dv)``, the key and the query as
    columns ``(dk, 1)``, ``v``, the decay ``a = exp(g)`` and ``beta`` as rows
    ``(1, dv)`` -> ``(o (1, dv), S)``.  The kernel's body and the dense cache's
    step (``models.generate``) are this one function: a served token and a
    solo ``generate()`` token take the same formulas in the same order.  The
    kernel hands it several heads side by side, ``S (dk, G dv)`` with ``kc`` and
    ``qc`` as wide (each lane its head's column): every line is a lane's own."""
    kS = jnp.sum(S * kc, axis=0, keepdims=True)
    qS = jnp.sum(S * qc, axis=0, keepdims=True)
    d = beta * (v - a * kS)
    return a * qS + jnp.sum(qc * kc, axis=0, keepdims=True) * d, a * S + kc * d


def _gdn_group(Hv: int, dv: int) -> int:
    """Heads a step of the decode kernel's walk: the fewest whose width is
    whole 128-lane tiles, where the row's heads come in whole such groups;
    else all of them, one group of the whole width."""
    G = 128 // math.gcd(dv, 128)
    return G if Hv % G == 0 else Hv


def _gdn_decode_kernel(slot_ref, qT_ref, kT_ref, v_ref, a_ref, b_ref, s_ref, o_ref, so_ref, *, Hv, rep):
    del slot_ref   # the row's slot lives in the BlockSpec index maps
    f32 = jnp.float32
    qT, kT = qT_ref[0], kT_ref[0]                                     # (dk, Hk) float32
    dv = s_ref.shape[3] // Hv
    G = _gdn_group(Hv, dv)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, G * dv), 1)

    def columns(cT, first):
        """Each lane of the group its head's column of ``cT``: ``(dk, 1)`` for one head, else ``(dk, G dv)``."""
        col = cT[:, first // rep:first // rep + 1]
        for j in range(1, G):
            if (first + j) // rep != (first + j - 1) // rep:
                col = jnp.where(lane >= j * dv, cT[:, (first + j) // rep:(first + j) // rep + 1], col)
        return col

    for first in range(0, Hv, G):
        lanes = slice(first * dv, (first + G) * dv)
        o, S = gdn_step_math(s_ref[0, 0, :, lanes].astype(f32), columns(kT, first), columns(qT, first),
                             v_ref[0, :, lanes].astype(f32), a_ref[0, :, lanes], b_ref[0, :, lanes])
        so_ref[0, 0, :, lanes] = S.astype(so_ref.dtype)
        o_ref[0, :, lanes] = o.astype(o_ref.dtype)


def gdn_decode_step(arena, slots, q, k, v, g, beta, *, layer: int):
    """One token a row through the gated delta rule, the state read and
    written once, in place.  ``arena (slots + 1, L_lin, dk, Hv dv)``, a row's value
    heads side by side (float32, or what the pool was told to store); ``slots
    (rows,)`` int32, 0 the sink; q, k ``(rows, Hk, dk)`` as the mixer hands them
    (unit keys, scaled queries), v ``(rows, Hv, dv)``, g (log-decay) and beta
    ``(rows, Hv)`` float32.  Returns ``(o (rows, Hv, dv) in v's dtype, arena)``."""
    stats["gdn_decode"] = stats.get("gdn_decode", 0) + 1
    rows, Hk, dk = q.shape
    Hv, dv = v.shape[1], v.shape[2]
    W = Hv * dv
    f32 = jnp.float32
    lanes = lambda x: _ssd_channels(x.astype(f32), dv)[:, None]  # noqa: E731 -- a head's number on each of its lanes
    col = lambda i, s: (i, 0, 0)  # noqa: E731
    mine = lambda i, s: (s[i], layer, 0, 0)  # noqa: E731
    kwargs = {}
    if not _interpret():
        # a row's state in and out, double-buffered: four blocks of (dk, Hv dv)
        tile = 4 * (-(-dk // 8) * 8) * (-(-W // 128) * 128) * 4
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=max(32 << 20, tile + (16 << 20)))
    o, arena = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, Hv=Hv, rep=Hv // Hk),
        name="gdn_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, dk, Hk), col), pl.BlockSpec((1, dk, Hk), col),
                      pl.BlockSpec((1, 1, W), col), pl.BlockSpec((1, 1, W), col), pl.BlockSpec((1, 1, W), col),
                      pl.BlockSpec((1, 1, dk, W), mine)],
            out_specs=[pl.BlockSpec((1, 1, W), col), pl.BlockSpec((1, 1, dk, W), mine)]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, W), v.dtype), jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        input_output_aliases={6: 1},     # operands: the slot table, five small ones, the arena
        interpret=_interpret(),
        **kwargs,
    )(slots.astype(jnp.int32), jnp.swapaxes(q, 1, 2).astype(f32), jnp.swapaxes(k, 1, 2).astype(f32),
      v.reshape(rows, 1, W), lanes(jnp.exp(g)), lanes(beta), arena)
    return o.reshape(rows, Hv, dv), arena


def _gdn_full(q, k, v, g, beta):
    res = gdn_chunk(q, k, v, g, beta)
    if res is None:
        from thunder_tpu.executors.jaxex import _gdn_chunked

        return _gdn_chunked(q, k, v, g, beta, GDN_CHUNK)
    return res


_gdn_op = ex.register_operator("pallas_gdn_chunk", like=prim_lookup[PrimIDs.GDN_CHUNK], fn=_gdn_full)


def _gdn_checker(q, k, v, g, beta):
    from thunder_tpu.core import dtypes as _dt

    return _enabled() and _gdn_supported(tuple(q.shape), tuple(v.shape), _dt.to_jax_dtype(v.dtype), GDN_CHUNK)


ex.register_implementation(PrimIDs.GDN_CHUNK, _gdn_op, checker=_gdn_checker)


def _gdn_backward_full(do, q, k, v, g, beta, states):
    res = gdn_chunk_backward(do, q, k, v, g, beta, states)
    if res is None:
        from thunder_tpu.executors.jaxex import _gdn_chunked_backward

        return _gdn_chunked_backward(do, q, k, v, g, beta, GDN_CHUNK)
    return res


_gdn_bwd_op = ex.register_operator(
    "pallas_gdn_chunk_backward", like=prim_lookup[PrimIDs.GDN_CHUNK_BACKWARD], fn=_gdn_backward_full)
ex.register_implementation(PrimIDs.GDN_CHUNK_BACKWARD, _gdn_bwd_op,
                           checker=lambda do, *a: _gdn_checker(*a[:5]))


# ---------------------------------------------------------------------------
# Grouped matrix products over rows sorted by group: ``moe_grouped_mm`` and
# ``moe_grouped_mm_dw``.  The plan (``jaxex.moe_plan``) pads every group to
# whole row tiles, so a grid step is one tile against a block of one group's
# weights, chosen by the scalar-prefetched ``tile_group``.  The block is the
# whole matrix where VMEM holds that twice (``_gmm_blocks``): a group's tiles
# follow one another, a block index that does not change is not fetched again,
# so a group's weights are copied once however many tiles it has.  Tiles past
# ``tiles_used`` point at the last used tile's rows and weight block, so
# nothing is fetched for them; they write zeros.  The time follows the tiles
# used.
#
# Pallas fetches a ``BlockSpec`` operand one grid step ahead, and a group's
# tiles follow one another under one block index: the next group's weights
# would start to arrive behind the group's *last* tile, and the fetch and all
# but one tile's product add up.  So where a group can have several tiles (a
# row tile of ``MOE_ROW_TILE``: a prompt's, the trainer's) and the block is the
# whole matrix, the kernel copies the weights itself, a group ahead
# (``weights_ahead``, ``_gmm_ahead_kernel``): ``w`` stays in HBM, a *run* (a
# group's tiles, one after another) holds the buffer of its ordinal's parity,
# and its first tile starts the copy of the next run's group into the other
# one, whose tiles have all passed, and then waits for its own.  Step 0 starts
# its own group's copy first: one block a call stays bare.  No copy is started
# for a run that begins at or past ``tiles_used``: nothing is in flight when
# the call ends.  Two things about that copy decide whether it hides at all (on
# a v5e, ``PERF.md`` PR 60): it is started *before* the wait for the run's own,
# so that through a stretch of one-tile groups the queue always holds the next
# block, and at the low priority, so that the row tiles the pipeline fetches a
# step ahead do not queue behind megabytes of weights (at the default one the
# form ran no faster than the ``BlockSpec`` form, and slower under a skewed
# routing).  A decode step's tile is smaller, its groups are one tile, one grid
# step ahead is a group ahead, and it keeps the ``BlockSpec`` form.
# ---------------------------------------------------------------------------


def _gmm_kernel(tg_ref, used_ref, x_ref, w_ref, o_ref, *, transpose_w):
    t = pl.program_id(0)

    @pl.when(t < used_ref[0])
    def _compute():
        dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(x_ref[...], w_ref[0], dims,
                                         preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(t >= used_ref[0])
    def _pad():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_ahead_kernel(tg_ref, used_ref, opens_ref, parity_ref, next_ref, x_ref, w_hbm, o_ref, buf0, buf1, sem, *,
                      transpose_w):
    t = pl.program_id(0)
    used = used_ref[0]
    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    bufs = (buf0, buf1)

    def copy(tile, k):      # the weights of ``tile``'s group into buffer k
        return pltpu.make_async_copy(w_hbm.at[tg_ref[tile]], bufs[k], sem.at[k])

    def tile_of_a_run_in(k):
        @pl.when(opens_ref[t] == 1)
        def _open():
            if k == 0:      # the call's first run is in buffer 0
                @pl.when(t == 0)
                def _():
                    copy(0, 0).start()

            nxt = next_ref[t]

            @pl.when(nxt < used)
            def _():
                copy(nxt, 1 - k).start(priority=1)

            copy(t, k).wait()

        o_ref[...] = jax.lax.dot_general(x_ref[...], bufs[k][...], dims,
                                         preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(t < used)
    def _compute():
        for k in (0, 1):
            pl.when(parity_ref[t] == k)(functools.partial(tile_of_a_run_in, k))

    @pl.when(t >= used)
    def _pad():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_runs(tile_group):
    """The runs of ``tile_group (nt,)``, a group's tiles one after another, as
    the kernel that copies its own weights reads them, int32 ``(nt,)`` each:
    whether a tile opens a run, its run's parity, and the tile that opens the
    next run (``nt``: none)."""
    nt = tile_group.shape[0]
    t = jnp.arange(nt, dtype=jnp.int32)
    opens = jnp.concatenate([jnp.ones((1,), bool), tile_group[1:] != tile_group[:-1]])
    parity = (jnp.cumsum(opens, dtype=jnp.int32) - 1) % 2
    from_here = jax.lax.cummin(jnp.where(opens, t, nt), reverse=True)       # the first run that opens at t or after
    return opens.astype(jnp.int32), parity, jnp.concatenate([from_here[1:], jnp.full((1,), nt, jnp.int32)])


# what a kernel gets of VMEM without asking, and what Mosaic keeps beside the blocks
_GMM_VMEM_DEFAULT = 16 << 20
_GMM_VMEM_MARGIN = 4 << 20

# what the last grouped product built was laid out as (trace time; a dict of
# its own, as ``flash_schedule``)
gmm_schedule: dict[str, int] = {}


def _gmm_vmem_cap() -> int:
    """VMEM a grouped product may ask for: three quarters of the core's where
    the device says what it has (96 of a v5e's 128 MiB), else what a kernel
    gets without asking, which compiles anywhere."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes * 3 // 4
    except ValueError:       # not a TPU jax knows the sizes of (the interpreter, a lowering for another host's chip)
        return _GMM_VMEM_DEFAULT


def _gmm_vmem(TM: int, K: int, TN: int, itemsize: int) -> int:
    """Bytes a grid step holds: the weight block, the row tile and the output
    tile twice each (the pipeline's two buffers), the float32 product once."""
    return 2 * (K * TN + TM * K + TM * TN) * itemsize + 4 * TM * TN


def _gmm_blocks(K: int, N: int, itemsize: int, TM: int, nt: int) -> dict[str, int]:
    """How ``moe_grouped_mm`` walks ``nt`` tiles of ``TM`` rows against
    matrices of ``K x N``, from the shapes alone.  The weight block is the
    whole matrix where two of them fit the VMEM the kernel may ask for
    (``_gmm_vmem_cap``): one grid step a tile, and a group's block fetched
    once.  Else it is the widest 128-multiple of columns that fits, walked
    inside a tile, and a group's weights are fetched once for each of its
    tiles (``weight_fetches_a_group`` then says how many that can be: every
    tile).  At ``K x N x itemsize <= 2 MiB`` this is the whole matrix inside
    the default limit, as it always was.  ``weights_ahead``: the kernel copies
    the whole matrix itself, a group ahead of its tiles, where a group can
    have several (a row tile of ``MOE_ROW_TILE``; a decode step's is smaller)."""
    def need(TN):
        return _gmm_vmem(TM, K, TN, itemsize) + _GMM_VMEM_MARGIN

    widths = [b for b in range(N, 0, -128) if N % b == 0] if N % 128 == 0 else [N]
    cap = _gmm_vmem_cap()
    TN = next((b for b in widths if need(b) <= cap), widths[-1])
    return {"col_block": TN, "col_blocks": N // TN, "weight_block_bytes": K * TN * itemsize,
            "vmem_limit_bytes": need(TN) if need(TN) > _GMM_VMEM_DEFAULT else 0,
            "weight_fetches_a_group": 1 if TN == N else nt, "weights_ahead": int(TN == N and TM >= MOE_ROW_TILE)}


def _gmm_specs(TM: int, K: int, nt: int, plan: dict[str, int], transpose_w: bool):
    """The grid of a grouped product laid out as ``plan`` and the block of the
    rows, of the weights and of the product a grid step holds.  Where the
    kernel copies its own weights (``weights_ahead``) they stay whole where
    they are and the grid is the tiles alone."""
    TN, nj = plan["col_block"], plan["col_blocks"]
    # a tile past the used ones stays on the rows and the weight block the last used one held: no copy for it
    tile = lambda t, used: jnp.minimum(t, jnp.maximum(used[0] - 1, 0))  # noqa: E731
    if plan["weights_ahead"]:
        return ((nt,), [pl.BlockSpec((TM, K), lambda t, tg, used, *_: (tile(t, used), 0)), pl.BlockSpec(memory_space=pl.ANY)],
                pl.BlockSpec((TM, TN), lambda t, *_: (t, 0)))
    col = lambda t, j, used: jnp.where(t < used[0], j, nj - 1)  # noqa: E731
    if transpose_w:
        w_spec = pl.BlockSpec((1, TN, K), lambda t, j, tg, used: (tg[tile(t, used)], col(t, j, used), 0))
    else:
        w_spec = pl.BlockSpec((1, K, TN), lambda t, j, tg, used: (tg[tile(t, used)], 0, col(t, j, used)))
    return ((nt, nj), [pl.BlockSpec((TM, K), lambda t, j, tg, used: (tile(t, used), 0)), w_spec],
            pl.BlockSpec((TM, TN), lambda t, j, tg, used: (t, j)))


@functools.partial(jax.jit, static_argnames=("transpose_w",))
def _moe_grouped_mm(x, w, tile_group, tiles_used, transpose_w: bool = False):
    R, K = x.shape
    nt = tile_group.shape[0]
    N = w.shape[1] if transpose_w else w.shape[2]
    plan = _gmm_blocks(K, N, x.dtype.itemsize, R // nt, nt)
    grid, in_specs, out_specs = _gmm_specs(R // nt, K, nt, plan, transpose_w)
    params = {}
    if not _interpret():
        limit = {"vmem_limit_bytes": plan["vmem_limit_bytes"]} if plan["vmem_limit_bytes"] else {}
        params["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * len(grid), **limit)
    if plan["weights_ahead"]:
        kernel, prefetched = _gmm_ahead_kernel, (tile_group, tiles_used, *_gmm_runs(tile_group))
        scratch = [pltpu.VMEM(w.shape[1:], w.dtype), pltpu.VMEM(w.shape[1:], w.dtype), pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel, prefetched, scratch = _gmm_kernel, (tile_group, tiles_used), []
    return pl.pallas_call(
        functools.partial(kernel, transpose_w=transpose_w),
        name="moe_grouped_mm",
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=len(prefetched), grid=grid, in_specs=in_specs,
                                               out_specs=out_specs, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        interpret=_interpret(),
        **params,
    )(*prefetched, x, w)


def _gmm_dw_kernel(tg_ref, used_ref, x_ref, dy_ref, zero_ref, o_ref, acc_ref):
    del zero_ref   # aliased to the output: groups without rows keep its zeros
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    used = used_ref[0]
    g = tg_ref[t]
    first = jnp.logical_or(t == 0, tg_ref[jnp.maximum(t - 1, 0)] != g)
    last = jnp.logical_or(t == used - 1, tg_ref[jnp.minimum(t + 1, nt - 1)] != g)

    @pl.when(t < used)
    def _compute():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups",))
def _moe_grouped_mm_dw(x, dy, tile_group, tiles_used, groups: int):
    R, K = x.shape
    N = dy.shape[1]
    nt = tile_group.shape[0]
    TM = R // nt
    tile = lambda t, tg, used: (jnp.minimum(t, jnp.maximum(used[0] - 1, 0)), 0)  # noqa: E731
    params = {}
    if not _interpret():
        params["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        _gmm_dw_kernel,
        name="moe_grouped_mm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt,),
            in_specs=[pl.BlockSpec((TM, K), tile), pl.BlockSpec((TM, N), tile),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, K, N), lambda t, tg, used: (tg[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((K, N), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, K, N), x.dtype),
        input_output_aliases={4: 0},
        interpret=_interpret(),
        **params,
    )(tile_group, tiles_used, x, dy, jnp.zeros((groups, K, N), x.dtype))


def _gmm_supported(R: int, nt: int, K: int, N: int, dtype) -> bool:
    if str(dtype) not in ("bfloat16", "float32") or nt <= 0 or R % nt:
        return False
    if not _interpret() and ((R // nt) % 16 or K % 128 or N % 128):
        return False
    return True


def _gmm_dispatchable(*operands) -> bool:
    mesh = _mesh_var.get()
    return not ((mesh is not None and mesh.devices.size > 1) or any(_concrete_multi_device(x) for x in operands))


def grouped_mm(x, w, tile_group, tiles_used, transpose_w=False):
    """``moe_grouped_mm``, or None where the shapes do not qualify."""
    N = w.shape[1] if transpose_w else w.shape[2]
    if not (_enabled() and _gmm_supported(x.shape[0], tile_group.shape[0], x.shape[1], N, x.dtype)
            and _gmm_dispatchable(x, w)):
        return None
    stats["grouped_mm"] = stats.get("grouped_mm", 0) + 1
    gmm_schedule.update(_gmm_blocks(x.shape[1], N, x.dtype.itemsize, x.shape[0] // tile_group.shape[0],
                                    tile_group.shape[0]))
    stats["grouped_mm_ahead"] = stats.get("grouped_mm_ahead", 0) + gmm_schedule["weights_ahead"]
    return _moe_grouped_mm(x, w, tile_group, tiles_used, transpose_w=bool(transpose_w))


def grouped_mm_dw(x, dy, tile_group, tiles_used, groups):
    """``moe_grouped_mm_dw``, or None where the shapes do not qualify."""
    if not (_enabled() and _gmm_supported(x.shape[0], tile_group.shape[0], x.shape[1], dy.shape[1], x.dtype)
            and _gmm_dispatchable(x, dy)):
        return None
    stats["grouped_mm"] = stats.get("grouped_mm", 0) + 1
    return _moe_grouped_mm_dw(x, dy.astype(x.dtype), tile_group, tiles_used, groups=int(groups))


# ---------------------------------------------------------------------------
# The tokens take their experts' rows back: ``moe_combine``.  Token rows ``(N,
# C)`` from the sorted buffer's rows ``vb (R, C)``, each the sum of the rows its
# assignments landed in, added lowest row first in float32, in one pass over
# the bytes: where XLA runs a scatter-add that walks its updates one after
# another (``pos`` None in ``jaxex``) or keeps a ``(k, N, C)`` gather.
#
# A slice of a tiled array in HBM starts and ends on a tile of rows (Mosaic
# refuses a copy of one row of ``(R, C)``), so the buffer is read in *chunks*
# of a sublane tile of rows (8 float32, 16 bfloat16), every chunk that holds a
# routed row once.  The plan pads a group to whole row tiles and sorts a
# group's rows by token, so a walk over the tokens a tile at a time, a tile's
# rows in buffer order, meets each group's rows one after another: a group is
# a stream with two chunk buffers of static roles (a chunk's parity).  Every
# group's first chunk is started at the first grid step (``tile_group`` says
# where a group's run of row tiles begins); where the walk reaches a chunk's
# first row it waits for that chunk and starts the group's next one behind it;
# the one left in flight a group is awaited at the last grid step.  A 16-bit
# chunk is widened to float32 where it arrives (whole vregs), so a row is read
# and added as a float32 sublane.  (The sums laid out a lane tile a sublane, so
# that a row is whole vregs, and the chunks laid out again to match by strided
# stores, ran no faster at any shape and a tenth slower at Xing4.0's: what a
# row costs is the walk's scalar steps, 40 ns, and at A.X-K1's width the
# result's own write, 235 MB a wave at 610 GB/s; ``PERF.md``, PR 58.)
#
# Grid: a tile of tokens a step, in order (the streams' state crosses steps).
# The walk is one loop over the tile's routed rows: ``ent`` (prefetched whole)
# lists the wave's routed rows by token tile, buffer order inside a tile, one
# int32 a row: its buffer row, its group and its token in the tile; ``off`` is
# where each tile's rows start.  It costs by the rows routed: a token without
# a row here is never looked at, and keeps the zeros its tile started from.
# ---------------------------------------------------------------------------

_COMBINE_TOKENS = 128      # tokens a grid step, the most
_COMBINE_ROWS = 1 << 17    # rows a wave, the most: ``ent`` is prefetched whole (512 KB of SMEM compile for a v5e)

# what the last combine was laid out as, or why it kept to XLA (trace time; a dict of its own, as ``hc_schedule``)
combine_schedule: dict = {}


def _combine_vmem(TN: int, C: int, groups: int, itemsize: int, out_itemsize: int) -> int:
    """Bytes a grid step holds: two chunks a group at the rows' dtype, one a
    group widened where that is 16 bits, the float32 sums of a tile where the
    result is not float32, the result's block twice (the pipeline's two
    buffers), and what Mosaic keeps beside them."""
    G = 32 // itemsize      # a sublane tile of rows
    chunks = 2 * groups * G * C * itemsize + (groups * G * C * 4 if itemsize < 4 else 0)
    return chunks + (TN * C * 4 if out_itemsize < 4 else 0) + 2 * TN * C * out_itemsize + _GMM_VMEM_MARGIN


def _combine_tile(N: int, C: int, groups: int, itemsize: int, out_itemsize: int) -> int | None:
    """Tokens a grid step of ``moe_combine`` holds: ``_COMBINE_TOKENS``, halved
    until the step fits the VMEM the kernel may ask for (``_gmm_vmem_cap``), all
    ``N`` where they are fewer; None where the chunks alone do not fit."""
    TN, cap = _COMBINE_TOKENS, _gmm_vmem_cap()
    while _combine_vmem(TN, C, groups, itemsize, out_itemsize) > cap:
        if TN == 8:
            return None
        TN //= 2
    return min(N, TN)


def _combine_bits(groups: int) -> tuple[int, int]:
    """Bits of an entry of ``ent`` that hold the token in its tile, and the group."""
    return (_COMBINE_TOKENS - 1).bit_length(), max(1, (groups - 1).bit_length())


def _combine_kernel(off_ref, ent_ref, tg_ref, vb, o_ref, ring, wide, acc, sem, flying, *, G, groups, tile):
    f32 = jnp.float32
    i = pl.program_id(0)
    nq = vb.shape[0] // G
    tb, gb = _combine_bits(groups)
    sums = o_ref if acc is None else acc

    def copy(g, q):     # chunk q of the buffer (past its end: the last one again) into group g's buffer of q's parity
        rows = pl.ds(pl.multiple_of(jnp.minimum(q, nq - 1) * G, G), G)
        return pltpu.make_async_copy(vb.at[rows], ring.at[2 * g + q % 2], sem.at[g, q % 2])

    @pl.when(i == 0)
    def _first():
        def idle(g, _):
            flying[g] = -1      # the chunk of group g that is in flight

        def head(p, _):         # a group's first chunk: that of the first row tile of its run
            g = tg_ref[p]

            @pl.when(jnp.logical_or(p == 0, tg_ref[jnp.maximum(p - 1, 0)] != g))
            def _():
                copy(g, p * (tile // G)).start()
                flying[g] = p * (tile // G)

        jax.lax.fori_loop(0, groups, idle, None)
        jax.lax.fori_loop(0, tg_ref.shape[0], head, None)

    sums[...] = jnp.zeros_like(sums)

    def row(e, _):
        v = ent_ref[e]
        t, g, r = v & ((1 << tb) - 1), (v >> tb) & ((1 << gb) - 1), v >> (tb + gb)
        q = r // G

        @pl.when(r % G == 0)        # a group's rows are met one after another: a chunk's first row is met first
        def _next_chunk():
            copy(g, q).wait()
            copy(g, q + 1).start()
            flying[g] = q + 1
            if wide is not None:
                wide[g] = ring[2 * g + q % 2].astype(f32)

        here = pl.ds(t, 1)
        src = ring[2 * g + q % 2, pl.ds(r % G, 1), :] if wide is None else wide[g, pl.ds(r % G, 1), :]
        sums[here, :] = sums[here, :] + src

    jax.lax.fori_loop(off_ref[i], off_ref[i + 1], row, None)
    if acc is not None:
        o_ref[...] = acc[...].astype(o_ref.dtype)

    @pl.when(i == pl.num_programs(0) - 1)
    def _last():
        def drain(g, _):
            q = flying[g]

            @pl.when(q >= 0)
            def _():
                copy(g, q).wait()

        jax.lax.fori_loop(0, groups, drain, None)


@functools.partial(jax.jit, static_argnames=("N", "k", "groups", "dtype", "TN", "interpret"))
def _moe_combine(vb, row_src, tile_group, *, N, k, groups, dtype, TN, interpret):
    R, C = vb.shape
    tile = R // tile_group.shape[0]
    G = _sublane_rows(vb.dtype)
    i32 = jnp.int32
    steps = -(-N // TN)
    tb, gb = _combine_bits(groups)
    # the routed rows by token tile, buffer order inside a tile (the sort is stable): row, group and token in one int32
    token = jnp.maximum(row_src, 0) // k
    step = jnp.where(row_src >= 0, token // TN, steps).astype(i32)
    ent = (jnp.arange(R, dtype=i32) << (tb + gb)) | (jnp.repeat(tile_group.astype(i32), tile) << tb) | (token % TN).astype(i32)
    _, ent = jax.lax.sort((step, ent), num_keys=1, is_stable=True)
    per_step = jnp.sum(step[None, :] == jnp.arange(steps, dtype=i32)[:, None], axis=1, dtype=i32)
    off = jnp.concatenate([jnp.zeros((1,), i32), jnp.cumsum(per_step)])
    widen = vb.dtype.itemsize < 4
    scratch = [pltpu.VMEM((2 * groups, G, C), vb.dtype)]
    scratch += [pltpu.VMEM((groups, G, C), jnp.float32)] if widen else []
    scratch += [pltpu.VMEM((TN, C), jnp.float32)] if dtype != jnp.float32 else []
    scratch += [pltpu.SemaphoreType.DMA((groups, 2)), pltpu.SMEM((groups,), i32)]

    def kernel(off_ref, ent_ref, tg_ref, vb_ref, o_ref, ring, *rest):
        rest = list(rest)
        wide = rest.pop(0) if widen else None
        acc = rest.pop(0) if dtype != jnp.float32 else None
        _combine_kernel(off_ref, ent_ref, tg_ref, vb_ref, o_ref, ring, wide, acc, *rest, G=G, groups=groups, tile=tile)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(_combine_vmem(TN, C, groups, vb.dtype.itemsize, jnp.dtype(dtype).itemsize), _GMM_VMEM_DEFAULT))
    return pl.pallas_call(
        kernel,
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TN, C), lambda i, *_: (i, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((N, C), dtype),
        interpret=interpret,
        **params,
    )(off, ent, tile_group.astype(i32), vb)


def combine_declines(N: int, R: int, tiles: int, C: int, groups: int, dtype, out_dtype) -> str:
    """Why ``moe_combine`` leaves a call of these shapes to XLA, or ``""``: the
    shapes and the device alone decide (``_gmm_supported`` / ``_gmm_dispatchable``'s tests)."""
    if not _enabled():
        return "no Pallas"
    if jnp.dtype(dtype).name not in ("bfloat16", "float32") or jnp.dtype(out_dtype) not in (jnp.dtype(dtype), jnp.float32):
        return "dtype"      # the sum is the share's float32 or, of the rows' gradient, at the rows' own
    mesh = _mesh_var.get()
    if mesh is not None and mesh.devices.size > 1:
        return "several devices"
    if C % 128 or tiles <= 0 or R % tiles or (R // tiles) % _sublane_rows(dtype):
        return "shape"
    if R > _COMBINE_ROWS or (R - 1) >> (31 - sum(_combine_bits(groups))):
        return "shape"      # a row, its group and its token do not fit an int32 of ``ent``, or ``ent`` SMEM
    if _combine_tile(N, C, groups, jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize) is None:
        return "VMEM"
    return ""


def combine(vb, row_src, tile_group, N, k, groups, dtype):
    """``moe_combine``: the token rows ``(N, C)`` at ``dtype`` from the buffer
    rows ``vb (R, C)`` by ``row_src (R,)`` (the assignment ``n k + s`` a row
    holds, -1 for none) and ``tile_group`` (the group of each row tile,
    ``groups`` of them), or None where XLA's forms are to run;
    ``stats["moe_combine"]`` counts the calls taken (trace time) and
    ``combine_schedule`` keeps the last one's layout, or the reason it declined."""
    R, C = vb.shape
    why = combine_declines(N, R, tile_group.shape[0], C, groups, vb.dtype, dtype)
    if not why and any(_concrete_multi_device(a) for a in (vb, row_src)):
        why = "several devices"
    combine_schedule.clear()
    if why:
        combine_schedule.update(fallback=why, tokens=N, slots=k, rows=R, width=C)
        return None
    itemsize, out_itemsize = vb.dtype.itemsize, jnp.dtype(dtype).itemsize
    TN = _combine_tile(N, C, groups, itemsize, out_itemsize)
    stats["moe_combine"] = stats.get("moe_combine", 0) + 1
    combine_schedule.update(block_tokens=TN, grid_steps=-(-N // TN), chunk_rows=_sublane_rows(vb.dtype), streams=groups,
                            rows_listed=R, vmem_limit_bytes=_combine_vmem(TN, C, groups, itemsize, out_itemsize))
    return _moe_combine(vb, row_src, tile_group, N=int(N), k=int(k), groups=int(groups), dtype=jnp.dtype(dtype), TN=TN,
                        interpret=_interpret())


# ---------------------------------------------------------------------------
# The DeltaNet layers' causal depthwise conv with its activation, one pass over
# HBM each way: ``causal_conv1d_fwd`` and ``causal_conv1d_bwd``.
#
#   y[t] = sum_s w_s x[t - s], s = 0 .. K - 1 (tap j of ``w (C, K)`` is s = K - 1 - j);  out = act(y)
#   gy = g act'(y);  dx[t] = sum_s w_s gy[t + s];  dw_s = sum_{b, t} gy[t] x[t - s]
#
# Channels lie on lanes and time on sublanes.  Grid (B, C / tC, T / tT); a grid
# step holds a ``(tT, tC)`` tile and the rows around it that its taps reach as
# further blocks of the same array, one sublane tile of the dtype each (x
# behind; in the backward pass x and g ahead too), zeros past either end of the
# sequence.  Inside a tile a loop walks ``rows`` rows at a time, so the float32
# terms of a step stay near the registers; a shift in time is a sublane roll of
# the rows with the eight before (or after) them.  The forward loop hands the
# next step its last eight rows of x; the backward loop runs from the tile's
# end and hands on the first eight rows of gy, so ``y`` of the rows ahead is
# made once a tile.  ``dw`` gathers in a float32 block revisited along T, eight
# partial rows a tap (whole vregs: no reduce across sublanes in the kernel),
# summed over them and the batch outside.  Products and sums are float32 and
# ``y`` is never rounded.
# ---------------------------------------------------------------------------

_CONV_TILE_BYTES = 1 << 20     # of x's dtype: the double-buffered blocks of the backward pass are six of them
_CONV_STEP_VREGS = 16          # float32 vregs a term of a loop step fills


def _conv_tiles(T: int, C: int, itemsize: int) -> tuple[int, int, int]:
    """``(tT, tC, rows)``: the widest whole-lane-tile divisor of ``C`` up to
    1024, as many rows as keep the tile within ``_CONV_TILE_BYTES`` (all of a
    shorter sequence; the last tile of a longer one may be ragged), and the
    rows a loop step takes: they divide the tile and a float32 term of theirs
    is ``_CONV_STEP_VREGS`` vregs where that is a whole sublane tile of the
    dtype.  Measured on one v5e at ``(2, 8192, 8192)`` bfloat16 (PERF.md, PR
    37; forward / backward ms a call): terms of 128, 64, 32, 16 vregs 1.38 /
    2.81, 1.19 / 2.39, 1.00 / 1.86, 0.95 / 1.58 (what does not fit the
    registers spills), of 8 slower again (1.06 / 1.63); tiles of 256, 512 and
    1024 rows 1.21, 1.19, 1.18 forward; 2048 channels wide Mosaic refuses."""
    tC = 128 * max(d for d in range(1, 9) if (C // 128) % d == 0)
    tT = min(T, _CONV_TILE_BYTES // (tC * itemsize) // 64 * 64)
    fit = max(32 // itemsize, _CONV_STEP_VREGS * 1024 // tC)   # at least a sublane tile of the dtype
    return tT, tC, next(r for r in (128, 64, 32, 16, 8) if r <= fit and tT % r == 0)


def _sublane_rows(dtype) -> int:
    """Rows of a sublane tile of ``dtype``: 8 of four bytes, 16 of two."""
    return 32 // jnp.dtype(dtype).itemsize


def _conv_supported(x_shape, w_shape, dtype, activation) -> bool:
    """Whole lane tiles of channels, whole sublane tiles of time in ``dtype``,
    taps that an eight-row halo holds."""
    if str(dtype) not in ("bfloat16", "float32") or activation not in CAUSAL_CONV_ACTIVATIONS:
        return False
    T, C = x_shape[1:]
    return C % 128 == 0 and T % _sublane_rows(dtype) == 0 and 1 <= w_shape[1] <= 8


def _rows_back(rows, s: int, lo: int, n: int):
    """``rows[lo - s : lo - s + n]`` of a float32 value whose rows are whole
    sublane tiles: a roll and an aligned slice (negative ``s`` looks ahead)."""
    return rows[lo:lo + n] if s == 0 else pltpu.roll(rows, s % rows.shape[0], 0)[lo:lo + n]


def _conv_sum(ext, w, n: int):
    """``y`` of the ``n`` rows after the first eight of ``ext``; and x as each tap sees it."""
    taps = [_rows_back(ext, s, 8, n) for s in range(w.shape[0])]
    y = taps[0] * w[0:1]
    for s in range(1, len(taps)):
        y = y + taps[s] * w[s:s + 1]
    return y, taps


def _sigmoid(y, exact: bool):
    """The quotient for float32 operands; for 16-bit ones the tanh unit's form,
    a third of the quotient's vector operations and about 1e-5 off it, a
    200th of the rounding their result takes (PERF.md, PR 37)."""
    return 1.0 / (1.0 + jnp.exp(-y)) if exact else 0.5 * jnp.tanh(0.5 * y) + 0.5


def _conv_fwd_kernel(x_ref, xb_ref, w_ref, o_ref, *, rows, silu):
    f32 = jnp.float32
    tT = x_ref.shape[1]
    w = w_ref[...]
    behind = jnp.where(pl.program_id(2) == 0, 0.0, xb_ref[0].astype(f32)[-8:])

    def step(r, behind):
        at = pl.multiple_of(r * rows, rows)
        x = x_ref[0, pl.ds(at, rows), :].astype(f32)
        y, _ = _conv_sum(jnp.concatenate([behind, x], 0), w, rows)
        o_ref[0, pl.ds(at, rows), :] = (y * _sigmoid(y, x_ref.dtype == f32) if silu else y).astype(o_ref.dtype)
        return x[-8:]

    jax.lax.fori_loop(0, tT // rows, step, behind)


def _conv_bwd_kernel(g_ref, ga_ref, x_ref, xb_ref, xa_ref, w_ref, dx_ref, dw_ref, *, rows, silu, T):
    f32 = jnp.float32
    tT = x_ref.shape[1]
    t, nt = pl.program_id(2), pl.num_programs(2)
    w = w_ref[...]
    K = w.shape[0]
    ragged = T % tT != 0
    h = xb_ref.shape[1]

    def gy_of(g, y):
        if not silu:
            return g
        s = _sigmoid(y, x_ref.dtype == f32)
        return g * (s * (1.0 + y * (1.0 - s)))

    def load(ref, at, n):
        v = ref[0, pl.ds(at, n), :].astype(f32)
        if ragged:   # what a ragged last tile holds past the sequence is not zeros
            row = t * tT + at + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < T, v, 0.0)
        return v

    def eight_before(at):
        """x's eight rows before row ``at`` of the tile: read a whole sublane tile of the dtype."""
        return load(x_ref, pl.multiple_of(at - h, h), h)[-8:]

    @pl.when(t == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # gy of the eight rows after the tile: from x's last rows here and the rows ahead
    last = t == nt - 1
    x_ahead = jnp.where(last, 0.0, xa_ref[0].astype(f32)[:8])
    y_ahead, _ = _conv_sum(jnp.concatenate([eight_before(tT), x_ahead], 0), w, 8)
    gy_ahead = gy_of(jnp.where(last, 0.0, ga_ref[0].astype(f32)[:8]), y_ahead)
    x_behind = jnp.where(t == 0, 0.0, xb_ref[0].astype(f32)[-8:])
    n = tT // rows

    def step(i, gy_ahead):
        r = n - 1 - i
        at = pl.multiple_of(r * rows, rows)
        behind = jnp.where(r == 0, x_behind, eight_before(jnp.maximum(at, h)))
        y, taps = _conv_sum(jnp.concatenate([behind, load(x_ref, at, rows)], 0), w, rows)
        gy = gy_of(load(g_ref, at, rows), y)
        gext = jnp.concatenate([gy, gy_ahead], 0)
        dx = gy * w[0:1]
        for s in range(1, K):
            dx = dx + _rows_back(gext, -s, 0, rows) * w[s:s + 1]
        dx_ref[0, pl.ds(at, rows), :] = dx.astype(dx_ref.dtype)
        for s in range(K):
            dw_ref[0, 8 * s:8 * s + 8, :] += jnp.sum((gy * taps[s]).reshape(rows // 8, 8, -1), axis=0)
        return gy[:8]

    jax.lax.fori_loop(0, n, step, gy_ahead)


def _conv_params(last: str):
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", last))}


def _conv_specs(T: int, tT: int, tC: int, h: int):
    """BlockSpecs of a tile, of the sublane tile behind it and of the one
    ahead (each clamped into the array: the kernels put zeros there)."""
    tile = pl.BlockSpec((1, tT, tC), lambda b, c, t: (b, t, c))
    behind = pl.BlockSpec((1, h, tC), lambda b, c, t: (b, jnp.maximum(t * (tT // h) - 1, 0), c))
    ahead = pl.BlockSpec((1, h, tC), lambda b, c, t: (b, jnp.minimum((t + 1) * (tT // h), T // h - 1), c))
    return tile, behind, ahead


def _conv_taps_first(w):
    """``w (C, K)`` as the kernels take it: float32 ``(K, C)``, row s the tap s tokens back."""
    return jnp.flip(w.astype(jnp.float32), 1).T


@functools.partial(jax.jit, static_argnames=("activation", "tiles"))
def _conv_fwd(x, w, activation, tiles):
    B, T, C = x.shape
    K = w.shape[1]
    tT, tC, rows = tiles
    tile, behind, _ = _conv_specs(T, tT, tC, _sublane_rows(x.dtype))
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, rows=rows, silu=activation == "silu"),
        name="causal_conv1d_fwd",
        grid=(B, C // tC, pl.cdiv(T, tT)),
        in_specs=[tile, behind, pl.BlockSpec((K, tC), lambda b, c, t: (0, c))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret(),
        **_conv_params("parallel"),
    )(x, x, _conv_taps_first(w))


@functools.partial(jax.jit, static_argnames=("activation", "tiles"))
def _conv_bwd(g, x, w, activation, tiles):
    B, T, C = x.shape
    K = w.shape[1]
    tT, tC, rows = tiles
    tile, behind, ahead = _conv_specs(T, tT, tC, _sublane_rows(x.dtype))
    dx, dw = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, rows=rows, silu=activation == "silu", T=T),
        name="causal_conv1d_bwd",
        grid=(B, C // tC, pl.cdiv(T, tT)),
        in_specs=[tile, ahead, tile, behind, ahead, pl.BlockSpec((K, tC), lambda b, c, t: (0, c))],
        out_specs=[tile, pl.BlockSpec((1, 8 * K, tC), lambda b, c, t: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((B, 8 * K, C), jnp.float32)],
        interpret=_interpret(),
        **_conv_params("arbitrary"),
    )(g, g, x, x, x, _conv_taps_first(w))
    # eight partial rows a tap a sequence: summed here, and back to (C, K) in w's order of taps
    return dx, jnp.flip(dw.reshape(B, K, 8, C).sum(axis=(0, 2)), 0).T.astype(w.dtype)


#: the last conv built, at trace time (a dict of its own, as ``flash_schedule``)
conv_schedule: dict[str, int] = {}


def _conv_claim(x, w, activation, *others):
    """The tiles of a call the kernels take, counted; or None."""
    if not (_enabled() and _conv_supported(x.shape, w.shape, x.dtype, activation) and _gmm_dispatchable(x, *others)):
        return None
    stats["causal_conv"] = stats.get("causal_conv", 0) + 1
    B, T, C = x.shape
    tiles = _conv_tiles(T, C, x.dtype.itemsize)
    conv_schedule.update(tile_t=tiles[0], tile_c=tiles[1], halo_rows=_sublane_rows(x.dtype),
                         bytes_a_forward_call=2 * B * T * C * x.dtype.itemsize,
                         bytes_a_backward_call=3 * B * T * C * x.dtype.itemsize)
    return tiles


def causal_conv1d(x, w, activation=None):
    """``causal_conv1d_fwd``: x ``(B, T, C)``, w ``(C, K)`` -> ``act(conv(x))``
    in x's dtype; or None where the shapes do not qualify (the XLA form runs
    then)."""
    tiles = _conv_claim(x, w, activation)
    return None if tiles is None else _conv_fwd(x, w, activation=activation, tiles=tiles)


def causal_conv1d_backward(g, x, w, activation=None):
    """``causal_conv1d_bwd``: ``(dx, dw)`` from the output's cotangent and the
    operands; or None."""
    tiles = _conv_claim(x, w, activation, g)
    return None if tiles is None else _conv_bwd(g.astype(x.dtype), x, w, activation=activation, tiles=tiles)


# ---------------------------------------------------------------------------
# The selective scan of a state-space layer (Mamba-1), for the server.  A
# sequence's state is ``S (N, d)`` in float32: ``N`` states (16) on sublanes,
# the ``d`` channels on lanes, so a token's decay ``exp(dt_t A)`` (``A (N, d)``),
# its input ``(dt_t u_t) B_t^T`` and its read-out ``sum_n S[n] C_t[n]`` are whole
# vector operations and ``B_t``, ``C_t`` broadcast along lanes as columns.
#   S_t = exp(dt_t * A) * S_t-1 + (dt_t * u_t) * B_t        y_t = sum_n S_t * C_t
# A padded token carries ``dt = 0``: decay one, input zero, the state as it was.
#
# ``ssm_scan_fwd`` (a whole prompt): a grid step is a tile of channels and a
# block of tokens; the state stays in VMEM scratch across a tile's token blocks
# (the innermost grid axis, walked in order) and leaves as the scan's third
# result after the last.  Tokens go eight a loop turn: one aligned load of
# their rows of u and dt, eight steps in straight-line code, one aligned store
# of their rows of y.  A token's ``B_t`` and ``C_t`` are rows of a ``(8, N)`` block
# and become columns by a masked lane sum (``_column``).  The work is the vector
# unit's: about ``7 N d`` float32 operations and ``N d`` exponentials a token
# against ``12 d`` bytes, so the kernel is bound by the vector unit, not by HBM
# (``chipbench/kernels/ssm_scan.py`` counts both).
#
# ``ssm_decode_step`` (one token a row): the row's slot of the state arena in
# and out through one aliased block, as ``gdn_decode_step`` does.
# ---------------------------------------------------------------------------

_SSM_TOKENS = 8            # tokens a loop turn: a float32 sublane tile
_SSM_BLOCK_TOKENS = 256    # tokens a grid step, the most
_SSM_TILE_LANES = 1024     # channels a grid step, the most


def ssm_step_math(S, dt, u, b, c, A):
    """One token in float32: ``S (N, d)``, ``dt`` and ``u`` rows ``(1, d)``, ``b``
    and ``c`` columns ``(N, 1)``, ``A (N, d)`` -> ``(y (1, d), S)``.  The scan
    kernel's step, the decode kernel's body and the XLA forms are this one
    function."""
    S = jnp.exp(dt * A) * S + (dt * u) * b
    return jnp.sum(S * c, axis=0, keepdims=True), S


def _ssm_scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, last_ref, s_ref, *, TB):
    f32 = jnp.float32
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _start():
        s_ref[...] = h0_ref[0].astype(f32)

    A = a_ref[...]
    N = A.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)).astype(f32)

    def turn(i, S):
        at = pl.multiple_of(i * _SSM_TOKENS, _SSM_TOKENS)
        u, dt = u_ref[0, pl.ds(at, _SSM_TOKENS), :], dt_ref[0, pl.ds(at, _SSM_TOKENS), :]
        b, c = b_ref[0, pl.ds(at, _SSM_TOKENS), :], c_ref[0, pl.ds(at, _SSM_TOKENS), :]
        ys = []
        for j in range(_SSM_TOKENS):
            y, S = ssm_step_math(S, dt[j:j + 1], u[j:j + 1], _column(b[j:j + 1], eye), _column(c[j:j + 1], eye), A)
            ys.append(y)
        y_ref[0, pl.ds(at, _SSM_TOKENS), :] = jnp.concatenate(ys, axis=0)
        return S

    s_ref[...] = jax.lax.fori_loop(0, TB // _SSM_TOKENS, turn, s_ref[...])

    @pl.when(t == pl.num_programs(2) - 1)
    def _end():
        last_ref[0] = s_ref[...].astype(last_ref.dtype)


def _ssm_tiles(T: int, d: int) -> tuple[int, int] | None:
    """``(tokens a grid step, channels a grid step)`` of :func:`_ssm_scan_fwd`, or
    None where the shapes do not tile: whole sublane tiles of tokens, whole lane
    tiles of channels."""
    if T % _SSM_TOKENS or d % 128:
        return None
    TB = next(b for b in (256, 128, 64, 32, 16, 8) if b <= _SSM_BLOCK_TOKENS and T % b == 0)
    tC = next(c for c in (1024, 512, 256, 128) if c <= _SSM_TILE_LANES and d % c == 0)
    return TB, tC


@functools.partial(jax.jit, static_argnames=("TB", "tC"))
def _ssm_scan_fwd(u, dt, Bm, Cm, A, h0, TB: int, tC: int):
    """u, dt ``(B, T, d)``, Bm, Cm ``(B, T, N)``, A ``(N, d)``, all float32, h0
    ``(B, N, d)`` -> y ``(B, T, d)`` float32 and the state after the last token
    in ``h0``'s dtype."""
    B, T, d = u.shape
    N = A.shape[0]
    tile = pl.BlockSpec((1, TB, tC), lambda b, c, t: (b, t, c))
    cols = pl.BlockSpec((1, TB, N), lambda b, c, t: (b, t, 0))
    state = pl.BlockSpec((1, N, tC), lambda b, c, t: (b, 0, c))
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_ssm_scan_kernel, TB=TB),
        name="ssm_scan_fwd",
        grid=(B, d // tC, T // TB),
        in_specs=[tile, tile, cols, cols, pl.BlockSpec((N, tC), lambda b, c, t: (0, c)), state],
        out_specs=[tile, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, d), jnp.float32), jax.ShapeDtypeStruct((B, N, d), h0.dtype)],
        scratch_shapes=[pltpu.VMEM((N, tC), jnp.float32)],
        interpret=_interpret(),
        **kwargs,
    )(u, dt, Bm, Cm, A, h0)


def ssm_scan_xla(u, dt, Bm, Cm, A, h0):
    """The scan's XLA form, a token a step of ``lax.scan``: the same operands,
    the same results (what the CPU runs, and what the kernel is tested against)."""
    f32 = jnp.float32

    def step(S, xs):
        u_t, dt_t, b_t, c_t = xs
        y, S = jax.vmap(ssm_step_math, in_axes=(0, 0, 0, 0, 0, None))(
            S, dt_t[:, None], u_t[:, None], b_t[:, :, None], c_t[:, :, None], A)
        return S, y[:, 0]

    tm = lambda a: jnp.swapaxes(a.astype(f32), 0, 1)  # noqa: E731 -- token-major
    S, ys = jax.lax.scan(step, h0.astype(f32), (tm(u), tm(dt), tm(Bm), tm(Cm)))
    return jnp.swapaxes(ys, 0, 1), S.astype(h0.dtype)


#: the last scan built, at trace time (a dict of its own, as ``flash_schedule``)
ssm_schedule: dict[str, int] = {}


def ssm_scan(u, dt, Bm, Cm, A, h0):
    """A prompt's selective scan from the state ``h0 (B, N, d)``: u, dt ``(B, T,
    d)``, Bm, Cm ``(B, T, N)``, ``A (N, d)`` (negative) -> ``(y (B, T, d) float32,
    the state after the last token)``.  ``ssm_scan_fwd`` where Pallas runs and
    the shapes tile, else the XLA form."""
    tiles = _ssm_tiles(u.shape[1], u.shape[2]) if _enabled() and _gmm_dispatchable(u, dt, h0) else None
    if tiles is None:
        return ssm_scan_xla(u, dt, Bm, Cm, A, h0)
    stats["ssm_scan"] = stats.get("ssm_scan", 0) + 1
    ssm_schedule.update(block_tokens=tiles[0], tile_channels=tiles[1], states=A.shape[0])
    f32 = jnp.float32
    return _ssm_scan_fwd(u.astype(f32), dt.astype(f32), Bm.astype(f32), Cm.astype(f32), A.astype(f32), h0,
                         TB=tiles[0], tC=tiles[1])


def _ssm_decode_kernel(slot_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, s_ref, y_ref, so_ref):
    del slot_ref   # the row's slot lives in the BlockSpec index maps
    y, S = ssm_step_math(s_ref[0, 0].astype(jnp.float32), dt_ref[0], u_ref[0], b_ref[0], c_ref[0], a_ref[...])
    so_ref[0, 0] = S.astype(so_ref.dtype)
    y_ref[0] = y


def ssm_decode_step_xla(arena, slots, u, dt, Bm, Cm, A, *, layer: int):
    """:func:`ssm_decode_step`'s XLA form: the rows' states gathered, stepped, scattered."""
    f32 = jnp.float32
    y, S = jax.vmap(ssm_step_math, in_axes=(0, 0, 0, 0, 0, None))(
        arena[slots, layer].astype(f32), dt.astype(f32)[:, None], u.astype(f32)[:, None],
        Bm.astype(f32)[:, :, None], Cm.astype(f32)[:, :, None], A.astype(f32))
    return y[:, 0], arena.at[slots, layer].set(S.astype(arena.dtype))


def ssm_decode_step(arena, slots, u, dt, Bm, Cm, A, *, layer: int):
    """One token a row through the selective scan, the state read and written
    once, in place.  ``arena (slots + 1, L_ssm, N, d)`` (float32, or what the
    pool was told to store); ``slots (rows,)`` int32, 0 the sink; u, dt ``(rows,
    d)``, Bm, Cm ``(rows, N)``, ``A (N, d)``.  Returns ``(y (rows, d) float32,
    arena)``.  The kernel where Pallas runs and the channels are whole lane
    tiles, else the XLA form."""
    rows, d = u.shape
    N = A.shape[0]
    if not (_enabled() and d % 128 == 0):
        return ssm_decode_step_xla(arena, slots, u, dt, Bm, Cm, A, layer=layer)
    stats["ssm_decode"] = stats.get("ssm_decode", 0) + 1
    f32 = jnp.float32
    row = lambda i, s: (i, 0, 0)  # noqa: E731
    mine = lambda i, s: (s[i], layer, 0, 0)  # noqa: E731
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    y, arena = pl.pallas_call(
        _ssm_decode_kernel,
        name="ssm_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, 1, d), row), pl.BlockSpec((1, 1, d), row),
                      pl.BlockSpec((1, N, 1), row), pl.BlockSpec((1, N, 1), row),
                      pl.BlockSpec((N, d), lambda i, s: (0, 0)), pl.BlockSpec((1, 1, N, d), mine)],
            out_specs=[pl.BlockSpec((1, 1, d), row), pl.BlockSpec((1, 1, N, d), mine)]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, d), f32), jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        input_output_aliases={6: 1},     # operands: the slot table, five small ones, the arena
        interpret=_interpret(),
        **kwargs,
    )(slots.astype(jnp.int32), u.astype(f32)[:, None], dt.astype(f32)[:, None],
      Bm.astype(f32)[:, :, None], Cm.astype(f32)[:, :, None], A.astype(f32), arena)
    return y[:, 0], arena


# ---------------------------------------------------------------------------
# The state-space duality scan of a Mamba-2 layer, for the server.  A
# sequence's state is one matrix a head, ``S[h] (P, N)`` (``P`` channels of the
# head, ``N`` states), with one scalar decay a head and ``B``, ``C`` shared by a
# group of heads.  It is held ``(N, d)``, ``d = H P``: head ``h``'s matrix
# transposed in columns ``[h P, (h + 1) P)``, so the channels lie on lanes (a
# head of 64 fills a lane tile with its neighbour), a token's ``B_t`` and ``C_t``
# of a group are columns ``(N, 1)`` that broadcast along them, and the decay
# and the input are rows ``(1, d)``:
#   S_t = a_t * S_t-1 + (dt_t x_t) * B_t        y_t = sum_n S_t * C_t
# with ``a_t = exp(dt_t A)`` a channel (a head's value repeated).  A padded
# token carries ``dt = 0``: decay one, input zero, the state as it was.
#
# ``ssd_chunk_fwd`` (a whole prompt): grid (row, group, block of tokens); the
# block axis is sequential and a group's ``(N, d / G)`` float32 state stays in
# VMEM scratch across it, starting from ``h0`` and leaving as the second result.
# A block is chunks of ``SSD_CHUNK`` tokens; a chunk is matrix products (the
# structure of ``gdn_chunk_fwd`` without the delta rule's triangular inverse):
#   G = C B^T                                         one a group, (Q, Q)
#   W_h = G * exp(cum_t - cum_s) [s <= t] * dt_s     a head's decayed scores
#   y = W_h x_h + exp(cum_t) (C S_prev)_h            within the chunk, and what came before it
#   S <- exp(cum_Q) S_prev + B^T (x * dt * exp(cum_Q - cum))
# ``cum`` is the head's log-decay ``dt A`` summed from the chunk's first token
# (made outside, a row and a column layout of it: a ``(Q, 1)`` column in HBM is
# a 128-lane tile a row).  Heads of fewer than 128 channels share a lane tile:
# a tile's heads are told apart by a lane mask, so every product is whole
# tiles and no half tile is sliced out.  The scores and the read-out go
# through the matrix unit in one bfloat16 pass (their sum is rounded to
# bfloat16 before ``W_out`` anyway); the state's update in two (the scaled
# input's high and low halves: the state is what a request keeps, in float32).
#
# ``ssd_decode_step`` (one token a row): the row's slot of the state arena,
# ``(N, d)`` = 4.19 MB at 128 heads of 64 and 128 states, in and out through one
# aliased block as ``gdn_decode_step`` does; a lane tile of channels at a time
# stays in registers through decay, input and read-out.  Memory bound: a read
# and a write of the state a row.
# ---------------------------------------------------------------------------

SSD_CHUNK = 128            # tokens of a chunk: the published ``chunk_size``; the scan's block, no part of the maths
_SSD_BLOCK_TOKENS = 512    # tokens a grid step, the most


def ssd_step_math(S, a, xdt, b, c):
    """One token of one group in float32: ``S (N, dg)``, the decay ``a`` and the
    input ``xdt = dt x`` rows ``(1, dg)``, ``b`` and ``c`` columns ``(N, 1)`` -> ``(y
    (1, dg), S)``.  The decode kernel's body, the dense cache's step and the XLA
    forms are this one function."""
    S = a * S + xdt * b
    return jnp.sum(S * c, axis=0, keepdims=True), S


def _ssd_channels(v, P: int):
    """A head's value on each of its ``P`` channels: ``(..., H)`` to ``(..., H P)``."""
    return jnp.repeat(v, P, axis=-1)


def ssd_scan_xla(x, dt, Bm, Cm, A, h0):
    """The scan's XLA form, a token a step of ``lax.scan``: x ``(B, T, d)``, dt
    ``(B, T, H)``, Bm, Cm ``(B, T, G, N)``, ``A (H,)``, h0 ``(B, N, d)`` -> ``(y (B, T,
    d) float32, the state after the last token)`` (what the CPU runs, and what
    the kernel is tested against)."""
    f32 = jnp.float32
    B, T, d = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    P = d // dt.shape[-1]
    dtc = _ssd_channels(dt.astype(f32), P)                              # (B, T, d)
    a = jnp.exp(dtc * _ssd_channels(A.astype(f32), P))
    xdt = dtc * x.astype(f32)
    step = jax.vmap(jax.vmap(ssd_step_math))                            # over rows and groups

    def one(S, xs):
        a_t, xdt_t, b_t, c_t = xs
        grp = lambda v: v.reshape(B, G, 1, d // G)  # noqa: E731
        y, S = step(S, grp(a_t), grp(xdt_t), b_t[..., None], c_t[..., None])
        return S, y.reshape(B, d)

    tm = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731 -- token-major
    S0 = h0.astype(f32).reshape(B, N, G, d // G).transpose(0, 2, 1, 3)  # (B, G, N, dg)
    S, ys = jax.lax.scan(one, S0, (tm(a), tm(xdt), tm(Bm.astype(f32)), tm(Cm.astype(f32))))
    return jnp.swapaxes(ys, 0, 1), S.transpose(0, 2, 1, 3).reshape(B, N, d).astype(h0.dtype)


def _ssd_chunk_kernel(x_ref, b_ref, c_ref, dtc_ref, cumc_ref, dtr_ref, cumr_ref, h0_ref, y_ref, last_ref, s_ref,
                      *, Q, NC, P):
    f32, bf = jnp.float32, jnp.bfloat16
    exact = x_ref.dtype == f32          # float32 operands (the tests, a witness): every product at full precision

    def dot(a, b, dims):
        if exact:
            return jax.lax.dot_general(a.astype(f32), b.astype(f32), dims, precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=f32)
        return jax.lax.dot_general(a.astype(bf), b.astype(bf), dims, preferred_element_type=f32)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = h0_ref[0].astype(f32)

    N, dg = s_ref.shape
    per_tile = max(1, 128 // P)                 # heads that share a lane tile
    width = max(P, 128)                         # lanes of a step: a tile of heads, or one wide head
    low = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // P
    for c in range(NC):
        rows = slice(c * Q, (c + 1) * Q)
        Bc, Cc = b_ref[0, 0, rows, :], c_ref[0, 0, rows, :]                           # (Q, N)
        scores = dot(Cc, Bc, _AB_T)                                                   # (Q, Q): C_t . B_s
        dtc, cumc = dtc_ref[0, 0, rows, :], cumc_ref[0, 0, rows, :]                   # (Q, Hg) columns a head
        dtr, cumr = dtr_ref[0, 0, :, rows], cumr_ref[0, 0, :, rows]                   # (Hg, Q) rows a head
        for t in range(dg // width):
            lanes = slice(t * width, (t + 1) * width)
            xt = x_ref[0, rows, lanes]                                                # (Q, width)
            S = s_ref[:, lanes]                                                       # (N, width)
            before = dot(Cc, S, _AB)                                                  # (Q, width): C_t S_prev
            y = jnp.zeros((Q, width), f32)
            e_in = jnp.zeros((Q, width), f32)       # exp(cum_t): what the state before the chunk has decayed by
            scale = jnp.zeros((Q, width), f32)      # dt_t exp(cum_Q - cum_t): a token's share of the state after it
            e_all = jnp.zeros((1, width), f32)      # exp(cum_Q)
            for j in range(per_tile):
                h = t * per_tile + j
                mine = lane_head == j                                                 # (1, width)
                col, row = cumc[:, h:h + 1], cumr[h:h + 1, :]                         # (Q, 1), (1, Q)
                W = scores * jnp.exp(jnp.where(low, col - row, _MASK_VALUE)) * dtr[h:h + 1, :]
                y = y + dot(W, jnp.where(mine, xt, jnp.zeros_like(xt)) if per_tile > 1 else xt, _AB)
                last = col[Q - 1:Q, :]                                                # (1, 1)
                e_in = jnp.where(mine, jnp.exp(col), e_in)
                scale = jnp.where(mine, dtc[:, h:h + 1] * jnp.exp(last - col), scale)
                e_all = jnp.where(mine, jnp.exp(jnp.broadcast_to(last, (1, width))), e_all)
            y_ref[0, rows, lanes] = (y + e_in * before).astype(y_ref.dtype)
            xs = xt.astype(f32) * scale
            if exact:
                S = e_all * S + dot(Bc, xs, _AT_B)
            else:       # two passes: the scaled input's high and low halves (B itself is bfloat16)
                hi = xs.astype(bf)
                S = e_all * S + dot(Bc, hi, _AT_B) + dot(Bc, xs - hi.astype(f32), _AT_B)
            s_ref[:, lanes] = S

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _end():
        last_ref[0] = s_ref[...].astype(last_ref.dtype)


def _ssd_tiles(T: int, H: int, P: int, G: int) -> int | None:
    """Tokens a grid step of :func:`_ssd_chunk_fwd`, or None where the shapes
    do not tile: whole chunks of tokens, a group's channels in whole lane
    tiles, a lane tile's heads in one group."""
    dg = H * P // G
    if T % SSD_CHUNK or H % G or dg % 128 or (P < 128 and 128 % P) or (P > 128 and P % 128):
        return None
    return next(b for b in (512, 256, 128) if b <= _SSD_BLOCK_TOKENS and T % b == 0)


@functools.partial(jax.jit, static_argnames=("TB", "P"))
def _ssd_chunk_fwd(x, Bm, Cm, dt, cum, h0, TB: int, P: int):
    """x ``(B, T, d)``, Bm, Cm ``(B, G, T, N)`` in x's dtype, dt and cum (the
    log-decay summed within each chunk) ``(B, G, T, Hg)`` float32, h0 ``(B, N, d)``
    -> y ``(B, T, d)`` float32 and the state after the last token in ``h0``'s
    dtype."""
    B, T, d = x.shape
    G, N, Hg = Bm.shape[1], Bm.shape[3], dt.shape[3]
    dg = d // G
    tile = pl.BlockSpec((1, TB, dg), lambda b, g, t: (b, t, g))
    cols = pl.BlockSpec((1, 1, TB, N), lambda b, g, t: (b, g, t, 0))
    heads = pl.BlockSpec((1, 1, TB, Hg), lambda b, g, t: (b, g, t, 0))
    heads_t = pl.BlockSpec((1, 1, Hg, TB), lambda b, g, t: (b, g, 0, t))
    state = pl.BlockSpec((1, N, dg), lambda b, g, t: (b, 0, g))
    kwargs = {}
    if not _interpret():
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 << 20)
    return pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, Q=SSD_CHUNK, NC=TB // SSD_CHUNK, P=P),
        name="ssd_chunk_fwd",
        grid=(B, G, T // TB),
        in_specs=[tile, cols, cols, heads, heads, heads_t, heads_t, state],
        out_specs=[tile, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, d), jnp.float32), jax.ShapeDtypeStruct((B, N, d), h0.dtype)],
        scratch_shapes=[pltpu.VMEM((N, dg), jnp.float32)],
        interpret=_interpret(),
        **kwargs,
    )(x, Bm, Cm, dt, cum, jnp.swapaxes(dt, 2, 3), jnp.swapaxes(cum, 2, 3), h0)


#: the last scan built, at trace time (a dict of its own, as ``flash_schedule``)
ssd_schedule: dict[str, int] = {}


def _ssd_claim(which: str) -> None:
    stats["ssd"] = stats.get("ssd", 0) + 1
    stats[which] = stats.get(which, 0) + 1


def ssd_chunk(x, dt, Bm, Cm, A, h0):
    """A prompt's Mamba-2 scan from the state ``h0 (B, N, d)``: x ``(B, T, d)``
    (``d = H P``), dt ``(B, T, H)`` float32 (0 on a padded token), Bm, Cm ``(B, T,
    G, N)``, ``A (H,)`` (negative) -> ``(y (B, T, d) float32, the state after the
    last token)``.  ``ssd_chunk_fwd`` where Pallas runs and the shapes tile, else
    the XLA form."""
    B, T, d = x.shape
    H, G = dt.shape[-1], Bm.shape[2]
    P = d // H
    TB = _ssd_tiles(T, H, P, G) if _enabled() and _gmm_dispatchable(x, dt, h0) else None
    if TB is None:
        return ssd_scan_xla(x, dt, Bm, Cm, A, h0)
    _ssd_claim("ssd_chunk")
    ssd_schedule.update(block_tokens=TB, chunk=SSD_CHUNK, heads=H, head_dim=P, groups=G, states=Bm.shape[3])
    f32 = jnp.float32
    dt = dt.astype(f32)
    # the log-decay summed from each chunk's first token, a group's heads together
    cum = jnp.cumsum((dt * A.astype(f32)).reshape(B, T // SSD_CHUNK, SSD_CHUNK, H), axis=2).reshape(B, T, H)
    by_group = lambda v: v.reshape(B, T, G, H // G).transpose(0, 2, 1, 3)  # noqa: E731
    cdt = x.dtype if str(x.dtype) == "bfloat16" else f32
    return _ssd_chunk_fwd(x.astype(cdt), jnp.swapaxes(Bm, 1, 2).astype(cdt), jnp.swapaxes(Cm, 1, 2).astype(cdt),
                          by_group(dt), by_group(cum), h0, TB=TB, P=P)


def _ssd_decode_kernel(slot_ref, a_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *, G):
    del slot_ref   # the row's slot lives in the BlockSpec index maps
    f32 = jnp.float32
    N, d = s_ref.shape[2], s_ref.shape[3]
    dg = d // G
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)).astype(f32)
    for g in range(G):
        # a group's B_t and C_t: rows in HBM (a column there is a lane tile a number), columns here
        b = jnp.broadcast_to(_column(b_ref[0, g:g + 1, :], eye), (N, 128))
        c = jnp.broadcast_to(_column(c_ref[0, g:g + 1, :], eye), (N, 128))
        for t in range(dg // 128):      # a lane tile at a time: sixteen vregs through decay, input and read-out
            lanes = slice(g * dg + t * 128, g * dg + (t + 1) * 128)
            y, S = ssd_step_math(s_ref[0, 0, :, lanes].astype(f32), a_ref[0, :, lanes], xdt_ref[0, :, lanes], b, c)
            so_ref[0, 0, :, lanes] = S.astype(so_ref.dtype)
            y_ref[0, :, lanes] = y


def _ssd_step_operands(x, dt, A):
    """The step's rows a channel, float32: the decay ``exp(dt A)`` and the input ``dt x``, ``(rows, d)`` each."""
    f32 = jnp.float32
    P = x.shape[-1] // dt.shape[-1]
    dtc = _ssd_channels(dt.astype(f32), P)
    return jnp.exp(dtc * _ssd_channels(A.astype(f32), P)), dtc * x.astype(f32)


def ssd_decode_step_xla(arena, slots, x, dt, Bm, Cm, A, *, layer: int):
    """:func:`ssd_decode_step`'s XLA form: the rows' states gathered, stepped, scattered."""
    f32 = jnp.float32
    rows, d = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    a, xdt = _ssd_step_operands(x, dt, A)
    grp = lambda v: v.reshape(rows, G, 1, d // G)  # noqa: E731
    S = arena[slots, layer].astype(f32).reshape(rows, N, G, d // G).transpose(0, 2, 1, 3)
    y, S = jax.vmap(jax.vmap(ssd_step_math))(S, grp(a), grp(xdt), Bm.astype(f32)[..., None], Cm.astype(f32)[..., None])
    S = S.transpose(0, 2, 1, 3).reshape(rows, N, d)
    return y.reshape(rows, d), arena.at[slots, layer].set(S.astype(arena.dtype))


def ssd_decode_step(arena, slots, x, dt, Bm, Cm, A, *, layer: int):
    """One token a row through the Mamba-2 scan, the state read and written
    once, in place.  ``arena (slots + 1, L_m, N, d)`` (float32, or what the pool
    was told to store); ``slots (rows,)`` int32, 0 the sink; x ``(rows, d)``, dt
    ``(rows, H)``, Bm, Cm ``(rows, G, N)``, ``A (H,)``.  Returns ``(y (rows, d)
    float32, arena)``.  The kernel where Pallas runs and a group's channels are
    whole lane tiles, else the XLA form."""
    rows, d = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    if not (_enabled() and (d // G) % 128 == 0 and d % G == 0):
        return ssd_decode_step_xla(arena, slots, x, dt, Bm, Cm, A, layer=layer)
    _ssd_claim("ssd_decode")
    f32 = jnp.float32
    a, xdt = _ssd_step_operands(x, dt, A)
    row = lambda i, s: (i, 0, 0)  # noqa: E731
    mine = lambda i, s: (s[i], layer, 0, 0)  # noqa: E731
    kwargs = {}
    if not _interpret():
        # a row's state in and out, double-buffered: four blocks of (N, d) float32
        tile = 4 * (-(-N // 8) * 8) * d * 4
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=max(32 << 20, tile + (16 << 20)))
    y, arena = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, G=G),
        name="ssd_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, 1, d), row), pl.BlockSpec((1, 1, d), row),
                      pl.BlockSpec((1, G, N), row), pl.BlockSpec((1, G, N), row),
                      pl.BlockSpec((1, 1, N, d), mine)],
            out_specs=[pl.BlockSpec((1, 1, d), row), pl.BlockSpec((1, 1, N, d), mine)]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, d), f32), jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        input_output_aliases={5: 1},     # operands: the slot table, four small ones, the arena
        interpret=_interpret(),
        **kwargs,
    )(slots.astype(jnp.int32), a[:, None], xdt[:, None], Bm.astype(f32), Cm.astype(f32), arena)
    return y[:, 0], arena


# ---------------------------------------------------------------------------
# A hyper-connection's boundary between two sublayers, one pass over the stream:
# ``hc_mix`` closes the sublayer that ended and opens the one that begins.
#
#   close   X' = H_res X + H_post^T f                      float32, rounded to the stream's dtype, written once
#   open    r = rsqrt(mean_nC(X'^2) + eps)                 on X' as rounded: what ``hc_open`` reads back from memory
#           z = scale * (phi X') r + bias                   (n (n + 2), TQ): the tokens on the lanes
#           H_pre = sigmoid(z), H_post = 2 sigmoid(z), H_res = Sinkhorn(exp(clip(z)))
#           u = H_pre X'                                    float32 as summed: the sublayer's norm rounds it, once
#
# (``models.generate.hc_maps`` / ``hc_open`` / ``hc_close`` are the same lines in
# ``jax.numpy``: the fallback, and what the tests hold this to.)  Grid (B, T /
# TQ); a grid step holds all ``n`` streams of ``TQ`` tokens, ``(n, TQ, C)``, and
# ``phi`` stays where the first step put it.  A map is a few numbers a token and
# lives a row a map with the tokens on the lanes, in HBM ``(B, n + n n, T)``
# float32: ``[H_post | H_res]``, what the next boundary's close reads.  The
# mixing wants a token's number down a column, so the rows are turned in the
# kernel (a transpose of ``(128, TQ)`` float32: data moves, nothing is summed, so
# a ragged tile's rows past the end stay their own).  Two loops walk the tile
# ``rows`` tokens at a time, a lane tile at a time, so the float32 terms of a
# step stay near the registers: the close (with the squares of what it rounds),
# and, once the maps are known, ``u`` from the block the close wrote.  Between
# them the products run on the matrix unit: a bfloat16 stream is exact as it
# stands, so ``phi`` comes as three bfloat16 pieces (``generate._stream_product``)
# and each is one pass with float32 sums; a float32 stream takes the unit's own
# multi-pass float32.  Either half may be absent (the model's first open has
# nothing to close, its last close opens nothing): a static flag, one kernel.
# A token's numbers depend on its own row alone.
# ---------------------------------------------------------------------------

# On one v5e at the Xing4.0 cell's widths (four streams of 3,584, an 8,192-token prompt's twelve sublayers: 11.2 ms,
# 0.58 of the count's roofline, which leaves ``f`` and ``u`` out; PERF.md, PR 56) the copies bind: with the close's
# loop, the products, the iterations and the read taken out one at a time or all together the prompt takes as long
# (10.1-10.4 ms of 10.4 with ``u`` at 16 bits: 7.3 GB at 717 GB/s; 7.75 GB at 692 as it is).  So the tile is the
# shortest (128 and 256 tokens run alike, 512 a tenth slower and over half of VMEM), and the lane tiles a loop turn
# the fewest that keep the arithmetic under the copies (1: 12.5 ms; 2: 10.9; 4, 7, 14: 10.4; all 28 unrolled: 10.2): a
# turn's lines are traced and lowered once a form and a program, and all 28 cost a start 11 s at three buckets.
_HC_TILE = 128          # tokens a grid step
_HC_LANE_TILES = 4      # lane tiles a loop turn, the most


def _hc_vmem(TQ: int, n: int, C: int, itemsize: int) -> int:
    """Bytes a grid step of both halves holds: the stream's block in and out,
    what the sublayer gave and what the next one reads (float32), the maps and ``phi``
    (three pieces of a 16-bit stream), twice each (the pipeline's two buffers);
    the three turned tiles; the products' float32 sums; and what Mosaic keeps
    beside them."""
    m, nm, pieces = n * (n + 2), n * (n + 1), 3 if itemsize == 2 else 1
    blocks = (2 * n + 1) * TQ * C * itemsize + TQ * C * 4 + 2 * nm * TQ * 4 + pieces * m * n * C * itemsize
    return 2 * blocks + 3 * 128 * TQ * 4 + 4 * pieces * m * TQ * 4 + _GMM_VMEM_MARGIN


def _hc_tile(T: int, n: int, C: int, itemsize: int) -> int | None:
    """Tokens a grid step of ``hc_mix`` holds, or None where the shapes are not
    the kernel's: under a tile of tokens (a decode step's rows: XLA fuses a
    sublayer's fifty small operations into a handful), a ``C`` that is not
    whole lane tiles, no room in the VMEM the kernel may ask for."""
    if C % 128 or n < 2 or T < _HC_TILE or _hc_vmem(_HC_TILE, n, C, itemsize) > _gmm_vmem_cap():
        return None
    return _HC_TILE


def _hc_lanes(l, K: int):
    """The ``K`` lane tiles of loop turn ``l``."""
    return [pl.ds(pl.multiple_of(l * (128 * K) + k * 128, 128), 128) for k in range(K)]


def _hc_mix_kernel(*refs, n, close, opens, eps, iters, clamp):
    f32 = jnp.float32
    refs = list(refs)
    x_ref = refs.pop(0)
    f_ref, m_ref = (refs.pop(0), refs.pop(0)) if close else (None, None)
    w_ref, sb_ref = (refs.pop(0), refs.pop(0)) if opens else (None, None)
    xo_ref = refs.pop(0) if close else None
    u_ref, mo_ref = (refs.pop(0), refs.pop(0)) if opens else (None, None)
    rows_ref, cols_ref, ss_ref = refs
    dt = x_ref.dtype
    TQ, C = x_ref.shape[2], x_ref.shape[3]
    m, nm = n * (n + 2), n * (n + 1)
    R = _sublane_rows(dt)
    K = next(k for k in range(_HC_LANE_TILES, 0, -1) if (C // 128) % k == 0)
    down = lambda tile, k: jnp.broadcast_to(tile[:, k:k + 1], (R, 128))  # noqa: E731 -- map k of the rows' tokens

    if close:       # the closing maps, a token's down a column
        rows_ref[0:nm, :] = m_ref[0]
        cols_ref[...] = rows_ref[...].T

    def mix(r, carry):
        rows = pl.ds(pl.multiple_of(r * R, R), R)
        if close:
            tile = cols_ref[rows, :]
            post = [down(tile, i) for i in range(n)]
            res = [[down(tile, n + i * n + j) for j in range(n)] for i in range(n)]

        def lane_tiles(l, ss):
            for lanes in _hc_lanes(l, K):
                xs = [x_ref[0, j, rows, lanes].astype(f32) for j in range(n)]
                if close:
                    ff = f_ref[0, rows, lanes].astype(f32)
                    for i in range(n):
                        o = res[i][0] * xs[0]
                        for j in range(1, n):
                            o = o + res[i][j] * xs[j]
                        o = (o + post[i] * ff).astype(dt)
                        xo_ref[0, i, rows, lanes] = o
                        if opens:
                            o = o.astype(f32)
                            ss = ss + o * o
                elif opens:
                    for xj in xs:
                        ss = ss + xj * xj
            return ss

        ss = jax.lax.fori_loop(0, C // (128 * K), lane_tiles, jnp.zeros((R, 128), f32))
        if opens:
            ss_ref[rows, :] = jnp.broadcast_to(jnp.sum(ss, axis=1, keepdims=True), (R, 128))
        return carry

    jax.lax.fori_loop(0, TQ // R, mix, 0)
    if not opens:
        return
    src = xo_ref if close else x_ref
    exact = {"precision": jax.lax.Precision.HIGHEST} if dt == f32 else {}
    acc = None
    for j in range(n):      # (pieces m, TQ): both operands' last axes contract, no slab is turned
        p = jax.lax.dot_general(w_ref[j], src[0, j], _AB_T, preferred_element_type=f32, **exact)
        acc = p if acc is None else acc + p
    raw = acc[0:m]
    for k in range(1, w_ref.shape[1] // m):
        raw = raw + acc[k * m:(k + 1) * m]
    r = jax.lax.rsqrt(ss_ref[...].T[0:1, :] * (1.0 / (n * C)) + eps)                   # (1, TQ)
    z = (raw * r) * sb_ref[:, 0:1] + sb_ref[:, 1:2]
    gate = _sigmoid(z[0:2 * n], True)
    gate = gate * jnp.where(jax.lax.broadcasted_iota(jnp.int32, (2 * n, 1), 0) < n, 1.0, 2.0)     # [H_pre | H_post]
    to = jnp.exp(jnp.clip(z[2 * n:], clamp[0], clamp[1]))
    to = [to[i * n:(i + 1) * n] for i in range(n)]                                      # to stream i, from j down the sublanes

    def sinkhorn(_, to):
        cols = to[0]
        for t in to[1:]:
            cols = cols + t
        to = [t / (cols + eps) for t in to]
        return tuple(t / (jnp.sum(t, axis=0, keepdims=True) + eps) for t in to)

    to = jax.lax.fori_loop(0, iters, sinkhorn, tuple(to))
    mo_ref[0, 0:n, :] = gate[n:2 * n]
    for i in range(n):
        mo_ref[0, n + i * n:n + (i + 1) * n, :] = to[i]
    rows_ref[0:n, :] = gate[0:n]
    cols_ref[...] = rows_ref[...].T

    def read(r, carry):
        rows = pl.ds(pl.multiple_of(r * R, R), R)
        tile = cols_ref[rows, :]
        pre = [down(tile, j) for j in range(n)]

        def lane_tiles(l, carry):
            for lanes in _hc_lanes(l, K):
                u = pre[0] * src[0, 0, rows, lanes].astype(f32)
                for j in range(1, n):
                    u = u + pre[j] * src[0, j, rows, lanes].astype(f32)
                u_ref[0, rows, lanes] = u
            return carry

        return jax.lax.fori_loop(0, C // (128 * K), lane_tiles, carry)

    jax.lax.fori_loop(0, TQ // R, read, 0)


@functools.partial(jax.jit, static_argnames=("eps", "iters", "clamp", "TQ"))
def _hc_mix(x, owed, hp, *, eps, iters, clamp, TQ):
    B, n, T, C = x.shape
    f32, dt = jnp.float32, x.dtype
    m, nm = n * (n + 2), n * (n + 1)
    close, opens = owed is not None, hp is not None
    stream = pl.BlockSpec((1, n, TQ, C), lambda b, t: (b, 0, t, 0))
    slab = pl.BlockSpec((1, TQ, C), lambda b, t: (b, t, 0))
    maps = pl.BlockSpec((1, nm, TQ), lambda b, t: (b, 0, t))
    operands, in_specs, out_specs, out_shape = [x], [stream], [], []
    if close:
        f, (h_post, h_res) = owed
        held = jnp.concatenate([h_post, h_res.reshape(n * n, B, T)]).transpose(1, 0, 2)      # (B, n + n n, T)
        operands += [f, held]
        in_specs += [slab, maps]
        out_specs.append(stream)
        out_shape.append(jax.ShapeDtypeStruct(x.shape, dt))
    if opens:
        phi = (hp["phi"].astype(f32) * hp["norm"].astype(f32)).reshape(m, n, C)
        pieces = []
        for _ in range(1 if dt == f32 else 3):      # three bfloat16 pieces hold float32's 24 bits
            pieces.append(phi.astype(dt))
            phi = phi - pieces[-1].astype(f32)
        w = jnp.concatenate(pieces).transpose(1, 0, 2)                                      # (n, pieces m, C)
        scale = jnp.repeat(hp["alpha"].astype(f32), np.array([n, n, n * n]), total_repeat_length=m)
        operands += [w, jnp.stack([scale, hp["bias"].astype(f32)], axis=1)]
        in_specs += [pl.BlockSpec(w.shape, lambda b, t: (0, 0, 0)), pl.BlockSpec((m, 2), lambda b, t: (0, 0))]
        out_specs += [slab, maps]
        out_shape += [jax.ShapeDtypeStruct((B, T, C), f32), jax.ShapeDtypeStruct((B, nm, T), f32)]
    params = {}
    if not _interpret():
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_hc_vmem(TQ, n, C, dt.itemsize))
    out = pl.pallas_call(
        functools.partial(_hc_mix_kernel, n=n, close=close, opens=opens, eps=eps, iters=iters, clamp=clamp),
        name="hc_mix",
        grid=(B, pl.cdiv(T, TQ)),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((128, TQ), f32), pltpu.VMEM((TQ, 128), f32), pltpu.VMEM((TQ, 128), f32)],
        interpret=_interpret(),
        **params,
    )(*operands)
    out = list(out)
    xo = out.pop(0) if close else x
    if not opens:
        return xo, None, None
    u, held = out
    held = held.transpose(1, 0, 2)
    return xo, u, (held[:n], held[n:].reshape(n, n, B, T))


# what the last boundary built was laid out as, or why it kept to ``jax.numpy`` (trace time; a dict of its own, as ``flash_schedule``)
hc_schedule: dict = {}


def hc_mix(x, owed, hp, *, eps: float, iters: int, clamp: tuple, why: str = ""):
    """The stream ``x (B, n, T, C)`` through one boundary between two sublayers,
    read once: the close of the sublayer that ended (``owed``: ``(f (B, T, C),
    (H_post (n, B, T), H_res (n, n, B, T)))``, what it gave and the maps its open
    returned; None at the model's first open) and the open of the one that
    begins (``hp``: its ``phi``, ``norm``, ``alpha``, ``bias``; None at the last
    close).  Returns ``(x', u, (H_post, H_res))`` as ``generate.hc_close`` then
    ``hc_open`` give them (``u`` in float32; it and the maps None without an open half), or None
    where the caller's ``jax.numpy`` lines are to run: it has a reason of its
    own (``why``: a mesh, planted maps), Pallas is off, the operands live on
    several devices, or the shapes are not the kernel's (:func:`_hc_tile`).
    ``stats["hc_fused"]`` / ``["hc_fallback"]`` count the boundaries (trace
    time); ``hc_schedule`` keeps the last one's tile or reason."""
    B, n, T, C = x.shape
    itemsize = x.dtype.itemsize
    if not why:
        why = ("no Pallas" if not _enabled() else "several devices" if not _gmm_dispatchable(x)
               else "dtype" if str(x.dtype) not in ("bfloat16", "float32") else "")
    TQ = None if why else _hc_tile(T, n, C, itemsize)
    if TQ is None:
        stats["hc_fallback"] = stats.get("hc_fallback", 0) + 1
        hc_schedule.clear()
        hc_schedule.update(fallback=why or "shape", tokens=T, width=C)
        return None
    stats["hc_fused"] = stats.get("hc_fused", 0) + 1
    hc_schedule.clear()
    hc_schedule.update(block_tokens=TQ, grid_steps=B * -(-T // TQ),
                       vmem_limit_bytes=_hc_vmem(TQ, n, C, itemsize))
    return _hc_mix(x, owed, hp, eps=float(eps), iters=int(iters), clamp=tuple(float(c) for c in clamp), TQ=TQ)


# install the fast paths so XLA fusion regions and TrainStep trace evaluation
# reach the same kernels
from thunder_tpu.executors import jaxex as _jaxex

_jaxex._sdpa_fast_path = flash_sdpa
_jaxex._sdpa_bwd_fast_path = flash_sdpa_backward
_jaxex._ce_fast_path = flash_cross_entropy
_jaxex._gdn_fast_path = gdn_chunk
_jaxex._gdn_bwd_fast_path = gdn_chunk_backward
_jaxex._gdn_state_fast_path = gdn_chunk_state
_jaxex._grouped_mm_fast_path = grouped_mm
_jaxex._grouped_mm_dw_fast_path = grouped_mm_dw
_jaxex._tokens_of_rows_fast_path = combine
_jaxex._causal_conv_fast_path = causal_conv1d
_jaxex._causal_conv_bwd_fast_path = causal_conv1d_backward
