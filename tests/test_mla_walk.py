"""``mla_paged_decode``'s walk (PR 35) interpreted against ``_mla_decode_xla``:
what the loop can get wrong.  Two chunks a turn in two buffers, a slot's copies
awaited once, a row's first chunk started by the row before it: every edge of a
block and of a chunk, an even and an odd count of chunks, the longest row the
A.X-K1 cell's window times, neighbours in every order around an empty row, a
row's output bit for bit in any batch at any place under any table, and a few,
half a pass and a whole pass of heads.  (The kernel inside the engine and the
other latent cases are ``tests/test_mla_serving.py``: split from it for the
file's run time.)"""
from __future__ import annotations

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from thunder_tpu.executors import pallasex as px  # noqa: E402
from tools import mla_tune  # noqa: E402

# ``CHUNK`` keys a chunk here (two blocks), so a few hundred tokens are many chunks
CHUNK, WBS = 32, 16


def _walk_case(contexts, *, nh=4, dtype=jnp.float32, table=None, W=256, dc=128, layers=2):
    """The tool's operands for rows of the given contexts: each row's blocks
    scattered over the pool, the table sink-padded to ``table`` entries."""
    need = [-(-c // WBS) for c in contexts]
    args = mla_tune.operands(np.asarray(contexts, np.int32), nh=nh, W=W, dc=dc, bs=WBS, layers=layers,
                             table=table or max(need) + 1, pool=sum(need) + 8, dtype=dtype)
    return args, dict(layer=layers - 1, dc=dc, scale=0.11)


def _both(args, kw):
    return (np.asarray(px.mla_paged_decode(*args, **kw), np.float32), np.asarray(px._mla_decode_xla(*args, **kw), np.float32))


@pytest.fixture
def interpreted_walk(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(px, "_MLA_CHUNK_KEYS", CHUNK)


@pytest.mark.parametrize("context", [0, 1, WBS - 1, WBS, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 3 * CHUNK, 20 * CHUNK - 5, 20 * CHUNK],
                         ids=lambda c: f"ctx{c}")
def test_the_walk_at_every_edge_of_a_block_and_a_chunk(context, interpreted_walk):
    """One row alone (the grid's first step and its last at once): nothing
    cached, a token, a block's and a chunk's edges, an even and an odd count of
    chunks, twenty."""
    got, want = _both(*_walk_case([context]))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_walk_over_the_longest_row_the_window_times(monkeypatch):
    """10,176 tokens (8192 + 1984, the cell's longest) at the chunk the call's
    own shapes derive, which the cell's check never compares."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    assert px.mla_chunk_keys(WBS, 256, 4) == 1024 and px.mla_chunk_keys(16, 640, 2) == 1024       # the cell's
    assert px.mla_chunk_keys(WBS, 2048, 4) == 256 and px.mla_chunk_keys(WBS, 1 << 20, 4) == WBS        # the budget; a block at least
    got, want = _both(*_walk_case([10176, 2432], layers=1))
    np.testing.assert_allclose(got, want, atol=1e-5)


MIXED = (0, CHUNK - 3, 20 * CHUNK)          # an empty row, a one-chunk row, a twenty-chunk row


@pytest.mark.parametrize("order", [(0, 1, 2), (0, 2, 1), (1, 0, 2), (1, 2, 0), (2, 0, 1), (2, 1, 0)], ids=lambda o: "".join(map(str, o)))
def test_neighbours_hand_a_first_chunk_over_in_every_order(order, interpreted_walk):
    """The empty row first, last and between: who starts whose first chunk, the
    first and the last grid step, a row that passes the chain on untouched."""
    got, want = _both(*_walk_case([MIXED[k] for k in order] + [0, 3 * CHUNK, 0]))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_rows_output_is_its_own_in_any_batch_at_any_place_under_any_table(dtype, interpreted_walk):
    """Bit for bit: a row alone, the same row among others at every place of the
    grid, and under a table twice as wide."""
    contexts = [0, CHUNK, 5 * CHUNK + 7, 20 * CHUNK]
    (q, arena, fresh, tables, pos), kw = _walk_case(contexts, dtype=dtype)
    together = np.asarray(px.mla_paged_decode(q, arena, fresh, tables, pos, **kw), np.float32)
    wide = jnp.concatenate([tables, jnp.zeros_like(tables)], axis=1)
    np.testing.assert_array_equal(np.asarray(px.mla_paged_decode(q, arena, fresh, wide, pos, **kw), np.float32), together)
    for r in range(len(contexts)):
        alone = px.mla_paged_decode(q[r:r + 1], arena, fresh[r:r + 1], tables[r:r + 1], pos[r:r + 1], **kw)
        np.testing.assert_array_equal(np.asarray(alone, np.float32)[0], together[r])
    turned = np.asarray([2, 0, 3, 1])
    moved = px.mla_paged_decode(q[turned], arena, fresh[turned], tables[turned], pos[turned], **kw)
    np.testing.assert_array_equal(np.asarray(moved, np.float32), together[turned])


@pytest.mark.parametrize("nh", [4, 64, 128])
def test_the_walk_at_a_few_a_half_and_a_whole_pass_of_heads(nh, interpreted_walk):
    """4, 64 and 128 heads against the matrix unit's 128 rows: one form."""
    got, want = _both(*_walk_case([0, 3 * CHUNK + 1, WBS, 2 * CHUNK], nh=nh, layers=1))
    np.testing.assert_allclose(got, want, atol=1e-5)
