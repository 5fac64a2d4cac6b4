"""``paged_attn_decode``'s walk (PR 47: ``mla_paged_decode``'s loop) interpreted
against ``paged_attn_xla``: what the loop can get wrong.  Two chunks a turn in
two slots of static buffers, a slot's copies awaited once an array, a row's
first chunk started by the row before it through ``chain``: plain, int8 and fp8
stores, with and without a window, lane-packed with and without ``packed_out``,
rows with nothing cached first, last and in runs, even and odd chunk counts,
and a row's output bit for bit alone and in any batch at any place under any
table.  (The walk's other cases, inside the engine among them, are
``tests/test_paged_attention.py`` and ``tests/test_lane_packed_arena.py``.)"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from conftest import set_attn_form  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.serving.quant import quantize_kv  # noqa: E402
from tools import paged_tune  # noqa: E402

_FP8 = getattr(jnp, "float8_e4m3fn", None)
# ``CHUNK`` keys a chunk here (two blocks of 8), so a hundred tokens are many chunks
CHUNK, WBS, LAYERS = 16, 8, 2
TABLE, POOL = 24, 128
STORES = ["plain", "int8", pytest.param("fp8", marks=pytest.mark.skipif(_FP8 is None, reason="no float8_e4m3fn"))]
LAYOUTS = {"head_a_row": dict(hs=128, packed_out=False), "lane_packed": dict(hs=64, packed_out=False),
           "packed_out": dict(hs=64, packed_out=True)}


@pytest.fixture
def interpreted_walk(monkeypatch):
    set_attn_form(monkeypatch, "interpreted")
    monkeypatch.setattr(px, "_PAGED_CHUNK_KEYS", CHUNK)
    assert px.paged_kv_chunk_blocks(2, WBS, 128, 4) * WBS == CHUNK


def _walk_case(contexts, *, store="plain", hs=128, ng=2, rep=2, window=None, packed_out=False, dtype=jnp.float32, seed=0):
    """The tool's operands for rows of the given contexts (each row's blocks
    scattered over the pool, the table sink-padded), the arenas quantised for an
    int8 or fp8 store: ``(args, kwargs)`` of both forms.  One table width and
    one pool for every case, so the cases of a batch size share a compiled call."""
    q, k, v, fk, fv, tables, pos = paged_tune.operands(
        np.asarray(contexts, np.int32), nh=ng * rep, ng=ng, hs=hs, bs=WBS, layers=LAYERS,
        table=TABLE, pool=POOL, dtype=dtype, seed=seed)
    kw = dict(layer=LAYERS - 1, window=window, packed_out=packed_out)
    if store != "plain":
        (k, ks), (v, vs) = (quantize_kv(a, jnp.int8 if store == "int8" else _FP8) for a in (k, v))
        kw.update(k_scale=ks, v_scale=vs)
    return (q, k, v, fk, fv, tables, pos), kw


def _both(args, kw):
    q, k, v, fk, fv, tables, pos = args
    got = px.paged_attn_decode(*args, **kw)
    want = jnp.squeeze(px.paged_attn_xla(q[:, :, None], k, v, fk[:, :, None], fv[:, :, None], tables, pos, **kw), -2)
    return np.asarray(got, np.float32), np.asarray(want, np.float32).reshape(got.shape)


# nothing cached, a token, a block's and a chunk's edges, an even and an odd count of chunks, eleven
EDGES = [0, 1, WBS - 1, WBS, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 3 * CHUNK, 11 * CHUNK - 5]


@pytest.mark.parametrize("context", EDGES, ids=lambda c: f"ctx{c}")
@pytest.mark.parametrize("store", STORES)
def test_the_walk_at_every_edge_of_a_block_and_a_chunk(store, context, interpreted_walk):
    """One row alone (the grid's first step and its last at once)."""
    got, want = _both(*_walk_case([context], store=store))
    np.testing.assert_allclose(got, want, atol=1e-5)


# (window, context), two windows for every case so that the cases share compiled calls: the window's first slot inside a
# block of the first chunk; two chunks left of three; of many; the fresh token alone (``window`` 1: the row has no chunk
# and hands nothing over); a window wider than the context; an odd count of chunks from the window's first block
WINDOWS = [(20, 23), (20, 45), (20, 11 * CHUNK - 5), (1, 9), (20, 17), (20, 100)]


@pytest.mark.parametrize("window,context", WINDOWS)
@pytest.mark.parametrize("store", STORES)
def test_the_window_moves_a_rows_first_chunk(store, window, context, interpreted_walk):
    contexts = [context, 3, context + 7]
    got, want = _both(*_walk_case(contexts, store=store, window=window))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("window", [None, 20])
def test_the_lane_packed_arena_rides_the_same_loop(layout, window, interpreted_walk):
    """Heads of 64 two to a 128-lane row, their outputs taken apart or left
    whole (``packed_out``: the head pair's rows, differential attention's),
    beside a head a row: ragged rows, one with nothing cached."""
    got, want = _both(*_walk_case([3 * CHUNK + 5, 0, 2 * CHUNK, WBS, 90], ng=4, window=window, **LAYOUTS[layout]))
    np.testing.assert_allclose(got, want, atol=1e-5)


# where the rows with nothing cached stand: the chain's edges.  A row with none is handed nothing and hands nothing
# over; the first row with a token is grid step 0's to start, the last fetches its own last chunk again.  (Five rows a
# case, and one alone: the cases of a form share a compiled call.)
EMPTIES = {"first": [0, 40, 17, 3, 9], "last": [40, 17, 3, 9, 0], "a_run": [40, 0, 0, 0, 17], "both_ends": [0, 0, 33, 9, 0],
           "all": [0, 0, 0, 0, 0], "alone": [0], "none": [40, 17, 33, 3, 9], "every_other": [0, 16, 0, 48, 0]}


@pytest.mark.parametrize("where", EMPTIES)
@pytest.mark.parametrize("store,window", [("plain", None), ("plain", 20), ("int8", None)])
def test_rows_with_nothing_cached_at_the_chains_edges(store, window, where, interpreted_walk):
    got, want = _both(*_walk_case(EMPTIES[where], store=store, window=window))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("counts", [(1, 1, 1), (2, 2, 2), (1, 2, 3), (3, 2, 1), (2, 1, 2), (1, 4, 1), (5, 1, 4), (3, 3, 3)],
                         ids=lambda c: "".join(map(str, c)))
def test_neighbours_hand_a_first_chunk_over_at_every_parity(counts, interpreted_walk):
    """Rows of these chunk counts in a batch: a row begins in the slot that the
    parity of all chunks before it names, whatever mix of odd and even counts."""
    got, want = _both(*_walk_case([n * CHUNK - 3 for n in counts]))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("store,window", [("plain", None), ("plain", 20), ("int8", None)])
def test_a_rows_output_is_its_own_in_any_batch_at_any_place_under_any_table(store, window, interpreted_walk):
    """Bit for bit: alone, first, last and in the middle of a batch whose other
    rows differ, and under a table twice as wide.  What the neighbours are
    changes what is prefetched and when, never a chunk's boundaries nor the
    order of a row's sums."""
    contexts = [3 * CHUNK + 5, 0, 7, 2 * CHUNK, 5 * CHUNK - 1]
    (q, k, v, fk, fv, tables, pos), kw = _walk_case(contexts, store=store, window=window, dtype=jnp.bfloat16)
    call = lambda rows, tabs=tables: np.asarray(px.paged_attn_decode(                                   # noqa: E731
        q[rows], k, v, fk[rows], fv[rows], tabs[rows], pos[rows], **kw), np.float32)
    batched = call(np.arange(5))
    wide = jnp.concatenate([tables, jnp.zeros_like(tables)], axis=1)            # sink-padded
    for order in ([4, 3, 2, 1, 0], [2, 0, 4, 1, 3]):
        moved = call(np.asarray(order))
        for at, r in enumerate(order):
            assert np.array_equal(moved[at], batched[r]), (order, r)
    for r in range(5):
        assert np.array_equal(call(np.asarray([r]))[0], batched[r]), r
        assert np.array_equal(call(np.asarray([r]), wide)[0], batched[r]), r


def test_a_call_site_is_counted_by_the_form_it_took(monkeypatch):
    """``pallasex.stats``, at trace time: the walk, a block a grid step (rows
    Mosaic cannot slice), the XLA form (Pallas off)."""
    args, kw = _walk_case([20, 0, 9])
    before = dict(px.stats)
    set_attn_form(monkeypatch, "interpreted")
    px.paged_attn_decode(*args, **kw)
    with monkeypatch.context() as m:
        m.setattr(px, "paged_walk_lanes_ok", lambda lanes: False)
        px.paged_attn_decode(*args, **kw)
    set_attn_form(monkeypatch, "xla")
    px.paged_attn_decode(*args, **kw)
    took = {name: px.stats.get(name, 0) - before.get(name, 0) for name in ("paged_walk", "paged_by_blocks", "paged_xla")}
    assert took == {"paged_walk": 1, "paged_by_blocks": 1, "paged_xla": 1}
    assert all(type(n) is int for n in px.stats.values())


def test_a_programs_layers_share_one_traced_body(interpreted_walk):
    """The layer is an operand: three layers' calls of one form are one
    ``_paged_decode_call`` trace, and a second form (a window) is one more."""
    (q, k, v, fk, fv, tables, pos), kw = _walk_case([20, 0, 9], seed=3)

    def program(q, k, v, fk, fv, tables, pos):
        out = [px.paged_attn_decode(q, k, v, fk, fv, tables, pos, layer=l % LAYERS) for l in range(3)]
        out += [px.paged_attn_decode(q, k, v, fk, fv, tables, pos, layer=l, window=20) for l in range(2)]
        return sum(out)

    jaxpr = jax.make_jaxpr(program)(q, k, v, fk, fv, tables, pos)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("pjit", "jit") and e.params["name"] == "_paged_decode_call"]
    assert len(calls) == 5 and len({id(e.params["jaxpr"]) for e in calls}) == 2
