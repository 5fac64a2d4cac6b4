"""The paged pool's lane-packed K/V arena (``P = 128 // head_size`` KV heads of a
token side by side in a 128-lane row), at tiny widths in float32, seeded: what
the token writer lands comes back through the gather and through
``engine.held``'s form of it; the interpreted walk at heads of 64 and 32 against
its XLA form at contexts below, at and past a chunk; a head of 128 keeps the
arena and the tokens it had before the layout; a narrow head that cannot be
packed (a quantised arena) says what it costs; what is refused.  The model that
needs the layout is served in ``tests/test_shortconv_serving.py``.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402
from thunder_tpu.serving import kv_pool  # noqa: E402


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


def _serve(eng, prompts, new):
    handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while not all(h.done() for h in handles):
        eng.step()
    return [np.asarray(h.result(drive=False).new_tokens) for h in handles]


NARROW = dict(name="head64", n_layer=2, n_head=4, n_query_groups=2, n_embd=64, head_size=64,
              intermediate_size=96, vocab_size=256, block_size=128)


def _packed_case(hs, ng, rep, B=3, nbb=6, bs=8, L=2, seed=0):
    """A lane-packed arena and the plain one holding the same keys, tables and queries."""
    rng = np.random.default_rng(seed)
    P = 128 // hs
    nb = B * nbb + 1
    plain = [jnp.asarray(rng.normal(size=(nb, L, ng, bs, hs)), jnp.float32) for _ in range(2)]
    packed = [a.reshape(nb, L, ng // P, P, bs, hs).transpose(0, 1, 2, 4, 3, 5).reshape(nb, L, ng // P, bs, 128)
              for a in plain]
    tables = jnp.asarray(1 + rng.permutation(B * nbb).reshape(B, nbb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, ng * rep, hs)), jnp.float32)
    fk, fv = (jnp.asarray(rng.normal(size=(B, ng, hs)), jnp.float32) for _ in range(2))
    return plain, packed, tables, q, fk, fv


def _attend_dense(q, kd, vd, fk, fv, pos, layer):
    """The XLA form: softmax over a row's strictly older slots and the fresh token."""
    B, nh, hs = q.shape
    ng = fk.shape[1]
    out = []
    for i in range(B):
        k = jnp.concatenate([kd[layer, i, :, :int(pos[i])], fk[i][:, None]], axis=1)       # (ng, n + 1, hs)
        v = jnp.concatenate([vd[layer, i, :, :int(pos[i])], fv[i][:, None]], axis=1)
        qi = q[i].reshape(ng, nh // ng, hs)
        w = jax.nn.softmax(jnp.einsum("grh,gsh->grs", qi, k) / np.sqrt(hs), axis=-1)
        out.append(jnp.einsum("grs,gsh->grh", w, v).reshape(nh, hs))
    return jnp.stack(out)


@pytest.mark.parametrize("hs,ng,rep", [(64, 4, 2), (64, 2, 4), (32, 4, 2)])
def test_the_interpreted_walk_over_packed_rows_is_its_xla_form(hs, ng, rep, monkeypatch):
    """Contexts of nothing, under a chunk, of a chunk exactly and past it (a
    chunk here is ``C`` blocks of 8 keys), every row alone or in a batch."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(px, "_PAGED_CHUNK_KEYS", 16)                # two blocks a chunk: 48 keys span three
    plain, packed, tables, q, fk, fv = _packed_case(hs, ng, rep)
    assert px.paged_kv_chunk_blocks(ng * hs // 128, 8, 128, 4) == 2
    kd, vd = kv_pool.gather_dense(*plain, tables)
    np.testing.assert_array_equal(np.asarray(kv_pool.gather_dense(*packed, tables, 128 // hs)[0]), np.asarray(kd))
    for pos in ([0, 5, 16], [16, 17, 48], [33, 47, 1]):
        pos = jnp.asarray(pos, jnp.int32)
        want = _attend_dense(q, kd, vd, fk, fv, pos, layer=1)
        got = px.paged_attn_decode(q, *packed, fk, fv, tables, pos, layer=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        same = px.paged_attn_decode(q, *plain, fk, fv, tables, pos, layer=1)   # the plain arena, same kernel body
        np.testing.assert_allclose(np.asarray(got), np.asarray(same), atol=2e-5)
    wpos = jnp.asarray([40, 9, 47], jnp.int32)
    windowed = px.paged_attn_decode(q, *packed, fk, fv, tables, wpos, layer=0, window=12)
    plain_w = px.paged_attn_decode(q, *plain, fk, fv, tables, wpos, layer=0, window=12)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(plain_w), atol=2e-5)
    # and the entry's own XLA form (Pallas off), over the packed rows: heads taken apart, the window in the mask
    monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET")
    assert px.paged_decode_path(128, 12) == "xla"
    xla = px.paged_attn_decode(q, *packed, fk, fv, tables, wpos, layer=0, window=12)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(windowed), atol=2e-5)


@pytest.mark.parametrize("hs,ng", [(64, 4), (32, 4)])
def test_what_the_token_writer_lands_in_packed_rows_comes_back(hs, ng, monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    plain, packed, tables, _, _, _ = _packed_case(hs, ng, 1)
    B, L, P = 3, 2, 128 // hs
    vals = jnp.asarray(np.random.default_rng(9).normal(size=(B, L, ng, hs)), jnp.float32)
    pos = jnp.asarray([0, 13, 47], jnp.int32)
    wrote = px.paged_token_write(packed[0], vals, tables, pos, block_size=8)
    assert wrote.shape == packed[0].shape
    dense = kv_pool.gather_rows(wrote, tables, P)                    # (L, B, ng, tokens, hs)
    before = kv_pool.gather_rows(plain[0], tables)
    for i in range(B):
        np.testing.assert_array_equal(np.asarray(dense[:, i, :, int(pos[i])]), np.asarray(vals[i]))
        others = np.arange(48) != int(pos[i])
        np.testing.assert_array_equal(np.asarray(dense[:, i][:, :, others]), np.asarray(before[:, i][:, :, others]))
    # the XLA writers land the same bytes
    blk = jnp.take_along_axis(tables, (pos // 8)[:, None], axis=1)[:, 0]
    np.testing.assert_array_equal(np.asarray(kv_pool.scatter_token(packed[0], vals, blk, pos % 8)), np.asarray(wrote))
    one = kv_pool.gather_rows(plain[0], tables[:1])                  # (L, 1, ng, 48, hs)
    back = kv_pool.scatter_blocks(jnp.zeros_like(packed[0]), one, tables[0])
    np.testing.assert_array_equal(np.asarray(kv_pool.gather_rows(back, tables[:1], P)), np.asarray(one))


# a served sequence at a head of 128, as the commit before the lane-packed layout served it
# (tiny seeded model below, float32, the decode kernel's XLA form and the kernels interpreted alike)
HEAD_128 = dict(name="head128", n_layer=2, n_head=2, n_query_groups=1, n_embd=64, head_size=128,
                intermediate_size=96, vocab_size=256, block_size=128)
HEAD_128_TOKENS = [[2, 232, 232, 232, 232, 232, 232, 232, 36, 36, 36, 36], [104, 23, 103, 23, 103, 23, 103, 57, 57, 57, 57, 57]]


def test_a_head_of_128_keeps_its_arena_and_its_tokens(attn_form):
    cfg = llama.Config(**HEAD_128)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    assert G.kv_lane_pack(cfg) == 1 and G.kv_block_shape(cfg, 16) == (2, 1, 16, 128)
    eng = tt.serve(None, params, cfg, num_blocks=12, block_size=16, max_batch=2, prefill_buckets=(16, 32))
    assert eng.pool.k_arena.shape == (12, 2, 1, 16, 128) and eng.pool.lane_pack == 1
    occ = eng.stats()["pool_occupancy"]
    assert occ["token_bytes_counted"] == occ["token_bytes_laid_out"] == 2 * 2 * 128 * 4
    got = _serve(eng, [tokens(21, 3), tokens(9, 4)], new=12)
    assert [g.tolist() for g in got] == HEAD_128_TOKENS
    assert eng.stats()["attn"]["path"] == ("walk" if attn_form == "interpreted" else "xla")


def test_a_narrow_head_unpacked_is_laid_out_at_twice_its_bytes():
    cfg = llama.Config(**NARROW)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    want = np.asarray(G.generate(params, tokens(21, 3)[None], cfg, 6))[0, 21:]
    eng = tt.serve(None, params, cfg, num_blocks=12, block_size=16, max_batch=2, prefill_buckets=(16, 32))
    occ = eng.stats()["pool_occupancy"]
    assert eng.pool.lane_pack == 2 and eng.pool.k_arena.shape == (12, 2, 1, 16, 128)
    assert occ["token_bytes_counted"] == occ["token_bytes_laid_out"] == 2 * 2 * 2 * 64 * 4
    np.testing.assert_array_equal(_serve(eng, [tokens(21, 3)], new=6)[0], want)
    quant = tt.serve(None, params, cfg, num_blocks=12, block_size=16, max_batch=2, prefill_buckets=(16, 32),
                     kv_dtype="int8")                               # a quantised arena keeps a head a row
    occ = quant.stats()["pool_occupancy"]
    assert quant.pool.lane_pack == 1 and quant.pool.k_arena.shape == (12, 2, 2, 16, 64)
    scales = 2 * 2 * 2 * 4                                          # K's and V's float32 scale a layer a head a token
    assert occ["token_bytes_counted"] == 2 * 2 * 2 * 64 and occ["token_bytes_laid_out"] == 2 * occ["token_bytes_counted"] + scales
    assert len(_serve(quant, [tokens(21, 3)], new=4)[0]) == 4


def test_the_multi_query_kernel_refuses_packed_rows_and_the_pool_a_wrong_pack():
    cfg = llama.Config(**NARROW)
    _, packed, tables, _, _, _ = _packed_case(64, 4, 1)
    q = jnp.zeros((3, 4, 5, 64))
    with pytest.raises(NotImplementedError, match="lane-packed arena"):
        px.paged_attn_verify(q, *packed, q, q, tables, jnp.zeros((3,), jnp.int32), layer=0)
    with pytest.raises(ValueError, match="lane_pack=4"):
        kv_pool.PagedKVPool(cfg, num_blocks=4, block_size=16, lane_pack=4)
    assert kv_pool.PagedKVPool(cfg, num_blocks=4, block_size=16, lane_pack=1).k_arena.shape[-1] == 64
    assert kv_pool.PagedKVPool(cfg, num_blocks=4, block_size=16, kv_dtype="int8").lane_pack == 1
