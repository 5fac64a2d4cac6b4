"""The kernels a decoder-hybrid-decoder brought (``ssm_scan_fwd``, ``ssm_decode_step``,
the differential form on ``paged_attn_decode``'s walk and in a prompt's flash call),
interpreted, each against its XLA form; and the model-wide window's decode programs,
which are what they were.  ``tests/test_hybrid_decoder_serving.py`` has the model and
the engine; ``tests/test_pallas_tpu_lowering.py`` compiles the kernels for a v5e."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.executors import pallasex as px
from thunder_tpu.models import generate as G
from thunder_tpu.models import llama
from thunder_tpu.serving import kv_pool


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


def _scan_operands(B, T, d, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, T, d)), jax.nn.softplus(jax.random.normal(k[1], (B, T, d)) - 2.0),
            jax.random.normal(k[2], (B, T, N)), jax.random.normal(k[3], (B, T, N)),
            -jnp.exp(jax.random.normal(k[4], (N, d))), jax.random.normal(k[5], (B, N, d)))


@pytest.mark.parametrize("T,d", [(32, 256), (264, 128), (16, 1152)])
def test_ssm_scan_fwd_is_its_xla_form(T, d, interpreted):
    ops = _scan_operands(2, T, d, 16)
    before = px.stats.get("ssm_scan", 0)
    y, last = px.ssm_scan(*ops)
    assert px.stats["ssm_scan"] == before + 1 and px.ssm_schedule["block_tokens"] in (8, 16, 32, 256)
    y0, last0 = px.ssm_scan_xla(*ops)
    np.testing.assert_allclose(y, y0, atol=2e-5)
    np.testing.assert_allclose(last, last0, atol=2e-5)
    # a token with dt = 0 leaves the state as it was: a padded tail
    u, dt, Bm, Cm, A, h0 = ops
    _, cut = px.ssm_scan(u, dt.at[:, T // 2:].set(0.0), Bm, Cm, A, h0)
    _, half = px.ssm_scan_xla(u[:, :T // 2], dt[:, :T // 2], Bm[:, :T // 2], Cm[:, :T // 2], A, h0)
    np.testing.assert_allclose(cut, half, atol=2e-5)


def test_ssm_scan_takes_the_xla_form_where_the_shapes_do_not_tile(interpreted):
    before = px.stats.get("ssm_scan", 0)
    for T, d in [(30, 256), (32, 192)]:
        y, _ = px.ssm_scan(*_scan_operands(1, T, d, 16))
        assert y.shape == (1, T, d)
    assert px.stats.get("ssm_scan", 0) == before


def test_ssm_decode_step_is_its_xla_form_and_touches_its_rows_slots_alone(interpreted):
    u, dt, Bm, Cm, A, _ = _scan_operands(1, 3, 256, 16, seed=1)
    arena = jax.random.normal(jax.random.PRNGKey(9), (5, 3, 16, 256))
    slots = jnp.asarray([2, 4, 0])
    args = (arena, slots, u[0], dt[0], Bm[0], Cm[0], A)
    y, out = px.ssm_decode_step(*args, layer=1)
    y0, out0 = px.ssm_decode_step_xla(*args, layer=1)
    np.testing.assert_allclose(y, y0, atol=2e-5)
    np.testing.assert_allclose(out[1:], out0[1:], atol=2e-6)               # slot 0 is the sink
    untouched = np.ones((5, 3), bool)
    untouched[[2, 4, 0], 1] = False
    np.testing.assert_array_equal(np.asarray(out)[untouched], np.asarray(arena)[untouched])


@pytest.mark.parametrize("window", [None, 16])
def test_the_differential_walk_is_two_softmaxes_over_one_row(window, interpreted):
    """``paged_attn_decode(packed_out=True)`` over a lane-packed arena against
    ``generate.diff_attend_dense`` over the same rows gathered: a pair's first
    query on its first key, its second on its second, both on ``V_g`` whole."""
    B, G2, J, hs, bs, nbb, L = 3, 2, 2, 64, 8, 6, 2
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    k_arena, v_arena = (jax.random.normal(kk, (1 + B * nbb, L, G2, bs, 2 * hs)) for kk in k[:2])
    tables = jnp.arange(1, 1 + B * nbb, dtype=jnp.int32).reshape(B, nbb)
    pos = jnp.asarray([5, 30, 47], jnp.int32)
    q = jax.random.normal(k[2], (B, G2, 2, J, 1, hs))
    fk, fv = (jax.random.normal(kk, (B, 2 * G2, hs)) for kk in k[3:5])
    got = px.paged_attn_decode(q[..., 0, :].reshape(B, 2 * G2 * J, hs), k_arena, v_arena, fk, fv, tables, pos, layer=1,
                               window=window, packed_out=True).reshape(B, G2, 2, J, 1, 2 * hs)
    put = jax.vmap(lambda rows, new, p: jax.lax.dynamic_update_slice_in_dim(rows, new[:, None], p, axis=1))
    kr, vr = (put(kv_pool.gather_rows(a[:, 1:2], tables)[0], f.reshape(B, G2, 2 * hs), pos)
              for a, f in ((k_arena, fk), (v_arena, fv)))
    j = jnp.arange(nbb * bs)[None, :]
    keep = j <= pos[:, None]
    if window is not None:
        keep = jnp.logical_and(keep, j > pos[:, None] - window)
    want = G.diff_attend_dense(q, kr, vr, keep[:, None, None, None, :])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and with the pair's lanes swapped in the arena's rows the walk reads another head's keys: far off
    swapped = jnp.concatenate([k_arena[..., hs:], k_arena[..., :hs]], axis=-1)
    off = px.paged_attn_decode(q[..., 0, :].reshape(B, 2 * G2 * J, hs), swapped, v_arena, fk, fv, tables, pos, layer=1,
                               window=window, packed_out=True).reshape(got.shape)
    assert rel(off, want) > 0.3


def test_a_prompts_flash_call_is_the_masked_softmax(interpreted):
    """``diff_attend_dense`` through ``_flash_fwd`` (the queries padded with zeros
    into their half of a row) against its own einsum form, with and without a window."""
    B, G2, J, T, hs = 1, 2, 2, 256, 64
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k[0], (B, G2, 2, J, T, hs), jnp.float32)
    kr, vr = (jax.random.normal(kk, (B, G2, T, 2 * hs), jnp.float32) for kk in k[1:])
    for W in (None, 128):
        keep = G._band_keep(T, W)[None]
        before = px.stats["direct"]
        got = G.diff_attend_dense(q, kr, vr, keep, causal_window=W)
        assert px.stats["direct"] == before + 1
        np.testing.assert_allclose(got, G.diff_attend_dense(q, kr, vr, keep), atol=2e-5)


# --------------------------------------------------------------------------
# the model-wide window's programs are what they were
# --------------------------------------------------------------------------

MISTRAL_LIKE = dict(name="tiny-window", n_layer=2, n_head=4, n_query_groups=2, n_embd=64, head_size=128, vocab_size=128,
                    intermediate_size=128, sliding_window=24, block_size=256)
# sha256 of the decode program's jaxpr (kernel bodies included), computed on the commit before this kind came
# (PR 40's tree, d1d06c0) and again here (and again at PR 44, which moved the choice of the attention call's form
# behind the kernel's entry: the program with the kernel in it is what it was); PR 47 rebuilt the walk's chunk loop (every
# form of it, the window's among them) and made its call an inner jit: the digest is that tree's.  Regenerate with
# `PYTHONPATH=.:tests python tests/test_hybrid_decoder_kernels.py` after a deliberate change to the windowed path
WINDOW_PROGRAMS = {
    "decode_paged": "6025f42b9b46ad3abc0d7692fb28dd193d5060aef83852c4957852530de62d1e",
}


def _window_program_digests() -> dict:
    import hashlib
    import os

    cfg = llama.Config(**MISTRAL_LIKE)
    params = jax.eval_shape(lambda: llama.init_params(cfg, dtype=jnp.float32))
    out = {}
    for kind in WINDOW_PROGRAMS:
        old = os.environ.get("THUNDER_TPU_PALLAS_INTERPRET")
        os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = "1"
        try:
            eng = tt.serve(None, params, cfg, block_size=8, num_blocks=32, max_batch=2,
                           cache_dtype=jnp.float32, prefix_sharing=False)
            prog = eng._build_decode_paged(2, 8)
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
            args = (params, i32(2), i32(2), i32(2, 8), jax.eval_shape(lambda: eng.pool.arenas),
                    jax.ShapeDtypeStruct((2, 2), jnp.uint32), {}, i32(2))
            out[kind] = hashlib.sha256(str(jax.make_jaxpr(prog)(*args)).encode()).hexdigest()
            eng.shutdown(drain=False)
        finally:
            if old is None:
                os.environ.pop("THUNDER_TPU_PALLAS_INTERPRET", None)
            else:
                os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = old
    return out


def test_the_model_wide_windows_decode_programs_are_unchanged():
    assert _window_program_digests() == WINDOW_PROGRAMS


if __name__ == "__main__":
    print(_window_program_digests())
