"""The flash backward in one walk of the score blocks (PR 63): ``_flash_bwd``'s
``one_walk`` form against its ``two_kernels`` form and the float32 reference,
under the interpreter; which form the byte rule takes, and what it counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu.executors import pallasex
from thunder_tpu.executors.jaxex import _sdpa_backward_reference, _sdpa_reference


def _mask(kind, rng, B, H, Tq, Tk):
    """An additive mask of each canonical mode (``pallasex._canon_mask``)."""
    if kind is None:
        return None
    shape = {"shared_row": (1, 1, 1, Tk), "shared": (Tq, Tk), "batch": (B, 1, 1, Tk), "head": (1, H, Tq, Tk),
             "full": (B, H, Tq, Tk)}[kind]
    if shape[-2] == 1:      # a padding mask: some keys out, the first never
        out = np.where(rng.uniform(size=shape) < 0.15, -1e9, 0.0)
        out[..., :8] = 0.0
        return jnp.asarray(out, jnp.float32)
    return jnp.asarray(0.5 * rng.standard_normal(shape, np.float32))


def _backward(form, g, q, k, v, out, lse, mask, causal, scale, window):
    """``pallasex._bwd_local`` with the form given."""
    *_, Tq, hs = q.shape
    Tk, hp = k.shape[-2], pallasex._pad128(hs)
    BH, BG, H, G = pallasex._local_geometry(q.shape, k.shape)
    mask3, mode, mq = pallasex._canon_mask_operand(mask, q.shape, k.shape)
    r3 = lambda x, T, n: pallasex._pad_hs(x.reshape(n, T, hs), hs, hp)   # noqa: E731
    got = pallasex._flash_bwd(
        r3(g, Tq, BH), r3(q, Tq, BH), r3(k, Tk, BG), r3(v, Tk, BG), r3(out, Tq, BH), lse.reshape(BH, 1, Tq), mask3,
        causal, scale, H, G, mode, mq, window=window, form=form)
    return [x[..., :hs].reshape(like.shape) for x, like in zip(got, (q, k, v))]


# B, G, rep, Tq, Tk, hs, causal, window, mask, block, dtype.  No two cases share shapes and statics: the blocks'
# variables are read when a call is traced
CASES = {
    "causal": (1, 1, 1, 256, 256, 128, True, None, None, 128, jnp.float32),
    "full_rectangle_rep4": (1, 1, 4, 256, 384, 128, False, None, None, 128, jnp.float32),
    "band_four_blocks_wide_rep8": (1, 1, 8, 768, 768, 128, True, 512, None, 128, jnp.float32),   # the Mistral cell's 4,096 in 1,024s
    "window_of_a_block_two_sequences_two_groups": (2, 2, 2, 384, 384, 128, True, 128, None, 128, jnp.float32),
    "causal_short_queries": (1, 2, 2, 256, 512, 128, True, None, None, 128, jnp.float32),        # columns that keep no pair
    "both_tails": (1, 1, 2, 384, 640, 128, False, None, None, 256, jnp.float32),
    "both_tails_causal_window_rep4": (1, 1, 4, 640, 640, 128, True, 320, None, 256, jnp.float32),
    "mask_shared_row_past_the_keys": (1, 1, 2, 640, 384, 128, False, None, "shared_row", 256, jnp.float32),
    "mask_shared": (1, 1, 2, 256, 256, 128, True, None, "shared", 128, jnp.float32),
    "mask_batch": (2, 1, 2, 256, 256, 128, False, None, "batch", 128, jnp.float32),
    "mask_head_rows_past_both_ends": (1, 1, 2, 384, 640, 128, True, None, "head", 256, jnp.float32),
    "mask_full": (2, 1, 2, 256, 384, 128, True, None, "full", 128, jnp.float32),
    "head_64_padded_rep4": (1, 1, 4, 256, 256, 64, True, None, None, 128, jnp.float32),
    "head_192_latent_bfloat16": (1, 2, 2, 384, 384, 192, True, None, None, 128, jnp.bfloat16),
    "head_256_rep8_tail": (1, 1, 8, 384, 384, 256, True, None, None, 256, jnp.float32),          # the hybrid cell's kind
}


@pytest.mark.parametrize("case", CASES)
def test_one_walk_matches_the_two_kernels_and_the_reference(monkeypatch, case):
    """dq, dk, dv of the one walk, of the two kernels it replaces, and of the
    float32 reference.  On the chip the two forms give the same bits
    (``tools/flash_tune.py --check``, PERF.md, PR 63); this CPU's XLA sums a
    product with a transposed operand in another order, so here they agree to
    float32's last places."""
    B, G, rep, Tq, Tk, hs, causal, window, mask_kind, block, dtype = CASES[case]
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("THUNDER_TPU_FLASH_BQ", str(block))
    monkeypatch.setenv("THUNDER_TPU_FLASH_BK", str(block))
    rng = np.random.default_rng(len(case) + Tq + Tk + hs)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape, np.float32), dtype)   # noqa: E731
    q, g, k, v = draw(B, G * rep, Tq, hs), draw(B, G * rep, Tq, hs), draw(B, G, Tk, hs), draw(B, G, Tk, hs)
    mask = _mask(mask_kind, rng, B, G * rep, Tq, Tk)
    scale = hs ** -0.5

    @jax.jit
    def reference(g, q, k, v, mask):
        g, q, k, v = (x.astype(jnp.float32) for x in (g, q, k, v))
        out, lse = _sdpa_reference(q, k, v, mask, causal, scale, window)
        return out, lse, _sdpa_backward_reference(g, q, k, v, out, lse, mask, causal, scale, window)

    # what the forward pass would hand over, from the reference: the backward kernels alone are traced here
    oref, lse, want = reference(g, q, k, v, mask)
    out = oref.astype(dtype)
    before = dict(pallasex.stats)
    walk = _backward("one_walk", g, q, k, v, out, lse, mask, causal, scale, window)
    sched = dict(pallasex.flash_schedule)
    hp = pallasex._pad128(hs)
    by_row, by_column = (len(pallasex._flash_schedule(Tq, Tk, block, block, causal, window, by_column=by)[0]) for by in (False, True))
    assert sched["bwd_form"] == "one_walk" and sched["bwd_grid_steps"] == rep * by_column
    assert sched["bwd_resident_bytes"] == (4 * hp * block * (-(-Tq // block) + 2 * -(-Tk // block))
                                           + 2 * jnp.dtype(dtype).itemsize * hp * (Tq + 2 * Tk))
    assert (sched["block_q"], sched["tail_rows"]) == (block, -Tq % block)
    pair = _backward("two_kernels", g, q, k, v, out, lse, mask, causal, scale, window)
    assert pallasex.flash_schedule["bwd_form"] == "two_kernels"
    assert pallasex.flash_schedule["bwd_grid_steps"] == rep * (by_row + by_column)
    assert pallasex.flash_schedule["bwd_resident_bytes"] == 0
    for form in ("one_walk", "two_kernels"):
        assert pallasex.stats["flash_bwd_" + form] == before.get("flash_bwd_" + form, 0) + 1
    close, tol = (2e-6, 2e-4) if dtype == jnp.float32 else (2e-2, 4e-2)
    for a, b, c, n in zip(walk, pair, want, ("dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), n
        np.testing.assert_allclose(a, b, atol=close * max(1.0, np.abs(b).max()), rtol=close, err_msg=n)
        np.testing.assert_allclose(a, np.asarray(c), atol=tol * max(1.0, np.abs(c).max()), rtol=tol, err_msg=n)


def _trace_bwd(H, G, T, hs):
    q = jax.ShapeDtypeStruct((H, T, hs), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((G, T, hs), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((H, 1, T), jnp.float32)
    jax.eval_shape(lambda g, q, k, v, o, l: pallasex._flash_bwd.__wrapped__(
        g, q, k, v, o, l, None, True, hs ** -0.5, H, G, None, 1, None), q, q, k, k, q, lse)
    return dict(pallasex.flash_schedule)


def test_the_form_follows_the_bytes_and_the_vmem(monkeypatch):
    """The one walk where its sums and the 16 MiB of block tiles fit the VMEM a
    kernel may ask for, the two kernels on the other side of that byte; what
    a v5e's 96 MiB take (both train cells' shapes, and 16,384 tokens) and
    leave (32,768 at a head of 128); the interpreter, which has no VMEM to
    fill, walks once whatever the length; each traced call counted."""
    for which in "QK":
        monkeypatch.delenv(f"THUNDER_TPU_FLASH_B{which}", raising=False)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    mib = 1 << 20
    sums = lambda T, hs: (4 + 2 * 2) * hs * 3 * T      # noqa: E731 - float32 sums, two bfloat16 buffers an output
    assert pallasex._flash_walk_bytes(8192, 8192, 1024, 1024, 128, 2) == sums(8192, 128) == 24 * mib
    for cap, H, G, T, hs, form in [
            (40 * mib, 32, 8, 8192, 128, "one_walk"), (40 * mib - 1, 32, 8, 8192, 128, "two_kernels"),
            (96 * mib, 32, 8, 8192, 128, "one_walk"), (96 * mib, 16, 2, 8192, 256, "one_walk"),
            (96 * mib, 8, 2, 16384, 128, "one_walk"), (96 * mib, 4, 2, 16384, 256, "two_kernels"),
            (96 * mib, 4, 2, 32768, 128, "two_kernels"), (pallasex._GMM_VMEM_DEFAULT, 4, 2, 256, 128, "two_kernels")]:
        monkeypatch.setattr(pallasex, "_gmm_vmem_cap", lambda cap=cap: cap)
        before = dict(pallasex.stats)
        sched = _trace_bwd(H, G, T, hs)
        assert sched["bwd_form"] == form, (cap, T, hs)
        assert sched["bwd_resident_bytes"] == (sums(T, hs) if form == "one_walk" else 0)
        assert sched["bwd_grid_steps"] == (1 if form == "one_walk" else 2) * (H // G) * sched["grid_steps"]   # Tq = Tk
        other = "two_kernels" if form == "one_walk" else "one_walk"
        assert pallasex.stats["flash_bwd_" + form] == before.get("flash_bwd_" + form, 0) + 1
        assert pallasex.stats.get("flash_bwd_" + other, 0) == before.get("flash_bwd_" + other, 0)
    monkeypatch.setattr(pallasex, "_interpret", lambda: True)
    assert _trace_bwd(4, 2, 32768, 128)["bwd_form"] == "one_walk"
    assert all(type(n) is int for n in pallasex.stats.values())


@pytest.mark.parametrize("case,rep", [((8192, 8192, 1024, 1024, True, 4096), 4), ((640, 384, 256, 256, False, None), 2),
                                      ((512, 256, 128, 128, True, None), 3)])
def test_the_walks_order_is_heads_then_columns_then_rows(case, rep):
    """``_flash_walk``: a column's entries as ``_flash_schedule`` lists them by
    column, every column once a head, the heads one after the other;
    ``_FIRST`` and ``_LAST`` on a head's first and last entry and nowhere
    else, the other flags the blocks' own."""
    qi1, kj1, _, flag1 = pallasex._flash_schedule(*case, by_column=True)
    qi, kj, head, flag = pallasex._flash_walk(*case, rep)
    n = len(qi1)
    assert len(qi) == rep * n
    ends = pallasex._FIRST | pallasex._LAST
    for r in range(rep):
        part = slice(r * n, (r + 1) * n)
        assert (qi[part] == qi1).all() and (kj[part] == kj1).all() and (head[part] == r).all()
        assert ((flag[part] & ~ends) == (flag1 & ~ends)).all()
        assert [i for i, f in enumerate(flag[part]) if f & pallasex._FIRST] == [0]
        assert [i for i, f in enumerate(flag[part]) if f & pallasex._LAST] == [n - 1]
    assert not any(a.flags.writeable for a in (qi, kj, head, flag))
