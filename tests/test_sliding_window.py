"""Sliding-window (Mistral-style) attention through the whole stack.

The band is a *structural* parameter of the fused SDPA prim — not an O(T²)
additive mask — so the flash kernels never visit a block outside
[i-window, i]: their grid is the list of blocks with a kept pair
(``pallasex._flash_schedule``, by scalar prefetch), a block outside the band
is neither a grid step nor a copy, only the blocks on the band's two edges
build a mask, and long-T attention cost scales O(T·window).  (Beyond-ref: the
reference's sdpaex checker matrix, sdpaex.py:240-474, has no sliding-window
case; HF Mistral there pays for a materialized banded mask.)
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
import thunder_tpu.torch as ltorch
from thunder_tpu.models import llama


def _ref_banded_sdpa(q, k, v, window):
    """Plain-jnp reference: full causal scores with an explicit band mask."""
    H, G = q.shape[-3], k.shape[-3]
    if H != G:
        rep = H // G
        k = jnp.repeat(k, rep, axis=-3)
        v = jnp.repeat(v, rep, axis=-3)
    hs = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32)
    s = s / (hs ** 0.5)
    Tq, Tk = q.shape[-2], k.shape[-2]
    row = jnp.arange(Tq)[:, None]
    col = jnp.arange(Tk)[None, :]
    keep = (row >= col) & (col > row - window)
    s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v).astype(q.dtype)


def _qkv(B=2, H=4, G=None, T=128, hs=32, seed=0):
    G = H if G is None else G
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, hs), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, G, T, hs), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, G, T, hs), dtype=jnp.float32)
    return q, k, v


class TestSlidingWindowSDPA:
    @pytest.mark.parametrize("window", [16, 50, 128, 1000])
    def test_forward_matches_banded_reference(self, window):
        q, k, v = _qkv()
        jfn = tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
            q, k, v, is_causal=True, sliding_window=window))
        out = jfn(q, k, v)
        ref = _ref_banded_sdpa(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_window_geq_T_equals_full_causal(self):
        q, k, v = _qkv()
        w = tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
            q, k, v, is_causal=True, sliding_window=4096))(q, k, v)
        c = tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
            q, k, v, is_causal=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(w), np.asarray(c), atol=1e-6)

    def test_gqa_with_window(self):
        q, k, v = _qkv(H=8, G=2)
        jfn = tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
            q, k, v, is_causal=True, sliding_window=40))
        out = jfn(q, k, v)
        ref = _ref_banded_sdpa(q, k, v, 40)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("G", [None, 2])
    def test_grads_match_banded_reference(self, G):
        q, k, v = _qkv(G=G, T=64)
        window = 24

        def thunder_loss(q, k, v):
            return ltorch.scaled_dot_product_attention(
                q, k, v, is_causal=True, sliding_window=window).sum()

        def ref_loss(q, k, v):
            return _ref_banded_sdpa(q, k, v, window).astype(jnp.float32).sum()

        gq, gk, gv = tt.grad(thunder_loss, argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=5e-5, rtol=5e-5)

    def test_window_requires_causal(self):
        q, k, v = _qkv(T=32)
        with pytest.raises(Exception, match="sliding_window requires is_causal"):
            tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
                q, k, v, sliding_window=8))(q, k, v)

    def test_flash_kernel_path_matches_in_interpret_mode(self):
        # force the Pallas kernels (interpret mode off-TPU) and compare
        from thunder_tpu.executors import pallasex

        q, k, v = _qkv(H=4, G=2, T=256, hs=64)
        os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = "1"
        try:
            before = pallasex.stats["direct"]
            out = tt.jit(lambda q, k, v: ltorch.scaled_dot_product_attention(
                q, k, v, is_causal=True, sliding_window=100))(q, k, v)
            assert pallasex.stats["direct"] > before, "flash kernel was not claimed"
        finally:
            del os.environ["THUNDER_TPU_PALLAS_INTERPRET"]
        ref = _ref_banded_sdpa(q, k, v, 100)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,steps,edges_full_row", [
    (None, 136, 1), (8192, 136, 1), (4096, 108, 2), (1024, 45, 2), (512, 31, 2), (100, 31, 2), (1, 16, 1)])
def test_grid_steps_follow_the_band(window, steps, edges_full_row):
    """T 8192 in blocks of 512: the flash grid has as many steps a head as the
    band has blocks, not the rectangle's 256."""
    from thunder_tpu.executors import pallasex

    qi, kj, _, flag = pallasex._flash_schedule(8192, 8192, 512, 512, True, window)
    assert len(qi) == steps
    full_row = (qi == 15)
    assert int(((flag[full_row] & pallasex._EDGE) != 0).sum()) == edges_full_row
    # every listed block lies in the band
    assert ((qi - kj) >= 0).all() and (window is None or ((qi - kj - 1) * 512 + 1 < window).all())


class TestMistralModel:
    def test_tiny_mistral_loss_and_grads(self):
        cfg = llama.Config.from_name("tiny-mistral-debug")
        assert cfg.sliding_window == 32
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 2, 64
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        loss, grads = tt.value_and_grad(
            lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg))(params, idx, tgt, cos, sin)
        assert np.isfinite(float(loss))
        flat = jax.tree_util.tree_leaves(grads)
        assert flat and all(np.isfinite(np.asarray(g)).all() for g in flat)

    def test_window_changes_the_math_vs_full_causal(self):
        cfg_w = llama.Config.from_name("tiny-mistral-debug")
        cfg_full = llama.Config.from_name("tiny-mistral-debug", sliding_window=None)
        params = llama.init_params(cfg_w, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 1, 128  # > window=32 so the band binds
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg_w.vocab_size)
        cos, sin = llama.build_rope_cache(cfg_w, T)
        out_w = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg_w))(params, idx, cos, sin)
        out_f = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg_full))(params, idx, cos, sin)
        assert not np.allclose(np.asarray(out_w), np.asarray(out_f), atol=1e-3)


class TestRingKVCache:
    """Sliding-window decode uses a ring cache (slot = position % window):
    O(window) serving memory.  Ground truth: greedy decode by re-running the
    full banded training forward over the growing sequence."""

    def _greedy_ref(self, params, prompt, cfg, n_new):
        toks = np.asarray(prompt)
        for _ in range(n_new):
            T = toks.shape[1]
            cos, sin = llama.build_rope_cache(cfg, T)
            logits = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg))(
                params, jnp.asarray(toks), cos, sin)
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
            toks = np.concatenate([toks, nxt], axis=1)
        return toks

    @pytest.mark.parametrize("T_prompt", [3, 8, 20])
    def test_ring_decode_matches_full_banded_forward(self, T_prompt):
        from thunder_tpu.models import generate as gen

        cfg = llama.Config.from_name("tiny-mistral-debug", sliding_window=8)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, n_new = 2, 12
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T_prompt), 0, cfg.vocab_size)
        out = gen.generate(params, prompt, cfg, n_new, cache_dtype=jnp.float32)
        ref = self._greedy_ref(params, prompt, cfg, n_new)
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_cache_is_window_sized(self):
        from thunder_tpu.models import generate as gen

        cfg = llama.Config.from_name("tiny-mistral-debug", sliding_window=8)
        cache = gen.init_cache(cfg, B=2, T_max=64)
        assert cache["k"].shape[3] == 8  # ring of window slots, not T_max

    def test_full_cache_when_window_exceeds_tmax(self):
        from thunder_tpu.models import generate as gen

        cfg = llama.Config.from_name("tiny-mistral-debug", sliding_window=64)
        cache = gen.init_cache(cfg, B=1, T_max=16)
        assert cache["k"].shape[3] == 16
        # and decode still matches the banded reference
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, cfg.vocab_size)
        out = gen.generate(params, prompt, cfg, 8, cache_dtype=jnp.float32)
        ref = self._greedy_ref(params, prompt, cfg, 8)
        np.testing.assert_array_equal(np.asarray(out), ref)
