"""``pallasex.hc_mix``: a hyper-connection's boundary between two sublayers as one
Pallas kernel (the close of the sublayer that ended and the open of the one that
begins, one read of the stream), under the interpreter, every call under
``jax.jit``, tiny widths.

What is held: the joined call, the open alone and the close alone against
``generate.hc_close`` / ``hc_open`` (the fallback) and the float32 reference's lines
(``chipbench/models/latent_hc_moe_decoder.py``) for a bfloat16 and a float32 stream
of two and of four, a whole number of tiles and a ragged one; a token's numbers
whatever shares its tile; ``H_res`` doubly stochastic out of the kernel; which
boundaries take the kernel and which keep to XLA's fusions, by ``pallasex.stats``;
a two-layer model's logits with the kernel against without it, the benchmark's
three planted faults with it on; served against solo ``generate()`` with it on.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import thunder_tpu as tt  # noqa: E402
from chipbench import common  # noqa: E402
from conftest import set_attn_form  # noqa: E402
from thunder_tpu.executors import pallasex as px  # noqa: E402
from thunder_tpu.models import generate as G  # noqa: E402
from thunder_tpu.models import llama  # noqa: E402

arch = common.load_module("models", "latent_hc_moe_decoder")

C = 128                 # one lane tile: the narrowest stream the kernel takes
FORMS = ("open", "join", "close")
F32 = jnp.float32


def _cfg(n, iters=20):      # twenty Sinkhorn iterations, as the benchmark's configuration
    return llama.Config(n_layer=2, n_head=2, n_embd=C, hc_mult=n, hc_sinkhorn_iters=iters, vocab_size=256,
                        padded_vocab_size=256, block_size=512)


def _hp(key, n):
    k = jax.random.split(key, 3)
    m = n * (n + 2)
    return {"phi": 0.1 * jax.random.normal(k[0], (m, n * C), F32), "norm": 1 + 0.1 * jax.random.normal(k[1], (n * C,), F32),
            "alpha": jnp.asarray([0.4, 0.3, 0.5], F32), "bias": 0.5 * jax.random.normal(k[2], (m,), F32)}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The kernel under the interpreter, float32 products at the highest precision."""
    set_attn_form(monkeypatch, "interpreted")
    with jax.default_matmul_precision("highest"):
        yield


def _operands(n, T, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, n, T, C), F32).astype(dtype), jax.random.normal(k[1], (1, T, C), F32).astype(dtype),
            _hp(k[2], n), _hp(k[3], n))


@functools.cache
def _programs(n, dtype):
    """``{form: (x', u, (H_post, H_res))}`` of a stream, what a sublayer gave and two hyper-connections, three ways: the
    kernel's three forms; the ``jax.numpy`` lines and the reference's lines on the same numbers.  Two programs a shape."""
    cfg = _cfg(n, iters=2)      # the lines it is held to unroll theirs, and twenty compile for seconds a shape
    kw = dict(eps=cfg.hc_eps, iters=cfg.hc_sinkhorn_iters, clamp=cfg.hc_res_clamp)
    s = {"hc_eps": cfg.hc_eps, "clamp": cfg.hc_res_clamp, "iters": cfg.hc_sinkhorn_iters}

    def kernel(x, f, h1, h2):
        opened = px.hc_mix(x, None, h1, **kw)
        owed = (f, opened[2])
        return {"open": opened, "join": px.hc_mix(x, owed, h2, **kw), "close": px.hc_mix(x, owed, None, **kw)}

    def lines(x, f, h1, h2):
        u, maps = G.hc_open(h1, x, cfg)
        x1 = G.hc_close(x, f, maps)
        want = {"open": (x, u, maps), "join": (x1, *G.hc_open(h2, x1, cfg)), "close": (x1, None, None)}
        X, ff = x[0].astype(F32).transpose(1, 0, 2), f[0].astype(F32)                   # (T, n, C) as the reference carries it
        there = lambda X_: X_.transpose(1, 0, 2)[None]  # noqa: E731
        maps_of = lambda post, res: (post.T[:, None], res.transpose(1, 2, 0)[:, :, None])  # noqa: E731 -- (n, 1, T), (n, n, 1, T)
        pre, post, res = arch.hc_maps(X, h1, s)
        X1 = arch.hc_write(X, ff, post, res).astype(x.dtype).astype(F32)                # the stream is stored at its dtype
        pre2, post2, res2 = arch.hc_maps(X1, h2, s)
        ref = {"open": (x, arch.hc_read(X, pre)[None], maps_of(post, res)),
               "join": (there(X1), arch.hc_read(X1, pre2)[None], maps_of(post2, res2)), "close": (there(X1), None, None)}
        return want, ref

    return jax.jit(kernel), jax.jit(lines)


@functools.cache
def _boundary(n, T, dtype):
    operands = _operands(n, T, dtype)
    kernel, lines = _programs(n, dtype)
    return (kernel(*operands), *lines(*operands))


@pytest.mark.parametrize("T", [128, 200], ids=["whole", "ragged"])
@pytest.mark.parametrize("n", [4, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("form", FORMS)
def test_a_boundary_is_hc_close_then_hc_open_and_the_references_lines(form, dtype, n, T):
    got, want, ref = _boundary(n, T, dtype)
    (x1, u, maps), (wx, wu, wmaps), (rx, ru, rmaps) = got[form], want[form], ref[form]
    # a 16-bit stream's numbers are rounded once where they are stored: one unit in the last place of the largest
    ulp = float(jnp.finfo(dtype).eps) if dtype != F32 else 0.0
    f32 = lambda a: np.asarray(a.astype(F32))  # noqa: E731
    assert x1.dtype == dtype and x1.shape == (1, n, T, C)
    if form != "open":
        np.testing.assert_allclose(f32(x1), f32(wx), atol=1e-5 + ulp * 8)
        np.testing.assert_allclose(f32(x1), f32(rx), atol=1e-5 + ulp * 8)
    if form == "close":
        assert u is None and maps is None
        return
    assert u.shape == (1, T, C) and maps[0].shape == (n, 1, T) and maps[1].shape == (n, n, 1, T)
    assert u.dtype == maps[0].dtype == maps[1].dtype == F32       # what a sublayer reads is rounded by its norm, once
    np.testing.assert_allclose(f32(u), f32(wu), atol=1e-5 + ulp * 8)
    np.testing.assert_allclose(f32(u), f32(ru), atol=1e-5 + ulp * 8)
    for m, w, r in zip(maps, wmaps, rmaps):
        # float32 on every side; a 16-bit stream's maps are float32's on the same numbers (three pieces of phi)
        # (joined, the maps are of a stream that two roundings of one sum may leave a last place apart, an element in hundreds)
        near = 5e-6 if form == "open" or dtype == F32 else 1e-3
        np.testing.assert_allclose(np.asarray(m), np.asarray(w), atol=near)
        np.testing.assert_allclose(np.asarray(m), np.asarray(r), atol=near)


@pytest.mark.parametrize("others", ["other tokens", "nothing a number"])
def test_a_tokens_numbers_are_its_own_whatever_shares_its_tile(others):
    """Bit for bit: the engine serves a request beside any other and alone alike."""
    n, T, at = 4, 200, 131      # in the ragged tile
    x, f, h1, h2 = _operands(n, T, jnp.bfloat16)
    kernel, _ = _programs(n, jnp.bfloat16)
    mine = (jnp.arange(T) == at)
    if others == "other tokens":
        x2, f2, _, _ = _operands(n, T, jnp.bfloat16, seed=9)
    else:       # what a ragged tile's rows past the end hold under the interpreter
        x2, f2 = jnp.full_like(x, jnp.nan), jnp.full_like(f, jnp.inf)
    x2, f2 = jnp.where(mine[None, None, :, None], x, x2), jnp.where(mine[None, :, None], f, f2)
    a, b = kernel(x, f, h1, h2), kernel(x2, f2, h1, h2)
    for form in FORMS:
        for p, q in zip(jax.tree_util.tree_leaves(a[form]), jax.tree_util.tree_leaves(b[form])):
            mine_p, mine_q = (np.asarray(jnp.take(v.astype(F32), at, axis=p.shape.index(T))) for v in (p, q))
            assert np.isfinite(mine_q).all()
            np.testing.assert_array_equal(mine_p, mine_q)


def test_h_res_is_doubly_stochastic_out_of_the_kernel():
    """Twenty iterations, as the configuration says: the bounds of ``test_hc_serving``'s ``hc_maps`` case."""
    n, T = 4, 128
    cfg = _cfg(n)
    x, _, hp, _ = _operands(n, T, F32, seed=3)
    run = lambda c: jax.jit(lambda x: px.hc_mix(  # noqa: E731
        x, None, hp, eps=c.hc_eps, iters=c.hc_sinkhorn_iters, clamp=c.hc_res_clamp))(x)
    _, _, (h_post, h_res) = run(cfg)
    h_res = np.asarray(h_res)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=5e-6)
    cols = np.abs(h_res.sum(axis=0) - 1.0)
    assert cols.max() < 3e-2 and np.median(cols) < 1e-4, (cols.max(), np.median(cols))
    assert (h_res > 0).all() and (0 < np.asarray(h_post)).all() and (np.asarray(h_post) < 2).all()
    np.testing.assert_allclose(h_res, np.asarray(jax.jit(lambda x: G.hc_maps(hp, x, cfg))(x)[2]), atol=5e-6)
    once = np.asarray(run(dataclasses.replace(cfg, hc_sinkhorn_iters=1))[2][1])       # the first planted control, in the kernel
    assert np.abs(once.sum(axis=0) - 1.0).max() > 10 * cols.max()


# --------------------------------------------------------------------------
# which boundaries take the kernel
# --------------------------------------------------------------------------

def _counted(fn, *shapes):
    before = dict(px.stats)
    jax.eval_shape(fn, *shapes)         # the counts are trace time's
    return {k: px.stats.get(k, 0) - before.get(k, 0) for k in ("hc_fused", "hc_fallback")}


@pytest.mark.parametrize("case,why", [("a decode step", "shape"), ("under a tile", "shape"), ("an unaligned C", "shape"),
                                      ("a mesh", "mesh"), ("planted maps", "planted maps"), ("no Pallas", "no Pallas"),
                                      ("float16", "dtype"), ("a prompt", "")])
def test_what_is_not_the_kernels_keeps_to_xlas_fusions_and_the_counts_say_so(case, why, monkeypatch):
    n = 4
    T, width, dtype = {"a decode step": (1, C, F32), "under a tile": (96, C, F32), "an unaligned C": (128, 192, F32),
                       "float16": (128, C, jnp.float16)}.get(case, (128, C, F32))
    cfg = dataclasses.replace(_cfg(n), n_embd=width)
    hp = jax.eval_shape(lambda: {**_hp(jax.random.PRNGKey(0), n), "phi": jnp.zeros((n * (n + 2), n * width)),
                                 "norm": jnp.ones((n * width,))})
    if case == "planted maps":
        monkeypatch.setattr(G, "hc_maps", lambda hp, x, cfg: G._HC_MAPS(hp, x, cfg))
    if case == "no Pallas":
        set_attn_form(monkeypatch, "xla")
    x, f = jax.ShapeDtypeStruct((2, n, T, width), dtype), jax.ShapeDtypeStruct((2, T, width), dtype)

    def both(x, f, hp):
        x, u, maps = G.hc_step(hp, (x, None), cfg, sharded=case == "a mesh")
        return G.hc_step(hp, (x, (f, maps)), cfg, sharded=case == "a mesh")

    counts = _counted(both, x, f, hp)
    if case == "a prompt":
        assert counts == {"hc_fused": 2, "hc_fallback": 0}
        assert px.hc_schedule["block_tokens"] == 128 and px.hc_schedule["grid_steps"] == 2
        assert 0 < px.hc_schedule["vmem_limit_bytes"] < 16 << 20
    else:
        assert counts == {"hc_fused": 0, "hc_fallback": 2} and px.hc_schedule["fallback"] == why, px.hc_schedule


# --------------------------------------------------------------------------
# a model under the kernel
# --------------------------------------------------------------------------

# float32 on both sides: what is left is the order of the sums, some 1e-6 of logits near 1 (the widest seen 4e-6); the
# mildest planted fault (the maps in bfloat16) reads 3e-3
LOGIT_ATOL = 5e-5


@pytest.fixture(scope="module")
def model():
    cfg = _cfg(4)
    params = llama.init_params(cfg, jax.random.PRNGKey(5), dtype=F32)
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    for bp in params["blocks"]:     # the token's own part of the maps as large as the benchmark's weights make it
        for name in ("hc_1", "hc_2"):
            hp = bp[name]
            bp[name] = {**hp, "phi": 4 * hp["phi"], "alpha": jnp.full((3,), 0.4, F32),
                        "norm": hp["norm"] + 0.1 * jax.random.normal(next(keys), hp["norm"].shape),
                        "bias": hp["bias"] + 0.3 * jax.random.normal(next(keys), hp["bias"].shape)}
    return cfg, params


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


def _logits(cfg, params, seq, **kw):
    cos, sin = llama.build_rope_cache(cfg, 256)
    before = dict(px.stats)
    run = jax.jit(lambda p, t: G.forward_with_cache(p, t, 0, G.init_cache(cfg, 1, 256, dtype=F32), cos, sin, cfg, **kw)[0])
    out = np.asarray(run(params, jnp.asarray(seq[None])))
    return out, {k: px.stats.get(k, 0) - before.get(k, 0) for k in ("hc_fused", "hc_fallback")}


@pytest.fixture(scope="module")
def sound(model):
    """A ragged prompt's logits through the kernel: every boundary of two layers fused, the last close too."""
    cfg, params = model
    seq = _tokens(160, 11)
    with pytest.MonkeyPatch.context() as env, jax.default_matmul_precision("highest"):
        set_attn_form(env, "interpreted")
        got, counts = _logits(cfg, params, seq)
    assert counts == {"hc_fused": 2 * cfg.n_layer + 1, "hc_fallback": 0}
    return seq, got


def test_a_models_logits_with_the_kernel_are_the_fallbacks(model, sound, monkeypatch):
    cfg, params = model
    seq, got = sound
    set_attn_form(monkeypatch, "xla")
    want, counts = _logits(cfg, params, seq)
    assert counts == {"hc_fused": 0, "hc_fallback": 2 * cfg.n_layer + 1}
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)
    assert np.abs(want).max() > 0.5


def test_one_rows_logits_close_one_row_and_a_mesh_keeps_every_boundary_off_the_kernel(model):
    """A prefill samples from one row: the prompt's last close is that row's (XLA's), the others the kernel's."""
    cfg, params = model
    shapes = jax.eval_shape(lambda: (params, jnp.zeros((1, 128), jnp.int32), G.init_cache(cfg, 1, 128, dtype=F32)))
    cos, sin = llama.build_rope_cache(cfg, 128)
    fwd = lambda **kw: lambda p, t, c: G.forward_with_cache(p, t, 0, c, cos, sin, cfg, **kw)[0]  # noqa: E731
    assert _counted(fwd(logits_at=jnp.int32(7)), *shapes) == {"hc_fused": 2 * cfg.n_layer, "hc_fallback": 1}
    assert px.hc_schedule == {"fallback": "shape", "tokens": 1, "width": C}
    assert _counted(fwd(sharded=True), *shapes) == {"hc_fused": 0, "hc_fallback": 2 * cfg.n_layer + 1}
    assert px.hc_schedule["fallback"] == "mesh"


@pytest.mark.parametrize("which", ["sinkhorn1", "static", "bfloat16"])
def test_a_planted_fault_of_the_hyper_connection_fails_the_comparison_with_the_kernel_on(model, sound, monkeypatch, which):
    """The benchmark's three controls plant ``generate.hc_maps`` (``chipbench/drivers/serve_latent_hc.py``): a planted
    map is honoured, so the boundaries go through it and not through the kernel's own maps, and the fault shows."""
    cfg, params = model
    seq, want = sound
    monkeypatch.setattr(G, "hc_maps", G.hc_maps)            # restored after the plant
    common.load_module("drivers", "serve_latent_hc").plant_hc_control(which)
    got, counts = _logits(cfg, params, seq)
    assert counts == {"hc_fused": 0, "hc_fallback": 2 * cfg.n_layer + 1} and px.hc_schedule["fallback"] == "planted maps"
    assert np.max(np.abs(got - want)) > 10 * LOGIT_ATOL


def test_served_is_solo_generate_with_the_kernel_on(model):
    """A prompt past a tile of tokens, its whole-prompt prefill at a bucket of 256 (the second tile's tail padding), then
    decode: the tokens solo ``generate()`` gives at the prompt's own length (its last tile ragged), and the engine's counts."""
    cfg, params = model
    prompt = _tokens(150, 41)
    before = dict(px.stats)
    eng = tt.serve(None, params, cfg, num_blocks=24, block_size=16, max_batch=2, prefill_buckets=(256,))
    handle = eng.submit(prompt, max_new_tokens=3)
    while not handle.done():
        eng.step()
    assert eng.stats()["compile_counts"]["prefill_fresh"] == 1
    # the prefill program: every boundary but the one row's last close; a decode step: every one XLA's
    assert px.stats["hc_fused"] - before.get("hc_fused", 0) == 2 * cfg.n_layer
    assert eng.stats()["hc"] == {"fused": px.stats["hc_fused"], "fallback": px.stats["hc_fallback"]}
    want = np.asarray(G.generate(params, prompt[None], cfg, 3))[0, len(prompt):]
    np.testing.assert_array_equal(np.asarray(handle.result(drive=False).new_tokens), want)
