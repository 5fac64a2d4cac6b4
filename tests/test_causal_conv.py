"""``prims.causal_conv1d`` with its activation: the XLA form against a plain
reference, and the Pallas kernels (``causal_conv1d_fwd``, ``causal_conv1d_bwd``,
interpreted here) against the XLA form: forward, ``dx`` and ``dw``, with and
without SiLU, sequences below, at and past a tile (a ragged last tile among
them), an impulse whose taps reach across a tile's edge, and what the kernels
decline.  ``tests/test_pallas_tpu_lowering.py`` compiles them for a v5e."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import thunder_tpu as tt
import thunder_tpu.torch as ltorch
from thunder_tpu.executors import jaxex
from thunder_tpu.executors import pallasex as px

F32, BF = jnp.float32, jnp.bfloat16


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")


def rel(a, b) -> float:
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def operands(B, T, C, K, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x, g = (jax.random.normal(k, (B, T, C)).astype(dtype) for k in ks[:2])
    return g, x, (0.5 * jax.random.normal(ks[2], (C, K))).astype(dtype)


def plain(x, w, activation):
    """The definition, token by token over the taps: float32 throughout."""
    K, T = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + T] * w.astype(F32)[:, j] for j in range(K))
    return y * jax.nn.sigmoid(y) if activation == "silu" else y


@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("K", [1, 2, 4])
def test_xla_form_and_its_backward_match_the_definition(K, activation):
    g, x, w = operands(2, 40, 96, K, F32)
    assert rel(jaxex._causal_conv1d_xla(x, w, activation), plain(x, w, activation)) < 1e-6
    want = jax.grad(lambda x_, w_: jnp.sum(plain(x_, w_, activation) * g), argnums=(0, 1))(x, w)
    got = jaxex._causal_conv1d_backward_xla(g, x, w, activation)
    assert max(rel(a, b) for a, b in zip(got, want)) < 1e-5


# T by where it stands against the tile of rows that (C, dtype) derive: below it,
# the tile itself, past it by a ragged part, two whole tiles
WHERE = {"below": lambda tT, h: max(h, tT // 2 // h * h), "at": lambda tT, h: tT,
         "past_ragged": lambda tT, h: tT + 3 * h, "two_tiles": lambda tT, h: 2 * tT}
# (C, K, dtype, B, bytes of a tile: the module's own, or small ones so that short sequences span tiles)
WIDTHS = [(128, 4, F32, 2, 1 << 15), (128, 2, BF, 1, 1 << 14), (384, 3, BF, 2, 1 << 16), (384, 4, F32, 1, 1 << 17),
          (1024, 4, BF, 1, 1 << 17), (1024, 3, F32, 2, 1 << 18), (1024, 4, BF, 1, None), (128, 4, F32, 1, None)]


@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("where", sorted(WHERE))
@pytest.mark.parametrize("C,K,dtype,B,tile_bytes", WIDTHS,
                         ids=[f"C{c}-K{k}-{jnp.dtype(d).name}-B{b}-{'derived' if t is None else t}"
                              for c, k, d, b, t in WIDTHS])
def test_interpreted_kernels_match_the_xla_form(interpreted, monkeypatch, C, K, dtype, B, tile_bytes, where, activation):
    if tile_bytes is None and where == "two_tiles":
        pytest.skip("the module's own tile twice over is the lowering test's shape, not the interpreter's")
    if tile_bytes is not None:
        monkeypatch.setattr(px, "_CONV_TILE_BYTES", tile_bytes)
    item = jnp.dtype(dtype).itemsize
    h = 32 // item
    tT = px._conv_tiles(1 << 20, C, item)[0]
    T = WHERE[where](tT, h)
    g, x, w = operands(B, T, C, K, dtype, seed=K)
    before = px.stats.get("causal_conv", 0)
    out = px.causal_conv1d(x, w, activation)
    dx, dw = px.causal_conv1d_backward(g, x, w, activation)
    assert px.stats["causal_conv"] == before + 2
    assert px.conv_schedule == {"tile_t": min(T, tT), "tile_c": C,     # every width here is one tile of channels
                                "halo_rows": h, "bytes_a_forward_call": 2 * B * T * C * item,
                                "bytes_a_backward_call": 3 * B * T * C * item}
    assert out.dtype == dx.dtype == x.dtype and dw.dtype == w.dtype and dw.shape == w.shape
    want = jaxex._causal_conv1d_xla(x, w, activation)
    wdx, wdw = jaxex._causal_conv1d_backward_xla(g, x, w, activation)
    # the same float32 terms in another order, rounded once: the last place of the dtype at most
    tol = 1e-6 if dtype == F32 else 2e-3
    assert rel(out, want) < tol and rel(dx, wdx) < tol and rel(dw, wdw) < tol, (rel(out, want), rel(dx, wdx), rel(dw, wdw))
    assert bool(jnp.isfinite(dw.astype(F32)).all()) and bool(jnp.isfinite(dx.astype(F32)).all())


@pytest.mark.parametrize("dtype", [F32, BF], ids=["float32", "bfloat16"])
def test_an_impulse_on_a_tiles_last_row_reaches_the_next_tile_through_the_halo(interpreted, monkeypatch, dtype):
    """x is one at the last row of the first tile: the next K - 1 rows, the
    first of the second tile, read it through the block behind.  A gradient
    of one on the second tile's first row reaches the K - 1 rows before it,
    the last of the first tile, through the block ahead."""
    monkeypatch.setattr(px, "_CONV_TILE_BYTES", 1 << 15)
    C, K = 128, 4
    tT = px._conv_tiles(1 << 20, C, jnp.dtype(dtype).itemsize)[0]
    T = 2 * tT
    w = (jnp.arange(1, K + 1, dtype=F32)[None, :] * jnp.ones((C, 1))).astype(dtype)     # tap j weighs j + 1
    x = jnp.zeros((1, T, C), dtype).at[0, tT - 1].set(1.0)
    out = px.causal_conv1d(x, w, None)
    assert px.conv_schedule["tile_t"] == tT
    # tap j weighs the token K - 1 - j back: s rows after the impulse reads w[:, K - 1 - s]
    want = jnp.zeros((T,)).at[tT - 1:tT - 1 + K].set(jnp.arange(K, 0, -1.0))
    assert jnp.array_equal(out[0, :, 0].astype(F32), want) and jnp.array_equal(out[0, :, 0], out[0, :, C - 1])
    g = jnp.zeros((1, T, C), dtype).at[0, tT].set(1.0)
    dx, dw = px.causal_conv1d_backward(g, x, w, None)
    want = jnp.zeros((T,)).at[tT - K + 1:tT + 1].set(jnp.arange(1.0, K + 1))
    assert jnp.array_equal(dx[0, :, 0].astype(F32), want)
    # dw[:, j] = sum_t g[t] x[t - (K - 1 - j)]: the impulse is one row behind the gradient
    assert jnp.array_equal(dw[0].astype(F32), jnp.zeros((K,)).at[K - 2].set(1.0))


@pytest.mark.parametrize("why,shape,K,dtype,activation", [
    ("channels_no_whole_lane_tile", (2, 64, 96), 4, F32, "silu"),
    ("time_no_whole_sublane_tile", (1, 36, 128), 4, F32, None),
    ("bfloat16_rows_pack_by_sixteen", (1, 40, 128), 4, BF, "silu"),
    ("more_taps_than_a_halo_holds", (1, 64, 128), 9, F32, None),
    ("float16", (1, 64, 128), 4, jnp.float16, None),
])
def test_what_does_not_fit_takes_the_xla_form_and_counts_no_claim(interpreted, why, shape, K, dtype, activation):
    g, x, w = operands(*shape, K, dtype)
    before = px.stats.get("causal_conv", 0)
    assert px.causal_conv1d(x, w, activation) is None and px.causal_conv1d_backward(g, x, w, activation) is None
    out = jaxex._causal_conv1d_impl(x, w, activation)
    dx, dw = jaxex._causal_conv1d_backward_impl(g, x, w, activation)
    assert px.stats.get("causal_conv", 0) == before
    assert rel(out, plain(x, w, activation)) < 1e-2 and dx.shape == x.shape and dw.shape == w.shape


def test_without_pallas_the_prim_runs_the_xla_form(monkeypatch):
    monkeypatch.delenv("THUNDER_TPU_PALLAS_INTERPRET", raising=False)
    g, x, w = operands(1, 64, 128, 4, F32)
    before = px.stats.get("causal_conv", 0)
    assert rel(jaxex._causal_conv1d_impl(x, w, "silu"), plain(x, w, "silu")) < 1e-6
    assert px.stats.get("causal_conv", 0) == before


@pytest.mark.parametrize("activation", [None, "silu"])
def test_the_prim_through_jit_claims_both_kernels_and_saves_x_alone(interpreted, activation):
    """``ltorch.causal_conv1d`` traced and differentiated by its own backward
    rule: both passes claimed, the gradients those of the definition, and the
    backward trace takes ``x`` and ``w`` from the forward pass and no sum
    before the activation."""
    g, x, w = operands(2, 64, 128, 4, F32)
    before = px.stats.get("causal_conv", 0)
    vg = tt.value_and_grad(lambda x_, w_, g_: ltorch.sum(ltorch.causal_conv1d(x_, w_, activation=activation) * g_),
                           argnums=(0, 1))
    loss, grads = vg(x, w, g)
    assert px.stats["causal_conv"] == before + 2
    ref_loss, ref = jax.value_and_grad(lambda x_, w_: jnp.sum(plain(x_, w_, activation) * g), argnums=(0, 1))(x, w)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * abs(float(ref_loss)) + 1e-5
    assert max(rel(a, b) for a, b in zip(grads, ref)) < 1e-5
    bw = tt.last_backward_traces(vg)[-1]
    assert sum(getattr(p, "shape", None) == x.shape for p in bw.args) == 2, [(p.name, p.shape) for p in bw.args]   # x, g


def test_an_unknown_activation_is_refused_at_trace_time():
    g, x, w = operands(1, 16, 128, 4, F32)
    with pytest.raises(Exception, match="activation"):
        tt.jit(lambda x_, w_: ltorch.causal_conv1d(x_, w_, activation="gelu"))(x, w)
