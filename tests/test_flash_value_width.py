"""The forward flash call with keys and values at their own widths (PR 54): a
latent prompt's heads of 192 over values of 128 against the float32 reference
on the same operands, what ``flash_schedule`` says the call was built with,
and the one-width rule the backward kernels keep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import torch as ltorch
from thunder_tpu.executors import pallasex
from thunder_tpu.executors.jaxex import _sdpa_reference

# (q/k, v) widths: the latent cells', the rehearsal's, and one width as the control
WIDTHS = [(192, 128), (24, 16), (128, 128)]
# widths the kernel runs at, zeros added a row over q, k and v together
BUILT = {(192, 128): (192, 128, 0), (24, 16): (128, 128, 320), (128, 128): (128, 128, 0)}


def _qkv(hs, hv, rep, T, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + hs + rep + T), 3)
    q = jax.random.normal(ks[0], (1, 2 * rep, T, hs), dtype)
    k = jax.random.normal(ks[1], (1, 2, T, hs), dtype)
    v = jax.random.normal(ks[2], (1, 2, T, hv), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T", [256, 384], ids=["divided", "ragged"])
@pytest.mark.parametrize("rep", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("widths", WIDTHS, ids=lambda w: f"{w[0]}over{w[1]}")
def test_flash_forward_takes_values_at_their_own_width(attn_form, widths, rep, T, dtype):
    """out (as wide as v) and lse against the reference on the same operands;
    256 goes in one block of its own, 384 in one of 512 with 128 rows past the
    end.  Without Pallas the call declines and its caller takes its XLA form."""
    hs, hv = widths
    q, k, v = _qkv(hs, hv, rep, T, dtype)
    scale = hs ** -0.5
    res = pallasex.flash_sdpa(q, k, v, None, True, scale)
    if attn_form == "xla":
        assert res is None
        return
    out, lse = res
    sched = dict(pallasex.flash_schedule)
    assert out.shape == (1, 2 * rep, T, hv) and out.dtype == dtype and lse.shape == (1, 2 * rep, T)
    assert (sched["head_qk"], sched["head_v"], sched["lanes_padded"]) == BUILT[widths]
    assert sched["tail_rows"] == {256: 0, 384: 128}[T]
    oref, lref = _sdpa_reference(q, k, v, None, True, scale)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, n in ((out, oref, "out"), (lse, lref, "lse")):
        assert np.isfinite(np.asarray(a, np.float32)).all(), n
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, rtol=tol, err_msg=n)


@pytest.mark.parametrize("widths", [(192, 128), (128, 256)], ids=lambda w: f"{w[0]}over{w[1]}")
def test_the_backward_kernels_keep_one_width(monkeypatch, widths):
    """``flash_sdpa_backward`` and its checker refuse a v of another width, and
    the forward's claim follows them: the checker takes such a forward only
    where no operand asks for a gradient."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    hs, hv = widths
    q, k, v = _qkv(hs, hv, 1, 128, jnp.float32)
    out, lse = pallasex.flash_sdpa(q, k, v, None, True, hs ** -0.5)
    g = jnp.ones_like(out)
    assert pallasex._sdpa_checker(q, k, v, None, True, hs ** -0.5)
    assert not pallasex._sdpa_bwd_checker(g, q, k, v, out, lse, None, True, hs ** -0.5)
    assert pallasex.flash_sdpa_backward(g, q, k, v, out, lse, None, True, hs ** -0.5) is None
    assert not pallasex._supported(q.shape, k.shape, v.shape, q.dtype, True)
    assert pallasex._supported(q.shape, k.shape, v.shape, q.dtype, True, own_v_width=True)

    class Wants:      # what the checker reads of a proxy in a differentiated trace
        shape, dtype, requires_grad = q.shape, q.dtype, True

    assert not pallasex._sdpa_checker(Wants, k, v, None, True, hs ** -0.5)
    same = _qkv(hs, hs, 1, 128, jnp.float32)
    assert pallasex._sdpa_checker(Wants, *same[1:], None, True, hs ** -0.5)


def test_a_trained_program_with_narrow_values_claims_neither_kernel(monkeypatch):
    """``tt.value_and_grad`` over heads of 192 and values of 128: the pallas
    executor claims the forward of the inference trace and neither pass of the
    differentiated one, whose gradients are the reference's."""
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(192, 128, 1, 128, jnp.float32)

    def attend(q, k, v):
        return ltorch.scaled_dot_product_attention(q, k, v, is_causal=True)

    served = tt.jit(attend)
    out = served(q, k, v)
    assert "pallas_sdpa" in tt.last_traces(served)[-1].python()
    trained = tt.value_and_grad(lambda q, k, v: attend(q, k, v).sum(), argnums=(0, 1, 2))
    _, grads = trained(q, k, v)
    assert "pallas_sdpa" not in tt.last_traces(trained)[-1].python()
    assert "pallas_sdpa" not in tt.last_backward_traces(trained)[-1].python()

    def ref(q, k, v):
        return _sdpa_reference(q, k, v, None, True, 192 ** -0.5)[0]

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    for a, b in zip(grads, jax.grad(lambda *x: ref(*x).sum(), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
