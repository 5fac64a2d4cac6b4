"""Ring attention (sequence/context parallelism) on the virtual 8-device mesh.

Beyond-reference capability (the reference has no sequence parallelism,
SURVEY §2.6): blockwise ring attention over ``sp`` must reproduce the
single-device softmax exactly — forward and gradients.

Every ring here runs under ``jax.jit``: eagerly, each operation inside the
``shard_map`` is dispatched alone over the eight virtual devices (the gradients'
test took 95 s so, and takes seconds compiled, to the same tolerances).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu import distributed as dist
from thunder_tpu.distributed.ring_attention import ring_attention, ring_self_attention
from thunder_tpu.models import llama

rng = np.random.default_rng(23)


def _ref_attention(q, k, v, causal, scale=None, window=None):
    hs = q.shape[-1]
    scale = scale or 1.0 / np.sqrt(hs)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        if window is not None:
            col = jnp.arange(T)
            mask = mask & (col[None, :] > col[:, None] - window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), p.dtype.type(1) * v).astype(q.dtype)


def _qkv(B=2, H=2, T=64, hs=16, dtype=np.float32):
    q = rng.standard_normal((B, H, T, hs)).astype(dtype)
    k = rng.standard_normal((B, H, T, hs)).astype(dtype)
    v = rng.standard_normal((B, H, T, hs)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [True, False])
def test_matches_single_device(causal):
    q, k, v = _qkv()
    mesh = dist.make_mesh({"sp": 8})
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=causal))(q, k, v)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_composes_with_other_axes():
    q, k, v = _qkv(T=32)
    mesh = dist.make_mesh({"dp": 2, "sp": 4})
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh, axis="sp", causal=True))(q, k, v)
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_gradients_match_single_device():
    q, k, v = _qkv(T=32, B=1, H=2, hs=8)
    mesh = dist.make_mesh({"sp": 8})

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=True) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), rtol=1e-4, atol=1e-5)


def test_bf16_inputs():
    q, k, v = _qkv(dtype=np.float32)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    mesh = dist.make_mesh({"sp": 8})
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True))(q, k, v)
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(ref, dtype=np.float32), rtol=5e-2, atol=5e-2
    )


def test_self_attention_layer():
    B, T, C, H = 2, 64, 32, 4
    x = jnp.asarray(rng.standard_normal((B, T, C)).astype(np.float32))
    wq, wk, wv, wo = (jnp.asarray(rng.standard_normal((C, C)).astype(np.float32) * 0.1) for _ in range(4))
    mesh = dist.make_mesh({"sp": 8})
    got = jax.jit(
        lambda x, wq, wk, wv, wo: ring_self_attention(x, wq, wk, wv, wo, mesh=mesh, n_head=H)
    )(x, wq, wk, wv, wo)

    q = (x @ wq.T).reshape(B, T, H, C // H).transpose(0, 2, 1, 3)
    k = (x @ wk.T).reshape(B, T, H, C // H).transpose(0, 2, 1, 3)
    v = (x @ wv.T).reshape(B, T, H, C // H).transpose(0, 2, 1, 3)
    y = _ref_attention(q, k, v, True).transpose(0, 2, 1, 3).reshape(B, T, C)
    ref = y @ wo.T
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [1, 8, 9, 10, 24])
def test_sliding_window_exact_and_skips_far_steps(window):
    """The band must match a dense banded softmax exactly, AND fully-masked
    ring steps must disappear at trace time: window=8 over t_loc=8 shards
    needs 2 resident blocks (1 k/v rotation), not the full 8-step ring."""
    q, k, v = _qkv(T=64)  # sp=8 -> t_loc=8
    mesh = dist.make_mesh({"sp": 8})
    ring = lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True, window=window)  # noqa: E731
    got = jax.jit(ring)(q, k, v)
    ref = _ref_attention(q, k, v, True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-6)

    t_loc = 8
    expected_steps = min(8, 1 if window <= 1 else (window - 2) // t_loc + 2)
    jaxpr = str(jax.make_jaxpr(ring)(q, k, v))
    # one k + one v ppermute per rotation; the last step does not rotate
    assert jaxpr.count("ppermute") == 2 * (expected_steps - 1), (window, expected_steps)


def test_long_sequence_under_jit():
    # the point of the ring: a long sequence sharded 8 ways compiles and runs
    q, k, v = _qkv(B=1, H=2, T=1024, hs=16)
    mesh = dist.make_mesh({"sp": 8})
    fn = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True))
    out = fn(q, k, v)
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


class TestUlysses:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism — the
    second long-context scheme next to the ring (neither exists in the
    reference, SURVEY §2.6)."""

    def _setup(self, T=64, B=2):
        cfg = llama.Config.from_name("tiny-llama-debug")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)
        return cfg, params, idx, tgt, cos, sin

    def test_loss_matches_single_device(self):
        cfg, params, idx, tgt, cos, sin = self._setup()
        single_mesh = dist.make_mesh({"sp": 1}, devices=jax.devices()[:1])
        single = float(jax.jit(
            lambda p: dist.sp_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=single_mesh)
        )(params))
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss = float(jax.jit(
            lambda p: dist.ulysses_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh)
        )(params))
        np.testing.assert_allclose(loss, single, rtol=1e-5)

    def test_grads_match_ring_sp(self):
        cfg, params, idx, tgt, cos, sin = self._setup()
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        _, g_u = jax.jit(jax.value_and_grad(
            lambda p: dist.ulysses_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh)
        ))(params)
        _, g_r = jax.jit(jax.value_and_grad(
            lambda p: dist.sp_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh)
        ))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_u), jax.tree_util.tree_leaves(g_r)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4)

    def test_attend_shard_matches_dense(self):
        """ulysses_attend_shard under shard_map == dense causal attention."""
        from jax.sharding import PartitionSpec as P

        B, H, T, hs = 2, 4, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, H, T, hs))
        k = jax.random.normal(ks[1], (B, H, T, hs))
        v = jax.random.normal(ks[2], (B, H, T, hs))
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        from thunder_tpu.distributed.prims import shard_map_compat

        out = jax.jit(shard_map_compat(
            lambda q, k, v: dist.ulysses_attend_shard(q, k, v, axis="sp", sp=4),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
        ))(q, k, v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / (hs ** 0.5)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
