"""Sequence-parallel training on the virtual 8-device mesh: the full llama
loss under one shard_map over ``sp`` (``distributed/sp.py``) against the
single-device train step.  The kernels' tests are in ``test_ring_attention.py``.

Every loss here runs under ``jax.jit``: called eagerly, each operation inside
the ``shard_map`` is dispatched alone over the four virtual devices, and the
five tests took one worker 746 s of tier-1's run (``--dist load``, six workers,
cut at 1,470 s on PR 54's tree) where they take under a minute compiled, to
the same loss.
"""
import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.models import llama


class TestSequenceParallelTraining:
    """Full llama loss under one shard_map over sp (distributed/sp.py)."""

    def _setup(self, **over):
        cfg = llama.Config.from_name("tiny-llama-debug", **over)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 2, 32
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)
        return cfg, params, idx, tgt, cos, sin

    def _ref(self, cfg, params, idx, tgt, cos, sin):
        import optax

        from thunder_tpu import distributed as dist

        mesh1 = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        step = dist.make_train_step(
            lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg),
            optax.sgd(0.0), mesh1, remat=False,
        )
        return step.grads(params, step.init_optimizer_state(params), idx, tgt, cos, sin)

    def _sp_loss(self, cfg, loss_fn=None):
        """``loss_fn`` (``dist.sp_gpt_loss`` unless given) over ``sp=4`` as a
        function of the arrays alone, for ``jax.jit`` and ``jax.grad``."""
        from thunder_tpu import distributed as dist

        loss_fn = loss_fn or dist.sp_gpt_loss
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        return lambda p, idx, tgt, cos, sin: loss_fn(p, idx, tgt, cos, sin, cfg, mesh=mesh)

    def test_sp_loss_matches_single_device(self):
        cfg, params, idx, tgt, cos, sin = self._setup()
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)

        loss = jax.jit(self._sp_loss(cfg))(params, idx, tgt, cos, sin)
        assert abs(float(loss) - float(ref_loss)) < 1e-4

    def test_sp_grads_match_single_device(self):
        cfg, params, idx, tgt, cos, sin = self._setup()
        ref_loss, ref_grads = self._ref(cfg, params, idx, tgt, cos, sin)

        loss, grads = jax.jit(jax.value_and_grad(self._sp_loss(cfg)))(params, idx, tgt, cos, sin)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        jax.tree_util.tree_map(
            lambda g, r: np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-5
            ),
            grads, ref_grads,
        )

    def test_sp_gqa_config(self):
        cfg, params, idx, tgt, cos, sin = self._setup(n_head=4, n_query_groups=2)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        loss = jax.jit(self._sp_loss(cfg))(params, idx, tgt, cos, sin)
        assert abs(float(loss) - float(ref_loss)) < 1e-4

    def test_sp_sliding_window_matches_single_device(self):
        # an sp loss that drops the window computes full causal attention for
        # sliding-window (Mistral-family) configs, silently.  The window must
        # thread into the ring and match the fused-SDPA reference numerics.
        cfg, params, idx, tgt, cos, sin = self._setup(sliding_window=8)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        loss = jax.jit(self._sp_loss(cfg))(params, idx, tgt, cos, sin)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        # the band must actually bite at T=32 > window=8: dropping it diverges
        nowin = llama.Config.from_name("tiny-llama-debug")
        full = jax.jit(self._sp_loss(nowin))(params, idx, tgt, cos, sin)
        assert abs(float(full) - float(ref_loss)) > 1e-4

    def test_ulysses_sliding_window_matches_ring(self):
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup(sliding_window=8)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        loss = jax.jit(self._sp_loss(cfg, dist.ulysses_gpt_loss))(params, idx, tgt, cos, sin)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
