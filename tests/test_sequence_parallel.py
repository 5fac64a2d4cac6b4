"""Sequence-parallel training on the virtual 8-device mesh: the full llama
loss under one shard_map over ``sp`` (``distributed/sp.py``) against the
single-device train step.  A file of its own (the kernels' tests are in
``test_ring_attention.py``) so that ``--dist loadfile`` can give it a worker.
"""
import jax
import jax.numpy as jnp
import numpy as np

from thunder_tpu.models import llama


class TestSequenceParallelTraining:
    """Full llama loss under one shard_map over sp (distributed/sp.py)."""

    def _setup(self, **over):
        from thunder_tpu.models import llama

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
        from thunder_tpu.models import llama

        mesh1 = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        step = dist.make_train_step(
            lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg),
            optax.sgd(0.0), mesh1, remat=False,
        )
        return step.grads(params, step.init_optimizer_state(params), idx, tgt, cos, sin)

    def test_sp_loss_matches_single_device(self):
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup()
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)

        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss = dist.sp_gpt_loss(params, idx, tgt, cos, sin, cfg, mesh=mesh)
        assert abs(float(loss) - float(ref_loss)) < 1e-4

    def test_sp_grads_match_single_device(self):
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup()
        ref_loss, ref_grads = self._ref(cfg, params, idx, tgt, cos, sin)

        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss, grads = jax.value_and_grad(
            lambda p: dist.sp_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh)
        )(params)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        jax.tree_util.tree_map(
            lambda g, r: np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-5
            ),
            grads, ref_grads,
        )

    def test_sp_gqa_config(self):
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup(n_head=4, n_query_groups=2)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss = dist.sp_gpt_loss(params, idx, tgt, cos, sin, cfg, mesh=mesh)
        assert abs(float(loss) - float(ref_loss)) < 1e-4

    def test_sp_sliding_window_matches_single_device(self):
        # an sp loss that drops the window computes full causal attention for
        # sliding-window (Mistral-family) configs, silently.  The window must
        # thread into the ring and match the fused-SDPA reference numerics.
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup(sliding_window=8)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss = dist.sp_gpt_loss(params, idx, tgt, cos, sin, cfg, mesh=mesh)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        # the band must actually bite at T=32 > window=8: dropping it diverges
        nowin = llama.Config.from_name("tiny-llama-debug")
        full = dist.sp_gpt_loss(params, idx, tgt, cos, sin, nowin, mesh=mesh)
        assert abs(float(full) - float(ref_loss)) > 1e-4

    def test_ulysses_sliding_window_matches_ring(self):
        from thunder_tpu import distributed as dist

        cfg, params, idx, tgt, cos, sin = self._setup(sliding_window=8)
        ref_loss, _ = self._ref(cfg, params, idx, tgt, cos, sin)
        mesh = dist.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        loss = dist.ulysses_gpt_loss(params, idx, tgt, cos, sin, cfg, mesh=mesh)
        assert abs(float(loss) - float(ref_loss)) < 1e-4
