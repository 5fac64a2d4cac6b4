"""MoE model family + int8 quantization executor (BASELINE milestone E).

Reference parity: litgpt-style LLaMAMoE (``thunder/tests/litgpt_model.py:98-110``)
and the TransformerEngine FP8 executor (``thunder/executors/
transformer_engineex.py:183-331``) — here the MoE is a dense top-k router over
stacked expert weights and quantization is dynamic int8 on the MXU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

import thunder_tpu as tt
import thunder_tpu.torch as ltorch
from thunder_tpu.models import llama

rng = np.random.default_rng(11)


def _torch_llama_moe(x, gate_w, fc1, fc2, proj, n_expert_per_token):
    """litgpt LLaMAMoE semantics: top-k on raw router logits, softmax over the
    selected k in float32, weighted sum of SwiGLU expert outputs."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    router = xf @ gate_w.T  # (S, E)
    probs, indices = torch.topk(router, n_expert_per_token)
    probs = probs.softmax(dim=1, dtype=torch.float).to(x.dtype)
    E = gate_w.shape[0]
    y = torch.zeros_like(xf)
    for e in range(E):
        mask = indices == e  # (S, k)
        w_tok = (probs * mask).sum(dim=1, keepdim=True)  # (S, 1)
        h = torch.nn.functional.silu(xf @ fc1[e].T) * (xf @ fc2[e].T)
        y = y + w_tok * (h @ proj[e].T)
    return y.reshape(B, T, C)


class TestMoE:
    def test_moe_matches_torch_reference(self):
        cfg = llama.Config.from_name("tiny-moe-debug")
        E, C, I = cfg.n_expert, cfg.n_embd, cfg.intermediate_size
        x = rng.standard_normal((2, 8, C)).astype(np.float32)
        gate = rng.standard_normal((E, C)).astype(np.float32) * 0.1
        fc1 = rng.standard_normal((E, I, C)).astype(np.float32) * 0.1
        fc2 = rng.standard_normal((E, I, C)).astype(np.float32) * 0.1
        proj = rng.standard_normal((E, C, I)).astype(np.float32) * 0.1

        mp = {"gate": jnp.asarray(gate), "fc_1": jnp.asarray(fc1), "fc_2": jnp.asarray(fc2), "proj": jnp.asarray(proj)}
        got = np.asarray(tt.jit(lambda p, t: llama.moe_mlp(p, t, cfg))(mp, x))
        ref = _torch_llama_moe(
            torch.from_numpy(x), torch.from_numpy(gate), torch.from_numpy(fc1),
            torch.from_numpy(fc2), torch.from_numpy(proj), cfg.n_expert_per_token,
        ).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_moe_model_trains(self):
        cfg = llama.Config.from_name("tiny-moe-debug")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 4, 16
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        def loss_fn(p, i, t, c, s):
            return llama.gpt_loss(p, i, t, c, s, cfg)

        v, g = tt.value_and_grad(loss_fn, argnums=(0,))(params, idx, tgt, cos, sin)
        leaves = jax.tree_util.tree_leaves(g)
        assert np.isfinite(float(v))
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in leaves)
        # router + every expert got gradient signal
        assert all(bool(jnp.any(x != 0)) for x in leaves)

    def test_moe_distributed_train_step(self):
        import optax
        from jax.sharding import PartitionSpec as P
        from thunder_tpu import distributed as dist

        cfg = llama.Config.from_name("tiny-moe-debug")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 8, 16
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        def loss_fn(p, i, t, c, s):
            return llama.gpt_loss(p, i, t, c, s, cfg)

        mesh = dist.make_mesh({"dp": 2, "fsdp": 4})
        p_sh = dist.fsdp(params, mesh, min_size=64)
        step = dist.make_train_step(
            loss_fn, optax.sgd(0.1), mesh,
            batch_specs=(P(("dp", "fsdp")), P(("dp", "fsdp")), P(), P()),
            donate=False,
        )
        opt_state = step.init_optimizer_state(p_sh)
        np_, no_, l1 = step(p_sh, opt_state, idx, tgt, cos, sin)
        _, _, l2 = step(np_, no_, idx, tgt, cos, sin)
        assert float(l2) < float(l1)

    def test_mixtral_like_config_traces(self):
        cfg = llama.Config.from_name("mixtral-like")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 2, 32
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)
        logits = tt.jit(lambda p, i, c, s: llama.gpt_forward(p, i, c, s, cfg))(params, idx, cos, sin)
        assert logits.shape == (B, T, cfg.padded_vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))


class TestExpertParallel:
    """GShard-style all_to_all expert dispatch over an ``ep`` mesh axis."""

    def _mk(self):
        from thunder_tpu import distributed as dist

        cfg = llama.Config.from_name("tiny-moe-debug")  # E=4, k=2
        E, C, I = cfg.n_expert, cfg.n_embd, cfg.intermediate_size
        x = rng.standard_normal((8, 16, C)).astype(np.float32)
        mp = {
            "gate": jnp.asarray(rng.standard_normal((E, C)).astype(np.float32) * 0.1),
            "fc_1": jnp.asarray(rng.standard_normal((E, I, C)).astype(np.float32) * 0.1),
            "fc_2": jnp.asarray(rng.standard_normal((E, I, C)).astype(np.float32) * 0.1),
            "proj": jnp.asarray(rng.standard_normal((E, C, I)).astype(np.float32) * 0.1),
        }
        mesh = dist.make_mesh({"ep": 4, "tp": 2})
        return cfg, mp, x, mesh

    def test_matches_dense_when_capacity_ample(self):
        from thunder_tpu.distributed import moe as ep

        cfg, mp, x, mesh = self._mk()
        dense = np.asarray(tt.jit(lambda p, t: llama.moe_mlp(p, t, cfg))(mp, x))
        out = jax.jit(lambda mp_, x_: ep.ep_moe_mlp(        # compiled, as every shard_map of this class: see below
            mp_, x_, mesh=mesh, n_expert=cfg.n_expert,
            n_expert_per_token=cfg.n_expert_per_token, capacity_factor=8.0,
        ))(mp, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-4, atol=1e-5)

    def test_grads_flow_through_all_to_all(self):
        from thunder_tpu.distributed import moe as ep

        cfg, mp, x, mesh = self._mk()

        def loss(mp_, x_):
            y = ep.ep_moe_mlp(mp_, x_, mesh=mesh, n_expert=cfg.n_expert,
                              n_expert_per_token=2, capacity_factor=8.0)
            return jnp.sum(y ** 2)

        # compiled: eagerly each operation under the shard_map is dispatched alone
        g = jax.jit(jax.grad(loss))(mp, jnp.asarray(x))
        leaves = jax.tree_util.tree_leaves(g)
        assert all(bool(jnp.all(jnp.isfinite(v))) for v in leaves)
        assert all(bool(jnp.any(v != 0)) for v in leaves)

    def test_tight_capacity_drops_but_runs(self):
        from thunder_tpu.distributed import moe as ep

        cfg, mp, x, mesh = self._mk()
        out = jax.jit(lambda mp_, x_: ep.ep_moe_mlp(mp_, x_, mesh=mesh, n_expert=cfg.n_expert,
                                                    n_expert_per_token=2, capacity_factor=0.5))(mp, jnp.asarray(x))
        assert bool(jnp.all(jnp.isfinite(out)))


class TestQuantExecutor:
    def test_int8_linear_accuracy(self):
        from thunder_tpu.executors import quantex

        a = rng.standard_normal((8, 256)).astype(np.float32)
        w = rng.standard_normal((128, 256)).astype(np.float32) * 0.05
        b = rng.standard_normal((128,)).astype(np.float32) * 0.1
        got = np.asarray(quantex.int8_linear(jnp.asarray(a), jnp.asarray(w), jnp.asarray(b)))
        ref = a @ w.T + b
        rel = np.abs(got - ref) / (np.abs(ref) + 1e-3)
        assert np.median(rel) < 2e-2, float(np.median(rel))

    def test_int8_matmul_accuracy(self):
        from thunder_tpu.executors import quantex

        a = rng.standard_normal((2, 8, 256)).astype(np.float32)
        b = rng.standard_normal((2, 256, 64)).astype(np.float32) * 0.05
        got = np.asarray(quantex.int8_matmul(jnp.asarray(a), jnp.asarray(b)))
        ref = a @ b
        rel = np.abs(got - ref) / (np.abs(ref) + 1e-3)
        assert np.median(rel) < 2e-2, float(np.median(rel))

    def test_executor_claims_linear(self):
        from thunder_tpu.executors import jaxex, quantex, xlaex

        a = rng.standard_normal((8, 256)).astype(np.float32)
        w = rng.standard_normal((64, 256)).astype(np.float32) * 0.05

        jfn = tt.jit(lambda x, ww: ltorch.linear(x, ww), executors=[quantex.ex, xlaex.ex, jaxex.ex])
        got = np.asarray(jfn(a, w))
        src = tt.last_traces(jfn)[-1].python()
        assert "int8_linear" in src, src
        ref = a @ w.T
        rel = np.abs(got - ref) / (np.abs(ref) + 1e-3)
        assert np.median(rel) < 2e-2

    def test_small_k_not_claimed(self):
        from thunder_tpu.executors import jaxex, quantex, xlaex

        # a generator of its own: the file's shared one gives data that follow which of its tests a worker ran before,
        # and a sum of 16 products that lands near zero has no relative precision to hold to 1e-5
        r = np.random.default_rng(59)
        a = r.standard_normal((8, 16)).astype(np.float32)
        w = r.standard_normal((8, 16)).astype(np.float32)
        jfn = tt.jit(lambda x, ww: ltorch.linear(x, ww), executors=[quantex.ex, xlaex.ex, jaxex.ex])
        got = np.asarray(jfn(a, w))
        src = tt.last_traces(jfn)[-1].python()
        assert "int8_linear" not in src
        np.testing.assert_allclose(got, a @ w.T, rtol=1e-5)

    def test_quantized_moe_inference(self):
        # milestone E: mixtral-like MoE forward under the int8 executor
        from thunder_tpu.executors import jaxex, quantex, xlaex

        cfg = llama.Config.from_name("mixtral-like")
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 2, 32
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        def fwd(p, i, c, s):
            return llama.gpt_forward(p, i, c, s, cfg)

        ref = np.asarray(tt.jit(fwd)(params, idx, cos, sin))
        jfn = tt.jit(fwd, executors=[quantex.ex, xlaex.ex, jaxex.ex])
        got = np.asarray(jfn(params, idx, cos, sin))
        src = tt.last_traces(jfn)[-1].python()
        assert "int8_linear" in src
        # logits agree to quantization tolerance
        denom = np.abs(ref).mean()
        assert np.abs(got - ref).mean() / denom < 0.1, float(np.abs(got - ref).mean() / denom)


class TestQuantizedTraining:
    """Int8 TRAINING (VERDICT r2 item 3): the TE-executor contract — int8
    forward GEMMs, full-precision grads (reference
    transformer_engineex.py:183-336 claims prims.linear inside the training
    fw+bw; here quant claims the forward trace only)."""

    def _train(self, quant, steps=12):
        import optax

        from thunder_tpu import distributed as dist

        cfg = llama.Config.from_name("tiny-llama-debug")
        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, T = 4, 32
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
        cos, sin = llama.build_rope_cache(cfg, T)

        def loss_fn(p, i, t, c, s):
            return llama.gpt_loss(p, i, t, c, s, cfg)

        step = dist.make_train_step(loss_fn, optax.adamw(3e-3), mesh, quant=quant)
        opt = step.init_optimizer_state(params)
        losses = []
        for _ in range(steps):
            params, opt, loss = step(params, opt, idx, tgt, cos, sin)
            losses.append(float(loss))
        return losses, step

    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_quantized_training_converges_like_fp32(self, mode):
        l_fp, _ = self._train(None)
        l_q, _ = self._train(mode)
        # both learn; the quantized path tracks full precision closely
        assert l_fp[-1] < l_fp[0] - 0.2
        assert l_q[-1] < l_q[0] - 0.2
        assert abs(l_q[-1] - l_fp[-1]) < 0.15, (l_q[-1], l_fp[-1])

    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_quant_claims_forward_only(self, mode):
        _, step = self._train(mode, steps=1)
        fw_src = step.fw_trace.python()
        bw_src = step.bw_trace.python()
        assert f"{mode}_linear" in fw_src or f"{mode}_matmul" in fw_src, fw_src[:2000]
        assert f"{mode}_linear" not in bw_src and f"{mode}_matmul" not in bw_src, (
            "grads must stay full precision (TE contract)"
        )

    def test_fp8_linear_numerics(self):
        from thunder_tpu.executors import quantex

        a = rng.standard_normal((16, 64)).astype(np.float32)
        w = rng.standard_normal((32, 64)).astype(np.float32) * 0.05
        got = np.asarray(quantex.fp8_linear(jnp.asarray(a), jnp.asarray(w)))
        ref = a @ w.T
        # e4m3 keeps ~2 significant digits (TE contract)
        err = np.abs(got - ref) / (np.abs(ref) + 1e-3)
        assert np.median(err) < 0.05, np.median(err)
