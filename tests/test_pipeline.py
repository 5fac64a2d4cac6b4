"""Pipeline parallelism (GPipe over a ``pp`` mesh axis) — correctness vs the
single-device reference model.  Beyond-reference capability (SURVEY §2.6: the
reference has no PP)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu import distributed as dist
from thunder_tpu.distributed.pipeline import (
    gpipe,
    place_pipeline_params,
    pp_gpt_loss,
    stack_blocks,
)
from thunder_tpu.models import llama


def _setup(n_layer=4, B=4, T=16):
    cfg = llama.Config.from_name("tiny-llama-debug", n_layer=n_layer)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, T)
    return cfg, params, idx, tgt, cos, sin


def test_gpipe_identity_schedule():
    """A stage_fn of +1 per stage: every microbatch must pass through every
    stage exactly once (output = input + S)."""
    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    n_micro, mb = 3, 2
    mbs = jnp.arange(n_micro * mb * 5, dtype=jnp.float32).reshape(n_micro, mb, 5)
    blocks = {"b": jnp.zeros((4, 1))}  # 4 stages, one dummy layer each

    def stage_fn(blocks_loc, x):
        return x + 1.0 + 0.0 * jnp.sum(blocks_loc["b"])

    out = gpipe(stage_fn, blocks, mbs, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mbs) + 4.0, rtol=1e-6)


def _ref_loss_and_grads(cfg, params, idx, tgt, cos, sin):
    """Single-device framework loss/grads via the TrainStep grads entry."""
    import optax

    mesh1 = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = dist.make_train_step(
        lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, cfg),
        optax.sgd(0.0),
        mesh1,
        remat=False,
    )
    opt_state = step.init_optimizer_state(params)
    return step.grads(params, opt_state, idx, tgt, cos, sin)


@pytest.mark.parametrize("n_micro", [1, 2, 4])
def test_pp_loss_matches_single_device(n_micro):
    cfg, params, idx, tgt, cos, sin = _setup()
    ref, _ = _ref_loss_and_grads(cfg, params, idx, tgt, cos, sin)
    ref = float(ref)

    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_params = place_pipeline_params(stack_blocks(params), mesh)
    loss = float(
        pp_gpt_loss(pp_params, idx, tgt, cos, sin, cfg, mesh=mesh, n_micro=n_micro)
    )
    assert abs(loss - ref) < 1e-4, f"pp loss {loss} vs single-device {ref}"


def test_pp_sliding_window_matches_single_device():
    """Sliding-window (Mistral-family) configs through pp: the stage fn
    traces models.llama.block_forward, which threads config.sliding_window
    into the fused SDPA — assert the numerics actually match (the
    sp/ulysses analogs of this path once dropped the window)."""
    cfg, params, idx, tgt, cos, sin = _setup(T=32)
    cfg = llama.Config.from_name("tiny-llama-debug", n_layer=4, sliding_window=8)
    ref, _ = _ref_loss_and_grads(cfg, params, idx, tgt, cos, sin)
    ref = float(ref)

    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_params = place_pipeline_params(stack_blocks(params), mesh)
    loss = float(
        pp_gpt_loss(pp_params, idx, tgt, cos, sin, cfg, mesh=mesh, n_micro=2)
    )
    assert abs(loss - ref) < 1e-4, f"pp loss {loss} vs single-device {ref}"
    # and the band bites at T=32 > window=8
    nowin = llama.Config.from_name("tiny-llama-debug", n_layer=4)
    full = float(
        pp_gpt_loss(pp_params, idx, tgt, cos, sin, nowin, mesh=mesh, n_micro=2)
    )
    assert abs(full - ref) > 1e-4


def test_pp_grads_match_single_device():
    cfg, params, idx, tgt, cos, sin = _setup()

    ref_loss, ref_grads = _ref_loss_and_grads(cfg, params, idx, tgt, cos, sin)
    ref_stacked = stack_blocks(
        {**params, "blocks": jax.tree_util.tree_map(lambda x: x, ref_grads["blocks"])}
    )["blocks"]

    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_params = place_pipeline_params(stack_blocks(params), mesh)
    # compiled: eagerly each operation under the shard_map is dispatched alone
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: pp_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh, n_micro=2)
    ))(pp_params)

    assert abs(float(loss) - float(ref_loss)) < 1e-4
    for name, ref_g in (("wte", ref_grads["wte"]), ("ln_f", ref_grads["ln_f"])):
        np.testing.assert_allclose(
            np.asarray(grads[name]), np.asarray(ref_g), rtol=2e-3, atol=2e-5
        )
    jax.tree_util.tree_map(
        lambda g, r: np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-5
        ),
        grads["blocks"],
        ref_stacked,
    )


def test_pp_trains():
    """Two pipeline train steps with optax decrease the loss."""
    import optax

    cfg, params, idx, tgt, cos, sin = _setup()
    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_params = place_pipeline_params(stack_blocks(params), mesh)
    opt = optax.adam(1e-2)
    opt_state = opt.init(pp_params)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(
            lambda p: pp_gpt_loss(p, idx, tgt, cos, sin, cfg, mesh=mesh, n_micro=2)
        )(p)
        upd, o = opt.update(g, o, p)
        return optax.apply_updates(p, upd), o, loss

    losses = []
    for _ in range(3):
        pp_params, opt_state, loss = step(pp_params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_pp_loss_layernorm_config():
    """norm_class dispatch in the replicated final norm (code-review round 2)."""
    cfg, params, idx, tgt, cos, sin = _setup()
    import dataclasses

    cfg = dataclasses.replace(cfg, norm_class="LayerNorm")
    ref, _ = _ref_loss_and_grads(cfg, params, idx, tgt, cos, sin)

    mesh = dist.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_params = dist.place_pipeline_params(dist.stack_blocks(params), mesh)
    loss = float(dist.pp_gpt_loss(pp_params, idx, tgt, cos, sin, cfg, mesh=mesh, n_micro=2))
    assert abs(loss - float(ref)) < 1e-4, f"pp layernorm loss {loss} vs {float(ref)}"
