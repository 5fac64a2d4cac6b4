"""Distributed tests on a virtual 8-device CPU mesh.

The reference needs real multi-GPU processes for these
(``thunder/tests/distributed/test_ddp.py``); on XLA we run true SPMD on
virtual devices — same compiled collectives, no hardware (SURVEY.md §4).
Correctness bar: a distributed train step must reproduce the single-device
step bit-for-bit-ish (fp32 tolerance) for DDP, FSDP(ZeRO), and TP×FSDP.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import thunder_tpu as tt
from thunder_tpu import distributed as dist
from thunder_tpu.models import llama


def _setup(B=8, T=16):
    cfg = llama.Config.from_name("tiny-llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, T)

    def loss_fn(params, idx, targets, cos, sin):
        return llama.gpt_loss(params, idx, targets, cos, sin, cfg)

    return cfg, params, (idx, tgt, cos, sin), loss_fn


BATCH_SPECS = (P(("dp", "fsdp")), P(("dp", "fsdp")), P(), P())


def _single_device_step(loss_fn, params, batch, optimizer):
    val, grads = tt.value_and_grad(loss_fn)(params, *batch)
    opt_state = optimizer.init(params)
    updates, _ = optimizer.update(grads, opt_state, params)
    return val, optax.apply_updates(params, updates)


def _assert_tree_close(a, b, atol=1e-5):
    fa, _ = jax.tree_util.tree_flatten(a)
    fb, _ = jax.tree_util.tree_flatten(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol, rtol=1e-4)


def test_device_count():
    assert jax.device_count() >= 8, "tests need the 8-device virtual CPU mesh (conftest)"


def test_comm_prims_under_shard_map():
    mesh = dist.make_mesh({"x": 8})
    from thunder_tpu.executors.jaxex import prim_impls
    from thunder_tpu.distributed.prims import DistPrimIDs, DistributedReduceOps

    ag = prim_impls[DistPrimIDs.ALL_GATHER]
    ar = prim_impls[DistPrimIDs.ALL_REDUCE]
    rs = prim_impls[DistPrimIDs.REDUCE_SCATTER]
    bc = prim_impls[DistPrimIDs.BROADCAST]
    pp = prim_impls[DistPrimIDs.PPERMUTE]

    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)

    def body(x):
        g = ag(x, "x", 8, 0, True)           # (8, 2) on each device
        s = ar(x, "x", DistributedReduceOps.SUM)  # (1, 2)
        r = rs(g, "x", 8, 0)                 # (1, 2): sum of gathered rows / scatter
        b = bc(x, "x", 3)
        p = pp(x, "x", [[i, (i + 1) % 8] for i in range(8)])
        return g, s, r, b, p

    from thunder_tpu.distributed.prims import shard_map_compat

    shard = shard_map_compat(
        body,
        mesh=mesh,
        in_specs=P("x"),
        out_specs=(P(None), P("x"), P("x"), P("x"), P("x")),
    )
    g, s, r, b, p = shard(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(x))  # gathered = full
    np.testing.assert_allclose(np.asarray(s), np.tile(x.sum(0, keepdims=True), (8, 1)))
    np.testing.assert_allclose(np.asarray(r), np.asarray(x) * 8)  # each row summed 8×
    np.testing.assert_allclose(np.asarray(b), np.tile(np.asarray(x[3:4]), (8, 1)))
    np.testing.assert_allclose(np.asarray(p), np.roll(np.asarray(x), 1, axis=0))


def test_ddp_train_step_matches_single_device():
    cfg, params, batch, loss_fn = _setup()
    optimizer = optax.sgd(0.1)
    ref_loss, ref_params = _single_device_step(loss_fn, params, batch, optimizer)

    mesh = dist.make_mesh({"dp": 8})
    p_ddp = dist.ddp(params, mesh)
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS)
    opt_state = step.init_optimizer_state(p_ddp)
    new_params, _, loss = step(p_ddp, opt_state, *batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    _assert_tree_close(new_params, ref_params)


def test_fsdp_zero_train_step_matches_single_device():
    cfg, params, batch, loss_fn = _setup()
    optimizer = optax.adamw(1e-2)
    ref_loss, ref_params = _single_device_step(loss_fn, params, batch, optimizer)

    mesh = dist.make_mesh({"fsdp": 8})
    p_sh = dist.fsdp(params, mesh, min_size=64)
    # verify actual sharding happened
    assert any(
        not s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(jax.tree_util.tree_map(lambda x: x.sharding, p_sh))
    )
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS)
    opt_state = step.init_optimizer_state(p_sh)
    new_params, new_opt, loss = step(p_sh, opt_state, *batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    _assert_tree_close(new_params, ref_params, atol=1e-4)
    # ZeRO property: optimizer state for sharded params is itself sharded
    mu_sh = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x.sharding if isinstance(x, jax.Array) else None, new_opt)
    )
    assert any(getattr(s, "is_fully_replicated", True) is False for s in mu_sh)


def test_fsdp_zero3_train_step_matches_single_device():
    # ZeRO-3 mode (regather-in-backward via aggressive remat) must keep exact
    # numerics: same loss and updated params as the single-device step
    cfg, params, batch, loss_fn = _setup()
    optimizer = optax.adamw(1e-2)
    ref_loss, ref_params = _single_device_step(loss_fn, params, batch, optimizer)

    mesh = dist.make_mesh({"fsdp": 8})
    p_sh = dist.fsdp(params, mesh, min_size=64)
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS, zero3=True)
    opt_state = step.init_optimizer_state(p_sh)
    new_params, new_opt, loss = step(p_sh, opt_state, *batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    _assert_tree_close(new_params, ref_params, atol=1e-4)


def test_train_step_rebuilds_for_new_batch_shape():
    cfg, params, batch, loss_fn = _setup(B=8)
    _, _, batch2, _ = _setup(B=16)
    mesh = dist.make_mesh({"dp": 8})
    p_sh = dist.ddp(params, mesh)
    optimizer = optax.sgd(0.1)
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS, donate=False)
    opt_state = step.init_optimizer_state(p_sh)
    _, _, loss8 = step(p_sh, opt_state, *batch)
    # different batch shape: a fresh program is compiled with re-pruned shardings
    _, _, loss16 = step(p_sh, opt_state, *batch2)
    assert len(step._cache) == 2
    assert np.isfinite(float(loss8)) and np.isfinite(float(loss16))


def test_tp_fsdp_dp_train_step_matches_single_device():
    cfg, params, batch, loss_fn = _setup()
    optimizer = optax.sgd(0.1)
    ref_loss, ref_params = _single_device_step(loss_fn, params, batch, optimizer)

    mesh = dist.make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    p_sh = dist.tp_fsdp(params, mesh)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, p_sh)
    # the attention projections must actually be tensor-parallel
    wq_sh = shardings["blocks"][0]["attn"]["wq"]
    assert not wq_sh.is_fully_replicated
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS)
    opt_state = step.init_optimizer_state(p_sh)
    new_params, _, loss = step(p_sh, opt_state, *batch)

    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5, rtol=1e-5)
    _assert_tree_close(new_params, ref_params, atol=1e-4)


def test_train_step_loss_decreases():
    cfg, params, batch, loss_fn = _setup()
    mesh = dist.make_mesh({"dp": 2, "fsdp": 4})
    p_sh = dist.fsdp(params, mesh, min_size=64)
    optimizer = optax.adamw(3e-3)
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS)
    opt_state = step.init_optimizer_state(p_sh)
    losses = []
    for _ in range(5):
        p_sh, opt_state, loss = step(p_sh, opt_state, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_default_batch_shardings_heuristic():
    # a float side input whose leading dim coincidentally equals B (e.g. a
    # (T, d) rope cache with T == B) must replicate, not data-shard
    from jax.sharding import PartitionSpec as P

    from thunder_tpu.distributed.api import default_batch_shardings

    mesh = dist.make_mesh({"dp": 8})
    B = T = 8
    idx = jnp.zeros((B, T), jnp.int32)
    tgt = jnp.zeros((B, T), jnp.int32)
    rope = jnp.zeros((T, 16), jnp.float32)  # T == B coincidence
    mask = jnp.zeros((B, T, T), jnp.float32)  # genuine per-sample input
    sh = default_batch_shardings(mesh, (idx, tgt, rope, mask))
    assert sh[0].spec != P() and sh[1].spec != P(), "token batch args must shard"
    assert sh[2].spec == P(), "rope cache must replicate despite T == B"
    assert sh[3].spec != P(), "per-sample float input sharing (B, T) prefix must shard"


def test_placement_does_not_alias_user_arrays():
    # device_put may zero-copy the same-device shard; donating the placed
    # params must not delete the user's original array (found via jax 0.9 CPU)
    def l2(w, x, y):
        return ((tt.ltorch.linear(x, w) - y) ** 2.0).mean()

    rs = np.random.RandomState(0)
    mesh = dist.make_mesh({"dp": 8})
    wp = jnp.asarray(rs.randn(4, 4), jnp.float32)
    xb = jnp.asarray(rs.randn(16, 4), jnp.float32)
    yb = jnp.asarray(rs.randn(16, 4), jnp.float32)
    step = dist.make_train_step(l2, optax.sgd(0.1), mesh)  # donate=True default
    wd = dist.ddp(wp, mesh)
    opt_state = step.init_optimizer_state(wd)
    w1, _, loss = step(wd, opt_state, xb, yb)

    assert not wp.is_deleted(), "donation of placed params deleted the original"
    jl, jg = jax.value_and_grad(lambda w: ((xb @ w.T - yb) ** 2).mean())(wp)
    np.testing.assert_allclose(float(loss), float(jl), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(wp - 0.1 * jg), rtol=1e-4, atol=1e-5)


def test_init_sharded_is_born_placed_with_the_eager_values():
    # a model larger than one chip cannot be built eagerly and moved
    # afterwards; under jit with out_shardings no leaf is ever whole on one
    # device — and the values are the eager call's, key for key (same PRNG
    # bits whatever the sharding; the fused program may round the float math
    # one bfloat16 ulp apart on a stray element)
    cfg = llama.Config.from_name("tiny-llama-debug")
    init = lambda: llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    mesh = dist.make_mesh({"fsdp": 8})
    eager = init()
    placed = dist.init_sharded(init, lambda shapes: dist.fsdp_shardings(shapes, mesh, min_size=64))
    want = dist.fsdp_shardings(eager, mesh, min_size=64)
    for x, y, sh in zip(*map(jax.tree_util.tree_leaves, (eager, placed, want))):
        assert y.sharding == sh
        np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32),
                                   rtol=2 ** -7, atol=0)
    assert any(len(y.sharding.spec) and y.sharding.spec[0] == "fsdp"
               for y in jax.tree_util.tree_leaves(placed))


def test_train_step_uses_sharded_flash_kernels(monkeypatch):
    # VERDICT round-1 weak #3: distributed TrainSteps must keep the Pallas
    # flash kernels (shard_map over batch/head axes), not fall back to the
    # O(T^2) reference. Kernel-eligible shapes: T=128, hs=64 (padded).
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    from thunder_tpu.executors import pallasex

    B, nh, T, hs = 4, 4, 128, 64
    C = nh * hs

    def loss_fn(params, x):
        B_, T_, _ = x.shape
        q = tt.ltorch.linear(x, params["wq"]).reshape(B_, T_, nh, hs).permute(0, 2, 1, 3)
        k = tt.ltorch.linear(x, params["wk"]).reshape(B_, T_, nh, hs).permute(0, 2, 1, 3)
        v = tt.ltorch.linear(x, params["wv"]).reshape(B_, T_, nh, hs).permute(0, 2, 1, 3)
        y = tt.ltorch.scaled_dot_product_attention(q, k, v, is_causal=True)
        y = y.permute(0, 2, 1, 3).reshape(B_, T_, C)
        return (tt.ltorch.linear(y, params["wo"]) ** 2.0).mean()

    rs = np.random.RandomState(0)
    params = {w: jnp.asarray(rs.randn(C, C) * 0.05, jnp.float32) for w in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rs.randn(B, T, C), jnp.float32)
    optimizer = optax.sgd(0.1)

    # single-device reference (kernels off → jnp decomposition)
    monkeypatch.setenv("THUNDER_TPU_DISABLE_PALLAS", "1")
    mesh1 = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step1 = dist.make_train_step(loss_fn, optimizer, mesh1, donate=False)
    opt1 = step1.init_optimizer_state(params)
    p1, _, loss1 = step1(params, opt1, x)
    monkeypatch.delenv("THUNDER_TPU_DISABLE_PALLAS")

    # distributed step with kernels: dp×tp mesh, sharded dispatch must fire
    mesh = dist.make_mesh({"dp": 2, "tp": 4})
    p_sh = dist.ddp(params, mesh)
    step = dist.make_train_step(loss_fn, optimizer, mesh, donate=False)
    opt_state = step.init_optimizer_state(p_sh)
    before = dict(pallasex.stats)
    p2, _, loss2 = step(p_sh, opt_state, x)
    assert pallasex.stats["sharded"] > before["sharded"], "flash kernels not sharded into the step"

    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-5, atol=1e-6)
    for w in params:
        np.testing.assert_allclose(
            np.asarray(p2[w]), np.asarray(p1[w]), rtol=1e-4, atol=1e-5, err_msg=w
        )


def test_grad_accumulation_equals_big_batch():
    # reference no_sync/grad-accumulation (distributed/__init__.py:28-95):
    # N micro steps + one apply == one step on the concatenated batch
    cfg, params, batch, loss_fn = _setup(B=16)
    idx, tgt, cos, sin = batch
    optimizer = optax.sgd(0.1)
    mesh = dist.make_mesh({"dp": 8})
    p_sh = dist.ddp(params, mesh)
    step = dist.make_train_step(loss_fn, optimizer, mesh, batch_specs=BATCH_SPECS, donate=False)
    opt_state = step.init_optimizer_state(p_sh)

    big_params, _, big_loss = step(p_sh, opt_state, *batch)

    micro = [(idx[:8], tgt[:8], cos, sin), (idx[8:], tgt[8:], cos, sin)]
    acc_params, _, acc_loss = step.accumulate(p_sh, opt_state, micro)

    np.testing.assert_allclose(float(acc_loss), float(big_loss), rtol=1e-6)
    _assert_tree_close(acc_params, big_params, atol=1e-6)


def test_hybrid_mesh_fallback_and_train():
    """hybrid_mesh without slice topology (virtual CPU devices) lays out a
    plain mesh with DCN axes leading; a train step runs on it."""
    import optax

    from thunder_tpu.distributed import hybrid_mesh
    from thunder_tpu.models import llama

    mesh = hybrid_mesh({"fsdp": 4}, {"dp": 2})
    assert mesh.axis_names == ("dp", "fsdp")
    assert dict(mesh.shape) == {"dp": 2, "fsdp": 4}

    cfg = llama.Config.from_name("tiny-llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    p = dist.fsdp(params, mesh, min_size=0)
    step = dist.make_train_step(
        lambda pp, i, t, c, s: llama.gpt_loss(pp, i, t, c, s, cfg),
        optax.sgd(1e-2), mesh,
    )
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, 16)
    o = step.init_optimizer_state(p)
    _, _, loss = step(p, o, idx, tgt, cos, sin)
    assert np.isfinite(float(loss))


def test_initialize_multihost_single_process_noop():
    from thunder_tpu.distributed import initialize_multihost

    initialize_multihost(num_processes=1)  # must not raise on one process
    assert jax.process_count() == 1


def test_no_sync_context_yields_micro_grads():
    import optax

    from thunder_tpu.models import llama

    cfg = llama.Config.from_name("tiny-llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = dist.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    p = dist.ddp(params, mesh)
    step = dist.make_train_step(
        lambda pp, i, t, c, s: llama.gpt_loss(pp, i, t, c, s, cfg),
        optax.sgd(1e-2), mesh,
    )
    o = step.init_optimizer_state(p)
    idx = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, 16)
    with step.no_sync() as micro:
        loss, grads = micro(p, o, idx, tgt, cos, sin)
    assert np.isfinite(float(loss))
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(p)


def test_comm_combine_threshold_round_trips():
    """The bucket_size_in_mb analog (SURVEY §2.6 "keep thresholds
    configurable"; reference distributed/transforms/ddp.py:101-204): the
    option maps to backend-accepted XLA compiler options and the step still
    trains."""
    import optax

    from thunder_tpu.models import llama

    cfg = llama.Config.from_name("tiny-llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = dist.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    p = dist.ddp(params, mesh)
    step = dist.make_train_step(
        lambda pp, i, t, c, s: llama.gpt_loss(pp, i, t, c, s, cfg),
        optax.sgd(1e-2), mesh, comm_combine_threshold_mb=4.0,
    )
    o = step.init_optimizer_state(p)
    idx = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, 16)
    p2, o2, loss = step(p, o, idx, tgt, cos, sin)
    assert np.isfinite(float(loss))
    # the threshold landed in compiler options under a backend-accepted name
    assert step.compiler_options, "no combine-threshold flag accepted by this backend"
    assert all(v == str(int(4.0 * 2**20)) for v in step.compiler_options.values())
    mapped = dist.combine_threshold_options(2.0)
    assert all("combine_threshold_bytes" in k for k in mapped)


def test_symbolic_cache_bucketed_shapes():
    """Shape-bucketed caching (the CACHE_OPTIONS.SYMBOLIC_VALUES analog,
    VERDICT r2 item 4; reference core/options.py:95): one compiled program
    serves every (B, T) inside a power-of-two bucket — TrainStep stops
    rebuilding per batch shape — with bit-exact losses (ignore_index
    padding + causal attention)."""
    import optax

    from thunder_tpu.models import llama

    cfg = llama.Config.from_name("tiny-llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    p = dist.ddp(params, mesh)

    def loss_fn(pp, i, t, c, s):
        return llama.gpt_loss(pp, i, t, c, s, cfg)

    step = dist.make_train_step(
        loss_fn, optax.sgd(1e-2), mesh, donate=False,
        bucketer=llama.batch_bucketer(cfg, min_t=16),
    )
    o = step.init_optimizer_state(p)

    losses = {}
    for T in (9, 12, 16):  # all inside the T=16 bucket
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)[:, :T]
        tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size)[:, :T]
        cos, sin = llama.build_rope_cache(cfg, T)
        _, _, loss = step(p, o, idx, tgt, cos, sin)
        losses[T] = float(loss)
    assert len(step._cache) == 1, f"bucketed shapes rebuilt: {list(step._cache)}"

    # a shape outside the bucket compiles a second program
    idx = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0, cfg.vocab_size)
    cos, sin = llama.build_rope_cache(cfg, 24)
    step(p, o, idx, tgt, cos, sin)
    assert len(step._cache) == 2

    # exactness: bucketed loss == unbucketed loss at the odd shape
    T = 9
    idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)[:, :T]
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size)[:, :T]
    cos, sin = llama.build_rope_cache(cfg, T)
    plain = dist.make_train_step(loss_fn, optax.sgd(1e-2), mesh, donate=False)
    o2 = plain.init_optimizer_state(p)
    _, _, ref_loss = plain(p, o2, idx, tgt, cos, sin)
    np.testing.assert_allclose(losses[T], float(ref_loss), rtol=1e-6, atol=1e-6)
