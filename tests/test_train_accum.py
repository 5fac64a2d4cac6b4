"""Gradient accumulation under donation (thunder_tpu.train.accum +
TrainStep(accum_steps=k)).

The contract: k microsteps inside ONE donated program (lax.scan over
(k, B/k, ...) slices, float32 accumulator in fixed summation order) match
the k×-batch step up to float reassociation, deterministically, with the
accumulator bytes visible to the memory accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from thunder_tpu import distributed as dist
from thunder_tpu.models import llama
from thunder_tpu.train.accum import (
    accum_buffer_bytes,
    microbatch_mask,
    pp_microbatches,
    split_for_accum,
)

CFG = llama.Config.from_name("tiny-llama-debug")
B, T = 8, 16


def _batch(seed=1):
    idx = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, CFG.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, T), 0, CFG.vocab_size)
    cos, sin = llama.build_rope_cache(CFG, T)
    return idx, tgt, cos, sin


def _loss_fn(p, i, t, c, s):
    return llama.gpt_loss(p, i, t, c, s, CFG)


def _run(accum_steps, seed=0, batch=None):
    mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    params = dist.ddp(llama.init_params(CFG, jax.random.PRNGKey(seed), dtype=jnp.float32), mesh)
    ts = dist.make_train_step(_loss_fn, optax.adamw(1e-3), mesh, accum_steps=accum_steps)
    opt = ts.init_optimizer_state(params)
    p, o, loss = ts(params, opt, *(batch or _batch()))
    return p, float(loss), ts


class TestSplitHelpers:
    def test_microbatch_mask_picks_leading_batch_args(self):
        idx, tgt, cos, sin = _batch()
        assert microbatch_mask((idx, tgt, cos, sin)) == (True, True, False, False)

    def test_split_reshapes_masked_args_only(self):
        idx, tgt, cos, sin = _batch()
        split, mask = split_for_accum((idx, tgt, cos, sin), 4)
        assert mask == (True, True, False, False)
        assert split[0].shape == (4, B // 4, T) and split[1].shape == (4, B // 4, T)
        assert split[2] is cos and split[3] is sin
        # slices reassemble the original batch exactly
        np.testing.assert_array_equal(np.asarray(split[0]).reshape(B, T), np.asarray(idx))

    def test_split_rejects_nondivisor(self):
        idx, tgt, cos, sin = _batch()
        with pytest.raises(ValueError, match="divide the batch size"):
            split_for_accum((idx, tgt, cos, sin), 3)

    def test_accum_buffer_bytes_counts_inexact_leaves_as_f32(self):
        params = {"w": jnp.ones((4, 4), jnp.bfloat16), "n": jnp.array(3)}
        assert accum_buffer_bytes(params) == 16 * 4  # f32 accumulator, ints skipped

    def test_pp_microbatches_clamps_to_divisor(self):
        assert pp_microbatches(4, 8) == 4
        assert pp_microbatches(3, 8) == 2
        assert pp_microbatches(5, 8) == 4
        assert pp_microbatches(1, 7) == 1


class TestAccumParity:
    def test_accum_matches_big_batch_step(self):
        """k microsteps == one k×-batch step up to float reassociation
        (the f32 accumulator sums per-microbatch means in fixed order;
        adamw's 1/sqrt(v) amplifies the reassociation delta slightly)."""
        p1, l1, _ = _run(1)
        p2, l2, _ = _run(2)
        assert abs(l1 - l2) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)

    def test_accum_is_deterministic(self):
        """Fixed summation order: the same accum step twice is bit-identical."""
        batch = _batch()
        _, la, tsa = _run(2, batch=batch)
        _, lb, _ = _run(2, batch=batch)
        assert np.float32(la).tobytes() == np.float32(lb).tobytes()

    def test_accum_rejects_nondivisor_batch(self):
        with pytest.raises(ValueError, match="divide the batch size"):
            _run(3)

    def test_accum_steps_validated_at_init(self):
        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="accum_steps"):
            dist.make_train_step(_loss_fn, optax.adamw(1e-3), mesh, accum_steps=0)


class TestAccumMemoryAccounting:
    def test_profile_stats_carries_accum_buffer(self):
        """The scan's f32 accumulator is real memory: profile_stats (and the
        donation report peak estimate) must include it, sized like the
        inexact params at 4 bytes each."""
        p2, _, ts = _run(2)
        st = ts.profile_stats()
        assert st["accum_steps"] == 2
        assert st["accum_buffer_bytes"] == accum_buffer_bytes(p2)
        assert st["peak_bytes_estimate"] >= st["accum_buffer_bytes"]
        # microbatch traces: the activation portion of the peak shrinks with
        # B/k (at toy shapes the param-sized accumulator can still dominate
        # the total)
        _, _, ts1 = _run(1)
        assert ts1.profile_stats()["accum_buffer_bytes"] == 0
        act_k2 = st["peak_bytes_estimate"] - st["accum_buffer_bytes"]
        assert act_k2 < ts1.profile_stats()["peak_bytes_estimate"]

    def test_profile_stats_requires_built_step(self):
        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        ts = dist.make_train_step(_loss_fn, optax.adamw(1e-3), mesh)
        with pytest.raises(RuntimeError, match="built"):
            ts.profile_stats()

    def test_examine_train_memory_report(self):
        from thunder_tpu import examine

        _, _, ts = _run(2)
        rep = examine.train_memory_report(ts)
        assert rep["accum_steps"] == 2 and rep["peak_bytes_estimate"] > 0
        assert rep["remat_policy"] in ("none", "attention", "full_block")
