"""Remat policies on TrainStep (thunder_tpu.train.remat).

The trace-layer rematerialization pass already existed; the policy layer
maps named levels onto its knobs — ``none`` / ``attention`` (max_cone=64) /
``full_block`` (max_cone=256, aggressive) — and surfaces what each bought
through ``profile_stats``.  Remat is a memory transform, never a math
transform: loss must be bit-identical across policies."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from thunder_tpu import distributed as dist
from thunder_tpu.models import llama
from thunder_tpu.train.remat import REMAT_POLICIES, resolve_remat, validate_remat

CFG = llama.Config.from_name("tiny-llama-debug")
B, T = 4, 16


class TestResolve:
    def test_policy_mapping(self):
        assert resolve_remat("none").apply is False
        att = resolve_remat("attention")
        assert att.apply and att.max_cone == 64 and not att.aggressive
        fb = resolve_remat("full_block")
        assert fb.apply and fb.max_cone == 256 and fb.aggressive

    def test_bools_are_legacy_aliases(self):
        assert resolve_remat(True).policy == "attention"
        assert resolve_remat(False).policy == "none"

    def test_zero3_forces_full_block(self):
        for r in (False, "none", "attention", "auto"):
            assert resolve_remat(r, zero3=True).policy == "full_block"

    def test_auto_consults_the_probe(self):
        assert resolve_remat("auto", auto=lambda: True).policy == "attention"
        assert resolve_remat("auto", auto=lambda: False).policy == "none"

    def test_invalid_value_raises(self):
        with pytest.raises(ValueError, match="remat must be"):
            validate_remat("dots")
        with pytest.raises(ValueError, match="remat must be"):
            resolve_remat("blocks")


class TestTrainStepPolicies:
    @pytest.fixture(scope="class")
    def sweep(self):
        idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, CFG.vocab_size)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, CFG.vocab_size)
        cos, sin = llama.build_rope_cache(CFG, T)
        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        out = {}
        for pol in REMAT_POLICIES:
            params = dist.ddp(llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32), mesh)
            ts = dist.make_train_step(
                lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, CFG),
                optax.adamw(1e-3), mesh, remat=pol,
            )
            opt = ts.init_optimizer_state(params)
            _, _, loss = ts(params, opt, idx, tgt, cos, sin)
            out[pol] = (float(loss), ts.profile_stats())
        return out

    def test_policies_recorded(self, sweep):
        for pol, (_, st) in sweep.items():
            assert st["remat_policy"] == pol

    def test_residuals_monotone_nonincreasing(self, sweep):
        res = [sweep[p][1]["residual_bytes"] for p in ("none", "attention", "full_block")]
        assert res[0] >= res[1] >= res[2], res
        assert res[2] < res[0]  # full_block must actually prune

    def test_peak_reduction_at_least_15pct(self, sweep):
        """The acceptance gate: donation-aware peak bytes under full_block
        at least 15% below remat=none at equal loss."""
        peak_none = sweep["none"][1]["peak_bytes_estimate"]
        peak_fb = sweep["full_block"][1]["peak_bytes_estimate"]
        assert 1.0 - peak_fb / peak_none >= 0.15, (peak_none, peak_fb)

    def test_loss_bit_identical_across_policies(self, sweep):
        base = np.float32(sweep["none"][0]).tobytes()
        for pol in ("attention", "full_block"):
            assert np.float32(sweep[pol][0]).tobytes() == base, (
                "remat changed the loss — recompute must be a memory "
                "transform, not a math transform")

    def test_recomputing_at_first_use_never_raises_the_peak(self, sweep):
        """Each recomputed op sits just before its first reader, so dropping
        residuals cannot lengthen what is alive at the peak."""
        peaks = [sweep[p][1]["peak_bytes_estimate"] for p in ("none", "attention", "full_block")]
        assert peaks[0] >= peaks[1] >= peaks[2], peaks

    def test_reduction_frac_surfaced(self, sweep):
        st = sweep["full_block"][1]
        assert 0.0 < st["remat_residual_reduction_frac"] <= 1.0
        assert st["residual_bytes_no_remat"] >= st["residual_bytes"]


def test_place_late_puts_each_recomputed_op_before_its_first_reader():
    """Needs first, then the op, then the reader; an op nobody reads is left out."""
    from types import SimpleNamespace as NS

    from thunder_tpu.core.rematerialization import _place_late

    def op(name, args, outs):
        return NS(name=name, flat_proxy_args=[NS(name=a) for a in args], flat_proxy_outs=[NS(name=o) for o in outs])

    recompute = [op("r1", ["x"], ["a"]), op("r2", ["a"], ["b"]), op("r3", ["y"], ["c"]), op("r4", ["x"], ["unused"])]
    body = [op("b1", ["g"], ["h"]), op("b2", ["h", "c"], ["i"]), op("b3", ["i", "b"], ["j"])]
    assert [o.name for o in _place_late(recompute, body)] == ["b1", "r3", "b2", "r1", "r2", "b3"]


@pytest.mark.parametrize("pol", ["none", "attention"])
def test_recomputation_is_held_behind_barriers(pol):
    """The backward trace, and the program lowered from it, carry an
    ``optimization_barrier`` for every group of recomputed ops that waits
    for a gradient; with nothing recomputed there is none.  (``full_block``
    chains every layer's cone to the one before, so its first reader pulls
    them all and nothing is left to hold.)"""
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, CFG.vocab_size)
    cos, sin = llama.build_rope_cache(CFG, T)
    mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    params = dist.ddp(llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32), mesh)
    ts = dist.make_train_step(lambda p, i, t, c, s: llama.gpt_loss(p, i, t, c, s, CFG),
                              optax.adamw(1e-3), mesh, remat=pol)
    opt = ts.init_optimizer_state(params)
    counts = (ts.lower_hlo(params, opt, idx, idx, cos, sin).count("optimization_barrier"),
              str(ts.bw_trace).count("optimization_barrier"))
    assert counts == (0, 0) if pol == "none" else min(counts) > 0, counts
