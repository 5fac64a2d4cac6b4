"""Del-aware buffer donation & input-output aliasing (ISSUE 4).

Two layers of coverage:

- **Analysis unit tests** on hand-constructed lowered traces (fusion bound
  symbols + explicit ``DEL`` placement), proving the safety contract directly:
  a buffer dead after region 1 is donated there, and moving its use into
  region 2 withdraws the donation — the acceptance-criterion scenario.
- **End-to-end tests** through ``tt.jit(fn, donate=...)``: the byte-identical
  guarantee when off, real buffer consumption when on (jax deletes donated
  CPU arrays too), strict-mode ``DonationError``, cache-key participation,
  ``donation.*`` metrics, the donation-aware memory timeline, and the
  ``TrainStep`` integration.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import thunder_tpu as tt
from thunder_tpu import distributed as dist
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.prims import python_del, python_return
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.symbol import Symbol
from thunder_tpu.core.trace import TraceCtx, tracectx
from thunder_tpu.executors.donation import (
    REJECT_ALIASED_VIEW,
    REJECT_LATER_USE,
    REJECT_NO_DEL,
    REJECT_TRACE_OUTPUT,
    DonationError,
    analyze_trace_donations,
    apply_donation,
    suppress_unusable_donation_warnings,
)
from thunder_tpu.observability.metrics import registry


def _fusion(name, inputs, outputs):
    sym = Symbol(name=name, meta=None, is_fusion=True)
    return sym.bind(*inputs, output=tuple(outputs))


def _mk_proxies(*names, shape=(4, 4)):
    tr = TraceCtx(lambda *a: None)
    with tracectx(tr):
        ps = tuple(
            TensorProxy(name=n, shape=shape, device="cpu", dtype=dtypes.float32)
            for n in names
        )
    return tr, ps


class TestDonationAnalysis:
    """Hand-built lowered traces: the pass proves safety from DEL adjacency
    and the consumers map alone."""

    def _two_region_trace(self, move_a_into_region2: bool):
        """region1(a, b) -> t2 ; region2(t2, b[, a]) -> t3 ; return t3.

        With ``move_a_into_region2=False``, ``a`` dies right after region 1
        (its DEL follows it) — the acceptance criterion's "donated there"
        case.  With ``True``, ``a`` is also an input of region 2 and its DEL
        moves after it — the "no longer donated [at region 1]" case.
        """
        tr, (a, b, t2, t3) = _mk_proxies("a", "b", "t2", "t3")
        r1 = _fusion("XLA0", [a, b], [t2])
        if move_a_into_region2:
            r2 = _fusion("XLA1", [t2, b, a], [t3])
            bsyms = [
                r1,
                r2,
                python_del.bind(a, t2, b, output=None),
                python_return.bind(t3, output=None),
            ]
        else:
            r2 = _fusion("XLA1", [t2, b], [t3])
            bsyms = [
                r1,
                python_del.bind(a, output=None),
                r2,
                python_del.bind(t2, b, output=None),
                python_return.bind(t3, output=None),
            ]
        tr.bound_symbols = bsyms
        tr.args = (a, b)
        return tr

    def test_dead_after_region1_is_donated_there(self):
        report = analyze_trace_donations(self._two_region_trace(False))
        r1, r2 = report.regions
        assert [p.name for _, p in r1.donated] == ["a"]
        # b is still read by region 2: rejected at region 1, donated at its
        # true last consumer
        assert r1.rejected["b"][0] == REJECT_LATER_USE
        assert r1.rejected["b"][1].sym.name == "XLA1"
        assert sorted(p.name for _, p in r2.donated) == ["b", "t2"]

    def test_use_moved_into_region2_withdraws_the_donation(self):
        report = analyze_trace_donations(self._two_region_trace(True))
        r1, r2 = report.regions
        # a is now read by region 2: region 1 may no longer consume it
        assert "a" not in [p.name for _, p in r1.donated]
        assert r1.rejected["a"][0] == REJECT_LATER_USE
        assert "a" in [p.name for _, p in r2.donated]

    def test_trace_outputs_are_never_donated(self):
        tr, (a, b, t2) = _mk_proxies("a", "b", "t2")
        r1 = _fusion("XLA0", [a, b], [t2])
        tr.bound_symbols = [
            r1,
            python_del.bind(b, output=None),
            # a escapes to the caller alongside the region's output
            python_return.bind(t2, a, output=None),
        ]
        tr.args = (a, b)
        report = analyze_trace_donations(tr)
        (r,) = report.regions
        assert r.rejected["a"][0] == REJECT_TRACE_OUTPUT
        assert [p.name for _, p in r.donated] == ["b"]
        assert "a" in report.protected_names

    def test_no_del_means_no_proof_means_no_donation(self):
        tr, (a, b, t2) = _mk_proxies("a", "b", "t2")
        r1 = _fusion("XLA0", [a, b], [t2])
        tr.bound_symbols = [r1, python_return.bind(t2, output=None)]
        tr.args = (a, b)
        report = analyze_trace_donations(tr)
        (r,) = report.regions
        assert not r.donated
        assert r.rejected["a"][0] == REJECT_NO_DEL
        assert r.rejected["b"][0] == REJECT_NO_DEL

    def test_eager_view_endpoints_are_never_donated(self):
        tr = TraceCtx(lambda *a: None)
        with tracectx(tr):
            a = TensorProxy(name="a", shape=(4, 4), device="cpu", dtype=dtypes.float32)
            b = TensorProxy(name="b", shape=(4, 4), device="cpu", dtype=dtypes.float32)
            # an eager (unfused) SHAPE_OP: its endpoints may alias at runtime
            v = prims.reshape(a, (16,))
        view_bsym = tr.bound_symbols[-1]
        with tracectx(tr):
            t2 = TensorProxy(name="t2", shape=(4, 4), device="cpu", dtype=dtypes.float32)
        r1 = _fusion("XLA0", [a, b], [t2])
        tr.bound_symbols = [
            view_bsym,
            r1,
            python_del.bind(a, b, output=None),
            python_return.bind(t2, v, output=None),
        ]
        tr.args = (a, b)
        report = analyze_trace_donations(tr)
        (r,) = report.regions
        assert r.rejected["a"][0] == REJECT_ALIASED_VIEW
        assert "a" in report.view_names and v.name in report.view_names
        assert [p.name for _, p in r.donated] == ["b"]

    def test_alias_hints_pair_dead_inputs_with_compatible_outputs(self):
        tr, (a, b, t2) = _mk_proxies("a", "b", "t2")
        r1 = _fusion("XLA0", [a, b], [t2])
        tr.bound_symbols = [
            r1,
            python_del.bind(a, b, output=None),
            python_return.bind(t2, output=None),
        ]
        tr.args = (a, b)
        report = analyze_trace_donations(tr)
        (r,) = report.regions
        # one output, shape/dtype-identical to the donated inputs: exactly
        # one alias claimed (greedy, first donated input wins)
        assert len(r.aliases) == 1 and set(r.aliases.values()) == {"t2"}

    def test_candidate_names_restrict_the_analysis(self):
        report = analyze_trace_donations(
            self._two_region_trace(False), candidate_names={"a"}
        )
        r1, r2 = report.regions
        assert [p.name for _, p in r1.donated] == ["a"]
        # b/t2 were never candidates: neither donated nor counted rejected
        assert not r1.rejected and not r2.donated and not r2.rejected

    def test_rejection_counters_published(self):
        reg = registry()
        before = {
            k: reg.counter(f"donation.rejected.{k}").value
            for k in (REJECT_LATER_USE, REJECT_TRACE_OUTPUT, REJECT_NO_DEL)
        }
        _, report = apply_donation(self._two_region_trace(False))
        assert report.donated_buffers == 3
        assert (
            reg.counter(f"donation.rejected.{REJECT_LATER_USE}").value
            == before[REJECT_LATER_USE] + 1
        )
        snap = tt.metrics_snapshot()
        assert snap["donation.buffers_donated"] >= 3
        assert f"donation.rejected.{REJECT_LATER_USE}" in snap


def _sgd(p, g):
    return p - 0.01 * g


def _arrs(shape=(16, 16)):
    return jnp.ones(shape), jnp.full(shape, 0.5)


def _fusion_callables(cfn):
    out = []
    for bsym in tt.last_traces(cfn)[-1].bound_symbols:
        if bsym.sym.is_fusion:
            out.append((bsym._call_ctx or {})[bsym.sym.name])
    return out


class TestJitDonation:
    def test_auto_donation_consumes_inputs_for_real(self):
        p, g = _arrs()
        f = tt.jit(_sgd, donate=True)
        pc, gc = p.copy(), g.copy()
        out = f(pc, gc)
        assert bool((out == 1.0 - 0.01 * 0.5).all())
        # XLA aliases the region's one output into one donated dead input and
        # deletes it for real, even on the CPU backend (the other donation is
        # "not usable" and degrades to a no-op — the warning the shared
        # helper silences)
        assert pc.is_deleted() or gc.is_deleted()
        stats = tt.donation_stats(f)
        fw = stats["forward"]
        assert fw["buffers_donated"] == 2 and fw["bytes_donated"] == 2 * 16 * 16 * 4
        (region,) = fw["regions"]
        assert sorted(region["donated"]) == sorted(["t0", "t1"])
        assert len(region["aliases"]) == 1  # one output, reused for one dead input
        assert (cal := _fusion_callables(f)) and cal[0].donate_argnums == (0, 1)

    def test_donate_false_program_is_byte_identical(self):
        p, g = _arrs()
        f_off = tt.jit(_sgd, donate=False)
        f_plain = tt.jit(_sgd)
        assert bool((f_off(p, g) == f_plain(p, g)).all())
        # the header names the last pass and the wall time it took: not the program's
        untimed = lambda trace: re.sub(r"\(took \d+ milliseconds\)", "", str(trace))
        assert untimed(tt.last_traces(f_off)[-1]) == untimed(tt.last_traces(f_plain)[-1])
        # and the fusion callables are unarmed: same jit, no donate_argnums
        for cal in _fusion_callables(f_off) + _fusion_callables(f_plain):
            assert cal.donate_argnums == () and cal.out_aliases == {}
        with pytest.raises(Exception, match="no donation data"):
            tt.donation_stats(f_off)

    def test_donated_then_reused_raises_framework_error(self):
        p, g = _arrs()
        f = tt.jit(_sgd, donate=True)
        pc, gc = p.copy(), g.copy()
        f(pc, gc)
        # reuse whichever buffer XLA actually consumed
        dead_p = pc if pc.is_deleted() else p.copy()
        dead_g = gc if gc.is_deleted() else g.copy()
        assert pc.is_deleted() or gc.is_deleted()
        with pytest.raises(DonationError, match="donated by an earlier call"):
            f(dead_p, dead_g)

    def test_explicit_argnums_donate_only_those(self):
        p, g = _arrs()
        f = tt.jit(_sgd, donate=(0,))
        pc, gc = p.copy(), g.copy()
        f(pc, gc)
        assert pc.is_deleted() and not gc.is_deleted()
        fw = tt.donation_stats(f)["forward"]
        assert fw["buffers_donated"] == 1

    def test_explicit_unsafe_donation_raises_with_reason(self):
        def ident(a, b):
            return a, a + b

        p, g = _arrs()
        f = tt.jit(ident, donate=(0,))
        with pytest.raises(DonationError, match=r"'t0'.*trace_output"):
            f(p.copy(), g.copy())

    def test_explicit_unsafe_donation_names_the_blocking_source(self):
        def escape(a, b):
            c = a * b + b
            return a, c  # a escapes: requested donation must fail loudly

        p, g = _arrs()
        f = tt.jit(escape, donate=(0,))
        with pytest.raises(DonationError, match="trace_output"):
            f(p.copy(), g.copy())

    def test_bad_donate_values_fail_at_jit_time(self):
        with pytest.raises(Exception, match="donates nothing"):
            tt.jit(_sgd, donate=())
        with pytest.raises(Exception, match="donate must be"):
            tt.jit(_sgd, donate="yes")

    def test_suppress_helper_filters_exactly_the_jax_note(self):
        import warnings

        with suppress_unusable_donation_warnings():
            with warnings.catch_warnings(record=True) as seen:
                warnings.simplefilter("always")
                # re-apply the scoped filter under the recorder
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                warnings.warn("Some donated buffers were not usable by XLA")
                warnings.warn("unrelated warning")
        assert [str(w.message) for w in seen] == ["unrelated warning"]


class TestDonationCacheKey:
    def test_donation_setting_salts_the_dispatch_key(self):
        from thunder_tpu.core.cache_key import compute_cache_key

        p, g = _arrs()
        k_plain = compute_cache_key((p, g), {})
        k_auto = compute_cache_key((p, g), {}, salt=("donate", "auto"))
        k_args = compute_cache_key((p, g), {}, salt=("donate", (0,)))
        assert len({k_plain, k_auto, k_args}) == 3

    def test_entry_key_fn_recomputes_the_salted_key(self):
        from thunder_tpu import _get_cs
        from thunder_tpu.core.cache_key import compute_cache_key

        p, g = _arrs()
        f_on = tt.jit(_sgd, donate=True)
        f_on(p.copy(), g.copy())
        cs = _get_cs(f_on)
        (entry,) = cs.interpreter_cache
        assert entry.key_meta.get("donate") == "auto"
        expected = compute_cache_key((p, g), {}, salt=("donate", "auto"))
        assert entry.cache_key_fn((p, g), {}) == expected
        # the dispatcher filed it under the salted key: a second call is a
        # keyed hit, not a rescan
        f_on(p.copy(), g.copy())
        assert tt.dispatch_stats(f_on)["key_hits"] == 1

    def test_distinct_settings_never_share_a_key(self):
        from thunder_tpu import _get_cs

        p, g = _arrs()
        f_on = tt.jit(_sgd, donate=True)
        f_off = tt.jit(_sgd, donate=False)
        f_on(p.copy(), g.copy())
        f_off(p, g)
        (e_on,) = _get_cs(f_on).interpreter_cache
        (e_off,) = _get_cs(f_off).interpreter_cache
        assert e_on.cache_key_fn((p, g), {}) != e_off.cache_key_fn((p, g), {})


class TestDonationMemoryTimeline:
    def test_peak_estimate_reflects_donated_reuse(self):
        from thunder_tpu.examine import memory_estimate, memory_timeline

        p, g = _arrs((32, 32))
        f_on = tt.jit(_sgd, donate=True)
        f_off = tt.jit(_sgd, donate=False)
        f_on(p.copy(), g.copy())
        f_off(p, g)
        t_on = memory_timeline(tt.last_traces(f_on)[-1])
        t_off = memory_timeline(tt.last_traces(f_off)[-1])
        nbytes = 32 * 32 * 4
        # undonated: p + g + new_p live at the peak; donated: the update
        # lands in the dead inputs' buffers
        assert t_off["peak_bytes_estimate"] == 3 * nbytes
        assert t_on["peak_bytes_estimate"] == 2 * nbytes
        assert t_on["donated_bytes"] == 2 * nbytes
        assert t_off["donated_bytes"] == 0
        est = memory_estimate(tt.last_traces(f_on)[-1])
        assert est["donated_bytes"] == 2 * nbytes

    def test_program_documents_its_donation(self):
        p, g = _arrs()
        f = tt.jit(_sgd, donate=True)
        f(p.copy(), g.copy())
        src = str(tt.last_traces(f)[-1])
        assert "# donation:" in src and "# donated:" in src


class TestTrainStepDonation:
    def _setup(self):
        def loss_fn(p, x, y):
            h = tt.ltorch.linear(x, p["w"])
            return ((h - y) ** 2.0).mean()

        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(8, 8) * 0.1, jnp.float32)}
        x = jnp.asarray(rs.randn(4, 8), jnp.float32)
        y = jnp.zeros((4, 8))
        mesh = dist.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        return loss_fn, params, x, y, mesh

    def test_train_step_reports_and_donates_top_level(self):
        loss_fn, params, x, y, mesh = self._setup()
        step = dist.make_train_step(loss_fn, optax.sgd(0.1), mesh)
        p2, o2, loss = step(params, step.init_optimizer_state(params), x, y)
        assert np.isfinite(float(loss))
        rep = step.donation_report
        assert rep is not None and set(rep) >= {"forward", "backward"}
        assert rep["fw_peak_bytes_estimate"] > 0
        assert step.last_donate_argnums == (0, 1)  # params + opt state

    def test_donate_batch_extends_only_to_dead_batch_args(self):
        loss_fn, params, x, y, mesh = self._setup()
        step = dist.make_train_step(
            loss_fn, optax.sgd(0.1), mesh, donate_batch=True
        )
        step(params, step.init_optimizer_state(params), x.copy(), y.copy())
        # x is a saved residual of linear's backward (protected); y dies in
        # the forward — only y's position joins the outer donation
        assert step.last_donate_argnums == (0, 1, 3)

    def test_donate_false_has_no_report_and_preserves_inputs(self):
        loss_fn, params, x, y, mesh = self._setup()
        step = dist.make_train_step(loss_fn, optax.sgd(0.1), mesh, donate=False)
        step(params, step.init_optimizer_state(params), x, y)
        assert step.donation_report is None
        assert step.last_donate_argnums == ()
        assert not params["w"].is_deleted()
