"""Debug tooling: examine(), sharp edges, patterns, profile markers.

Reference parity: ``thunder/examine/__init__.py:49``, sharp-edges policy
(``core/options.py:146`` + ``jit_ext.py:472``), ``core/patterns.py:99``,
``core/profile.py:7`` (here ``observability.span``).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import torch

import thunder_tpu as tt
import thunder_tpu.torch as ltorch

rng = np.random.default_rng(21)


class TestExamine:
    def test_cost_analysis_plain_fn(self):
        """XLA cost-model introspection: FLOPs/bytes from the compiled
        program, roofline estimate at explicit peaks."""
        from thunder_tpu.examine import cost_analysis

        def f(a, b):
            return (a @ b).sum()

        a = np.ones((64, 64), np.float32)
        out = cost_analysis(f, a, a)
        # 64^3 MACs = 2*64^3 - boundary flops; XLA reports ~2*64^3
        assert out["flops"] >= 2 * 64**3 * 0.9
        assert out["bytes_accessed"] >= 2 * 64 * 64 * 4
        assert out["arithmetic_intensity"] > 1
        out2 = cost_analysis(f, a, a, flops_per_sec=1e12, bytes_per_sec=1e9)
        assert out2["roofline_seconds"] == max(out2["compute_seconds"], out2["memory_seconds"])
        assert out2["bound"] in ("compute", "memory")

    def test_cost_analysis_thunder_trace(self):
        """The documented thunder path: analyze the execution trace's
        python_callable."""
        import numpy as np

        import thunder_tpu as tt
        import thunder_tpu.torch as ltorch
        from thunder_tpu.examine import cost_analysis

        def f(a, b):
            return ltorch.sum(ltorch.matmul(a, b))

        a = np.ones((32, 32), np.float32)
        jfn = tt.jit(f)
        jfn(a, a)
        trace = tt.last_traces(jfn)[-1]
        out = cost_analysis(trace.python_callable(), a, a)
        assert out["flops"] >= 2 * 32**3 * 0.9, out

    def test_supported_function(self, capsys):
        from thunder_tpu.examine import examine

        def f(a, b):
            return torch.nn.functional.relu(a) + torch.matmul(a, b)

        a = torch.randn(4, 4)
        b = torch.randn(4, 4)
        ok = examine(f, a, b)
        out = capsys.readouterr().out
        assert ok
        assert "supported by the tracer" in out
        assert "compiled and ran" in out

    def test_unsupported_function_reported(self, capsys):
        from thunder_tpu.examine import examine

        def f(a):
            # svd isn't on the ltorch surface
            u, s, v = torch.linalg.svd(a)
            return s

        ok = examine(f, torch.randn(4, 4))
        out = capsys.readouterr().out
        assert not ok
        assert "not supported" in out
        assert "svd" in out

    def test_broken_function_reported(self, capsys):
        from thunder_tpu.examine import examine

        def f(a):
            raise ValueError("boom")

        ok = examine(f, torch.randn(2))
        out = capsys.readouterr().out
        assert not ok
        assert "failed outside thunder_tpu" in out

    def test_get_fusions_and_memory(self):
        from thunder_tpu.examine import get_fusions, memory_estimate

        def f(a):
            return ltorch.sin(a) * ltorch.cos(a) + 1.0

        a = rng.standard_normal((16, 16)).astype(np.float32)
        jfn = tt.jit(f)
        jfn(a)
        trc = tt.last_traces(jfn)[-1]
        fusions = get_fusions(trc)
        assert len(fusions) == 1 and fusions[0][0] == "XLA0"
        mem = memory_estimate(trc)
        assert mem["input_bytes"] == 16 * 16 * 4
        assert mem["output_bytes"] == 16 * 16 * 4
        assert mem["peak_bytes_estimate"] >= mem["input_bytes"]

    def test_device_peaks_is_one_sourced_table_and_refuses_an_unknown_device(self):
        """Peaks live in examine.DEVICE_PEAKS keyed by device_kind, each with
        its source; a device that is not there is an error, not a default."""
        from thunder_tpu.examine import DEVICE_PEAKS, device_peaks

        assert DEVICE_PEAKS and all(row["source"] for row in DEVICE_PEAKS.values())
        assert device_peaks("TPU v5 lite") is DEVICE_PEAKS["TPU v5 lite"]
        for kind in ("cpu", "TPU v9 imaginary"):
            with pytest.raises(ValueError, match="no published peaks"):
                device_peaks(kind)


class TestTools:
    """``tools/`` needs the chip and cannot run here, but what can be held
    without one is: every tool byte-compiles, and a tool that times a device
    fails without a TPU instead of printing a CPU number."""

    TOOLS = Path(__file__).resolve().parent.parent / "tools"

    def test_all_tools_compile(self):
        import py_compile

        tools = sorted(self.TOOLS.glob("*.py"))
        assert tools, self.TOOLS
        for t in tools:
            py_compile.compile(str(t), doraise=True)

    @pytest.mark.parametrize("tool", ["flash_tune.py", "gdn_tune.py", "mla_tune.py", "conv_tune.py", "moe_tune.py"])
    def test_kernel_timers_without_a_tpu_exit_nonzero_and_print_no_result(self, tool):
        proc = subprocess.run(
            [sys.executable, str(self.TOOLS / tool)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == "" and "needs a TPU" in proc.stderr


    def test_flash_tune_times_a_latent_call_in_three_forms_that_agree(self, monkeypatch, capsys):
        """``--latent``: keys and values as they are, q/k padded to whole tiles,
        and all three padded to one width and sliced back (the call before PR
        54) are one result under the interpreter, and a line a form names its
        matrix passes a tile; the device's times are stubbed."""
        from thunder_tpu.executors import pallasex as px
        sys.path.insert(0, str(self.TOOLS.parent))
        from tools import flash_tune

        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(flash_tune, "LATENT", {"tiny": (2, (192, 128), (256,))})
        monkeypatch.setattr(flash_tune, "kernel_ms", lambda run, reps: (run(), {"_flash_fwd": 1.0, "pad": 0.5})[1])
        flash_tune.time_latent("tiny", check=True)
        out = capsys.readouterr().out
        assert [l.split("[")[1].split(" passes")[0] for l in out.splitlines() if " passes a tile" in l] == ["2 + 1", "2 + 1", "2 + 2"]
        assert "the forms differ by 0.000000" in out and "FAILED" not in out
        assert "head_qk" not in px.flash_schedule       # the kernel alone: the widths are its dispatcher's to note

    def test_mla_tune_builds_the_cells_operands_and_checks_under_the_interpreter(self, monkeypatch):
        """The tool's contexts are the mix's, its tables scatter a row's blocks
        and sink-pad, and its ``--check`` (the interpreted kernel against
        ``_mla_decode_xla`` here) passes at a small shape; taking a part out
        leaves a kernel that still traces and restores the kernel afterwards."""
        import numpy as np

        from thunder_tpu.executors import pallasex as px
        sys.path.insert(0, str(self.TOOLS.parent))
        from tools import mla_tune

        contexts = mla_tune.cell_contexts(64)
        assert contexts.min() == 2624 and contexts.max() == 9480 and round(float(contexts.mean())) == 6064
        shape = dict(rows=3, nh=4, W=256, dc=128, bs=16, layers=2, table=8, pool=24)
        small = np.asarray([0, 37, 100], np.int32)
        q, arena, fresh, tables, pos = mla_tune.operands(small, **shape)
        assert arena.shape == (24, 2, 1, 16, 256) and q.shape == (3, 4, 256) and fresh.shape == (3, 256)
        used = np.asarray(tables)[np.asarray(tables) > 0]
        assert len(used) == len(set(used)) == 0 + 3 + 7 and not np.asarray(tables)[0].any() and list(pos) == [0, 37, 100]
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(px, "_MLA_CHUNK_KEYS", 32)
        assert mla_tune.check(small, shape) < 0.02
        hooks = (px._mla_dot, px._mla_start_chunk, px._mla_wait_chunk)
        for part in ("products", "copies"):
            with mla_tune.taken_out(part):
                out = mla_tune.all_layers(2, 128)(q, arena, fresh, tables, pos)
            assert out.shape == (3, 4, 128) and (px._mla_dot, px._mla_start_chunk, px._mla_wait_chunk) == hooks


    def test_moe_tune_builds_the_cells_waves_and_checks_under_the_interpreter(self, monkeypatch, tmp_path):
        """The tool's shapes are the cells' (tile, wave and rows an expert as
        ``moe_row_tile`` / ``moe_wave_tiles`` give them), an even routing fills
        a tile an expert and the skewed one some twice, and its ``--check``
        (the interpreted kernel against ``lax.ragged_dot`` here) passes at a
        small shape, forward and transposed."""
        sys.path.insert(0, str(self.TOOLS.parent))
        from tools import moe_tune

        (tg, used), = (ws := moe_tune.waves(skew=False, **moe_tune.SHAPES["lfm2_decode"]))[0]
        assert (ws[1], tg.shape[0], int(used[0])) == (64, 56, 32) and set(ws[2]) == {32}
        ws = moe_tune.waves(skew=True, **moe_tune.SHAPES["lfm2_decode"])
        assert ws[2].sum() == 1024 and ws[2].max() > 64 and int(ws[0][0][1][0]) > 32 - (ws[2] == 0).sum()
        ws = moe_tune.waves(skew=False, **moe_tune.SHAPES["axk1_decode"])
        assert (ws[1], ws[0][0][0].shape[0], int(ws[0][0][1][0])) == (16, 16, 12)
        assert moe_tune.waves(skew=False, **moe_tune.SHAPES["lfm2_prefill"])[1] == 128
        monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setitem(moe_tune.SHAPES, "small", dict(tokens=64, k=4, held=8, total=8, C=256, I=128))
        monkeypatch.setitem(moe_tune.SHAPES, "small_t", dict(tokens=64, k=4, held=4, total=16, C=256, I=128, tile=8,
                                                             transposed=True))
        # a prompt's row tile: the product copies its own weights, and the check holds it to the BlockSpec form's bits too
        monkeypatch.setitem(moe_tune.SHAPES, "small_prompt", dict(tokens=1024, k=4, held=8, total=8, C=128, I=256))
        monkeypatch.setattr(moe_tune, "KEPT", str(tmp_path / "moe_tune.txt"))
        assert moe_tune.check(["small", "small_t", "small_prompt"]) < 1e-3
        assert "small_prompt       fc   relative error" in (tmp_path / "moe_tune.txt").read_text()
        assert "the BlockSpec form's bits: True" in (tmp_path / "moe_tune.txt").read_text()


class TestSharpEdges:
    def test_time_error_policy(self):
        import time

        def f(a):
            return a * time.time()

        a = rng.standard_normal((4,)).astype(np.float32)
        with pytest.raises(Exception, match="sharp edge"):
            tt.jit(f, sharp_edges="error")(a)

    def test_random_warn_policy(self):
        import random

        def f(a):
            return a + random.random()

        a = rng.standard_normal((4,)).astype(np.float32)
        with pytest.warns(UserWarning, match="sharp edge"):
            tt.jit(f, sharp_edges="warn")(a)

    def test_allow_is_silent_default(self):
        import random

        def f(a):
            return a + random.random()

        a = rng.standard_normal((4,)).astype(np.float32)
        out = tt.jit(f)(a)  # default allow: no warning, runs
        assert np.all(np.isfinite(np.asarray(out)))

    def test_numpy_random_detected(self):
        def f(a):
            return a + float(np.random.rand())

        a = rng.standard_normal((4,)).astype(np.float32)
        with pytest.raises(Exception, match="sharp edge"):
            tt.jit(f, sharp_edges="error")(a)

    def test_guard_restores_patches(self):
        import random

        r0 = random.random
        try:
            tt.jit(lambda a: a * random.random(), sharp_edges="error")(
                rng.standard_normal((2,)).astype(np.float32)
            )
        except Exception:
            pass
        assert random.random is r0


class TestPatterns:
    def test_match_mul_add_chain(self):
        from thunder_tpu.core.patterns import Pattern
        from thunder_tpu.core.prims import PrimIDs
        from thunder_tpu.functional import trace_from_fn

        def f(a, b, c):
            return a * b + c

        a = rng.standard_normal((4,)).astype(np.float32)
        tr = trace_from_fn(f, (a, a, a), {}).computation_trace
        from thunder_tpu.core.transforms import flatten_to_prims

        flat = tr.shallow_copy() if hasattr(tr, "shallow_copy") else tr
        import thunder_tpu.core.transforms as T

        flat_trace = tr
        flat_trace.bound_symbols = T.flatten_to_prims(tr.bound_symbols)

        p = Pattern()
        p.match(lambda bsym, ctx: (bsym.sym.id == PrimIDs.MUL, {"mul": bsym}))
        p.match(
            lambda bsym, ctx: (
                bsym.sym.id == PrimIDs.ADD
                and any(a.name in {o.name for o in ctx["mul"].flat_proxy_outs} for a in bsym.flat_proxy_args),
                {},
            )
        )
        matches = p(flat_trace)
        assert len(matches) == 1
        bsyms, ctx = matches[0]
        assert [b.sym.id for b in bsyms] == [PrimIDs.MUL, PrimIDs.ADD]
        assert "mul" in ctx

    def test_no_match_across_dependency(self):
        from thunder_tpu.core.patterns import Pattern
        from thunder_tpu.core.prims import PrimIDs
        from thunder_tpu.functional import trace_from_fn
        import thunder_tpu.core.transforms as T

        # mul → (sum barrier uses mul's out) → add(uses sum): the add depends
        # on the mul THROUGH the unmatched sum, so [mul, add] must not match
        def f(a):
            m = a * a
            s = ltorch.sum(m)
            return s + 1.0

        a = rng.standard_normal((4,)).astype(np.float32)
        tr = trace_from_fn(f, (a,), {}).computation_trace
        tr.bound_symbols = T.flatten_to_prims(tr.bound_symbols)

        p = Pattern()
        p.match(lambda bsym, ctx: (bsym.sym.id == PrimIDs.MUL, {"mul": bsym}))
        p.match(lambda bsym, ctx: (bsym.sym.id == PrimIDs.ADD, {}))
        matches = p(tr)
        assert matches == [] or all(
            len(bsyms) < 2 or True for bsyms, _ in matches
        )
        # specifically: no match may pair the mul with the add
        for bsyms, _ in matches:
            ids = [b.sym.id for b in bsyms]
            assert not (PrimIDs.MUL in ids and PrimIDs.ADD in ids)

    def test_match_replace_rewrites(self):
        from thunder_tpu.core.patterns import Pattern, match_replace
        from thunder_tpu.core.prims import PrimIDs
        from thunder_tpu.functional import trace_from_fn
        import thunder_tpu.core.transforms as T
        from thunder_tpu import clang

        def f(a, b, c):
            return a * b + c

        a = rng.standard_normal((4,)).astype(np.float32)
        tr = trace_from_fn(f, (a, a, a), {}).computation_trace
        tr.bound_symbols = T.flatten_to_prims(tr.bound_symbols)

        p = Pattern()
        p.match(lambda bsym, ctx: (bsym.sym.id == PrimIDs.MUL, {"mul": bsym}))
        p.match(
            lambda bsym, ctx: (
                bsym.sym.id == PrimIDs.ADD
                and any(x.name in {o.name for o in ctx["mul"].flat_proxy_outs} for x in bsym.flat_proxy_args),
                {"add": bsym},
            )
        )

        def fma_builder(ctx, mul_bsym, add_bsym):
            x, y = mul_bsym.args[0], mul_bsym.args[1]
            mul_out = {o.name for o in mul_bsym.flat_proxy_outs}
            other = next(x2 for x2 in add_bsym.flat_proxy_args if x2.name not in mul_out)
            # rewrite as (x + 0) * y + other via different ops to make the
            # rewrite observable in the trace while staying numerically equal
            return clang.add(clang.mul(clang.add(x, 0.0), y), other)

        new_tr = match_replace(tr, p, fma_builder)
        src = new_tr.python()
        assert "Pattern rewrite" in src
        # evaluate both traces and compare
        from thunder_tpu.executors.utils import eval_bsyms

        import jax.numpy as jnp

        env1 = {pr.name: jnp.asarray(a) for pr in tr.args}
        env2 = {pr.name: jnp.asarray(a) for pr in new_tr.args}
        eval_bsyms([b for b in tr.bound_symbols if b.sym.id != PrimIDs.RETURN], env1)
        eval_bsyms([b for b in new_tr.bound_symbols if b.sym.id != PrimIDs.RETURN], env2)
        out1 = [v for k, v in env1.items()][-1]
        out_name = tr.bound_symbols[-1].flat_proxy_args[0].name
        np.testing.assert_allclose(np.asarray(env1[out_name]), np.asarray(env2[out_name]), rtol=1e-6)


class TestProfileMarkers:
    """Reference ``add_markers`` is ``observability.span`` here: a jax
    profiler annotation with nothing to enable."""

    def test_span_marks_a_region_with_no_session_open(self):
        from thunder_tpu.observability import events, span

        with span("test-region", ring=False):
            x = np.ones(3).sum()  # an inactive annotation: the work runs as it is
        assert x == 3.0 and events() == []

    def test_span_wraps_a_jax_annotation_named_with_the_prefix(self, monkeypatch):
        import sys

        import thunder_tpu.observability.events  # noqa: F401

        ev = sys.modules["thunder_tpu.observability.events"]
        seen = []

        class Annotation:
            def __init__(self, name, **kw):
                seen.append(("init", name, kw))

            def __enter__(self):
                seen.append("enter")

            def __exit__(self, *exc):
                seen.append("exit")

        monkeypatch.setattr(ev, "TraceAnnotation", Annotation)
        with ev.span("region-x", ring=False, size=3):
            seen.append("body")
        assert seen == [("init", "thunder_tpu.region-x", {"size": 3}), "enter", "body", "exit"]


def test_execution_callback_file(tmp_path):
    """Generated programs dump to the execution file; a user-edited program
    is executed instead (reference trace.py:565-574)."""
    import glob
    import os

    import numpy as np

    import thunder_tpu as tt
    import thunder_tpu.torch as lt

    base = str(tmp_path / "prog")
    tt.set_execution_callback_file(base)
    try:
        def f(x):
            return lt.mul(x, 2.0)

        x = np.ones((3,), dtype=np.float32)
        out = np.asarray(tt.jit(f)(x))
        np.testing.assert_allclose(out, 2.0 * x)
        files = glob.glob(base + ".*.py")
        assert files, "no program dumped"
        comp = [p for p in files if "2.0" in open(p).read()]
        assert comp, f"no dumped program contains the computation: {files}"
        target = comp[0]
        src = open(target).read()
        edited = src.replace("2.0", "3.0")
        assert edited != src, src
        with open(target, "w") as fh:
            fh.write(edited)
        out2 = np.asarray(tt.jit(f)(x))
        np.testing.assert_allclose(out2, 3.0 * x)
    finally:
        tt.set_execution_callback_file(None)


def test_execution_callback_file_per_program(tmp_path):
    """Different functions (and retraces) get distinct dump files — one
    function's edited program is never executed for another."""
    import numpy as np

    import thunder_tpu as tt
    import thunder_tpu.torch as lt

    base = str(tmp_path / "prog")
    tt.set_execution_callback_file(base)
    try:
        x = np.ones((3,), dtype=np.float32)
        out2 = np.asarray(tt.jit(lambda a: lt.mul(a, 2.0))(x))
        out5 = np.asarray(tt.jit(lambda a: lt.mul(a, 5.0))(x))
        np.testing.assert_allclose(out2, 2.0 * x)
        np.testing.assert_allclose(out5, 5.0 * x)
        # retrace with a new shape must not reuse the old dumped prologue
        y = np.ones((5,), dtype=np.float32)
        out_y = np.asarray(tt.jit(lambda a: lt.mul(a, 2.0))(y))
        np.testing.assert_allclose(out_y, 2.0 * y)
    finally:
        tt.set_execution_callback_file(None)


def test_optimization_fuel_limits_fusions():
    """Fuel = 0 on the fusion executor: no XLA fusion regions are created
    (miscompile-bisection lever, reference extend/__init__.py:136)."""
    from thunder_tpu.examine import get_fusions
    from thunder_tpu.extend import get_default_executors

    def f(a):
        return ltorch.sin(a) * ltorch.cos(a) + 1.0

    a = rng.standard_normal((8, 8)).astype(np.float32)

    xla = next(e for e in get_default_executors() if hasattr(e, "set_fuel"))
    try:
        xla.set_fuel(0)
        jfn = tt.jit(f)
        out = np.asarray(jfn(a))
        np.testing.assert_allclose(out, np.sin(a) * np.cos(a) + 1.0, rtol=1e-6)
        assert get_fusions(tt.last_traces(jfn)[-1]) == []
    finally:
        xla.set_fuel(None)

    jfn2 = tt.jit(f)
    jfn2(a)
    assert len(get_fusions(tt.last_traces(jfn2)[-1])) == 1  # fuel restored
