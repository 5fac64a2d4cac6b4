"""examine(): support reporting and debug tooling.

Capability analog of the reference's ``thunder/examine/__init__.py:49`` —
runs a function under a collection mode, reports which torch operations are
(un)supported by the tracer, tries the jit, and prints a repro template.
Plus ``get_fusions`` (``:190``) and a trace memory calculator
(``examine/memory_caculation.py``).
"""
from __future__ import annotations

import collections
from typing import Any, Callable

__all__ = [
    "examine",
    "get_fusions",
    "get_fusion_symbols",
    "memory_estimate",
    "memory_timeline",
    "train_memory_report",
    "cost_analysis",
]


def _collect_torch_functions(fn, args, kwargs):
    """Runs ``fn`` on real torch tensors under TorchFunctionMode, collecting
    every torch callable used (reference CollectFunctionsUsed)."""
    import torch

    calls: dict[str, Any] = {}

    class Collect(torch.overrides.TorchFunctionMode):
        def __torch_function__(self, func, types, f_args=(), f_kwargs=None):
            f_kwargs = f_kwargs or {}
            qn = getattr(func, "__qualname__", None) or str(func)
            mod = getattr(func, "__module__", "") or ""
            calls.setdefault(f"{mod}.{qn}" if mod else qn, func)
            return func(*f_args, **f_kwargs)

    with Collect():
        result = fn(*args, **kwargs)
    return calls, result


def examine(fn: Callable, *args, **kwargs) -> bool:
    """Reports whether ``fn`` can run through thunder_tpu.jit and why not.

    Returns True when everything checked out.  Never raises — the reference's
    contract is "doesn't crash the user program".
    """
    try:
        import torch
    except ImportError:  # pragma: no cover
        print("examine() requires torch for operation collection")
        return False

    from thunder_tpu.torch import _torch_to_thunder_function_map

    if not callable(fn):
        print(f"examine(): expected a callable, got {type(fn)}")
        return False

    # Step 1: run eagerly, collect the torch surface used
    try:
        calls, torch_result = _collect_torch_functions(fn, args, kwargs)
    except Exception as e:
        print(f"examine(): the function failed outside thunder_tpu ({type(e).__name__}: {e}); fix that first")
        return False

    known = set(_torch_to_thunder_function_map)
    unsupported = {name: f for name, f in calls.items() if isinstance(f, Callable) and f not in known and not _is_benign(f)}

    if unsupported:
        print(f"Found {len(unsupported)} distinct operation(s) not supported by the tracer:")
        for name in sorted(unsupported):
            print(f"  {name}")
        print(
            "\nRepro template for an operator request:\n"
            "  import thunder_tpu as tt\n"
            "  import thunder_tpu.torch as ltorch\n"
            "  def repro(...):  # minimal fn using the op above\n"
            "      ...\n"
            "  tt.jit(repro)(...)\n"
        )
    else:
        print(f"All {len(calls)} collected operations are supported by the tracer")

    # Step 2: try the jit and compare
    try:
        import numpy as np

        import thunder_tpu as tt

        jfn = tt.jit(fn)
        jit_result = jfn(*args, **kwargs)
        diverged = False
        try:
            a = np.asarray(jit_result)
            b = torch_result.detach().to(torch.float32).numpy() if isinstance(torch_result, torch.Tensor) else np.asarray(torch_result)
            if a.shape == getattr(b, "shape", None):
                ok = np.allclose(np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32), rtol=1e-3, atol=1e-4)
                diverged = not ok
                print("jit result matches eager torch" if ok else "WARNING: jit result DIVERGES from eager torch")
        except Exception:
            pass
        print("thunder_tpu.jit compiled and ran the function successfully")
        return not unsupported and not diverged
    except Exception as e:
        print(f"thunder_tpu.jit failed: {type(e).__name__}: {e}")
        return False


def _is_benign(func) -> bool:
    """Attribute accesses and dunder plumbing that need no tracer support."""
    qn = getattr(func, "__qualname__", "") or ""
    return qn.startswith(("Tensor.__", "Tensor.shape", "Tensor.dtype", "Tensor.device", "_has_torch_function"))


def get_fusion_symbols(trace) -> list:
    """All fusion bound symbols (XLA regions) in ``trace``
    (reference examine/__init__.py:190 get_fusions)."""
    out = []
    for bsym in trace.bound_symbols:
        if getattr(bsym.sym, "is_fusion", False):
            out.append(bsym)
    return out


def get_fusions(trace) -> list[tuple[str, Callable]]:
    """(name, callable) for each fusion region in ``trace``."""
    out = []
    for bsym in get_fusion_symbols(trace):
        ctx = bsym._call_ctx or {}
        for name, fusion in ctx.items():
            out.append((name, fusion))
    return out


def memory_estimate(trace) -> dict[str, int]:
    """Bytes of inputs / outputs / peak-intermediate estimate for a trace
    (reference examine/memory_caculation.py).  The intermediate estimate
    walks the trace with del-aware liveness (the shared pass in
    ``observability/memory.py``): it is the ceiling XLA's own buffer reuse
    then improves on.  Donation-aware: on a trace compiled with
    ``tt.jit(fn, donate=...)`` the peak reflects donated buffers being
    reclaimed at their consuming region, and ``donated_bytes`` reports the
    total reclaimed that way.  ``memory_timeline(trace)`` returns the
    per-symbol live/peak rows behind this summary."""
    from thunder_tpu.observability.memory import memory_timeline

    t = memory_timeline(trace)
    return {
        "input_bytes": t["input_bytes"],
        "output_bytes": t["output_bytes"],
        "peak_bytes_estimate": t["peak_bytes_estimate"],
        "donated_bytes": t["donated_bytes"],
    }


def memory_timeline(trace) -> dict:
    """Per-symbol live/peak-bytes rows for ``trace`` (del-aware liveness,
    keyed to ``del_last_used`` placement) — see
    ``thunder_tpu.observability.memory.memory_timeline``."""
    from thunder_tpu.observability.memory import memory_timeline as _mt

    return _mt(trace)


def train_memory_report(train_step) -> dict:
    """Memory accounting for a built distributed ``TrainStep``: the
    donation-aware fw/bw peaks, the remat policy + residual-bytes delta it
    bought, the accumulation buffer the scan carries, and the overlap
    bucket layout (``TrainStep.profile_stats()``, surfaced here so the
    examine toolkit covers training-step memory the way
    ``memory_estimate`` covers a single trace).  Requires the step to have
    run (built) at least once."""
    return dict(train_step.profile_stats())


# Published peaks of one chip, keyed by jax's ``device_kind`` — the ONE table
# behind every roofline, MFU and utilization figure this package gives.  A
# device that is not here is an error, never a default.
DEVICE_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_sec": 197e12,
        "int8_ops_per_sec": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_sec": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`DEVICE_PEAKS` row of ``device_kind``; raises for a device
    whose peaks nobody has written down with their source."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add a row with its source to "
            f"thunder_tpu.examine.DEVICE_PEAKS — a peak is never assumed"
        ) from None


def cost_analysis(fn: Callable, *args, flops_per_sec: float | None = None,
                  bytes_per_sec: float | None = None) -> dict:
    """XLA's OWN cost model for ``fn`` at ``args``: FLOPs, HBM bytes
    accessed, arithmetic intensity, and — given peaks — a roofline step-time
    estimate.

    ``fn`` must be jax-traceable at ``args`` — a plain jax/numpy callable,
    or a thunder execution trace's ``python_callable()``
    (``tt.last_traces(jfn)[-1].python_callable()``).  This is the
    introspection behind the depth-fit extrapolations: the cost model sees
    the exact compiled program, not an analytic FLOPs formula.

    Roofline keys (``roofline_seconds``/``compute_seconds``/
    ``memory_seconds``/``bound``) are present when both peaks are passed
    (``device_peaks(kind)`` has a chip's).  The counts describe the program
    as THIS backend compiled it, whatever peaks they are divided by.
    """
    import jax

    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    out = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": (flops / bytes_accessed) if bytes_accessed else None,
    }
    if flops_per_sec is not None and bytes_per_sec is not None:
        t_compute = flops / flops_per_sec
        t_memory = bytes_accessed / bytes_per_sec
        out.update(
            roofline_seconds=max(t_compute, t_memory),
            compute_seconds=t_compute,
            memory_seconds=t_memory,
            bound="compute" if t_compute >= t_memory else "memory",
        )
    return out
