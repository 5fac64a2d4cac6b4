"""thunder_tpu: a TPU-native source-to-source JIT compiler framework.

A brand-new framework with the capabilities of Lightning Thunder (the
reference at /root/reference), designed TPU-first: traces lower to XLA via
JAX, hot ops to Pallas kernels, and distribution to shardings over a
``jax.sharding.Mesh``.

Public API parity with the reference's ``thunder/__init__.py``:
``jit`` (:302), ``last_traces`` (:729), ``last_prologue_traces``,
``compile_data``/``compile_stats`` (:709,718), ``list_transforms``,
``last_compile_options`` (:850).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

_T_IMPORT = time.perf_counter_ns()     # the start of the `import` event (this module's last lines)
import jax  # noqa: F401  (first and on its own: the event says what of the import was JAX's)

_T_JAX = time.perf_counter_ns()

from thunder_tpu import clang  # noqa: F401
from thunder_tpu import numpy  # noqa: F401  (registers the numpy langctx)

# einops interop: registers the TensorProxy backend (PARITY: test_einops).
# Gated on the PACKAGE being present — a broken interop module must raise,
# not silently leave proxies unknown to einops
import importlib.util as _ilu

if _ilu.find_spec("einops") is not None:
    from thunder_tpu import einops_support  # noqa: F401
from thunder_tpu import torch as ltorch  # noqa: F401  (registers the torch langctx)

# top-level dtype aliases (reference thunder/__init__.py exports these):
# thunder_tpu.bfloat16 etc. work anywhere a dtype is accepted
from thunder_tpu.core.dtypes import (  # noqa: F401
    bfloat16,
    bool8,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from thunder_tpu.common import CacheEntry, CompileData, CompileStats
from thunder_tpu.core import cache_key as _cache_key
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.compile_data import compile_data_and_stats
from thunder_tpu.core.options import (
    CACHE_OPTIONS,
    SHARP_EDGES_OPTIONS,
    resolve_cache_option,
    resolve_sharp_edges_option,
)
from thunder_tpu.core.autocast import autocast
from thunder_tpu.core.batching import jvp, vmap
from thunder_tpu.core.trace import TraceCtx, TraceResults, set_execution_callback_file
from thunder_tpu.core.transform_common import absorb_ce_widening_converts, cse, dce
from thunder_tpu.extend import resolve_executors
from thunder_tpu.functional import trace_from_fn
from thunder_tpu import observability  # noqa: F401  (metrics/events/profiler)
from thunder_tpu.observability import reset_observability
from thunder_tpu.observability.debug import AnomalyError
from thunder_tpu.executors.donation import DonationError
from thunder_tpu.observability.events import scope, span as _phase_span

__version__ = "0.1.0"

__all__ = [
    "jit",
    "compile",
    "autocast",
    "grad",
    "vjp",
    "jvp",
    "vmap",
    "value_and_grad",
    "last_traces",
    "last_backward_traces",
    "last_prologue_traces",
    "last_interpreter_log",
    "print_last_interpreter_log",
    "compile_data",
    "compile_stats",
    "cache_option",
    "cache_hits",
    "cache_misses",
    "dispatch_stats",
    "last_compile_options",
    "profile_stats",
    "donation_stats",
    "metrics_snapshot",
    "metrics_export_text",
    "serve",
    "export_chrome_trace",
    "flight_record",
    "scope",
    "observability",
    "reset_observability",
    "AnomalyError",
    "DonationError",
    "dtypes",
]


def _normalize_donate(donate):
    """``donate=`` → a hashable canonical form: ``None`` (off), ``"auto"``
    (True: every provably dead fusion input), or a sorted argnums tuple
    (explicit: those positional args' tensors MUST be donatable, else
    :class:`DonationError`).  Raises on anything else at jit() time."""
    if donate is None or donate is False:
        return None
    if donate is True:
        return "auto"
    if isinstance(donate, int) and not isinstance(donate, bool):
        return (donate,)
    if isinstance(donate, (tuple, list)) and all(
        isinstance(i, int) and not isinstance(i, bool) for i in donate
    ):
        check(len(donate) > 0, lambda: "donate=() donates nothing; pass False or argnums")
        return tuple(sorted(set(donate)))
    check(False, lambda: (
        f"donate must be True, False, or a tuple of positional argnums, got {donate!r}"
    ))


def jit(
    fn: Callable,
    *,
    langctx: Any | None = None,
    executors: Sequence | None = None,
    cache: str | CACHE_OPTIONS | None = None,
    sharp_edges: str | SHARP_EDGES_OPTIONS | None = None,
    transforms: Sequence | None = None,
    disable_grad: bool = False,
    max_cached_specializations: int | None = 512,
    **compile_options,
) -> Callable:
    """Compiles ``fn``: traces it into a thunder_tpu program, applies
    transforms (grad, distributed, autocast), and dispatches to the executor
    stack (XLA fusion ≻ Pallas ≻ eager JAX).

    The returned callable caches compilations keyed by input metadata; the
    prologue re-validates inputs on every call (reference thunder.jit,
    __init__.py:302).

    A ``torch.nn.Module`` argument returns a ``ThunderModule`` instead: its
    forward runs as a compiled program bridged into torch autograd
    (reference thunder.jit on modules, __init__.py:181).
    """
    # sugar: jit(fn, autocast="bf16"|"fp16") appends the autocast transform
    # (reference thunder.jit handles autocast in the jit entry, __init__.py:552)
    ac = compile_options.pop("autocast", None)
    if ac is not None:
        from thunder_tpu.core import dtypes as _dt

        _ac_map = {"bf16": _dt.bfloat16, "bfloat16": _dt.bfloat16,
                   "fp16": _dt.float16, "float16": _dt.float16}
        if isinstance(ac, str):
            dtype = _ac_map.get(ac)
        elif isinstance(ac, (bool, int, float, complex)) or hasattr(ac, "shape"):
            # numbers and arrays are typos, not dtype requests: fail fast
            dtype = None
        else:  # torch/jax/numpy/thunder dtype objects all convert
            try:
                dtype = _dt.to_dtype(ac)
            except Exception:
                dtype = None
            if dtype is not None and not _dt.is_float_dtype(dtype):
                dtype = None
        check(dtype is not None, lambda: f"unknown autocast target {ac!r} (use 'bf16'/'fp16' or a float dtype)")
        transforms = list(transforms or []) + [autocast(dtype)]

    try:
        import torch as _torch
    except ImportError:  # pragma: no cover - torch is an optional interop dep
        _torch = None
    if _torch is not None and isinstance(fn, _torch.nn.Module):
        # interop import errors must propagate: silently falling through
        # would bake the parameters in as constants and train nothing
        from thunder_tpu.torch_interop import ThunderModule

        check(langctx is None, lambda: (
            "langctx is not supported for torch.nn.Module inputs — the "
            "interop path traces through the torch surface by construction"))
        return ThunderModule(
            fn,
            executors=executors,
            cache=cache,
            sharp_edges=sharp_edges,
            transforms=transforms,
            disable_grad=disable_grad,
            max_cached_specializations=max_cached_specializations,
            **compile_options,
        )

    # persistent XLA compilation cache: every process compiling the same
    # HLO reuses the on-disk artifact (nvFuser serde-cache analog) — lazy
    # so a plain import never mutates jax config
    from thunder_tpu.core import compile_cache

    compile_cache.ensure_enabled()

    if langctx is not None:
        # resolve eagerly so a typo fails at jit() time, not first call
        # (reference jit's langctx kwarg, __init__.py:307)
        from thunder_tpu.core.langctxs import resolve_language

        compile_options["langctx"] = resolve_language(langctx)

    # normalized donation setting (None | "auto" | argnums tuple): validated
    # here so a typo fails at jit() time, and folded into the dispatch key as
    # a salt so the same fn under different donation settings never shares a
    # specialization (the donated and undonated programs differ)
    _donation_salt = _normalize_donate(compile_options.get("donate", None))

    cd = CompileData(
        fn=fn,
        executors_list=resolve_executors(executors),
        cache_option=resolve_cache_option(cache),
        sharp_edges=resolve_sharp_edges_option(sharp_edges),
        transforms=transforms,
        disable_grad=disable_grad,
        compile_options=compile_options,
        max_cached_specializations=max_cached_specializations,
    )
    cs = CompileStats()

    from itertools import chain

    from thunder_tpu.core.proxies import Proxy
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.core.trace import get_tracectx

    _fn_label = getattr(fn, "__name__", "fn")

    def fn_(*args, **kwargs):
        if get_tracectx() is not None and any(
            isinstance(a, Proxy)
            for a in chain(tree_flatten(args)[0], tree_flatten(kwargs)[0])
        ):
            # a compiled callable invoked ON PROXIES inside another trace —
            # e.g. tt.grad(tt.grad(f)) — would run its prologue on symbolic
            # values and silently produce garbage.  Higher-order composition
            # is not supported (the reference has no nested-grad path
            # either); fail with the workaround instead of a confusing
            # downstream TypeError
            raise NotImplementedError(
                "a thunder_tpu-compiled function was called inside another "
                "trace (nested jit/grad composition is unsupported) — "
                "compose at the trace level instead: pass the original "
                "Python function, e.g. tt.grad(lambda x: original_fn(x))"
            )
        cs.calls += 1
        dispatch_start = time.perf_counter_ns()
        cs.last_trace_host_start = dispatch_start

        # Two-tier dispatch.  Tier 1: one structural key computation + one
        # hash-map lookup selects the candidate bucket (vs the O(entries)
        # try-every-prologue scan this replaces).  Tier 2: the candidate's
        # prologue runs ONCE for exact guard validation — external-state
        # guards (globals/closures from the bytecode frontend) can't be
        # keyed.  A prologue failure after a key match shadows the entry
        # (demoted behind fresher same-key entries) instead of falling
        # through to a full rescan.
        cache_entry = None
        key = None
        inps = None
        if cd.cache_option is not CACHE_OPTIONS.NO_CACHING:
            key = _cache_key.compute_cache_key(
                args, kwargs,
                symbolic=cd.cache_option is CACHE_OPTIONS.SYMBOLIC_VALUES,
                salt=("donate", _donation_salt) if _donation_salt is not None else None,
            )
            cs.key_computations += 1
            if key is not None:
                bucket = cs.dispatch_cache.get(key)
                if bucket:
                    for idx, entry in enumerate(tuple(bucket)):
                        cs.prologue_runs += 1
                        try:
                            inps = entry.prologue_fn(*args, **kwargs)
                        except Exception:
                            # guard failure after a key match: external state
                            # changed since this entry was traced — shadow it
                            # (the recompile lands in front; reverting the
                            # state later re-finds it via the bucket scan)
                            cs.guard_evictions += 1
                            bucket.remove(entry)
                            bucket.append(entry)
                            continue
                        cache_entry = entry
                        if idx == 0:
                            cs.key_hits += 1
                        else:
                            cs.scan_hits += 1
                            bucket.remove(entry)
                            bucket.insert(0, entry)
                        break
            else:
                # unkeyable inputs (unhashable pytree aux, exotic leaves):
                # the legacy linear prologue scan, correct but O(entries)
                for entry in cs.interpreter_cache:
                    cs.prologue_runs += 1
                    try:
                        inps = entry.prologue_fn(*args, **kwargs)
                    except Exception:
                        continue
                    cache_entry = entry
                    cs.scan_hits += 1
                    break
            if cache_entry is not None:
                cs.cache_hits += 1
                cache_entry.last_used = cs.calls

        was_hit = cache_entry is not None
        if cache_entry is None:
            cs.cache_misses += 1
            observability.compile_begin(_fn_label)
            compile_start = time.perf_counter_ns()
            with _phase_span("compile", fn=_fn_label), compile_data_and_stats(cd, cs):
                cache_entry = _compile(cd, cs, args, kwargs)
            observability.compile_end(_fn_label, time.perf_counter_ns() - compile_start)
            if cd.cache_option is not CACHE_OPTIONS.NO_CACHING:
                cache_entry.cache_key = key
                cache_entry.last_used = cs.calls
                cs.interpreter_cache.append(cache_entry)
                if key is not None:
                    cs.dispatch_cache.setdefault(key, []).insert(0, cache_entry)
                _evict_lru(cd, cs)
            cs.prologue_runs += 1
            inps = cache_entry.prologue_fn(*args, **kwargs)
        cs.last_dispatch_ns = time.perf_counter_ns() - dispatch_start
        cs.dispatch_ns += cs.last_dispatch_ns
        # registry mirror + user hooks (one call; payloads only built when a
        # hook is registered — see observability.dispatch_event)
        observability.dispatch_event(_fn_label, ns=cs.last_dispatch_ns, hit=was_hit)

        if cache_entry.uses_rng:
            from thunder_tpu.core import rng

            inps = tuple(inps) + (rng.next_key(),)

        cs.last_trace_host_execution_start = time.perf_counter_ns()
        if cache_entry.backward_fn is not None and getattr(cache_entry, "vjp_mode", False):
            # proper backward entry point: the caller supplies cotangents
            from thunder_tpu.core.pytree import tree_flatten as _tfl

            output, saved = cache_entry.computation_fn(*inps)
            backward_fn = cache_entry.backward_fn
            postprocess = cache_entry.return_spec
            ct_positions = cache_entry.ct_positions

            def pullback(cotangents):
                """cotangents: same structure as the function's output; pass
                None for non-differentiable output leaves (None flattens
                away, so exactly the differentiable leaves remain, in
                output order)."""
                flat_cts, _ = _tfl(cotangents)
                check(
                    len(flat_cts) == len(ct_positions),
                    lambda: f"pullback expected cotangents for {len(ct_positions)} "
                    f"differentiable output leaves, got {len(flat_cts)} (pass None "
                    f"for non-differentiable outputs)",
                )
                flat_grads = backward_fn(*saved, *flat_cts)
                return postprocess(flat_grads) if postprocess else flat_grads

            result = (output, pullback)
        elif cache_entry.backward_fn is not None:
            # scalar-loss sugar: cotangent is ones (grad / value_and_grad)
            import jax.numpy as jnp

            output, saved = cache_entry.computation_fn(*inps)
            ct = jnp.ones(getattr(output, "shape", ()), dtype=getattr(output, "dtype", jnp.float32))
            flat_grads = cache_entry.backward_fn(*saved, ct)
            grads = cache_entry.return_spec(flat_grads) if cache_entry.return_spec else flat_grads
            result = (output, grads)
        else:
            result = cache_entry.computation_fn(*inps)
            if cache_entry.epilogue_fn is not None:
                # the computation returns (user_result, mutated_leaves); the
                # epilogue writes the mutated leaves back into the caller's
                # containers (reference epilogue execution, __init__.py:651)
                result, mutated = result
                cache_entry.epilogue_fn(args, kwargs, *mutated)
        cs.last_trace_host_execution_stop = time.perf_counter_ns()
        cs.last_trace_host_stop = cs.last_trace_host_execution_stop
        return result

    fn_._lc_cd = cd
    fn_._lc_cs = cs
    fn_.__wrapped__ = fn
    fn_.__name__ = getattr(fn, "__name__", "fn") + "_compiled"
    return fn_


def _evict_lru(cd: CompileData, cs: CompileStats) -> None:
    """Enforces the specialization bound: least-recently-validated entries are
    dropped from both cache views.  Runs at insert time only (compile cost
    already dominates), so the hot dispatch path never pays for it."""
    bound = cd.max_cached_specializations
    if not bound:
        return
    while len(cs.interpreter_cache) > bound:
        victim = min(cs.interpreter_cache, key=lambda e: e.last_used)
        cs.interpreter_cache.remove(victim)
        bucket = cs.dispatch_cache.get(victim.cache_key)
        if bucket is not None and victim in bucket:
            bucket.remove(victim)
            if not bucket:
                del cs.dispatch_cache[victim.cache_key]
        cs.lru_evictions += 1


def _compile(cd: CompileData, cs: CompileStats, args: tuple, kwargs: dict) -> CacheEntry:
    """Trace → transforms → executor dispatch → codegen (one cache entry)."""
    from thunder_tpu.core.compile_data import get_compile_option
    from thunder_tpu.executors.passes import del_last_used, transform_for_execution

    grad_argnums = cd.compile_options.get("_grad_argnums")
    vjp_mode = bool(cd.compile_options.get("_vjp_mode"))
    if vjp_mode and grad_argnums is None:
        grad_argnums = tuple(range(len(args)))

    # runtime profiling transform (observability): applied LAST, over the
    # execution trace(s), and only when requested — otherwise the generated
    # program is byte-identical to the uninstrumented one
    profile_opt = get_compile_option(
        "profile",
        "Enable the runtime profiling transform: every executed symbol/fusion "
        "region is wrapped in timing, queryable via thunder_tpu.profile_stats.",
        default=None,
    )
    profile_on = bool(profile_opt) if profile_opt is not None else observability.profiling_env_enabled()
    profile_report = None
    profile_barriers = True
    if profile_on:
        from thunder_tpu.observability.profiler import ProfileReport

        profile_barriers = bool(get_compile_option(
            "profile_barriers",
            "Fence each instrumented symbol with jax.block_until_ready for "
            "device-accurate per-symbol times (default True).",
            default=True,
        ))
        if cs.profile_report is None:
            cs.profile_report = ProfileReport()
        profile_report = cs.profile_report

    # numerics-debugging transform (observability/debug.py): pre/post hooks
    # on every executed symbol plus the NaN/Inf anomaly scan.  Like the
    # profiler, applied LAST and only when requested — off means the
    # generated program is byte-identical to the uninstrumented one
    detect_opt = get_compile_option(
        "detect_anomalies",
        "Scan every instrumented symbol's outputs for NaN/Inf and raise a "
        "structured AnomalyError naming the symbol and the user source line "
        "(forward and backward traces).",
        default=None,
    )
    anomaly_on = (
        bool(detect_opt) if detect_opt is not None else observability.anomaly_env_enabled()
    )
    debug_hooks_opt = get_compile_option(
        "debug_hooks",
        "Pre/post callbacks on every executed BoundSymbol/fusion region: "
        "(pre, post) tuple, {'pre':..., 'post':...} dict, or one callable "
        "(post).  Each receives a SymbolInfo with name and source provenance.",
        default=None,
    )
    debug_cfg = None
    if anomaly_on or debug_hooks_opt is not None:
        from thunder_tpu.observability.debug import resolve_debug_hooks

        dbg_pre, dbg_post = resolve_debug_hooks(debug_hooks_opt)
        debug_cfg = {"pre": dbg_pre, "post": dbg_post, "detect_anomalies": anomaly_on}

    # del-aware buffer donation (executors/donation.py): a post-lowering
    # pass arming each fusion region with the inputs the trace proves dead.
    # Off (None) means the pass never runs and the generated program stays
    # byte-identical to the undonated one
    donate_opt = get_compile_option(
        "donate",
        "Buffer donation for XLA fusion regions: True donates every input "
        "the lowered trace proves dead (its DEL follows the region; it is "
        "not a trace output, an aliased view, or consumed later); a tuple "
        "of positional argnums additionally asserts those args' tensors "
        "MUST donate (DonationError names the proxy and the blocking use "
        "otherwise).  Donated caller arrays are CONSUMED — do not reuse "
        "them after the call.  Default False: byte-identical program.",
        default=None,
    )
    donation = _normalize_donate(donate_opt)

    cs.last_trace_tracing_start = time.perf_counter_ns()
    from thunder_tpu.core.sharp_edges import sharp_edges_guard

    with sharp_edges_guard(cd.sharp_edges):
        trace_results: TraceResults = trace_from_fn(
            cd.fn,
            args,
            kwargs,
            grad_argnums=grad_argnums,
            interpretation=cd.compile_options.get("interpretation"),
            symbolic_numbers=cd.cache_option is CACHE_OPTIONS.SYMBOLIC_VALUES,
            language=cd.compile_options.get("langctx"),
        )
    cs.last_trace_tracing_stop = time.perf_counter_ns()

    prologue_trace = trace_results.prologue_trace
    computation_trace = trace_results.computation_trace
    computation_trace.set_provenance("Trace acquisition (functional frontend)")

    cs.last_traces = [computation_trace]
    cs.last_prologue_traces = [prologue_trace]
    cs.last_interpreter_log = getattr(computation_trace, "_interpreter_log", [])

    with _phase_span("transform:dce"):
        computation_trace = dce(computation_trace)
    cs.last_traces.append(computation_trace)
    with _phase_span("transform:cse"):
        computation_trace = cse(computation_trace)
    cs.last_traces.append(computation_trace)
    with _phase_span("transform:absorb_ce_widening_converts"):
        absorbed = absorb_ce_widening_converts(computation_trace)
    if absorbed is not computation_trace:  # no-op returns the input unchanged
        computation_trace = absorbed
        cs.last_traces.append(computation_trace)

    # user/distributed transforms (trace -> trace)
    for transform in cd.transforms:
        tname = getattr(transform, "__name__", type(transform).__name__)
        with _phase_span(f"transform:{tname}"):
            computation_trace = transform(computation_trace)
        cs.last_traces.append(computation_trace)

    bw_fn = None
    bw_extrace = None
    bw_donation_report = None
    grad_postprocess = None
    ct_positions = ()
    if grad_argnums is not None:
        from thunder_tpu.core.transforms import forward_and_backward_from_trace
        from thunder_tpu.core.proxies import TensorProxy as _TP
        from thunder_tpu.core.pytree import tree_flatten as _tf

        # grad contract (jax.grad-style): a single scalar differentiable
        # output — unless vjp mode, where the caller supplies cotangents for
        # every differentiable output leaf
        for bsym in computation_trace.bound_symbols:
            if bsym.sym.id is prims.PrimIDs.RETURN:
                flat_outs = _tf(bsym.args)[0]
                outs = [o for o in flat_outs if isinstance(o, _TP)]
                if vjp_mode:
                    ct_positions = tuple(
                        i
                        for i, o in enumerate(flat_outs)
                        if isinstance(o, _TP) and dtypes.is_inexact_dtype(o.dtype)
                    )
                    check(
                        len(ct_positions) > 0,
                        lambda: "vjp requires at least one differentiable output",
                    )
                else:
                    check(
                        len(outs) == 1 and outs[0].shape == () and dtypes.is_inexact_dtype(outs[0].dtype),
                        lambda: f"grad/value_and_grad require the function to return a single scalar float "
                        f"(got {[(tuple(o.shape), str(o.dtype)) for o in outs]})",
                    )

        with _phase_span("transform:forward_backward_split"):
            fw_trace, bw_trace = forward_and_backward_from_trace(computation_trace)
        cs.last_traces.append(fw_trace)
        cs.last_backward_traces = [bw_trace]
        if cd.compile_options.get("remat", True):
            from thunder_tpu.core.rematerialization import rematerialize_forward_and_backward

            with _phase_span("transform:rematerialization"):
                fw_trace, bw_trace = rematerialize_forward_and_backward(fw_trace, bw_trace)
            cs.last_traces.append(fw_trace)
            cs.last_backward_traces.append(bw_trace)
        computation_trace = fw_trace

        bw_extrace = transform_for_execution(bw_trace, cd.executors_list)
        cs.last_backward_traces.append(bw_extrace)
        bw_extrace = del_last_used(bw_extrace)
        cs.last_backward_traces.append(bw_extrace)
        if donation is not None:
            # backward donation is always automatic: its inputs are saved
            # residuals and cotangents, which user argnums cannot name
            from thunder_tpu.executors.passes import annotate_donations

            bw_extrace, bw_donation_report = annotate_donations(
                bw_extrace, which="backward"
            )
            cs.last_backward_traces.append(bw_extrace)
        if debug_cfg is not None:
            from thunder_tpu.observability.debug import instrument_for_debugging

            bw_extrace = instrument_for_debugging(
                bw_extrace, which="backward", **debug_cfg
            )
            cs.last_backward_traces.append(bw_extrace)
        if profile_report is not None:
            from thunder_tpu.observability.profiler import instrument_for_profiling

            bw_extrace = instrument_for_profiling(
                bw_extrace, profile_report, which="backward", barriers=profile_barriers
            )
            cs.last_backward_traces.append(bw_extrace)
        bw_fn = bw_extrace.python_callable()
        grad_postprocess = _make_grad_postprocess(trace_results.computation_trace, grad_argnums)

    extrace = transform_for_execution(computation_trace, cd.executors_list)
    cs.last_traces.append(extrace)
    extrace = del_last_used(extrace)
    cs.last_traces.append(extrace)
    if donation is not None:
        from thunder_tpu.executors.donation import donation_summary
        from thunder_tpu.executors.passes import annotate_donations

        candidate = None
        strict = False
        if donation != "auto":
            # explicit argnums: resolve the user's positional args to their
            # tensor-leaf proxies (functional.py records the map at trace
            # time) and assert donation of exactly those
            arg_map = getattr(trace_results.computation_trace, "_input_argnums", {})
            candidate = {n for n, a in arg_map.items() if a in donation}
            check(
                bool(candidate),
                lambda: f"donate={donation!r} matched no tensor arguments of "
                f"{getattr(cd.fn, '__name__', cd.fn)!r}",
            )
            strict = True
        extrace, fw_donation_report = annotate_donations(
            extrace, candidate_names=candidate, strict=strict
        )
        cs.last_traces.append(extrace)
        cs.donation_reports = {
            "forward": donation_summary(fw_donation_report),
            "backward": (
                donation_summary(bw_donation_report)
                if bw_donation_report is not None
                else None
            ),
        }
    if debug_cfg is not None:
        from thunder_tpu.observability.debug import instrument_for_debugging

        with _phase_span("transform:debug_instrumentation"):
            extrace = instrument_for_debugging(extrace, **debug_cfg)
        cs.last_traces.append(extrace)
    if profile_report is not None:
        from thunder_tpu.observability.profiler import instrument_for_profiling

        with _phase_span("transform:profiling_instrumentation"):
            extrace = instrument_for_profiling(
                extrace, profile_report, barriers=profile_barriers
            )
        cs.last_traces.append(extrace)

    comp_fn = extrace.python_callable()
    pro_fn = prologue_trace.python_callable()

    uses_rng = getattr(trace_results.computation_trace, "_rng_key_proxy", None) is not None

    entry = CacheEntry(
        prologue_fn=pro_fn,
        computation_fn=comp_fn,
        backward_fn=bw_fn,
        prologue_trace=prologue_trace,
        computation_trace=extrace,
        backward_trace=bw_extrace,
        epilogue_trace=trace_results.epilogue_trace,
        uses_rng=uses_rng,
        epilogue_fn=(
            trace_results.epilogue_trace.python_callable()
            if trace_results.epilogue_trace is not None
            else None
        ),
    )
    entry.return_spec = grad_postprocess
    entry.vjp_mode = vjp_mode
    entry.ct_positions = ct_positions
    # trace-time key emission (functional.py builds it next to the prologue):
    # the key function + metadata ride on the entry for introspection; the
    # dispatcher files the entry under the key it computed for this call
    key_meta = trace_results.cache_key_meta or {}
    entry.cache_key_fn = key_meta.get("cache_key_fn")
    if donation is not None:
        # the dispatcher salts this entry's key with the donation setting;
        # the recomputing key fn (and the introspectable meta) must agree
        entry.cache_key_fn = _cache_key.make_cache_key_fn(
            cd.cache_option is CACHE_OPTIONS.SYMBOLIC_VALUES,
            salt=("donate", donation),
        )
        key_meta = {**key_meta, "donate": donation}
    entry.key_meta = key_meta
    entry.has_state_guards = key_meta.get("state") is not None
    return entry


def _make_grad_postprocess(computation_trace, grad_argnums):
    """Builds grads-restructuring: flat grads (input order) → per-argnum pytrees."""
    from thunder_tpu.core.pytree import tree_unflatten

    grad_meta = getattr(computation_trace, "_grad_meta", [])

    def postprocess(flat_grads):
        flat_grads = list(flat_grads)
        it = iter(flat_grads)
        by_argnum = {}
        for argnum, spec_i, leaf_proxies in grad_meta:
            leaves = [next(it) if p is not None else None for p in leaf_proxies]
            by_argnum[argnum] = tree_unflatten(leaves, spec_i)
        ordered = tuple(by_argnum[a] for a in grad_argnums)
        return ordered[0] if len(ordered) == 1 else ordered

    return postprocess


def compile(fn: Callable, **kwargs) -> Callable:
    """Legacy alias for ``jit`` (reference thunder.compile, __init__.py:676)."""
    return jit(fn, **kwargs)


#
# grad APIs (populated by thunder_tpu.core.transforms; re-exported here)
#


def grad(fn: Callable, **jit_kwargs) -> Callable:
    from thunder_tpu.core.transforms import grad as _grad

    return _grad(fn, **jit_kwargs)


def value_and_grad(fn: Callable, **jit_kwargs) -> Callable:
    from thunder_tpu.core.transforms import value_and_grad as _value_and_grad

    return _value_and_grad(fn, **jit_kwargs)


def vjp(fn: Callable, argnums: Sequence[int] | None = None, **jit_kwargs) -> Callable:
    """jax.vjp-style backward entry point with user-supplied cotangents.

    ``vjp(fn)(*args)`` returns ``(out, pullback)`` where ``pullback(ct)``
    takes a cotangent matching ``out``'s structure and returns gradients for
    ``argnums`` (default: every positional arg).  Unlike ``grad``/
    ``value_and_grad``, the function may return non-scalar (and multiple)
    outputs.  Replaces the reference's ``ThunderFunction.backward`` contract
    (``thunder/executors/torch_autograd.py:57-78``) for the functional world;
    the torch bridge in ``thunder_tpu.torch_interop`` builds on it.
    """
    if argnums is not None:
        argnums = (argnums,) if isinstance(argnums, int) else tuple(argnums)
    return jit(fn, _vjp_mode=True, _grad_argnums=argnums, **jit_kwargs)


#
# Introspection (reference __init__.py:709-885)
#


def _unwrap_cfn(cfn):
    """ThunderModule holds its compiled function internally (the vjp of the
    functionalized forward, or the forward-only inference path);
    introspection accepts either, like the reference's last_traces on
    ThunderModule (reference __init__.py:709).  When both paths have been
    compiled, the most recently INVOKED one answers (tracked by the module)."""
    if not hasattr(cfn, "_lc_cs"):
        for attr in ("_last_compiled", "_vjp_fn", "_fwd_fn"):
            inner = getattr(cfn, attr, None)
            if inner is not None and hasattr(inner, "_lc_cs"):
                return inner
    return cfn


def _get_cs(cfn) -> CompileStats:
    cs = getattr(_unwrap_cfn(cfn), "_lc_cs", None)
    check(cs is not None, lambda: f"{cfn} is not a thunder_tpu-compiled function")
    return cs


def compile_data(cfn) -> CompileData:
    cd = getattr(_unwrap_cfn(cfn), "_lc_cd", None)
    check(cd is not None, lambda: f"{cfn} is not a thunder_tpu-compiled function")
    return cd


def compile_stats(cfn) -> CompileStats:
    return _get_cs(cfn)


def last_traces(cfn) -> list[TraceCtx]:
    return _get_cs(cfn).last_traces


def last_backward_traces(cfn) -> list[TraceCtx]:
    return _get_cs(cfn).last_backward_traces


def last_prologue_traces(cfn) -> list[TraceCtx]:
    return _get_cs(cfn).last_prologue_traces


def last_interpreter_log(cfn) -> list:
    """The bytecode frontend's per-opcode run log from the last trace
    (reference ``thunder.last_interpreter_log``, __init__.py:817).  Empty
    unless the function was compiled with ``interpretation="bytecode"``."""
    return _get_cs(cfn).last_interpreter_log


def print_last_interpreter_log(cfn, *, max_lines: int | None = 2000) -> None:
    """Prints the last bytecode-interpreter run as an indented instruction
    listing (reference ``print_last_interpreter_log``,
    core/interpreter.py:6683-6789) — the first tool to reach for when the
    bytecode frontend mis-traces a model."""
    from thunder_tpu.core.interpreter import format_interpreter_log

    print(format_interpreter_log(last_interpreter_log(cfn), max_lines=max_lines))


def cache_option(cfn) -> CACHE_OPTIONS:
    return compile_data(cfn).cache_option


def cache_hits(cfn) -> int:
    return _get_cs(cfn).cache_hits


def cache_misses(cfn) -> int:
    return _get_cs(cfn).cache_misses


def dispatch_stats(cfn) -> dict:
    """Two-tier dispatch counters: ``key_hits`` (O(1) hash-map hit, first
    bucket entry validated), ``scan_hits`` (shadowed-bucket or legacy linear
    scan), ``guard_evictions`` (prologue failed after a key match — external
    state changed), ``lru_evictions``, plus per-call dispatch timing.

    These are the per-function view; the dispatch path also publishes
    process-wide aggregates into the unified metrics registry
    (``observability.snapshot()``: ``dispatch.calls`` /
    ``dispatch.cache_hits`` / ``dispatch.cache_misses`` / ``dispatch.ns``)."""
    cs = _get_cs(cfn)
    return {
        "key_hits": cs.key_hits,
        "scan_hits": cs.scan_hits,
        "guard_evictions": cs.guard_evictions,
        "lru_evictions": cs.lru_evictions,
        "key_computations": cs.key_computations,
        "prologue_runs": cs.prologue_runs,
        "cached_specializations": len(cs.interpreter_cache),
        "last_dispatch_ns": cs.last_dispatch_ns,
        "dispatch_ns": cs.dispatch_ns,
    }


def profile_stats(cfn):
    """Per-symbol runtime profile of a function compiled with
    ``profile=True`` (or under ``THUNDER_TPU_PROFILE=1``): a mapping
    ``label -> {calls, total_ns, mean_ns, min_ns, max_ns, flops?, bytes?}``
    covering every instrumented BoundSymbol / fusion region (forward and,
    when present, backward).  ``print()`` the report for the table sorted by
    total time.  FLOP/byte estimates come from XLA's ``cost_analysis()`` at
    the traced shapes, computed lazily on first query."""
    cs = _get_cs(cfn)
    check(
        cs.profile_report is not None,
        lambda: "no profiling data: compile with tt.jit(fn, profile=True) "
        "(or set THUNDER_TPU_PROFILE=1 before the first call) and invoke "
        "the compiled function at least once",
    )
    return cs.profile_report


def donation_stats(cfn) -> dict:
    """The donation analysis of a function compiled with ``tt.jit(fn,
    donate=True|argnums)``: ``{"forward": summary, "backward": summary|None}``
    where each summary lists, per fusion region, the donated buffers, the
    input→output alias pairings, the donated byte count, and every rejection
    with its reason (``trace_output`` / ``later_use`` / ``aliased_view`` /
    ``no_del``).  Process-wide aggregates land in the ``donation.*`` metrics
    (``tt.metrics_snapshot()``)."""
    cs = _get_cs(cfn)
    check(
        cs.donation_reports is not None,
        lambda: "no donation data: compile with tt.jit(fn, donate=True) (or "
        "an argnums tuple) and call the compiled function at least once",
    )
    return cs.donation_reports


def metrics_snapshot() -> dict:
    """One plain dict of every registered metric — dispatch, compile,
    profiler, anomaly, memory, and ``donation.*`` counters included
    (alias of ``thunder_tpu.observability.snapshot()``)."""
    return observability.snapshot()


def metrics_export_text() -> str:
    """The registry rendered in Prometheus text exposition format (0.0.4):
    counters and gauges as-is, histograms as a ``summary`` family whose
    quantiles are computed over the histogram's bounded sample window (the
    HELP line carries that caveat).  Serve it from any HTTP handler to
    scrape thunder_tpu like vLLM's ``/metrics`` (alias of
    ``thunder_tpu.observability.export_text()``; see MIGRATION.md)."""
    return observability.export_text()


def export_chrome_trace(path: str) -> str:
    """Writes the buffered events — compile pipeline (interpret / transforms
    / lower / codegen / compile) AND any per-request serving lifecycle spans
    recorded by a ``tt.serve(..., trace=True)`` engine — as one merged
    Chrome-trace JSON loadable in chrome://tracing or ui.perfetto.dev, with
    the serving plane on its own labeled process/request tracks (see
    ``thunder_tpu.observability.events``/``tracing``)."""
    return observability.export_chrome_trace(path)


def flight_record(path) -> str:
    """Dumps the most recently active serving flight recorder (an engine
    built with ``flight_recorder=True`` or ``THUNDER_TPU_FLIGHT_RECORDER=1``)
    to ``path``: the bounded ring of recent engine events plus a
    scheduler/pool state snapshot (occupancy, free-list/sharing accounting,
    prefix-share hit rate, per-bucket compile causes).  The same payload is
    auto-dumped when ``engine.step()`` raises.  See
    ``thunder_tpu.observability.flight``."""
    from thunder_tpu.observability.flight import flight_record as _fr

    return _fr(path)


def last_compile_options(cfn) -> dict:
    """Which compile options the last compilation consulted (self-documented
    via get_compile_option; reference __init__.py:850)."""
    cs = _get_cs(cfn)
    return dict(cs.last_compile_reasons)


def serve(model_fn, params, cfg, **kwargs):
    """Continuous-batching inference engine over a paged KV-cache pool:
    ``tt.serve(None, params, cfg, num_blocks=..., max_batch=...)`` →
    :class:`thunder_tpu.serving.ServingEngine` with ``submit(prompt, *,
    max_new_tokens, deadline, stream_cb) -> RequestHandle``, a synchronous
    ``step()`` drive loop, and ``run()``/``drain()``/``shutdown()``.
    The model is ``cfg`` (a ``llama.Config``) served by the in-tree
    ``models.generate`` forward; the first argument must be ``None``.
    Mesh serving: ``mesh=`` (plus optional ``shardings=`` from
    ``distributed``'s rule tables) runs the whole engine SPMD — params
    placed once, the KV block arena sharded heads-over-``tp``
    (``distributed.kv_cache_spec``), bucket programs compiled once per
    (mesh, bucket) — with served tokens bit-identical to solo
    ``generate(..., mesh=mesh)``; see GUIDE.md "Sharded serving".
    Serving-plane observability (each off by default): ``trace=True`` for
    per-request lifecycle spans in ``tt.export_chrome_trace``, ``slo={...}``
    for burn-rate monitoring via ``engine.slo_report()``,
    ``flight_recorder=True`` for crash dumps (``tt.flight_record``), and
    ``goodput=True`` for the exact device-work ledger — every dispatched
    token-position classified committed-or-waste with per-dispatch
    conservation (``stats()["goodput"]`` / ``engine.goodput_report()``;
    GUIDE.md "Goodput & waste attribution").  All compile zero extra
    programs and leave the default off-path byte-identical.
    Speculative serving: ``speculative=serving.SpecConfig(draft_params,
    draft_cfg, K=...)`` runs a draft/verify lane over the paged arena —
    each decode turn drafts K tokens with the cheap model and verifies
    them in ONE target forward, emitting 1..K+1 tokens per round with
    served tokens bit-identical to solo ``speculative_generate()``.
    Strictly additive: nothing else in the pipeline changes by building an
    engine (the import is deferred to keep the off-path cost at zero).  See
    GUIDE.md "Serving" and ``thunder_tpu.serving``."""
    from thunder_tpu.serving import serve as _serve

    return _serve(model_fn, params, cfg, **kwargs)


# The package's own import as one complete event in the ring, on the ring's
# clock: a process's start-up timeline (``export_chrome_trace``) begins here.
observability.record_event(
    "X", "import", {"jax_s": (_T_JAX - _T_IMPORT) / 1e9},
    ts=_T_IMPORT / 1e3, dur=(time.perf_counter_ns() - _T_IMPORT) / 1e3)
