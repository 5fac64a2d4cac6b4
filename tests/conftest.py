"""Test configuration: force the CPU backend with 8 virtual devices.

The reference's distributed tests require multi-GPU hardware; on TPU/XLA we
instead test true SPMD on a virtual CPU mesh (SURVEY.md §4 design
requirement).  ``JAX_PLATFORMS=cpu`` (set by the tier-1 command) selects the
backend; thunder_tpu._platform.force_cpu adds the virtual device count to
``XLA_FLAGS`` before the backend initializes and checks that it took.
"""
from thunder_tpu._platform import force_cpu

force_cpu(8)


# shared differential-testing harness (test_interpreter_differential.py and
# test_interpreter_fuzz.py compare native vs interpreted with one contract)
def diff_native(fn, *args):
    try:
        return ("ok", fn(*args))
    except BaseException as e:
        return ("raise", type(e).__name__, str(e))


def diff_interpreted(fn, *args):
    from thunder_tpu.core.interpreter import interpret

    try:
        return ("ok", interpret(fn, *args)[0])
    except BaseException as e:
        return ("raise", type(e).__name__, str(e))


# fuzz-depth knob shared by the fuzz suites: CI seed counts multiply by
# THUNDER_TPU_FUZZ_SCALE for deep offline soaks
import os as _os

FUZZ_SCALE = max(1, int(_os.environ.get("THUNDER_TPU_FUZZ_SCALE", "1")))


# one reset for all accumulated observability state (metrics registry, compile-
# event ring buffer, profile reports) after every test — process-wide counters
# otherwise bleed across tests and make registry assertions order-dependent
import sys as _sys

import pytest as _pytest


@_pytest.fixture(autouse=True)
def _reset_observability_state():
    yield
    tt = _sys.modules.get("thunder_tpu")
    if tt is not None:
        tt.reset_observability()


@_pytest.fixture
def jax_stretches():
    """JAX's own trace / lower / compile stretches in the event ring and in
    ``compile_cache.stats()`` for one test (what ``compile_cache.enable()``
    registers, without a cache directory), and taken off again: a listener
    left on writes every later compile of the worker into the ring, which
    the next file's test then finds beside its own events."""
    from jax import monitoring

    from thunder_tpu.core import compile_cache as cc

    was = cc._listener_registered
    cc._register_listeners()
    yield cc
    if not was:
        monitoring.unregister_event_listener(cc._on_event)
        monitoring.unregister_event_duration_listener(cc._on_duration)
        monitoring.unregister_event_time_span_listener(cc._on_time_span)
        monitoring.unregister_scalar_listener(cc._on_scalar)
        cc._listener_registered = False


# The paged attention entries (``pallasex.paged_attn_decode`` / ``_verify``, and
# ``mla_paged_decode``) choose their form from the backend: on the CPU their
# XLA form, unless THUNDER_TPU_PALLAS_INTERPRET=1 opts into the kernels under
# the Pallas interpreter (the program family the chip runs).  A test that takes
# ``attn_form`` runs once in each; the environment is the test's alone.
PALLAS_INTERPRET = "THUNDER_TPU_PALLAS_INTERPRET"
ATTN_FORMS = ("xla", "interpreted")


def set_attn_form(env, form: str) -> None:
    """``env``: a ``pytest.MonkeyPatch`` (a test's, or a module fixture's own
    ``MonkeyPatch.context()``), which puts the variable back."""
    assert form in ATTN_FORMS, form
    if form == "interpreted":
        env.setenv(PALLAS_INTERPRET, "1")
    else:
        env.delenv(PALLAS_INTERPRET, raising=False)


def in_each_attn_form(fn) -> list:
    """``fn()`` once in each form, in ``ATTN_FORMS``' order: the results."""
    out = []
    for form in ATTN_FORMS:
        with _pytest.MonkeyPatch.context() as env:
            set_attn_form(env, form)
            out.append(fn())
    return out


_COMPILED_FORWARDS: dict = {}


def compiled_forward(cfg, *, decode: bool = False, **static):
    """``models.generate.forward_with_cache`` under ``jax.jit``: one callable a
    (config, static options, attention form), kept for the worker, so that the
    tests that run the same forward share a compile.  Called eagerly a served
    model's forward dispatches every operation of every layer alone: a tiny
    decoder-hybrid-decoder's 40 tokens took 8.7 s so and 1.3 s compiled, the
    logits 1.5e-5 apart under a limit of 1e-4 of the largest (PR 55).

    ``(params, tokens (B, T), cache, cos, sin)``: a whole prompt at the Python
    ``0`` for which the program takes its fresh path; with ``decode``
    ``(params, tokens, pos, cache, cos, sin)``, a piece at a traced position.
    A test that reads ``pallasex.stats``, counts programs, plants a fault in
    the program or compares the eager path itself calls ``forward_with_cache``."""
    import jax

    from thunder_tpu.models import generate as G

    key = (repr(cfg), decode, tuple(sorted(static.items())), _os.environ.get(PALLAS_INTERPRET))
    if key not in _COMPILED_FORWARDS:
        if decode:
            fn = lambda params, toks, pos, cache, cos, sin: G.forward_with_cache(  # noqa: E731
                params, toks, pos, cache, cos, sin, cfg, **static)
        else:
            fn = lambda params, toks, cache, cos, sin: G.forward_with_cache(  # noqa: E731
                params, toks, 0, cache, cos, sin, cfg, **static)
        _COMPILED_FORWARDS[key] = jax.jit(fn)
    return _COMPILED_FORWARDS[key]


def prim_names(jaxpr, *, skip=("pallas_call",)) -> list:
    """``(primitive name, eqn)`` of every equation of a jaxpr, recursing into
    sub-jaxprs (pjit, custom_vjp, scan, ...) but not into pallas kernel bodies."""
    names = []
    for eqn in jaxpr.eqns:
        names.append((eqn.primitive.name, eqn))
        if eqn.primitive.name in skip:
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                names.extend(prim_names(sub, skip=skip))
            elif hasattr(v, "eqns"):
                names.extend(prim_names(v, skip=skip))
    return names


def arena_census(arenas, jaxpr) -> tuple:
    """``(arena gathers, scatters)`` of a serving program's jaxpr: gathers whose
    operand is one of the pool's ``arenas`` or one layer's slice of it (what the
    kernels' XLA form gathers from), and scatters of any kind."""
    import jax

    shapes = {(a.shape[0], *a.shape[2:]) for a in jax.tree_util.tree_leaves(arenas)}
    gathers = scatters = 0
    for name, eqn in prim_names(jaxpr):
        shape = tuple(eqn.invars[0].aval.shape) if name == "gather" else ()
        gathers += len(shape) > 2 and (shape[0], *shape[2:]) in shapes
        scatters += name.startswith("scatter")
    return gathers, scatters


@_pytest.fixture(params=ATTN_FORMS)
def attn_form(request, monkeypatch):
    set_attn_form(monkeypatch, request.param)
    return request.param


@_pytest.fixture(autouse=True, scope="module")
def _a_file_leaves_the_environment_as_it_found_it():
    # a file that left the interpreter switched on changed which programs the
    # worker's next file built (PR 32)
    before = _os.environ.get(PALLAS_INTERPRET)
    yield
    assert _os.environ.get(PALLAS_INTERPRET) == before, "a test of this file left THUNDER_TPU_PALLAS_INTERPRET changed"


def _memory_mappings() -> tuple:
    """``(this process's memory mappings, the most the kernel gives a process)``, zeros where ``/proc`` does not say."""
    try:
        with open("/proc/self/maps") as own, open("/proc/sys/vm/max_map_count") as most:
            return sum(1 for _ in own), int(most.read())
    except (OSError, ValueError):
        return 0, 0


@_pytest.fixture(autouse=True, scope="module")
def _a_worker_drops_its_compiled_programs_before_it_runs_out_of_mappings():
    # Every program XLA compiles for the CPU holds three or four memory mappings for as long as JAX keeps it, and JAX
    # keeps them all.  A tier-1 worker compiles some sixteen thousand programs, Linux gives a process 65,530 mappings
    # (vm.max_map_count), and the compile that asks for one more dies of a segmentation fault in
    # ``backend_compile_and_load``, in whatever test runs then: 15 to 19 minutes into four whole runs of four
    # (PR 55: a worker's count read 65,428 twenty seconds before it died).  Past half the limit a file's end drops
    # the caches; what is still used compiles again.
    yield
    held, most = _memory_mappings()
    if held > most // 2 > 0:
        import jax

        jax.clear_caches()


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; soak/long-horizon tests opt out with it
    config.addinivalue_line("markers", "slow: long-running test, excluded from tier-1")


# No order is given to the tests.  The tier-1 command (`commands` in /root/TESTS_LAST_RUN.json) runs six xdist workers
# with `--dist load`, which hands a worker that runs dry a twelfth of what is left, in collection order, and takes
# nothing back: the wall is the work over six plus one late hand-out.  Long files first and the cheap tests last, the
# list that stood here for `--dist loadfile`, makes that hand-out the dearest (PR 55, replayed on one run's seconds:
# 956-1,086 s for three such orders, 842 s as collected, 822-965 s for thirty shuffles; `CHANGES.md`).
