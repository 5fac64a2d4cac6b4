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


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; soak/long-horizon tests opt out with it
    config.addinivalue_line("markers", "slow: long-running test, excluded from tier-1")
    # xdist hands files out by their number of tests, largest first, unless told
    # otherwise: the order below is to stand (no xdist, no such option)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


# The tier-1 command runs `--dist loadfile`: a file is one worker's from start
# to end, so the run lasts as long as the last long file to start.  xdist's own
# order (by number of tests) starts a file of five long tests last; collection
# order starts it late in the alphabet.  Start the long files first (seconds a
# file: ROADMAP.md D11); everything else keeps its order.
_LONGEST_FIRST = (
    "test_sequence_parallel.py", "test_ring_attention.py", "test_hybrid_moe.py",
    "test_train_cli.py", "test_paged_attention.py", "test_pallas.py",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
