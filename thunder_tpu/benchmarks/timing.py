"""Timing primitives shared by bench.py and the benchmark library.

JAX dispatch is asynchronous: a call returns before the device finishes, so
every timed region ends in ``jax.block_until_ready`` on its last output
(execution is in order per device, so that fences the whole loop).
"""
from __future__ import annotations

import time

import jax

__all__ = ["time_fn", "best_ms", "host_us_per_call"]


def time_fn(fn, *args, iters: int = 20) -> float:
    """Seconds per call over ``iters`` back-to-back calls, after one warm-up
    call that pays the compile."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def host_us_per_call(fn, *args, iters: int = 200) -> float:
    """Mean host-side wall time per call in µs.  For dispatch-overhead
    measurements, where the cost under test is the HOST work before the
    program launches (key computation, prologue guards, framework plumbing)
    — no device fence, so use ``time_fn`` for anything device-dominated."""
    fn(*args)  # warm: compile/caches populated outside the timed loop
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6


def best_ms(fn, *args, reps: int = 3) -> float:
    """Best-of-reps wall time in ms: the least-disturbed repetition."""
    return min(time_fn(fn, *args) for _ in range(reps)) * 1e3
