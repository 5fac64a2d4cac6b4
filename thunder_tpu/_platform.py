"""Pin JAX to the CPU backend with N virtual devices — for the test suite
and ``train_cli.py --virtual-cpu``.

``JAX_PLATFORMS=cpu`` alone selects the CPU (the tier-1 command sets it), but
a multi-device mesh also needs ``--xla_force_host_platform_device_count`` in
``XLA_FLAGS``, which XLA reads once at backend init.  One helper, so its
users (tests/conftest.py, train_cli.py, __graft_entry__.py) cannot diverge.

Importing :mod:`thunder_tpu` does not initialize the JAX backend, so calling
:func:`force_cpu` right after the package import is safe.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu(n_devices: int = 1) -> None:
    """Pin JAX to a CPU backend with at least ``n_devices`` virtual devices.

    Raises instead of silently proceeding on the wrong backend or with too
    few devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError as e:
        raise RuntimeError(
            "could not pin the JAX platform to CPU — the backend was already "
            "initialized (import order touched JAX before force_cpu)"
        ) from e

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"JAX backend is {backend!r} after pinning to CPU — the backend was "
            "initialized before force_cpu was called; call it earlier"
        )
    have = jax.local_device_count()
    if have < n_devices:
        raise RuntimeError(
            f"CPU backend has {have} devices but {n_devices} were requested — "
            "the backend was initialized before XLA_FLAGS could take effect"
        )


def device_info() -> dict:
    """The device as jax reports it — part of every result a tool or CLI
    prints, so that a number always says what it ran on."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
