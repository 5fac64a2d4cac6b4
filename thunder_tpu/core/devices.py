"""Devices for the TPU-native framework.

Analog of the reference's ``thunder/core/devices.py`` (DeviceType CPU/CUDA,
interned Device, string parsing, framework conversion) — here the accelerator
type is TPU and conversion targets ``jax.Device``.
"""
from __future__ import annotations

from enum import Enum, auto
from typing import Any, Optional

from thunder_tpu.core.baseutils import check

__all__ = [
    "DeviceType",
    "Device",
    "device_from_string",
    "to_device",
    "to_jax_device",
    "from_jax_device",
    "cpu",
    "available_device_types",
]


class DeviceType(Enum):
    CPU = auto()
    TPU = auto()
    GPU = auto()  # jax cuda backend, for completeness

    def __str__(self):
        return _devicetype_prettyprint_map[self]


_devicetype_prettyprint_map = {
    DeviceType.CPU: "cpu",
    DeviceType.TPU: "tpu",
    DeviceType.GPU: "gpu",
}
_inverse_devicetype_prettyprint_map = {v: k for k, v in _devicetype_prettyprint_map.items()}

all_devicetypes = (DeviceType.CPU, DeviceType.TPU, DeviceType.GPU)


def devicetype_string(devicetype: DeviceType) -> str:
    return _devicetype_prettyprint_map[devicetype]


# what torch's C-level argument parser sees when a Device is passed as a
# ``device=`` kwarg (torch interop): 'xla' is in torch's accepted device-type
# list, so factory calls like ``torch.arange(..., device=t.device)`` in
# unmodified HF code parse successfully and reach the TorchFunctionMode,
# which then diverts them into the thunder op surface before any real torch
# execution happens.
_torch_parser_str = {
    DeviceType.CPU: "cpu",
    DeviceType.TPU: "xla",
    DeviceType.GPU: "cuda",
}


class Device(str):
    """An interned (devicetype, index) pair.

    ``Device`` objects are compared by value and safe to use as dict keys.
    The accelerator index maps to ``jax.devices(backend)[index]``.

    Subclasses ``str`` (raw value: a torch-parseable device string such as
    ``"xla:0"``) purely so torch's argument parser accepts a Device as a
    ``device=`` kwarg during torch interop; thunder-facing rendering
    (``__str__``/``__format__``/``device_str``) stays ``"tpu:0"`` style.
    """

    _interned: dict[tuple[DeviceType, int], "Device"] = {}

    def __new__(cls, devicetype: DeviceType | str, index: int | None = None):
        if isinstance(devicetype, Device):
            return devicetype
        if isinstance(devicetype, str):
            devicetype, parsed_index = _parse_device_string(devicetype)
            if index is None:
                index = parsed_index
            else:
                check(
                    parsed_index is None or parsed_index == index,
                    lambda: f"Conflicting device indices {parsed_index} vs {index}",
                )
        if index is None:
            index = 0
        check(isinstance(index, int) and index >= 0, lambda: f"Invalid device index {index}")
        key = (devicetype, index)
        cached = cls._interned.get(key)
        if cached is not None:
            return cached
        self = super().__new__(cls, f"{_torch_parser_str[devicetype]}:{index}")
        self._devicetype = devicetype
        self._index = index
        cls._interned[key] = self
        return self

    @property
    def devicetype(self) -> DeviceType:
        return self._devicetype

    @property
    def type(self) -> str:
        return devicetype_string(self._devicetype)

    @property
    def index(self) -> int:
        return self._index

    def device_str(self) -> str:
        return f"{devicetype_string(self._devicetype)}:{self._index}"

    def __repr__(self) -> str:
        return f'Device(type="{self.device_str()}")'

    def __str__(self) -> str:
        return self.device_str()

    def __format__(self, spec: str) -> str:
        # f-strings must render the thunder-facing form, not the raw
        # torch-parseable str value
        return format(self.device_str(), spec)

    def __hash__(self) -> int:
        return hash((self._devicetype, self._index))

    def __eq__(self, other) -> bool:
        if isinstance(other, str) and not isinstance(other, Device):
            try:
                other = device_from_string(other)
            except Exception:
                return False  # e.g. device == "meta" in HF code: not equal, not an error
        return isinstance(other, Device) and self._devicetype == other._devicetype and self._index == other._index

    def __ne__(self, other) -> bool:
        # str.__ne__ would compare the raw "xla:0" value; keep != consistent
        # with the value-based __eq__
        return not self.__eq__(other)


def _parse_device_string(s: str) -> tuple[DeviceType, Optional[int]]:
    parts = s.split(":")
    check(1 <= len(parts) <= 2, lambda: f"Invalid device string {s!r}")
    dt = _inverse_devicetype_prettyprint_map.get(parts[0])
    # accept torch-style "cuda"/"xla" as aliases for the accelerator
    if dt is None and parts[0] in ("cuda", "xla"):
        dt = DeviceType.TPU
    check(dt is not None, lambda: f"Unknown device type in {s!r}")
    index = int(parts[1]) if len(parts) == 2 else None
    return dt, index


def device_from_string(s: str) -> Device:
    return Device(s)


cpu = Device(DeviceType.CPU, 0)


def to_device(x: Any) -> Device:
    """Converts strings, jax devices, torch devices, or Devices to a Device."""
    if x is None:
        return default_device()
    if isinstance(x, Device):
        return x
    if isinstance(x, str):
        return device_from_string(x)
    # jax.Device
    platform = getattr(x, "platform", None)
    if platform is not None:
        return from_jax_device(x)
    # torch.device
    typ = getattr(x, "type", None)
    if typ is not None:
        return Device(typ, getattr(x, "index", None) or 0)
    raise ValueError(f"Cannot convert {x} to a Device")


_jax_platform_map = {
    "cpu": DeviceType.CPU,
    "tpu": DeviceType.TPU,
    "gpu": DeviceType.GPU,
    "cuda": DeviceType.GPU,
    "rocm": DeviceType.GPU,
}


def from_jax_device(jd) -> Device:
    check(jd.platform in _jax_platform_map,
          lambda: f"Unknown jax platform {jd.platform!r} of device {jd} "
                  f"(known: {sorted(_jax_platform_map)}); it is not taken for a TPU")
    return Device(_jax_platform_map[jd.platform], jd.id)


def to_jax_device(d: Device | str):
    """Device → concrete jax.Device.  The index is a jax device ID: matched
    by ``.id`` first (multi-controller processes see global ids like
    cpu:2048 that are NOT list positions), with a positional fallback for
    user-written specs like "cpu:1" in single-process runs."""
    import jax

    d = to_device(d)
    if d.devicetype == DeviceType.CPU:
        pool = jax.devices("cpu")
    else:
        devs = jax.devices()
        accel = [x for x in devs if x.platform != "cpu"]
        pool = accel if accel else devs
    for x in pool:
        if x.id == d.index:
            return x
    check(d.index < len(pool), lambda: f"Device index {d.index} out of range ({len(pool)} devices)")
    return pool[d.index]


def default_device() -> Device:
    """The first LOCAL accelerator if present, else the first local cpu.

    Local, not global: in multi-controller runs a process's arrays live on
    its own devices, whose global ids are nonzero on processes > 0 —
    defaulting factory ops to device id 0 there makes every trace fail the
    same-device check against concrete inputs."""
    import jax

    local = jax.local_devices()
    for jd in local:
        if jd.platform != "cpu":
            return from_jax_device(jd)
    return from_jax_device(local[0]) if local else cpu


def available_device_types() -> tuple[DeviceType, ...]:
    import jax

    types = {from_jax_device(d).devicetype for d in jax.devices()}
    types.add(DeviceType.CPU)
    return tuple(types)
