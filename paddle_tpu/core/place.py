"""Places (devices).

TPU-native analog of the reference's ``phi::Place`` hierarchy
(reference: paddle/phi/common/place.h). A Place names a logical device;
resolution to a concrete ``jax.Device`` happens lazily so CPU-only test
environments and single-TPU environments both work.
"""
from __future__ import annotations

import functools

import jax


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        return _resolve(self.device_type, self.device_id)


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("cpu", device_id)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CustomPlace(Place):
    """A registered custom device type (reference: phi CustomPlace /
    the custom-runtime ABI, paddle/phi/backends/custom/). On this stack
    a PJRT plugin plays the CustomRuntime role: the type name maps to a
    JAX platform registered via device.register_custom_device."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_type, device_id)


# custom device-type name -> JAX platform name (the pluggable ABI)
_CUSTOM_DEVICE_TYPES: dict[str, str] = {}


def register_custom_device(device_type: str, jax_platform: str | None = None):
    """Register ``device_type`` as a place class backed by the given JAX
    platform (default: same name). ``set_device(f"{device_type}:0")``
    then resolves through jax.devices(platform)."""
    _CUSTOM_DEVICE_TYPES[device_type] = jax_platform or device_type
    _custom_devices.cache_clear()


@functools.lru_cache(maxsize=None)
def _custom_devices(platform: str):
    try:
        return jax.devices(platform)
    except RuntimeError:
        return []


# Any non-cpu device JAX reports belongs to the "tpu" device class for Place
# purposes.
@functools.lru_cache(maxsize=None)
def _accelerators():
    return [d for d in jax.devices() if d.platform != "cpu"]


def _cpu_requested() -> bool:
    """JAX_PLATFORMS (or the jax_platforms config it seeds) asks for the
    CPU alone — the test tier."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


@functools.lru_cache(maxsize=None)
def _cpus():
    try:
        return jax.devices("cpu")
    except RuntimeError:
        return []


def _resolve(device_type: str, device_id: int):
    if device_type == "cpu":
        devs = _cpus() or jax.devices()
    elif device_type in _CUSTOM_DEVICE_TYPES:
        devs = _custom_devices(_CUSTOM_DEVICE_TYPES[device_type]) \
            or jax.devices()
    else:
        devs = _accelerators()
        if _cpu_requested():
            # the CPU test tier (JAX_PLATFORMS=cpu): tpu:N names a virtual
            # host device, folded onto what exists
            devs = devs or jax.devices()
        elif device_id >= len(devs):
            raise RuntimeError(
                f"{device_type}:{device_id} names no device: JAX reports "
                f"{len(devs)} accelerator(s) ({[str(d) for d in devs]}); "
                "only JAX_PLATFORMS=cpu maps a tpu place onto host devices")
    return devs[device_id % len(devs)]


_default_place: Place | None = None


def set_device(device) -> Place:
    """``paddle.device.set_device`` analog: 'cpu', 'tpu', 'tpu:0'."""
    global _default_place
    _default_place = _parse(device)
    return _default_place


def get_device() -> str:
    p = get_default_place()
    return f"{p.device_type}:{p.device_id}"


def get_default_place() -> Place:
    global _default_place
    if _default_place is None:
        _default_place = TPUPlace(0) if _accelerators() else CPUPlace(0)
    return _default_place


def _parse(device) -> Place:
    if isinstance(device, Place):
        return device
    if isinstance(device, str):
        name, _, idx = device.partition(":")
        idx = int(idx) if idx else 0
        if name in ("cpu",):
            return CPUPlace(idx)
        if name in ("tpu", "gpu", "xpu", "device"):  # accelerator aliases
            return TPUPlace(idx)
        if name in _CUSTOM_DEVICE_TYPES:
            return CustomPlace(name, idx)
    raise ValueError(f"cannot parse device: {device!r}")


def is_compiled_with_tpu() -> bool:
    return bool(_accelerators())


__all__ = [
    "Place", "CPUPlace", "TPUPlace",
    "set_device", "get_device", "get_default_place", "is_compiled_with_tpu",
]
