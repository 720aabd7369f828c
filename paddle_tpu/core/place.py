"""Device placement.

Capability parity with `paddle/phi/common/place.h` (Place/AllocationType) and
`python/paddle/device` (set_device/get_device), expressed over JAX devices.
A Place names a logical device ("tpu:0", "cpu"); resolution to a concrete
`jax.Device` is lazy so module import works before backends initialize.
"""

from __future__ import annotations

import threading

import jax


class Place:
    """A logical device place, e.g. Place('tpu', 0)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    @staticmethod
    def parse(spec) -> "Place":
        if isinstance(spec, Place):
            return spec
        if isinstance(spec, jax.Device):
            return Place(spec.platform, spec.id)
        if not isinstance(spec, str):
            raise TypeError(f"cannot parse place from {spec!r}")
        s = spec.lower()
        if s in ("gpu", "cuda"):  # tolerated aliases from reference-style code
            s = "tpu"
        if ":" in s:
            kind, _, idx = s.partition(":")
            return Place(kind, int(idx))
        return Place(s, 0)

    def jax_device(self) -> jax.Device:
        devices = jax.devices(self.device_type)
        if self.device_id >= len(devices):
            raise ValueError(
                f"place {self} out of range: only {len(devices)} "
                f"{self.device_type} device(s) available"
            )
        return devices[self.device_id]

    def __eq__(self, other):
        if not isinstance(other, Place):
            return NotImplemented
        return (self.device_type, self.device_id) == (
            other.device_type,
            other.device_id,
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __str__(self):
        return f"{self.device_type}:{self.device_id}"


class _DeviceState(threading.local):
    def __init__(self):
        self.place = None


_state = _DeviceState()


def set_device(spec) -> Place:
    """Set the default device for subsequently created tensors."""
    place = Place.parse(spec)
    place.jax_device()  # validate it exists
    _state.place = place
    return place


def get_device() -> str:
    return str(_default_place())


def _default_place() -> Place:
    if _state.place is not None:
        return _state.place
    d = jax.devices()[0]
    return Place(d.platform, d.id)


def default_jax_device() -> jax.Device:
    return _default_place().jax_device()


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except RuntimeError:
        return False


def device_count(device_type: str | None = None) -> int:
    try:
        return len(jax.devices(device_type)) if device_type else jax.device_count()
    except RuntimeError:
        return 0


def synchronize() -> None:
    """Block until all dispatched device work completes."""
    # jax arrays are async; effectively a fence for profiling/benchmarks.
    (jax.device_put(0.0) + 0).block_until_ready()
