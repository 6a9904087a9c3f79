"""Device places.

Analogue of the reference's ``platform::Place`` hierarchy and
``DeviceContextPool`` (``paddle/fluid/platform/device_context.h``,
``place.h``). On TPU there is no per-device stream state to own — XLA owns
streams and memory — so a Place here is a thin, hashable handle resolving to
a ``jax.Device``, and the "pool" is a cached resolver. This keeps the
user-facing API (``paddle_tpu.TPUPlace(0)``, ``set_device``) while the
runtime stays JAX-native.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax

from .enforce import InvalidArgumentError, enforce_ge

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "set_device",
    "get_device",
    "device_count",
    "is_compiled_with_tpu",
]


@dataclasses.dataclass(frozen=True)
class Place:
    """A hashable device handle: (device_type, device_id)."""

    device_type: str
    device_id: int = 0

    def jax_device(self) -> jax.Device:
        """Resolve to the ``device_id``-th device whose platform IS
        ``device_type`` — no substitution: a TPUPlace on a host without
        a TPU is an error, and a backend that fails to initialize
        raises from ``jax.devices`` as it is."""
        if self.device_type == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices()
                    if d.platform == self.device_type]
        if not devs:
            raise InvalidArgumentError(
                f"no {self.device_type!r} devices visible to JAX "
                f"(have: {sorted({d.platform for d in jax.devices()})})"
            )
        enforce_ge(len(devs) - 1, self.device_id, f"device_id out of range for {self.device_type}")
        return devs[self.device_id]

    def __repr__(self) -> str:  # Place(tpu:0)
        return f"Place({self.device_type}:{self.device_id})"


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0) -> Place:  # API-parity shim: no CUDA in the build
    raise InvalidArgumentError(
        "paddle_tpu is built without CUDA; use TPUPlace()/CPUPlace()"
    )


class _DeviceState(threading.local):
    def __init__(self) -> None:
        self.place: Optional[Place] = None


_STATE = _DeviceState()


def set_device(device: str) -> Place:
    """``paddle.set_device``-style selector: "cpu", "tpu", "tpu:1"."""
    if ":" in device:
        kind, _, idx = device.partition(":")
        place = Place(kind, int(idx))
    else:
        place = Place(device, 0)
    place.jax_device()  # validate
    _STATE.place = place
    return place


def get_device() -> Place:
    if _STATE.place is not None:
        return _STATE.place
    return Place(jax.devices()[0].platform, 0)


def device_count(device_type: str = "tpu") -> int:
    return sum(1 for d in jax.devices() if d.platform == device_type)


def is_compiled_with_tpu() -> bool:
    return True
