"""Device placement model.

TPU-native analog of the reference's ``phi::Place`` hierarchy
(reference: paddle/phi/common/place.h:27 ``Place``/``AllocationType``,
``CPUPlace``/``GPUPlace``/``CustomPlace`` at place.h:116,124) and the
string->Place parsing in python/paddle/device/__init__.py:291 ``set_device``.

Design: a Place names a JAX platform + device index.  There are no
streams/contexts to manage (XLA owns scheduling), so Place is a thin value
type used for tensor placement, the kernel registry key, and API parity.
"""
from __future__ import annotations

import functools
import threading

import jax

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "CustomPlace",
    "set_device",
    "get_device",
    "get_all_devices",
    "device_count",
    "is_compiled_with_tpu",
    "current_jax_device",
]


class AllocationType:
    UNDEFINED = 0
    CPU = 1
    GPU = 2
    TPU = 9
    CUSTOM = 10


class Place:
    """A named device slot: ``Place('tpu', 0)``."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str = "cpu", device_id: int = 0):
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    # -- queries ----------------------------------------------------------
    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_gpu_place(self):
        return self.device_type in ("gpu", "cuda")

    # -- jax mapping ------------------------------------------------------
    def jax_device(self):
        """Resolve to the concrete ``jax.Device``."""
        devs = _devices_for(self.device_type)
        if not devs:
            raise RuntimeError(
                f"no jax devices for platform '{self.device_type}' "
                f"(available: {[d.platform for d in jax.devices()]})"
            )
        return devs[self.device_id % len(devs)]


def CPUPlace(device_id: int = 0) -> Place:
    return Place("cpu", device_id)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0) -> Place:
    return Place("gpu", device_id)


def CustomPlace(device_type: str, device_id: int = 0) -> Place:
    return Place(device_type, device_id)


@functools.lru_cache(maxsize=None)
def _devices_for(device_type: str):
    """Devices whose jax platform IS ``device_type`` ("tpu" means platform
    "tpu" and nothing else; "cuda" is jax's "gpu")."""
    if device_type == "cpu":
        try:
            return tuple(jax.devices("cpu"))   # the host, beside a TPU
        except RuntimeError:                   # JAX_PLATFORMS excludes it
            return ()
    platform = "gpu" if device_type == "cuda" else device_type
    return tuple(d for d in jax.devices() if d.platform == platform)


class _DeviceState(threading.local):
    # thread-local by design (set_device scopes per thread): no
    # guarded-by annotations — no attribute here is ever cross-thread
    def __init__(self):
        self.place = None


_state = _DeviceState()


def _default_place() -> Place:
    if _devices_for("tpu"):
        return TPUPlace(0)
    return CPUPlace(0)


def set_device(device: str) -> Place:
    """``set_device('tpu')`` / ``'tpu:1'`` / ``'cpu'``.

    Parity: python/paddle/device/__init__.py:291.
    """
    if isinstance(device, Place):
        _state.place = device
        return device
    dev = device.lower().strip()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        place = Place(kind, int(idx))
    else:
        place = Place(dev, 0)
    # validate eagerly so failures surface at set_device like the reference
    place.jax_device()
    _state.place = place
    return place


def get_device() -> str:
    p = _current_place()
    return f"{p.device_type}:{p.device_id}"


def _current_place() -> Place:
    if _state.place is None:
        _state.place = _default_place()
    return _state.place


def current_jax_device():
    return _current_place().jax_device()


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def device_count(device_type: str = "tpu") -> int:
    return len(_devices_for(device_type))


def is_compiled_with_tpu() -> bool:
    return bool(_devices_for("tpu"))
