"""Device/place abstraction for the TPU-native framework.

Reference parity: ``paddle/fluid/platform/place.h`` (Place variants) and
``platform/device_context.h:112,468,818`` (DeviceContext / DeviceContextPool).

On TPU the heavy lifting of streams/handles is owned by PJRT + XLA, so a
"Place" here is the identity of a jax.Device, and the "DeviceContextPool"
collapses to a small registry mapping places onto live ``jax.Device``
objects.  No per-device stream plumbing is needed: XLA orders work.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPinnedPlace",
    "set_device",
    "get_device",
    "device_count",
    "is_compiled_with_tpu",
    "DeviceContextPool",
    "pinned_host_kind",
]


class Place:
    """Identity of a physical device: (device_type, device_id)."""

    device_type: str = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    # -- paddle-compatible predicates ------------------------------------
    def is_cpu_place(self) -> bool:
        return self.device_type == "cpu"

    def is_tpu_place(self) -> bool:
        return self.device_type == "tpu"

    def is_gpu_place(self) -> bool:  # no CUDA in this stack
        return False

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self) -> int:
        return hash((self.device_type, self._device_id))

    def __repr__(self) -> str:
        return f"Place({self.device_type}:{self._device_id})"

    # -- jax bridge ------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """The live device behind this place; ``UnavailableError`` when
        the process has no such device (a TPUPlace on a CPU-only host)."""
        return DeviceContextPool.instance().device_for(self)


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPinnedPlace(Place):
    """Host-pinned staging memory.  On TPU, PJRT manages pinned staging
    buffers internally; this place exists for API compatibility and maps
    to host memory."""

    device_type = "cpu_pinned"

    def is_cpu_place(self) -> bool:
        return True


class DeviceContextPool:
    """Maps Place -> live jax.Device.  Parity with the reference's
    ``DeviceContextPool`` singleton (``platform/device_context.h:818``),
    minus streams (XLA's job)."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        self._cache = {}

    @classmethod
    def instance(cls) -> "DeviceContextPool":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    def device_for(self, place: Place) -> jax.Device:
        key = (place.device_type, place.get_device_id())
        dev = self._cache.get(key)
        if dev is None:
            backend = "tpu" if place.is_tpu_place() else "cpu"
            try:
                dev = jax.devices(backend)[place.get_device_id()]
            except (RuntimeError, IndexError) as e:
                from .errors import UnavailableError
                raise UnavailableError(
                    f"{place!r}: this process has no such {backend} "
                    f"device ({e})") from e
            self._cache[key] = dev
        return dev


_state = threading.local()


def _default_place() -> Place:
    if jax.default_backend() == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


def set_device(device: str) -> Place:
    """paddle.set_device parity: accepts 'cpu', 'tpu', 'tpu:1'."""
    device = device.lower()
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "xla"):
        place: Place = TPUPlace(idx)
    elif kind == "cpu":
        place = CPUPlace(idx)
    else:
        raise ValueError(
            f"device '{device}' not supported; this framework targets 'tpu' and 'cpu'"
        )
    _state.place = place
    return place


def get_device() -> str:
    place = getattr(_state, "place", None) or _default_place()
    return f"{place.device_type}:{place.get_device_id()}"


def _current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = _default_place()
        _state.place = place
    return place


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_tpu() -> bool:
    if jax.default_backend() == "tpu":
        return True
    try:
        return bool(jax.devices("tpu"))
    except RuntimeError:
        return False


def pinned_host_kind(dev: jax.Device) -> Optional[str]:
    """``"pinned_host"`` where ``dev`` can address that memory space —
    what optimizer-state offload and the KV swap place arrays in.  On a
    TPU its absence raises: offload that quietly kept everything in HBM
    would hide the device's real footprint.  A host backend without the
    space IS host memory, and offload there is a placement no-op (None).
    """
    if any(m.kind == "pinned_host" for m in dev.addressable_memories()):
        return "pinned_host"
    if dev.platform == "tpu":
        from .errors import UnavailableError
        raise UnavailableError(
            f"{dev}: no pinned_host memory space — host offload cannot "
            f"run on this runtime")
    return None
