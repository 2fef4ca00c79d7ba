"""Device/place abstraction.

Analog of the reference's ``paddle::platform::Place`` hierarchy
(/root/reference/paddle/fluid/platform/place.h) and
``paddle.set_device`` (python/paddle/device/__init__.py). Here a Place wraps a
PjRt device as surfaced by ``jax.devices()``; ``TPUPlace`` is first-class and
``CPUPlace`` doubles as the test/fake backend (SURVEY.md §4: CPU PjRt backend
is the fake device).
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def jax_device(self):
        """The PjRt device this place names. A place whose platform has
        no device here RAISES — a request for ``'tpu'`` is never answered
        with the CPU. (Nothing in the framework calls this yet: arrays
        land on jax's default device whatever ``set_device`` said; the
        DEFAULT place already names what exists.)"""
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"no {self.device_type!r} device is visible to jax "
                f"(default backend: {jax.default_backend()!r}); "
                f"{self!r} cannot be resolved")
        if self._device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.device_type!r} "
                f"device(s) visible")
        return devs[self._device_id]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class NPUPlace(Place):
    """Accepted for reference API parity; resolves to the TPU backend
    (same mapping as set_device's 'xpu' alias)."""

    device_type = "tpu"


class CUDAPinnedPlace(Place):
    """Reference parity: pinned host memory lives on the HOST, so this
    resolves to CPU; actual pinning is PjRt's concern on TPU."""

    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(Place):
    # Accepted for API parity with the reference; maps onto whatever
    # accelerator jax exposes.
    device_type = "gpu"


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    for d in jax.devices():
        if d.platform == "tpu":
            return TPUPlace(0)
        if d.platform == "gpu":
            return CUDAPlace(0)
    return CPUPlace(0)


_current_place: Place | None = None


def set_device(device) -> Place:
    """``paddle.set_device('tpu')`` / ``set_device('cpu')`` /
    ``set_device('tpu:1')``."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name = str(device).lower()
    idx = 0
    if ":" in name:
        name, sidx = name.split(":", 1)
        idx = int(sidx)
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace,
           "cuda": CUDAPlace, "xpu": TPUPlace, "npu": NPUPlace,
           "cuda_pinned": CUDAPinnedPlace}.get(name)
    if cls is None:
        raise ValueError(f"Unknown device {device!r}")
    _current_place = cls(idx)
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.get_device_id()}"


def current_place() -> Place:
    return _current_place if _current_place is not None else _default_place()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
