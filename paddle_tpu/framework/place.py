"""Device placement.

Reference parity: paddle/fluid/platform/place.h:30-106 (CPUPlace/CUDAPlace/...)
and python/paddle/device.py (set_device / get_device).  TPU-native: a Place is a
thin tag over a `jax.Device`; there are no streams or per-device contexts to
manage — XLA owns scheduling.  `CUDAPlace` is kept as a compatibility alias that
resolves to the accelerator (TPU) backend so reference scripts run unchanged.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base device tag. Equality is structural (type + device id)."""

    device_type: str = "cpu"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    # -- jax bridge -------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """The device this place names.  An explicit request is never
        rewritten: an accelerator place with no accelerator, or an id past
        the last device, raises and says what jax.devices() holds."""
        every = jax.devices() if self.device_type != "cpu" \
            else jax.devices("cpu")
        devs = [d for d in every if _platform_matches(d, self.device_type)]
        if not 0 <= self._device_id < len(devs):
            raise RuntimeError(
                f"{self!r} names no device of this process: "
                f"{len(devs)} {self.device_type} device(s) among "
                f"jax.devices() = {jax.devices()}")
        return devs[self._device_id]


def _platform_matches(dev: jax.Device, kind: str) -> bool:
    plat = dev.platform.lower()
    if kind == "cpu":
        return plat == "cpu"
    # any accelerator platform (tpu / gpu) counts as the "accelerator place"
    return plat != "cpu"


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference CUDAPlace scripts map to the accelerator."""

    device_type = "tpu"


class CUDAPinnedPlace(CPUPlace):
    device_type = "cpu"


class XPUPlace(TPUPlace):
    device_type = "tpu"


_current_place: Place | None = None


@functools.lru_cache(maxsize=None)
def _has_accelerator() -> bool:
    return any(d.platform.lower() != "cpu" for d in jax.devices())


def _default_place() -> Place:
    return TPUPlace(0) if _has_accelerator() else CPUPlace(0)


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.set_device('tpu:0'|'cpu'|'gpu:0'). 'gpu' aliases to tpu.
    Raises when this process has no such device (see Place.jax_device)."""
    global _current_place
    if isinstance(device, Place):
        place = device
    else:
        dev = device.lower()
        idx = 0
        if ":" in dev:
            dev, idx_s = dev.split(":", 1)
            idx = int(idx_s)
        if dev in ("tpu", "gpu", "cuda", "xpu", "npu"):
            place = TPUPlace(idx)
        elif dev == "cpu":
            place = CPUPlace(idx)
        else:
            raise ValueError(f"Unknown device {device!r}")
    place.jax_device()
    _current_place = place
    return _current_place


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.get_device_id()}"


def is_compiled_with_cuda() -> bool:  # reference API parity; always False
    return False


def is_compiled_with_tpu() -> bool:
    return _has_accelerator()


def device_count() -> int:
    p = get_place()
    return len([d for d in jax.devices() if _platform_matches(d, p.device_type)])


def is_compiled_with_xpu() -> bool:  # reference API parity; always False
    return False


def get_cudnn_version():
    """None: no cuDNN exists here (reference returns None when CUDA is
    absent)."""
    return None
