"""Device/place management.

Reference parity: paddle/fluid/platform/place.h:26-62 (CPUPlace/CUDAPlace
variants) and python paddle.device. On TPU the 'place' maps to a jax.Device;
CUDAPlace is accepted as an alias for the n-th accelerator so reference
scripts keep working.
"""
from __future__ import annotations

import jax


class Place:
    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and (self.device_type, self.device_id)
                == (other.device_type, other.device_id))

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        """The jax device this place names.  A place that names a device
        the process does not have is an error: an id past the last
        device is not clamped to it, and an accelerator place in a
        CPU-only process does not quietly become a CPU device."""
        devs = jax.devices(self.device_type)
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} "
                f"{self.device_type} device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):
    """Alias for the n-th accelerator (compat with reference scripts)."""

    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class XPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


_current_device = None


def set_device(device: str):
    """paddle.device.set_device parity ('cpu', 'tpu', 'tpu:0', 'gpu:0'...)."""
    global _current_device
    dev = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if dev in ("gpu", "cuda", "tpu", "xpu"):
        _current_device = TPUPlace(idx)
    else:
        _current_device = CPUPlace()
    return _current_device


def get_device() -> str:
    if _current_device is None:
        plat = jax.default_backend()
        return "cpu" if plat == "cpu" else f"{plat}:0"
    p = _current_device
    return p.device_type if p.device_type == "cpu" else f"{p.device_type}:{p.device_id}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()


def cuda_device_count() -> int:
    return 0
