"""Device abstraction (reference ``heat/core/devices.py``).

The reference pins each MPI rank to a CPU or a round-robin CUDA device
(``devices.py:79-100``). Under single-controller JAX the platform is chosen at
backend init; a :class:`Device` here names a *platform* ("tpu" or "cpu") whose
actual device placement is governed by the mesh in
:class:`~heat_tpu.core.communication.TPUCommunication`.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

__all__ = ["Device", "cpu", "get_device", "sanitize_device", "use_device"]


class Device:
    """Platform identity of a DNDarray (reference ``devices.py:17``)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = str(device_type)
        self.__device_id = int(device_id)

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    def __repr__(self) -> str:
        return f"device({str(self)!r})"

    def __str__(self) -> str:
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type and self.device_id == other.device_id
        if isinstance(other, str):
            try:
                return self == sanitize_device(other)
            except (ValueError, TypeError):
                return False
        return NotImplemented

    def __hash__(self):
        return hash(str(self))


cpu = Device("cpu", 0)
"""The host-CPU platform singleton (reference ``devices.py:79``)."""

# Platform detection is LAZY: importing heat_tpu must not initialize the
# XLA backend, or ``distributed_init()`` (which must run before any backend
# touch) could never be called after the import. The accelerator singleton
# and default device materialize on first use; ``tpu`` resolves via module
# ``__getattr__``.
_platform: Optional[str] = None
_accel: Optional[Device] = None
_default_device: Optional[Device] = None


def _detect() -> None:
    global _platform, _accel, _default_device
    if _platform is None:
        _platform = jax.default_backend()
        if _platform != "cpu":
            _accel = Device(_platform, 0)
        if _default_device is None:
            _default_device = _accel if _accel is not None else cpu


def __getattr__(name: str):
    if name == "tpu":
        _detect()
        return _accel if _accel is not None and _accel.device_type == "tpu" else None
    if name == "gpu":
        _detect()
        return _accel if _accel is not None and _accel.device_type == name else None
    raise AttributeError(f"module 'heat_tpu.core.devices' has no attribute {name!r}")


def get_device() -> Device:
    """Default device for new arrays (reference ``get_device``, ``devices.py:113``)."""
    _detect()
    return _default_device


def sanitize_device(device: Union[str, Device, None]) -> Device:
    """Normalize a device argument (reference ``sanitize_device``, ``devices.py:126``)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    name = str(device).split(":")[0].strip().lower()
    if name == "cpu":
        return cpu
    _detect()
    if _accel is not None and name == _accel.device_type:
        return _accel
    raise ValueError(f"Unknown device, must be 'cpu' or '{_platform}', got {device!r}")


def use_device(device: Union[str, Device, None] = None) -> None:
    """Set the default device (reference ``use_device``, ``devices.py:157``)."""
    global _default_device
    _default_device = sanitize_device(device)
