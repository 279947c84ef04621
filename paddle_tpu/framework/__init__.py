"""Device/runtime plumbing (reference: python/paddle/device/,
python/paddle/framework/). On TPU, device management is jax's: one process
sees its local TPU chips; placement is explicit via device_put/shardings."""
from __future__ import annotations

import jax

from ..core.flags import get_flag

_current_device = None


def _auto_device():
    devs = jax.devices()
    pref = get_flag("default_device")
    if pref:
        for d in devs:
            if d.platform == pref:
                return d
    return devs[0]


def get_default_device():
    global _current_device
    if _current_device is None:
        _current_device = _auto_device()
    return _current_device


def set_device(device: str):
    """paddle.device.set_device — accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'."""
    global _current_device
    name = device.lower()
    plat, _, idx = name.partition(":")
    plat = {"gpu": "cuda", "xpu": "tpu"}.get(plat, plat)
    idx = int(idx) if idx else 0
    cands = [d for d in jax.devices() if d.platform == plat] or \
            ([d for d in jax.local_devices(backend="cpu")] if plat == "cpu" else [])
    if idx >= len(cands):
        # no fallback to "whatever exists" and no clamping: 'tpu:3' on
        # one chip must not silently mean chip 0 (or the CPU)
        raise ValueError(
            f"set_device({device!r}): this process has {len(cands)} "
            f"{plat!r} device(s); available: "
            f"{sorted({d.platform for d in jax.devices()})}")
    _current_device = cands[idx]
    return _current_device


def get_device() -> str:
    d = get_default_device()
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role; report True for API parity of capability checks
    return True


class _Place:
    """Device placement token (reference paddle.CPUPlace/CUDAPlace/
    XPUPlace, paddle/phi/common/place.h). On this build placement is
    XLA's job; Places resolve to jax devices for `paddle.device` calls
    and to_tensor(place=...)."""

    _platform = "cpu"

    def __init__(self, device_id: int = 0):
        self._id = device_id

    def get_device_id(self) -> int:
        return self._id

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self._platform]
        if not devs:
            # API-compat places (CUDAPlace in ported code) name a KIND
            # of device this build may not have; they resolve to the
            # default device. The index is never clamped.
            devs = jax.devices()
        if self._id >= len(devs):
            raise ValueError(
                f"{self!r}: device index {self._id} out of range "
                f"({len(devs)} device(s))")
        return devs[self._id]

    def __repr__(self):
        return f"{type(self).__name__}({self._id})"

    def __eq__(self, other):
        return type(self) is type(other) and self._id == other._id

    def __hash__(self):
        return hash((type(self).__name__, self._id))


class CPUPlace(_Place):
    _platform = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class CUDAPlace(_Place):
    # accepted for API compat; resolves to the accelerator (TPU) device
    _platform = "tpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class TPUPlace(_Place):
    _platform = "tpu"
