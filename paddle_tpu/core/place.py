"""Device identity (Place) over the PJRT device model.

TPU-native analog of /root/reference/paddle/fluid/platform/place.h
(CPUPlace/CUDAPlace/XPUPlace variant) and DeviceContextPool
(platform/device_context.h:695). On TPU there are no user-managed streams —
XLA owns scheduling — so a Place is just a typed handle to a jax.Device, and
the "context pool" is jax's device list.
"""
from __future__ import annotations

import functools
import subprocess
import sys

import jax


class Place:
    """Base device identity."""

    device_type: str = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            # Fall back to CPU host devices (always present).
            devs = jax.devices("cpu")
        return devs[self._device_id % len(devs)]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    """The native accelerator place of this framework (CUDAPlace analog)."""

    device_type = "tpu"


# Alias so code written against the reference API ("gpu:0") keeps working.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace  # accelerator alias: the accelerator here IS the TPU


class TPUPinnedPlace(Place):
    """Host-pinned staging place (CUDAPinnedPlace analog). On PJRT, host
    staging buffers are managed by the runtime; this is an identity marker
    used by the DataLoader to request committed-host layouts."""

    device_type = "cpu"


CUDAPinnedPlace = TPUPinnedPlace


@functools.lru_cache(maxsize=None)
def _accelerator_platform():
    for d in jax.devices():
        if d.platform != "cpu":
            return d.platform
    return "cpu"


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() != "cpu"


# Parity alias (reference: paddle.is_compiled_with_cuda).
is_compiled_with_cuda = is_compiled_with_tpu


def get_device() -> str:
    p = _accelerator_platform()
    return "cpu" if p == "cpu" else f"{p}:0"


def device_count() -> int:
    return len(jax.devices())


# -- one process per chip ---------------------------------------------------
# A TPU chip belongs to one process at a time, and a process that
# initialises the TPU backend takes every chip it can see. A parent that
# hands chips to children therefore (a) never initialises a backend
# itself and (b) narrows each child's view to its chip with the
# variables libtpu reads at backend init.

def local_tpu_chip_count(env) -> int:
    """TPU chips a process started with `env` sees; 0 when its JAX lands
    on another platform. Asked of a throwaway child, so the calling
    process never initialises a backend (the child has exited, and let
    go of the chips, by the time this returns)."""
    if env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        return 0
    probe = ("import jax; d = jax.devices(); "
             "print('DEVICES', d[0].platform, len(d))")
    p = subprocess.run([sys.executable, "-c", probe], env=env,
                       capture_output=True, text=True, timeout=300)
    words = p.stdout.split()
    if p.returncode != 0 or "DEVICES" not in words:
        raise RuntimeError(
            f"device probe child failed (rc={p.returncode}):\n"
            + p.stderr[-2000:])
    platform, n = words[words.index("DEVICES") + 1:][:2]
    return int(n) if platform == "tpu" else 0


def single_chip_env(chip: int) -> dict:
    """Environment additions that make a child process see exactly one
    local TPU chip, as a one-chip topology of its own (libtpu 0.0.34
    honours TPU_VISIBLE_CHIPS: seen on the v5e, PR 21)."""
    return {"TPU_VISIBLE_CHIPS": str(int(chip)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def _place_to_jax_device(place):
    if place is None:
        return None
    if isinstance(place, Place):
        if isinstance(place, (TPUPlace,)) and place.device_type == "tpu":
            # Resolve against whatever accelerator platform is present.
            plat = _accelerator_platform()
            devs = jax.devices() if plat != "cpu" else jax.devices("cpu")
            return devs[place.get_device_id() % len(devs)]
        return place.jax_device
    if isinstance(place, jax.Device):
        return place
    raise TypeError(f"Expected Place or jax.Device, got {type(place)}")


def set_device(device: str):
    """paddle.set_device parity: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias)."""
    global _default_place
    device = device.lower()
    if device == "cpu":
        _default_place = CPUPlace()
        return _default_place
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name in ("tpu", "gpu", "xpu"):
        _default_place = TPUPlace(idx)
        return _default_place
    raise ValueError(f"Unknown device {device!r}")


# Resolved LAZILY: probing devices at import would initialize the XLA
# backend and break jax.distributed.initialize (fleet.init on multi-host
# must run before any backend touch).
_default_place = None


def get_default_place() -> Place:
    global _default_place
    if _default_place is None:
        _default_place = TPUPlace(0) if is_compiled_with_tpu() else \
            CPUPlace()
    return _default_place
