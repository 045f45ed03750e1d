"""Stat registry + device memory counters.

Reference: StatRegistry (platform/monitor.h:77 — global named int
counters, e.g. STAT_GPU_MEM) exported to python via
global_value_getter_setter.cc.

TPU-native: the named-counter surface is kept (stat_inc/stat_set/...)
but the backing store is the observability metrics registry — every
stat lands as a ``paddle_tpu_monitor_stat{name="..."}`` gauge sample,
so framework/user instrumentation shows up on the same ``/metrics``
scrape as the serving counters (docs/observability.md). Device memory
numbers come from PJRT (jax ``Device.memory_stats``) instead of
allocator internals, because XLA owns HBM on TPU (SURVEY.md rows 7/10).
A device that reports no memory stats (CPU) comes back empty/zero; a
backend that fails to initialise raises — an unreachable accelerator
must not read as "CPU".
"""
from __future__ import annotations

from typing import Dict, Optional

from ..observability import metrics as _metrics

__all__ = ["stat_inc", "stat_set", "stat_get", "stat_reset", "all_stats",
           "device_memory_stats", "all_device_memory_stats", "hbm_usage"]

_STATS = _metrics.gauge(
    "paddle_tpu_monitor_stat",
    "Named framework counters (StatRegistry parity surface: "
    "core.monitor.stat_inc/stat_set).",
    labelnames=("name",))


def stat_inc(name: str, value: int = 1) -> int:
    return int(_STATS.labels(name=str(name)).inc(int(value)))


def stat_set(name: str, value: int):
    _STATS.labels(name=str(name)).set(int(value))


def stat_get(name: str, default: int = 0) -> int:
    v = _STATS.value(name=str(name))
    return default if v is None else int(v)


def stat_reset(name: Optional[str] = None):
    if name is None:
        _STATS.clear()
    else:
        _STATS.remove(name=str(name))


def all_stats() -> Dict[str, int]:
    return {labels["name"]: int(child.get())
            for labels, child in _STATS.samples()}


def _stats_of(device) -> Dict[str, int]:
    try:
        return dict(device.memory_stats() or {})
    except Exception:       # this device cannot report; others may
        return {}


def device_memory_stats(device=None) -> Dict[str, int]:
    """PJRT per-device memory counters (bytes_in_use, peak_bytes_in_use,
    bytes_limit where the runtime reports them); ``{}`` when the device
    reports none (CPU). Raises when the backend cannot initialise."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return _stats_of(device)


def all_device_memory_stats() -> Dict[str, Dict[str, int]]:
    """{str(device): memory_stats} over every visible device; a device
    that cannot report comes back as an empty dict. Raises when the
    backend cannot initialise."""
    import jax
    return {str(d): _stats_of(d) for d in jax.devices()}


def hbm_usage(device=None):
    """(bytes_in_use, bytes_limit) — the STAT_GPU_MEM analog for HBM.
    (0, 0) when the device has nothing to report (CPU)."""
    st = device_memory_stats(device)
    return st.get("bytes_in_use", 0), st.get("bytes_limit", 0)
