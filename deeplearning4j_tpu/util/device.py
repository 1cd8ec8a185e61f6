"""Device-memory introspection shared by the two engines' HBM-resident
dataset caches (reference analog: workspace sizing around the nd4j
backends — here the budget bounds how much training data the fused
multi-epoch fit keeps device-resident)."""

from __future__ import annotations

from typing import Optional

_HOST_FALLBACK_BYTES = 4 << 30  # backends that report no limit (CPU)
_CACHE_FRACTION = 0.25          # leave the rest for params/acts/workspaces
_cached: Optional[int] = None


def device_cache_budget_bytes(device=None, refresh: bool = False) -> int:
    """Bytes of training data the HBM cache may pin: a quarter of the
    device's reported memory limit. A TPU reports its limit through
    ``memory_stats()["bytes_limit"]``; one that does not is an error —
    no size is assumed for an accelerator. The CPU backend reports
    nothing, and there host memory is the bound: 4 GiB. Cached per
    process — device memory size is static."""
    global _cached
    if _cached is not None and not refresh and device is None:
        return _cached
    import jax

    # this process's own device: under jax.distributed the global
    # list starts with another process's, which reports no stats
    d = device if device is not None else jax.local_devices()[0]
    limit = (d.memory_stats() or {}).get("bytes_limit")
    if limit:
        budget = max(256 << 20, int(limit * _CACHE_FRACTION))
    elif d.platform == "tpu":
        raise RuntimeError(
            f"{d} reports no memory_stats()['bytes_limit']; the HBM "
            "cache budget is derived from the real limit, never guessed"
        )
    else:
        budget = _HOST_FALLBACK_BYTES
    if device is None:
        _cached = budget
    return budget
