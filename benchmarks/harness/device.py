"""The device a run is on: found, never chosen. No CPU fallback."""

from benchmarks.harness.spec import load_json


def check_device(chips, rehearse=False):
    """The devices JAX reports, which must be TPUs and at least
    ``chips`` of them. Nothing here sets JAX_PLATFORMS. ``rehearse``
    (the benchmark's own CPU tests) accepts what is there."""
    import jax

    devices = jax.devices()
    if rehearse:
        return devices
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found no accelerator (platform "
            f"{devices[0].platform!r}); the benchmark has no CPU fallback"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}"
        )
    return devices


def peaks_of(device):
    """Published peaks of this device kind; an unknown kind is an
    error, never a default."""
    table = load_json("harness", "peaks.json")
    kind = device.device_kind
    if kind not in table:
        raise KeyError(
            f"benchmark: no peaks for device kind {kind!r} in "
            "harness/peaks.json"
        )
    return table[kind]


def describe(devices):
    """The device as JAX reports it."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """``(peak_bytes_in_use, peak_bytes_reserved)`` of the fullest
    device, as the runtime's ``memory_stats()`` names them: the
    allocator's peak of live arrays (weights, updater state, queued
    inputs, and on this runtime not the loaded programs' temporaries),
    and apart from it the most the runtime held reserved. They are two
    peaks taken at moments of their own and are never added. 0 where
    the backend reports nothing, as the CPU."""
    in_use = reserved = 0
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = max(in_use, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
    return in_use, reserved
