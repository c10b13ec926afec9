"""What the run is on, as JAX reports it. A run that finds no TPU, or fewer
chips than the cell asks for, prints no result and exits non-zero: a CPU
number is never written under the name of a device metric."""

from __future__ import annotations


import os


class NoChip(RuntimeError):
    pass


def use_compile_cache() -> None:
    """One fixed compile-cache directory inside the checkout (or wherever
    JAX_COMPILATION_CACHE_DIR places it): the program's own rule, taken from
    the program. Small programs (the reference's blocks) are cached too, so
    that only a cell's first run in a checkout compiles."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "3")
    from albedo_tpu.utils.compilation_cache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()


def describe_devices() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": getattr(devices[0], "device_kind", "?"),
        "count": len(devices),
    }


def require_chips(chips: int) -> dict:
    desc = describe_devices()
    if desc["platform"] != "tpu":
        raise NoChip(f"the benchmark measures a TPU; JAX reports platform {desc['platform']!r}")
    if desc["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX reports {desc['count']}")
    return desc


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
