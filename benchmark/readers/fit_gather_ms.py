"""Device milliseconds per sweep that the fused fit spends gathering rows:
self time of the operations under the ``als.gather`` scope in the traced
window / sweeps (``benchmark/phases.py``; layer: kernels)."""

from benchmark.phases import scope_ms_per_sweep


def read(ctx):
    return scope_ms_per_sweep(ctx, "als.gather")
