"""Device milliseconds per sweep that the fused fit spends in the conjugate-
gradient solve (right-hand side, preconditioner, matvecs, updates): self time
of the operations under the ``als.cg`` scope in the traced window / sweeps
(``benchmark/phases.py``; layer: kernels)."""

from benchmark.phases import scope_ms_per_sweep


def read(ctx):
    return scope_ms_per_sweep(ctx, "als.cg")
