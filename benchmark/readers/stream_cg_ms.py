"""Device milliseconds per sweep that the chunked fit's per-bucket programs spend
in the conjugate-gradient solve: self time of the operations whose outermost
scope is ``als.cg`` in the traced window / sweeps, by the swept reduction
(``benchmark/span_reads.py``; layer: kernels). Nothing where the program
carries no such scope."""

from benchmark.span_reads import scope_ms_per_sweep


def read(ctx):
    return scope_ms_per_sweep(ctx, "als.cg", swept=True)
