"""Device milliseconds per sweep that the exact solve spends in the back
substitution: self time of the operations whose innermost scope is
``als.cholesky.solve`` in the traced window / sweeps
(``benchmark/span_reads.py``; layer: kernels). Nothing where the program
carries no such scope."""

from benchmark.span_reads import scope_ms_per_sweep


def read(ctx):
    return scope_ms_per_sweep(ctx, "als.cholesky.solve", "inner")
