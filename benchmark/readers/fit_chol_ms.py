"""Device milliseconds per sweep that the fused fit spends in the exact solve
(the Gramian correction and the b-vector, the factorisation, the triangular
solves): self time of the operations under the ``als.cholesky`` scope in the
traced window / sweeps (``benchmark/phases.py``; layer: kernels). The scope is
the OUTERMOST one, which the program has carried since PR 26; what is under
its sub-scopes is in the scope table on standard error."""

from benchmark.phases import scope_ms_per_sweep


def read(ctx):
    return scope_ms_per_sweep(ctx, "als.cholesky")
