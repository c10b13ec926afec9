"""Milliseconds per sweep in which the device ran nothing while the host was
inside the compiled calls of the shape groups and the landing: the idle gaps
of the traced window whose innermost covering span is
``albedo.fit.shard.dispatch``, / sweeps (``benchmark/span_reads.py``; layer:
mesh). 0.0 where the span ran and no gap fell under it; nothing where the
trace holds no ``albedo.*`` span."""

from benchmark.span_reads import idle_ms_per_sweep


def read(ctx):
    return idle_ms_per_sweep(ctx, "fit.shard.dispatch")
