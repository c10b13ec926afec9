"""Device milliseconds per sweep under the ``als.shard.assemble`` scope (the
all-gather of a source table's shards and the relayout the gather reads):
SELF time of its operations on the ``XLA Ops`` line inside the sharded
programs of the traced window, averaged over the chips, / sweeps — time in
which no other operation ran, so the exposed part of the collective
(``benchmark/phases.py``; layer: kernels). Nothing where the program carries
no such scope."""

from benchmark.phases import scope_ms_per_sweep

SCOPE = "als.shard.assemble"


def read(ctx):
    return scope_ms_per_sweep(ctx, SCOPE)
