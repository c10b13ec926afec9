"""Share of the chip's published interconnect rate that the assembly of the
source tables reaches: the least time for the bytes a sweep REQUIRES a chip
to receive (``peaks_ici.py``: the other chips' shards of each table, once;
from the configuration alone) over the self time under the
``als.shard.assemble`` scope (``shard_assemble_ms``; layer: kernels)."""

from benchmark.manifest import load_module
from benchmark.peaks_ici import least_assembly_seconds


def read(ctx):
    ms = load_module("readers", "shard_assemble_ms").read(ctx)
    if not ms:
        return None
    return 100.0 * 1000.0 * least_assembly_seconds(ctx["config"], ctx["device_kind"]) / ms
