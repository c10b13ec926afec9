"""The whole step's share of the MESH's published bf16 peak: required FLOPs
per sweep x sweeps over the traced window's seconds and the peak of all the
configuration's chips together (layer: whole step). ``als_fit_mfu`` divides
by one chip's peak and is not listed for a mesh cell."""

from benchmark.peaks import peaks_for
from benchmark.workcounts import config_counts


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("sweeps"):
        return None
    chips = ctx["config"]["mesh_devices"]
    flops = config_counts(ctx["config"])["flops_per_sweep"] * ctx["sweeps"]
    return 100.0 * flops / trace["window_s"] / (chips * peaks_for(ctx["device_kind"])["bf16_flops"])
