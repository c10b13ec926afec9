"""The whole step's share of the chip's published bf16 peak: required FLOPs
per sweep x sweeps over the traced window's seconds (layer: whole step).
Bounds every kernel roofline that moves ``fit_sweep_ms``."""

from benchmark.peaks import peaks_for
from benchmark.workcounts import config_counts


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("sweeps"):
        return None
    flops = config_counts(ctx["config"])["flops_per_sweep"] * ctx["sweeps"]
    return 100.0 * flops / trace["window_s"] / peaks_for(ctx["device_kind"])["bf16_flops"]
