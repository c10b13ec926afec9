"""Share of its roofline that the row-sharded fit's programs reach: the least
time the MESH could take for the sweeps' REQUIRED work (``workcounts.py``:
logical matrix and published peaks only, over all the configuration's chips)
over the device time of those programs in the trace, averaged over the chips
(``traffic/fit-sharded.json: trace_programs``; layer: kernels)."""

from benchmark.manifest import load_module
from benchmark.workcounts import least_sweep_seconds


def read(ctx):
    seconds = load_module("readers", "als_fit_roofline").program_seconds(ctx)
    if not seconds or not ctx.get("sweeps"):
        return None
    chips = ctx["config"]["mesh_devices"]
    least = least_sweep_seconds(ctx["config"], ctx["device_kind"])["least_s"] / chips
    return 100.0 * least * ctx["sweeps"] / seconds
