"""Share of its roofline that the fused fit program reaches: the least time
the chip could take for the sweeps' REQUIRED work (``workcounts.py``: logical
matrix and published peaks only) over the device time of that program's
events in the trace (layer: kernels). Which peak bounds it is printed on an
earlier line."""

from benchmark.workcounts import least_sweep_seconds


def program_seconds(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    wanted = ctx["traffic"]["trace_programs"]
    total = sum(s for name, s in trace["programs"].items()
                if any(w in name for w in wanted))
    return total or None


def read(ctx):
    seconds = program_seconds(ctx)
    if not seconds or not ctx.get("sweeps"):
        return None
    least = least_sweep_seconds(ctx["config"], ctx["device_kind"])
    return 100.0 * least["least_s"] * ctx["sweeps"] / seconds
