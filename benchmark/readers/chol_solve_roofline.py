"""Share of its roofline that the exact solve reaches: the least time the
chip could take for the solve's REQUIRED work a sweep (``workcounts_exact.py``:
configuration and published peaks only) over the self time of the operations
under the ``als.cholesky`` scope a sweep (``benchmark/phases.py``; layer:
kernels). ``als_fit_mfu`` is the whole step's share beside it."""

from benchmark.phases import scope_ms_per_sweep
from benchmark.workcounts_exact import least_solve_seconds


def read(ctx):
    ms = scope_ms_per_sweep(ctx, "als.cholesky")
    if not ms:
        return None
    least = least_solve_seconds(ctx["config"], ctx["device_kind"])
    return 100.0 * least["least_s"] * 1000.0 / ms
