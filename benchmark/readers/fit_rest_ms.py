"""Device milliseconds per sweep of the fused fit program outside its gathers
and its CG: the program's device time less ``als.gather`` and ``als.cg``, /
sweeps — Gramian, warm start, landing, init, the compiler's copies, anything
unscoped (``benchmark/phases.py``; layer: kernels). With the other two it adds
up to the program's device time; nothing where either scope is absent."""

from benchmark.phases import fit_phases


def read(ctx):
    reduced, sweeps = fit_phases(ctx), ctx.get("sweeps")
    if not reduced or not sweeps:
        return None
    gather, cg = reduced["scopes"].get("als.gather"), reduced["scopes"].get("als.cg")
    if not gather or not cg:
        return None
    return 1000.0 * (reduced["program_s"] - gather - cg) / sweeps
