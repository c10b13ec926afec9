"""How many times over a sweep assembles the two factor tables on a chip:
``last_fit_report["assembled_bytes_per_sweep"]`` (the program's counter: the
all-gather results in the compiled programs its sweeps called, a call each)
over both tables' bytes from the configuration (layer: mesh).
1.0 is the layout's need — each table once; a program that assembles inside
every bucket's program reads in the hundreds. Nothing where the program
counts no such bytes."""

from benchmark.peaks_ici import table_bytes


def read(ctx):
    reports = ctx.get("reports") or []
    assembled = [r.get("assembled_bytes_per_sweep") for r in reports]
    if not assembled or any(a is None for a in assembled):
        return None
    return max(assembled) / table_bytes(ctx["config"])
