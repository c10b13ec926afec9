"""Host milliseconds per sweep inside the chunked fit's per-bucket uploads,
the four ``jnp.asarray`` of each slab: span ``fit.stream.upload`` summed over
the window's fits / sweeps (``last_fit_report["spans"]``; layer: host
stream). Nothing where the program has no such span."""

from benchmark.phases import span_seconds


def window_span_seconds(ctx, name):
    """One span's seconds over all the window's fits; nothing where a fit
    lacks it."""
    seconds = [span_seconds(r, name) for r in ctx.get("reports") or []]
    return sum(seconds) if seconds and all(s is not None for s in seconds) else None


def read(ctx):
    seconds, sweeps = window_span_seconds(ctx, "fit.stream.upload"), ctx.get("sweeps")
    return 1000.0 * seconds / sweeps if seconds and sweeps else None
