"""Host milliseconds per sweep inside the row-sharded fit's compiled calls of
the shape groups and the landing: span ``fit.shard.dispatch`` summed over the
window's fits / sweeps (``last_fit_report["spans"]``; layer: mesh). The call
returns once the work is queued. Nothing where the program has no such
span."""

from benchmark.manifest import load_module


def read(ctx):
    seconds = load_module("readers", "stream_upload_ms").window_span_seconds(ctx, "fit.shard.dispatch")
    sweeps = ctx.get("sweeps")
    return 1000.0 * seconds / sweeps if seconds and sweeps else None
