"""Host milliseconds per sweep inside the chunked fit's compiled calls, one a
bucket: span ``fit.stream.dispatch`` summed over the window's fits / sweeps
(``last_fit_report["spans"]``; layer: host stream). The call returns once
the work is queued, or once the device's queue has room for it. Nothing
where the program has no such span."""

from benchmark.manifest import load_module


def read(ctx):
    seconds = load_module("readers", "stream_upload_ms").window_span_seconds(ctx, "fit.stream.dispatch")
    sweeps = ctx.get("sweeps")
    return 1000.0 * seconds / sweeps if seconds and sweeps else None
