"""Gigabytes a second at which the chunked fit's bucket slabs left the host:
``last_fit_report["streamed_bytes_per_sweep"]`` x the fit's sweeps, over the
seconds of span ``fit.stream.upload``, both summed over the window's fits
(layer: host stream). Nothing where the program counts no such bytes."""

from benchmark.manifest import load_module


def read(ctx):
    reports = ctx.get("reports") or []
    per_sweep = [r.get("streamed_bytes_per_sweep") for r in reports]
    seconds = load_module("readers", "stream_upload_ms").window_span_seconds(ctx, "fit.stream.upload")
    if not seconds or not ctx.get("sweeps") or any(b is None for b in per_sweep):
        return None
    # every fit of a window runs the configuration's max_iter sweeps
    sweeps_a_fit = ctx["sweeps"] / len(reports)
    return sum(per_sweep) * sweeps_a_fit / seconds / 1e9
