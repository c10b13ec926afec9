"""Seconds the set-up fit spent running the executable on the deterministic
probe and hashing its output, summed over the probe runs
(``last_fit_report["spans"]``: ``fit.acquire.probe``; layer: executable
acquisition). A part of ``fit_compile_s``."""

from benchmark.phases import setup_span_seconds


def read(ctx):
    return setup_span_seconds(ctx, "fit.acquire.probe")
