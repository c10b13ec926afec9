"""Seconds the set-up fit spent building the CSR and CSC views, the two
argsorts over the entries, side by side (``last_fit_report["spans"]``:
``fit.prep.index``; layer: host prep). A part of ``prep_bucket_s``."""

from benchmark.phases import setup_span_seconds


def read(ctx):
    return setup_span_seconds(ctx, "fit.prep.index")
