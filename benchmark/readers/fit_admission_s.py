"""Seconds the set-up fit spent in capacity admission: two bincounts over the
entries and the planner (``last_fit_report["spans"]``: ``fit.admission``;
layer: admission)."""

from benchmark.phases import setup_span_seconds


def read(ctx):
    return setup_span_seconds(ctx, "fit.admission")
