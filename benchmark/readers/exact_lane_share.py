"""Lanes the exact solve factorises a sweep over the logical rows of both
tables (``last_fit_report["exact_lane_share"]`` of the window's first fit:
a bucket's systems ride whole 128-lane tiles, ``ops.als.exact_lanes``;
layer: kernels). 1.0 would be a lane a row; 0 under CG."""

from benchmark.span_reads import window_counter


def read(ctx):
    return window_counter(ctx, "exact_lane_share")
