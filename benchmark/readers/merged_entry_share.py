"""Share of the padded entries that travel in a dispatch of more than
``batch_size`` rows (``last_fit_report["merged_entry_share"]`` of the
window's first fit; layer: host stream): what the streamed fit's own
layout merged (``ImplicitALS._dispatch_rows``)."""

from benchmark.span_reads import window_counter


def read(ctx):
    return window_counter(ctx, "merged_entry_share")
