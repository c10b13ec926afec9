"""Share of the padded entries in buckets whose CG ran on the explicit
Gramian (``last_fit_report["cg_gram_entry_share"]`` of the window's first
fit: from the bucket shapes and ``ops.als.cg_uses_gramian``, the predicate
the kernel branches on; layer: kernels). 0 under the exact solve."""

from benchmark.span_reads import window_counter


def read(ctx):
    return window_counter(ctx, "cg_gram_entry_share")
