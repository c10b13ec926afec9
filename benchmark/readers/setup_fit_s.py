"""Seconds of the set-up fit, the whole ``ImplicitALS.fit`` call that builds
the layout, acquires the executable and runs the first sweeps: span ``fit`` of
``ctx["first_report"]`` (``last_fit_report["spans"]``; layer: model). The
program's whole share of ``setup_s``; the rest is the harness's (imports,
the matrix from the seed, the factors' download)."""

from benchmark.phases import span_seconds


def read(ctx):
    return span_seconds(ctx.get("first_report"), "fit")
