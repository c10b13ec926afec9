"""Host milliseconds of a fit outside its wait for the device: the ``fit``
span less the ``fit.wait`` span, mean over the window's fits
(``last_fit_report["spans"]``; layer: model). What the host adds to every
fit: cache look-ups, scalars, the dispatch."""

from benchmark.phases import span_seconds


def read(ctx):
    pairs = [(span_seconds(r, "fit"), span_seconds(r, "fit.wait")) for r in ctx.get("reports") or []]
    host = [fit - wait for fit, wait in pairs if fit is not None and wait is not None]
    return 1000.0 * sum(host) / len(host) if host else None
