"""The program's own count of dispatch-to-barrier milliseconds per sweep:
sum of ``last_fit_report["device_s"]`` over the window's fits / sweeps
(layer: model). Parts from ``fit_sweep_ms`` only by host time between fits."""


def read(ctx):
    reports, sweeps = ctx.get("reports"), ctx.get("sweeps")
    if not reports or not sweeps:
        return None
    return 1000.0 * sum(r["device_s"] for r in reports) / sweeps
