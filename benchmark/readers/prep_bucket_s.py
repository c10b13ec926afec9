"""Host seconds the set-up fit spent planning and filling bucket slabs
(``ImplicitALS.last_fit_report["bucket_s"]``; layer: host prep)."""


def read(ctx):
    report = ctx.get("first_report")
    return None if not report else report.get("bucket_s")
