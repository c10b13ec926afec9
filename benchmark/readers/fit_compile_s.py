"""Seconds the set-up fit spent acquiring its executable
(``last_fit_report["compile_s"]``: compile, or deserialise and probe; the
source is printed on an earlier line; layer: executable acquisition)."""


def read(ctx):
    report = ctx.get("first_report")
    return None if not report else report.get("compile_s")
