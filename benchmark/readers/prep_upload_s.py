"""Host seconds the set-up fit spent dispatching slab uploads and landing
permutations (``last_fit_report["upload_s"]``; layer: host prep)."""


def read(ctx):
    report = ctx.get("first_report")
    return None if not report else report.get("upload_s")
