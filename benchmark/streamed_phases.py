"""``phases.reduce_phases`` for a window of thousands of dispatches: the same
tables, with the idle gaps labelled in one sweep.

``phases.reduce_phases`` labels each idle gap by ``trace.label_gap``, which
looks through every host span. A resident fit has a dozen spans and a few
gaps. A traced ``fit-streamed`` window has 7,275 dispatches with three spans
each and a gap wherever two operations do not touch, and the product was four
minutes of a traced run on the chip's host (``PERF.md`` section 5).
``phases.py`` is not edited. Here its reduction runs over the window span
alone, and the gaps, which come sorted, meet the spans in start order: the
spans open at a gap's midpoint are few, and the shortest of them is the
label ``label_gap`` gives.
"""

from __future__ import annotations

import sys

from benchmark import phases, trace as trace_mod


def idle_by_span(gaps: list[tuple[float, float]], spans: list[tuple[str, float, float]]) -> dict:
    """Seconds of ``gaps`` (sorted, disjoint) by ``trace.label_gap``'s label."""
    out: dict[str, float] = {}
    pending = sorted(spans, key=lambda span: span[1])
    at, open_spans = 0, []
    for lo, hi in gaps:
        mid = 0.5 * (lo + hi)
        while at < len(pending) and pending[at][1] <= mid:
            open_spans.append(pending[at])
            at += 1
        open_spans = [span for span in open_spans if span[2] >= mid]
        label = (min((e - s, name) for name, s, e in open_spans)[1] if open_spans
                 else "host: no span")
        out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def reduce_phases(planes: list[dict], op_names: dict[str, str], programs: list[str]) -> dict | None:
    """As ``phases.reduce_phases``, key for key."""
    host = [p for p in planes if not p["name"].startswith("/device:")]
    spans = [ev for p in host for line in p["lines"] for ev in line["events"]
             if ev[0].startswith((phases.SPAN_PREFIX, "bench_"))]
    window = next((ev for ev in spans if ev[0] == trace_mod.WINDOW_SPAN), None)
    if window is None:
        return None
    devices = [p for p in planes if p["name"].startswith("/device:")]
    reduced = phases.reduce_phases(
        [{"name": "/host:window", "lines": [{"name": "window", "events": [window]}]}] + devices,
        op_names, programs)
    if reduced is None:
        return None
    lo, hi = window[1], window[2]
    spans.remove(window)
    idle: dict[str, float] = {}
    for plane in devices:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if not trace_mod.clip([(s, e) for name, s, e in lines.get(trace_mod.MODULES_LINE, [])
                               if any(p in name for p in programs)], lo, hi):
            continue
        busy = trace_mod.union(trace_mod.clip(
            [(s, e) for _, s, e in lines.get(trace_mod.OPS_LINE, [])], lo, hi))
        for label, seconds in idle_by_span(trace_mod.gaps(busy, lo, hi), spans).items():
            idle[label] = idle.get(label, 0.0) + seconds
    reduced["idle"] = {k: v / reduced["chips"] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])}
    reduced["spans"] = spans
    return reduced


def log_phases(path: str, programs: list[str]) -> dict | None:
    """One trace file's phases, logged as ``phases.phases_of`` logs them."""
    with open(path, "rb") as f:
        op_names = phases.op_names_from_xspace(f.read())
    reduced = reduce_phases(phases.planes_from_xplane(path), op_names, programs)
    if reduced is not None:
        print(phases.table(reduced), file=sys.stderr, flush=True)
    return reduced
