"""From a traced fit to its phases: the fit program's device time by the
``als.*`` scope the program's source gives each operation, and the program's
own ``albedo.*`` host spans on the same clock.

- The scopes are ``jax.named_scope`` names (``albedo_tpu/ops/als.py``). On a
  v5e they reach the trace as the ``tf_op`` stat of an operation's EVENT
  METADATA (``jit(als_init_fit_fused)/.../als.cg/als.cg.matvec/dot_general:``),
  which ``jax.profiler.ProfileData`` does not hand out: it gives an event's
  own stats only. So the metadata is read from the ``.xplane.pb`` itself, by a
  reader of the protobuf wire format that skips everything but the device
  planes' metadata, and joined to the events by name.
- An operation's SELF time (``trace.self_seconds``: a ``while`` holds its
  body's operations) goes to the OUTERMOST ``als.*`` scope in its op name, and
  to the innermost one for the table of sub-scopes. A fusion whose
  instructions come from two scopes is counted under its own (its root's) op
  name. What carries no scope is ``(unscoped)``.
- Only operations inside the fit program's events on the programs line count
  (``traffic/fit.json: trace_programs``), clipped to the ``bench_window`` host
  span and averaged over the chips that ran it — the same program time that
  ``als_fit_roofline`` divides by.
- The host spans are the events named ``albedo.*`` (``Timer.section``); idle
  gaps of the device are labelled by the innermost of them, or of the
  harness's ``bench_*`` spans, that covers the gap's midpoint.

A program without scopes or spans (the parent of the PR that added them)
gives nothing to read: every function here then returns nothing.
``python3 -m benchmark.phases [trace.xplane.pb]`` prints the tables for a
trace already written (the newest under ``.bench-trace/`` by default).
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys

from benchmark import trace as trace_mod
from benchmark.manifest import HERE, ROOT

SCOPE_PREFIX = "als."
SPAN_PREFIX = "albedo."
UNSCOPED = "(unscoped)"
OP_NAME_STAT = "tf_op"


# ------------------------------------------------------- the wire format

def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a varint
    or a fixed-width field, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        tag, at = _varint(buf, at)
        kind = tag & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {kind} is not one an XSpace uses")
        yield tag >> 3, value


def _map_entries(plane, field: int):
    """Values of a ``map<int64, message>`` field of a plane."""
    for number, entry in _fields(plane):
        if number == field:
            for key, value in _fields(entry):
                if key == 2:
                    yield value


def op_names_from_xspace(raw: bytes) -> dict[str, str]:
    """``{event name: op name}`` over the device planes of a serialized
    XSpace, from each plane's event metadata (XPlane.event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5; XStat
    .metadata_id = 1, .str_value = 5, .ref_value = 7)."""
    out: dict[str, str] = {}
    for number, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name = next((bytes(v) for n, v in _fields(plane) if n == 2), b"")
        if not name.startswith(b"/device:"):
            continue
        stat_names = {}
        for meta in _map_entries(plane, 5):
            fields = dict(_fields(meta))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        wanted = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        for meta in _map_entries(plane, 4):
            event_name, op_name = None, None
            for n, value in _fields(meta):
                if n == 2:
                    event_name = bytes(value).decode()
                elif n == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if event_name and op_name:
                out[event_name] = op_name
    return out


# ---------------------------------------------------------- the reduction

def scope_path(op_name: str | None) -> tuple[str, ...]:
    """The ``als.*`` scopes of an op name, outermost first."""
    if not op_name:
        return ()
    return tuple(part for part in op_name.split("/") if part.startswith(SCOPE_PREFIX))


def _inside(intervals: list[tuple[float, float]], at: float) -> bool:
    i = bisect.bisect_right(intervals, (at, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= at < intervals[i][1]


def reduce_phases(planes: list[dict], op_names: dict[str, str], programs: list[str]) -> dict | None:
    """``planes`` as ``trace.reduce_planes`` takes them (device events under
    their whole names). Returns ``program_s`` (device seconds of the matching
    programs in the window), ``scopes`` and ``inner`` (self seconds by
    outermost and by innermost scope, ``(unscoped)`` among them), ``spans``
    (the ``albedo.*`` host events) and ``idle`` (gap seconds by covering
    span), each averaged over the chips that ran the program; nothing where
    no such program ran in the window."""
    spans, window = [], None
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, e in line["events"]:
                if name == trace_mod.WINDOW_SPAN and window is None:
                    window = (s, e)
                elif name.startswith((SPAN_PREFIX, "bench_")):
                    spans.append((name, s, e))
    if window is None:
        return None
    lo, hi = window
    program_s, chips = 0.0, 0
    scopes: dict[str, float] = {}
    inner: dict[str, float] = {}
    idle: dict[str, float] = {}
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ran = trace_mod.union(trace_mod.clip(
            [(s, e) for name, s, e in lines.get(trace_mod.MODULES_LINE, [])
             if any(p in name for p in programs)], lo, hi))
        if not ran:
            continue
        chips += 1
        program_s += sum(e - s for s, e in ran)
        ops = [(name, max(s, lo), min(e, hi)) for name, s, e in lines.get(trace_mod.OPS_LINE, [])
               if min(e, hi) > max(s, lo)]
        own = trace_mod.self_seconds([ev for ev in ops if _inside(ran, ev[1])])
        for name, seconds in own.items():      # one look-up per distinct name
            path = scope_path(op_names.get(name)) or (UNSCOPED,)
            scopes[path[0]] = scopes.get(path[0], 0.0) + seconds
            inner[path[-1]] = inner.get(path[-1], 0.0) + seconds
        busy = trace_mod.union((s, e) for _, s, e in ops)
        for gap in trace_mod.gaps(busy, lo, hi):
            label = trace_mod.label_gap(gap, spans)
            idle[label] = idle.get(label, 0.0) + (gap[1] - gap[0])
    if not chips:
        return None

    def mean(d: dict) -> dict:
        return {k: v / chips for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    return {"program_s": program_s / chips, "chips": chips, "window_s": hi - lo,
            "scopes": mean(scopes), "inner": mean(inner), "spans": spans, "idle": mean(idle)}


def table(reduced: dict) -> str:
    """The scopes, the sub-scopes and the idle gaps, in seconds and share."""
    total = reduced["program_s"]
    rows = [f"phases: fit program {total:.6f} s on {reduced['chips']} chip(s), "
            f"window {reduced['window_s']:.6f} s"]
    for title, key in (("scope", "scopes"), ("innermost scope", "inner")):
        rows.append(f"  {title:<22} {'seconds':>12} {'share':>8}")
        rows += [f"  {name:<22} {s:>12.6f} {100 * s / total:>7.2f}%" for name, s in reduced[key].items()]
    rows.append(f"  {'idle under':<22} {'seconds':>12}")
    rows += [f"  {name:<22} {s:>12.6f}" for name, s in reduced["idle"].items()]
    return "\n".join(rows)


# --------------------------------------------------------- from the files

def planes_from_xplane(path: str) -> list[dict]:
    """As ``trace.planes_from_xplane``, with the device events under their
    whole names (the join key to their metadata) and the ``albedo.*`` host
    spans beside the harness's."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (trace_mod.OPS_LINE, trace_mod.MODULES_LINE):
                continue
            events = [
                (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
                if device or ev.name.startswith((SPAN_PREFIX, "bench_"))
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def newest_xplane() -> str | None:
    found = glob.glob(str(ROOT / ".bench-trace" / "**" / "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def phases_of(path: str, programs: tuple[str, ...]) -> dict | None:
    """One trace file's phases, read once per process and logged."""
    with open(path, "rb") as f:
        op_names = op_names_from_xspace(f.read())
    reduced = reduce_phases(planes_from_xplane(path), op_names, list(programs))
    if reduced is not None:
        print(table(reduced), file=sys.stderr, flush=True)
    return reduced


def fit_phases(ctx: dict) -> dict | None:
    """The phases of the traced window a reader's context speaks of: the
    newest trace under ``.bench-trace/``, taken only if its window is the one
    the driver reduced (``ctx["trace"]``). Without a trace in the context no
    file is looked for."""
    trace = ctx.get("trace")
    if not trace:
        return None
    path = newest_xplane()
    if path is None:
        return None
    reduced = phases_of(path, tuple(ctx["traffic"]["trace_programs"]))
    if reduced is None or abs(reduced["window_s"] - trace["window_s"]) > 1e-6:
        return None
    return reduced


def scope_ms_per_sweep(ctx: dict, scope: str) -> float | None:
    """Self milliseconds per sweep under one outermost scope; nothing where
    the program carries no such scope."""
    reduced, sweeps = fit_phases(ctx), ctx.get("sweeps")
    if not reduced or not sweeps or not reduced["scopes"].get(scope):
        return None
    return 1000.0 * reduced["scopes"][scope] / sweeps


def _span_totals(report: dict | None) -> dict:
    return ((report or {}).get("spans") or {}).get("totals") or {}


def span_seconds(report: dict | None, name: str) -> float | None:
    """Total seconds of one span of a fit report (``last_fit_report["spans"]``);
    nothing where the report has no spans or the span did not run."""
    return _span_totals(report).get(name) or None


@functools.lru_cache(maxsize=2)
def _log_setup_spans(spans: tuple) -> None:
    rows = ["spans: the set-up fit, seconds"]
    rows += [f"  {name:<28} {seconds:>12.6f}" for name, seconds in spans]
    print("\n".join(rows), file=sys.stderr, flush=True)


def setup_span_seconds(ctx: dict, name: str) -> float | None:
    """One span of the set-up fit (``ctx["first_report"]``), whose whole
    table of spans is logged the first time one of them is read."""
    totals = _span_totals(ctx.get("first_report"))
    if totals:
        _log_setup_spans(tuple(sorted(totals.items())))
    return totals.get(name) or None


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else newest_xplane()
    if path is None:
        print("no trace under .bench-trace/", file=sys.stderr)
        return 1
    programs = json.loads((HERE / "traffic" / "fit.json").read_text())["trace_programs"]
    return 0 if phases_of(path, tuple(programs)) is not None else 1


if __name__ == "__main__":
    sys.exit(main())
