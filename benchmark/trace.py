"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, the heaviest device operations and the longest idle gaps.

- Device planes are the ``/device:TPU:<n>`` planes. Each has an operations
  line (``XLA Ops``) and a programs line (``XLA Modules``); step and
  framework lines overlap them and are never summed.
- ``busy_s`` is the UNION of the operation intervals on the operations line,
  clipped to the traced window and averaged over the chips used — not a sum
  (fused operations nest, lines overlap), not host events.
- The window is the host span named ``bench_window`` that the harness puts
  around the measured work, on the trace's own clock.
- A program's device time is the union of the intervals of the programs-line
  events whose name matches, clipped to the window.
- The heaviest operations are ranked by SELF time (a ``while`` holds its
  body's operations on the same line), under a short form of the HLO name.

The intervals are plain ``(start, end)`` pairs in seconds, so the arithmetic
is tested on hand-made cases and on a recorded piece of a v5e trace
(``fixtures/``) without a profiler.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def union_seconds(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` that a merged busy list leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_seconds(events) -> dict[str, float]:
    """Each operation's own time on one line: its duration less that of the
    operations nested directly inside it (a ``while`` holds its body's
    operations), summed by name. The self times add up to the union."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, own seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 fusion:kCustom f32[2085288,128]`` from a whole HLO line;
    other names (programs, host spans) as they are."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    opcode = _OPCODE.search(rhs)
    kind = re.search(r"kind=(k[A-Za-z]+)", rhs)
    shape = "tuple" if rhs.startswith("(") else rhs.split("{", 1)[0].split(" ", 1)[0]
    parts = [lhs, (opcode.group(1) if opcode else "?") + (":" + kind.group(1) if kind else ""), shape]
    return " ".join(parts)[:120]


def label_gap(gap, host_spans) -> str:
    """The innermost (shortest) host span that covers the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [(e - s, name) for name, s, e in host_spans if s <= mid <= e]
    return min(covering)[1] if covering else "host: no span"


def reduce_planes(planes: list[dict], chips: int, host_prefix: str = "bench_") -> dict:
    """``planes`` is the trace as plain data: a list of
    ``{"name", "lines": [{"name", "events": [(name, start_s, end_s), ...]}]}``.
    Returns busy_s, window_s, programs, device_ops and idle_gaps."""
    host_spans, window = [], None
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, e in line["events"]:
                if name == WINDOW_SPAN and window is None:
                    window = (s, e)
                elif name.startswith(host_prefix):
                    host_spans.append((name, s, e))
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    devices = sorted(devices, key=lambda p: p["name"])[:chips]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    if window is None:
        raise ValueError(f"the trace holds no host span named {WINDOW_SPAN!r}")
    lo, hi = window
    busy_s, programs, ops, idle = [], {}, {}, {}
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane['name']} has no {OPS_LINE!r} line")
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in lines[OPS_LINE]
                   if min(e, hi) > max(s, lo)]
        merged = union((s, e) for _, s, e in clipped)
        busy_s.append(sum(e - s for s, e in merged))
        for name, own in self_seconds(clipped).items():
            ops[name] = ops.get(name, 0.0) + own
        by_program: dict[str, list] = {}
        for name, s, e in lines.get(MODULES_LINE, []):
            by_program.setdefault(name, []).extend(clip([(s, e)], lo, hi))
        for name, iv in by_program.items():
            programs[name] = programs.get(name, 0.0) + union_seconds(iv)
        for gap in gaps(merged, lo, hi):
            label = label_gap(gap, host_spans)
            idle[label] = idle.get(label, 0.0) + (gap[1] - gap[0])
    n = len(devices)

    def top(d: dict, k: int) -> list:
        return [[name, v / n] for name, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    # the heaviest kinds of operation (self time, all of a kind together),
    # then the heaviest single operations
    kinds: dict[str, float] = {}
    for name, own in ops.items():
        parts = name.split(" ")
        kind = "all " + parts[1] if len(parts) > 1 else name
        kinds[kind] = kinds.get(kind, 0.0) + own
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": hi - lo,
        "programs": {k: v / n for k, v in programs.items()},
        "device_ops": top(kinds, 5) + top(ops, 5),
        "idle_gaps": top(idle, 10),
    }


def planes_from_xplane(path: str) -> list[dict]:
    """Read an ``.xplane.pb`` with nothing but JAX into plain data (seconds)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        keep_all = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if keep_all and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                (short_name(ev.name), ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
                if keep_all or ev.name.startswith("bench_")
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
