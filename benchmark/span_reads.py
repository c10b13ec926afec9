"""What the per-layer readers that came with PR 37 share: the traced
window's phases by the reduction the reader asks for, device idle seconds
under one of the program's host spans, device time under one scope, and a
counter of the window's first fit report.

- The phases are ``phases.fit_phases`` (one reduction a process, kept by its
  ``lru_cache``) unless the reader asks for the swept reduction
  (``swept=True``). A reader of a window that holds thousands of dispatches
  asks: there ``phases.reduce_phases`` would label each idle gap by a search
  through every span (four minutes of a traced run, ``streamed_phases.py``),
  so ``streamed_phases.reduce_phases`` runs instead, ONCE, and is kept here
  for all the readers that ask. Which reduction a metric needs is its
  reader's to know; nothing here knows a cell or a traffic driver.
- Idle under a span is ``reduced["idle"]``: every idle gap of the device's
  ``XLA Ops`` line, whole, to the innermost (shortest) ``albedo.*`` /
  ``bench_*`` host span over its midpoint, averaged over the chips. A span
  that ran with no gap under it reads ``0.0``; a trace with no ``albedo.*``
  event at all (a program without spans) reads nothing. All the labels of
  one window add up to its idle seconds, so to ``device_idle.fit`` x window.
- A scope that the program does not carry reads nothing, never nought: an
  executable compiled from a tree with other scope names (XLA's persistent
  cache ignores op metadata) must not read as a phase that costs nothing.
"""

from __future__ import annotations

import functools

from benchmark import phases, streamed_phases


@functools.lru_cache(maxsize=2)
def _swept_phases(path: str, programs: tuple[str, ...]) -> dict | None:
    with open(path, "rb") as f:
        op_names = phases.op_names_from_xspace(f.read())
    return streamed_phases.reduce_phases(phases.planes_from_xplane(path), op_names, list(programs))


def window_phases(ctx: dict, swept: bool = False) -> dict | None:
    """The phases of the traced window the context speaks of (as
    ``phases.fit_phases``: the newest trace under ``.bench-trace/``, taken
    only if its window is the one the driver reduced), by the swept
    reduction where the reader asks for it; nothing without a trace in the
    context."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if not swept:
        return phases.fit_phases(ctx)
    path = phases.newest_xplane()
    if path is None:
        return None
    reduced = _swept_phases(path, tuple(ctx["traffic"]["trace_programs"]))
    if reduced is None or abs(reduced["window_s"] - trace["window_s"]) > 1e-6:
        return None
    return reduced


def idle_ms_per_sweep(ctx: dict, span: str, swept: bool = False) -> float | None:
    """Milliseconds per sweep in which no operation ran on the device while
    the innermost host span over the gap was ``albedo.<span>``."""
    reduced, sweeps = window_phases(ctx, swept), ctx.get("sweeps")
    if not reduced or not sweeps:
        return None
    if not any(name.startswith(phases.SPAN_PREFIX) for name, _, _ in reduced["spans"]):
        return None
    return 1000.0 * reduced["idle"].get(phases.SPAN_PREFIX + span, 0.0) / sweeps


def scope_ms_per_sweep(ctx: dict, scope: str, key: str = "scopes", swept: bool = False) -> float | None:
    """Self milliseconds per sweep under one scope: an outermost one
    (``key="scopes"``) or an innermost one (``"inner"``: a sub-scope)."""
    reduced, sweeps = window_phases(ctx, swept), ctx.get("sweeps")
    if not reduced or not sweeps or not reduced[key].get(scope):
        return None
    return 1000.0 * reduced[key][scope] / sweeps


def window_counter(ctx: dict, key: str) -> float | None:
    """A counter of the window's first ``last_fit_report``; nothing where the
    program counts no such thing. Nought is a reading (the share of a kernel
    the configuration does not use)."""
    reports = ctx.get("reports") or [{}]
    return reports[0].get(key)
