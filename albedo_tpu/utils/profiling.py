"""Timing and tracing: one span primitive.

Reference parity: the reference measures wall-clock by prefixing ``time`` on
every spark-submit (``Makefile:64,78,131``) and decorating crawler methods
with ``timing_decorator`` (``app/utils_timing.py:7-15``); deeper inspection
goes through the Spark UI. Here ``Timer.section`` is the one mechanism
(SURVEY.md §5): a section is a wall-clock total and count in
``Timer.snapshot()`` (fit reports, ``/metrics``) AND a host span named
``albedo.<name>`` in any running ``jax.profiler`` trace, on the same clock as
the device's ``XLA Ops`` line (the TensorBoard-viewable trace is the Spark-UI
analogue). Names are dotted, parent first (``fit.prep.index``): a span's self
time is its total less its children's, which is how ``Timer.report`` — the
one renderer, the operator's table of ``train_als`` / ``cv_als`` — prints it.
Children that run on threads (``fit.prep.upload``, the AOT branches under a
threaded acquisition) are summed over their threads and may exceed their
parent's wall-clock. ``Timer.collections`` marks the interpreter's full
garbage collections as a span of their own (``fit.gc``), so that a slow
window or an idle gap of the device says whether a collection sat in it.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Callable, Iterator

import jax

from albedo_tpu.analysis.locksmith import named_lock

# Every section's host span in a profiler trace carries this prefix, so a
# reader finds the program's spans among the runtime's own host events.
SPAN_PREFIX = "albedo."

# The last part of a span that ``Timer.collections`` makes (``fit.gc``). Such
# a span lies inside whichever spans a collection interrupted, its siblings
# among them, so ``Timer.report`` does not take it off its parent's self time.
GC_PART = "gc"


def _sync(value: Any) -> None:
    """Block until every jax array in a pytree is computed."""
    for leaf in jax.tree.leaves(value):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


class Timer:
    """Accumulating named wall-clock sections.

    >>> t = Timer()
    >>> with t.section("sweep"):
    ...     out = step()          # any jax outputs are synced on exit
    >>> t.report()                # or: Timer().absorb(snapshot).report(log)
    """

    def __init__(self) -> None:
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        # The serving pipeline accumulates sections from concurrent HTTP
        # threads; the read-modify-write below would lose increments
        # unlocked. Uncontended acquisition is ~100 ns — noise against the
        # device work the sections time.
        self._lock = named_lock("utils.profiling.timer")

    @contextlib.contextmanager
    def section(self, name: str, sync: Any = None) -> Iterator[None]:
        """Time the block under ``name`` and mark it ``albedo.<name>`` in a
        running profiler trace (with no profiler session the annotation is a
        flag test)."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                try:
                    yield
                finally:
                    _sync(sync)
        finally:
            self.add(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def collections(self, parent: str) -> Iterator[None]:
        """For the length of the block, every FULL garbage collection
        (generation 2: the ones that take long on a large heap) is a
        ``section``'s worth of ``<parent>.gc``: seconds and a count here, an
        ``albedo.<parent>.gc`` span in a running trace - opened and closed
        from the interpreter's own ``gc.callbacks``, on the thread the
        collection interrupts, so in the trace it is a child of whichever
        span it fell in. Younger generations cost the hook one dict look-up;
        with no collection the block pays the registration alone and the
        name stays out of the snapshot. The hook takes no lock (a collection
        may interrupt a thread that holds this timer's): the seconds are
        added when the block ends."""
        name = f"{parent}.{GC_PART}"
        open_span: list = []
        seconds: list[float] = []

        def hook(phase: str, info: dict) -> None:
            if info["generation"] != 2:
                return
            if phase == "start":
                span = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
                span.__enter__()
                open_span.append((span, time.perf_counter()))
            elif open_span:
                span, t0 = open_span.pop()
                seconds.append(time.perf_counter() - t0)
                span.__exit__(None, None, None)

        gc.callbacks.append(hook)
        try:
            yield
        finally:
            gc.callbacks.remove(hook)
            for s in seconds:
                self.add(name, s)

    def add(self, name: str, seconds: float, count: int = 1) -> None:
        """Record a duration the caller's own clock reads measured."""
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + seconds
            self.counts[name] = self.counts.get(name, 0) + count

    def absorb(self, snapshot: dict[str, dict]) -> "Timer":
        """Add a published ``snapshot()`` (a fit report's ``spans``) to this
        timer: what a job that ran several fits reports as one table."""
        for name, seconds in snapshot["totals"].items():
            self.add(name, seconds, snapshot["counts"].get(name, 1))
        return self

    def snapshot(self) -> dict[str, dict]:
        """Point-in-time copy of the accumulated sections:
        ``{"totals": {name: seconds}, "counts": {name: calls}}``.

        This is the one exchange format between the offline fit reports and
        the online `/metrics` plane (``serving.metrics.MetricsRegistry
        .observe_timer``) — both render the same dicts, so a stage timed here
        can never read differently in the two places."""
        with self._lock:
            return {"totals": dict(self.totals), "counts": dict(self.counts)}

    def report(self, printer: Callable[[str], None] = print) -> dict[str, float]:
        """Print the span table, a row a span in the order of the dotted
        names (parent first, children indented under it): total seconds,
        calls, and self seconds = the total less the direct children's
        (a ``collections`` span apart: it lies inside the others). A parent
        whose children were summed over threads past its own wall-clock is
        marked ``*`` and its self time printed as 0. Returns the totals."""
        snap = self.snapshot()
        totals, counts = snap["totals"], snap["counts"]
        children: dict[str, float] = {}
        for name, seconds in totals.items():
            parent, _, part = name.rpartition(".")
            if part != GC_PART:
                children[parent] = children.get(parent, 0.0) + seconds
        width = max([len("span")] + [len(n) + 2 * n.count(".") for n in totals])
        printer(f"{'span':<{width}} {'total s':>12} {'calls':>7} {'self s':>12}")
        for name in sorted(totals, key=lambda n: n.split(".")):
            own = totals[name] - children.get(name, 0.0)
            mark = "*" if own < -1e-9 else ""
            printer(f"{'  ' * name.count('.') + name:<{width}} {totals[name]:>12.6f} "
                    f"{counts[name]:>7} {max(own, 0.0):>12.6f}{mark}")
        return totals
