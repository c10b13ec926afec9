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
time is its total less its children's.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Iterator

import jax

from albedo_tpu.analysis.locksmith import named_lock

# Every section's host span in a profiler trace carries this prefix, so a
# reader finds the program's spans among the runtime's own host events.
SPAN_PREFIX = "albedo."


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
    >>> t.report()
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

    def add(self, name: str, seconds: float) -> None:
        """Record a duration the caller's own clock reads measured."""
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + seconds
            self.counts[name] = self.counts.get(name, 0) + 1

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
        for name in sorted(self.totals, key=self.totals.get, reverse=True):  # type: ignore[arg-type]
            printer(
                f"{name}: {self.totals[name]:.3f}s over {self.counts[name]} call(s)"
            )
        return dict(self.totals)
