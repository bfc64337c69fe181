"""Host spans: where a launch's wall time goes, layer by layer.

A span is a named stretch of host time around one layer of the driver
(staging, dispatch, the sync, harvesting). `SpanClock.span(name,
launch)` does two things:

  * it opens a `jax.profiler.TraceAnnotation(name, launch=<n>)`, so that
    in a profiler trace the span lands on the host plane, on the same
    clock as the device's operations (the super-tick launch itself is a
    `StepTraceAnnotation` with `step_num=<n>`);
  * it adds to `table[name]` one count, the span's wall seconds
    (`total_s`) and its self seconds (`self_s`: the total less the time
    its child spans cover).

The launch number `n` is the one identifier every span of a launch
shares: that of the launch open around it or, between launches, of the
last launch opened. The parent of a span is the span open around it.

Totals are always kept: a span costs a few `perf_counter` calls and one
annotation object, so spans sit around whole layers, once per launch
or once per micro-tick, never inside a per-edge or per-row loop.

Device planes are named by `jax.named_scope("d3.<plane>")` inside the
compiled tick (`core/tick.py`, `core/pipeline.py`, and `d3.wire` round
the collective in `dist/router.py`); scopes are op metadata only and
leave the compiled arithmetic as it is.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Dict

import jax


@dataclass
class SpanStat:
    """One span name's totals."""
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0


class OpenSpan:
    """A span while it is open: its start, and the time its closed
    children took."""
    __slots__ = ("t0", "child_s")

    def __init__(self):
        self.child_s = 0.0
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class SpanClock:
    """Keeps the stack of open spans of one pipeline and folds every span
    that closes into `table` ({name: SpanStat})."""

    def __init__(self, table: Dict[str, SpanStat]):
        self.table = table
        self._open: list = []

    @contextlib.contextmanager
    def span(self, name: str, launch: int, step: bool = False):
        """Time `name` as one span of launch `launch`; `step=True` marks
        it as the profiler's step (the launch itself). Yields the
        `OpenSpan`, whose `elapsed()` reads the span so far."""
        ann = (jax.profiler.StepTraceAnnotation(name, step_num=launch)
               if step else jax.profiler.TraceAnnotation(name, launch=launch))
        sp = OpenSpan()
        self._open.append(sp)
        try:
            with ann:
                yield sp
        finally:
            dt = sp.elapsed()
            self._open.pop()
            if self._open:
                self._open[-1].child_s += dt
            st = self.table.get(name)
            if st is None:
                st = self.table[name] = SpanStat()
            st.count += 1
            st.total_s += dt
            st.self_s += dt - sp.child_s

    def total(self, name: str) -> float:
        st = self.table.get(name)
        return st.total_s if st is not None else 0.0
