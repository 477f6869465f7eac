"""Host-clock spans the harness puts around its calls into the program.

Each span is kept in memory as (name, start, end) on ``time.perf_counter``.
While a profiler trace runs, the same span is written into the trace as a
``jax.profiler.TraceAnnotation``, so idle gaps on the device can be put
beside what the host was doing.
"""

from __future__ import annotations

import collections
import contextlib
import time


class Spans:
    def __init__(self):
        self.records = []          # (name, t0, t1), perf_counter seconds
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str, t0: float = float("-inf"),
                  t1: float = float("inf")):
        """Durations (s) of the spans called ``name`` inside [t0, t1]."""
        return [b - a for n, a, b in self.records
                if n == name and a >= t0 and b <= t1]

    def totals(self):
        out = collections.defaultdict(float)
        for n, a, b in self.records:
            out[n] += b - a
        return dict(out)
