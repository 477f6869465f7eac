"""In-process flight recorder of the program's spans and counters.

``span(name, **attrs)`` times a block on ``time.perf_counter`` and keeps
``Span(id, parent, root, name, t0, t1, attrs)``: ``parent`` is the span
open around it in the same thread or task, ``root`` the id of the
outermost one, which names the request the span belongs to.
``count(name, n)`` keeps a timestamped ``Count`` point event and adds ``n``
to a running total.  Both go into one bounded deque, so the recorder holds
the last ``maxlen`` records whatever the process's lifetime; an operator
reads them with ``records(t0, t1)`` and the totals with ``totals()``.

Each span is also a ``jax.profiler.TraceAnnotation`` called ``c3p.<name>``
with its attributes: free when no profile runs, and inside a profile the
program's spans sit on the host plane on the device events' clock.

Recording is on by default; ``disable()`` turns ``span`` into a shared
no-op context and ``count`` into a no-op.  Nothing is written anywhere.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax.profiler

PREFIX = "c3p."


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    root: int
    name: str
    t0: float
    t1: float
    attrs: dict


class Count(NamedTuple):
    name: str
    t: float
    n: int
    root: Optional[int]           # the request open when it was counted


class _NullSpan:
    """What ``span`` returns while recording is off."""

    __slots__ = ()

    def __enter__(self):
        return {}

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("rec", "name", "attrs", "id", "parent", "root", "t0",
                 "token", "ann")

    def __init__(self, rec, name, attrs):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec = self.rec
        stack = rec._stack.get()
        self.id = next(rec._ids)
        self.parent, self.root = stack[-1] if stack else (None, self.id)
        self.token = rec._stack.set(stack + ((self.id, self.root),))
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        rec = self.rec
        rec._stack.reset(self.token)
        rec._buf.append(Span(self.id, self.parent, self.root, self.name,
                             self.t0, t1, self.attrs))
        return False


class Recorder:
    """Spans and counters of one process, the last ``maxlen`` of them."""

    def __init__(self, maxlen: int = 1 << 16):
        self.enabled = True
        self._buf = collections.deque(maxlen=maxlen)
        self._totals = collections.Counter()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._stack = contextvars.ContextVar(f"c3p_spans_{id(self)}",
                                             default=())

    def span(self, name: str, **attrs):
        """Context manager timing its block; yields ``attrs``, which the
        block may add to (what it learns is kept with the span)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def count(self, name: str, n: int = 1):
        if not self.enabled:
            return
        stack = self._stack.get()
        self._buf.append(Count(name, time.perf_counter(), n,
                               stack[-1][1] if stack else None))
        with self._lock:
            self._totals[name] += n

    def records(self, t0: float = float("-inf"), t1: float = float("inf")):
        """Spans that lie inside [t0, t1] and counts made in it, oldest
        first (a span is kept when it ends, so inside it come after its
        children)."""
        out = []
        for r in list(self._buf):
            a, b = (r.t0, r.t1) if isinstance(r, Span) else (r.t, r.t)
            if a >= t0 and b <= t1:
                out.append(r)
        return out

    def totals(self) -> dict:
        """Every counter's total since the recorder was made."""
        with self._lock:
            return dict(self._totals)


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
records = RECORDER.records
totals = RECORDER.totals


def disable():
    RECORDER.enabled = False


def enable():
    RECORDER.enabled = True
