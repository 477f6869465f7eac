"""Reduction of a profiler trace (xplane) to device busy time, time per
module and per kernel, and idle gaps set beside the harness's host spans.

``read(path)`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`; everything after that works on plain tuples, so the
reduction is tested on synthetic traces (``tests/test_trace.py``).

Device planes are those named ``/device:<PLATFORM>:<n>``.  On each, the
line ``XLA Ops`` holds one event per operation run (busy time is the union
of their intervals) and ``XLA Modules`` one event per program run.  Host
spans are the events whose name starts with ``chipbench.`` on any host
plane; ``chipbench.window`` marks the measured window.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


def short_name(name: str) -> str:
    """An operation's instruction name: a TPU trace names each operation
    by its whole HLO text (``%coded_matmul.1 = bf16[...] custom-call(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    # device id -> line name -> events, each sorted by start
    devices: Dict[int, Dict[str, List[Event]]]
    spans: List[Event]                 # host spans, prefix stripped


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def read(path: str, platform: str = "TPU") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and m.group(1) == platform:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = sorted(
                        (short_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)) for ev in line.events)
            devices[int(m.group(2))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns), int(ev.duration_ns)))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def window(trace: Trace) -> Tuple[int, int]:
    """(start_ns, end_ns) of the harness's ``window`` span."""
    w = [s for s in trace.spans if s[0] == "window"]
    if not w:
        raise ValueError("trace holds no chipbench.window span")
    _, t0, d = w[-1]
    return t0, t0 + d


def _clip(events, w0, w1):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def busy_intervals(events: List[Event], w0: int, w1: int):
    """Union of the events' intervals inside [w0, w1], as sorted (a, b)."""
    out = []
    for _, a, b in sorted(_clip(events, w0, w1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def busy_ns(events: List[Event], w0: int, w1: int) -> int:
    return sum(b - a for a, b in busy_intervals(events, w0, w1))


def idle_gaps(events: List[Event], w0: int, w1: int):
    """Intervals of [w0, w1] in which no operation ran, as (a, b)."""
    gaps, t = [], w0
    for a, b in busy_intervals(events, w0, w1):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def time_matching(events: List[Event], pattern: str, w0: int, w1: int):
    """(count, summed ns) of events whose name contains ``pattern`` and
    that start inside [w0, w1]."""
    n = tot = 0
    for name, s, d in events:
        if pattern in name and w0 <= s < w1:
            n += 1
            tot += d
    return n, tot


def label_gap(spans: List[Event], a: int, b: int) -> str:
    """Name of the innermost host span (other than ``window``) that covers
    the gap's midpoint; ``host`` where none does."""
    mid = (a + b) // 2
    best = None
    for name, s, d in spans:
        if name != "window" and s <= mid < s + d and (best is None
                                                       or d < best[1]):
            best = (name, d)
    return best[0] if best else "host"


def breakdown(trace: Trace, w0: int, w1: int, top: int = 10) -> dict:
    """Device operations that took most time, summed by name over the
    chips, and the longest idle gaps of any chip by the host span in which
    they fell; seconds."""
    by_op: Dict[str, int] = {}
    gaps = []
    for dev in sorted(trace.devices):
        ops = trace.devices[dev].get(OPS_LINE, [])
        for name, a, b in _clip(ops, w0, w1):
            by_op[name] = by_op.get(name, 0) + (b - a)
        for a, b in idle_gaps(ops, w0, w1):
            gaps.append((b - a, label_gap(trace.spans, a, b)))
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[lab, ns / 1e9] for ns, lab in gaps]}


@dataclasses.dataclass
class Reduced:
    """What the per-layer metric readers take from a trace."""

    window_s: float
    busy_s: Dict[int, float]           # per device
    trace: Trace
    w0: int
    w1: int

    def module(self, pattern: str, device: int):
        return time_matching(self.trace.devices[device].get(MODULES_LINE, []),
                             pattern, self.w0, self.w1)

    def ops(self, pattern: str, device: int):
        return time_matching(self.trace.devices[device].get(OPS_LINE, []),
                             pattern, self.w0, self.w1)


def reduce(trace: Trace, devices: List[int]) -> Reduced:
    w0, w1 = window(trace)
    missing = [d for d in devices if d not in trace.devices]
    if missing:
        raise ValueError(f"trace has no plane for devices {missing}; "
                         f"planes: {sorted(trace.devices)}")
    busy = {d: busy_ns(trace.devices[d].get(OPS_LINE, []), w0, w1) / 1e9
            for d in devices}
    return Reduced((w1 - w0) / 1e9, busy, trace, w0, w1)
