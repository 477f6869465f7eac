"""The program's own spans, counters and named scopes, read beside the
harness's trace.

The program records spans and counters in memory (``repro.utils.tracing``,
on ``time.perf_counter``) and tags the operations of its simulator batch
program with ``jax.named_scope`` names.  The reduced trace
(``chipbench/trace.py``) keeps neither, so the readers of the per-layer
metrics built on them take three things from here:

- ``program_records``: the program's records inside the harness's
  ``window`` span;
- ``clock_offset``: the offset from ``perf_counter`` to the trace's clock,
  the median over the harness spans that exist both in the harness's
  records and in the trace (``window`` and every ``batch``, ``coded_matmul``
  and ``recover``), with the spread of those anchors;
- ``scope_map``: instruction name -> named scope, from the ``op_name``
  metadata of the compiled program's HLO text; ``sim_scope_ns`` compiles
  the simulator's batch program as the cell ran it (served from the
  persistent compile cache) and splits its device time by scope.

Where the program predates its spans or scopes, these return ``None`` and
the readers report nothing.  The pure functions below work on plain tuples
and are tested on synthetic data (``tests/test_program_trace.py``).
"""

from __future__ import annotations

import bisect
import functools
import re
import statistics
import time
from typing import Dict, List, Optional, Tuple

from chipbench import trace as trace_mod

SIM_SCOPES = ("draws", "policy_stream", "completion")
SIM_MODULE = "_sim_batch_jit"
NO_SPAN = "no program span"
ROOT_SELF = "recover (self)"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_LOC = re.compile(r'loc\("([^"]*)"')


# -- pure pieces ------------------------------------------------------------

def anchor_offsets(host: List[Tuple[str, float, float]],
                   traced: List[Tuple[str, int, int]]) -> List[float]:
    """Offsets (ns) from ``perf_counter`` seconds to the trace's clock, one
    per harness span found in both lists.  Spans are paired by name, in
    order of start; a name whose counts differ between the two is left
    out."""
    by_host: Dict[str, list] = {}
    by_trace: Dict[str, list] = {}
    for n, a, _ in sorted(host, key=lambda s: s[1]):
        by_host.setdefault(n, []).append(a)
    for n, a, _ in sorted(traced, key=lambda s: s[1]):
        by_trace.setdefault(n, []).append(a)
    out = []
    for n, starts in by_host.items():
        if len(by_trace.get(n, ())) == len(starts):
            out.extend(b - a * 1e9 for a, b in zip(starts, by_trace[n]))
    return out


def scope_of(op_name: str, scopes=SIM_SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path; a path part
    counts with its transform wrappers taken off (``vmap(draws)``)."""
    hit = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            hit = part
    return hit


def scope_map(hlo_text: str, scopes=SIM_SCOPES) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of the HLO text
    whose ``op_name`` metadata lies under one of ``scopes``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(m.group(2))
        s = scope_of(op.group(1), scopes) if op else None
        if s is not None:
            out[m.group(1)] = s
    return out


def union_ns(intervals) -> int:
    """Length of the union of (a, b) intervals."""
    tot, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            tot += b - a
            end = b
        elif b > end:
            tot += b - end
            end = b
    return tot


def outermost(ops: List[Tuple[str, int, int]]):
    """Split (name, start, duration) events into the outermost ones, each
    as (name, a, b) with the events nested inside it: a ``while`` and the
    operations of its body, or a fusion and what it calls."""
    tops = []
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        if tops and s < tops[-1][2]:
            tops[-1][3].append((name, s, s + d))
            tops[-1][2] = max(tops[-1][2], s + d)
        else:
            tops.append([name, s, s + d, []])
    return tops


def split_by_scope(ops, modules, scope_by_name: Dict[str, str],
                   w0: int, w1: int):
    """Device time inside [w0, w1] of the operations that ran inside the
    given module events, by the scope of each outermost operation; events
    nested in an outermost one count with it, once.  Returns
    ({scope or None: ns}, {scope or None: {op name: ns}}) with the
    per-operation sums of every event, nested ones included."""
    mods = sorted((s, s + d) for _, s, d in modules if s + d > w0 and s < w1)
    starts = [a for a, _ in mods]

    def in_module(s):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and s < mods[i][1]

    inside = [e for e in ops if in_module(e[1])]
    spans: Dict[Optional[str], list] = {}
    per_op: Dict[Optional[str], Dict[str, int]] = {}
    for name, a, b, nested in outermost(inside):
        scope = scope_by_name.get(name)
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        spans.setdefault(scope, []).append((a, b))
        ops_of = per_op.setdefault(scope, {})
        for n, x, y in [(name, a, b)] + nested:
            ops_of[n] = ops_of.get(n, 0) + max(0, min(y, w1) - max(x, w0))
    return ({s: union_ns(iv) for s, iv in spans.items()}, per_op)


def idle_by_span(gaps: List[Tuple[int, int]], spans) -> Dict[str, int]:
    """Idle time of ``gaps`` (a, b) by the innermost span covering it.
    ``spans`` are (name, a, b, depth) on the gaps' clock; idle time under
    no span goes to ``NO_SPAN``."""
    out: Dict[str, int] = {}
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0)
    for ga, gb in gaps:
        # The spans that overlap the gap start before its end, and no
        # earlier than the longest span before its start.
        lo = bisect.bisect_left(starts, ga - longest)
        cand = [s for s in spans[lo:bisect.bisect_left(starts, gb)]
                if s[2] > ga]
        cuts = sorted({ga, gb} | {t for s in cand for t in s[1:3]
                                  if ga < t < gb})
        for a, b in zip(cuts, cuts[1:]):
            over = [s for s in cand if s[1] <= a and b <= s[2]]
            label = max(over, key=lambda s: s[3])[0] if over else NO_SPAN
            out[label] = out.get(label, 0) + (b - a)
    return out


# -- reading a run ----------------------------------------------------------

def _cached(ctx, key, make):
    """``make()`` once per run's context."""
    if key not in ctx.__dict__:
        ctx.__dict__[key] = make()
    return ctx.__dict__[key]


def harness_window(ctx) -> Tuple[float, float]:
    """``perf_counter`` bounds of the harness's ``window`` span."""
    _, t0, t1 = [r for r in ctx.spans.records if r[0] == "window"][-1]
    return t0, t1


def program_records(ctx):
    """The program's spans and counts inside the window, or ``None`` where
    the program has no recorder."""
    try:
        from repro.utils import tracing
    except ImportError:
        return None
    return tracing.records(*harness_window(ctx))


def clock_offset(ctx) -> Optional[float]:
    """ns to add to ``perf_counter`` ns to land on the trace's clock."""
    def make():
        t0, t1 = harness_window(ctx)
        host = [r for r in ctx.spans.records if t0 <= r[1] and r[2] <= t1]
        offs = anchor_offsets(host, ctx.trace.trace.spans)
        if not offs:
            ctx.log("program_trace: no harness span pairs with the trace")
            return None
        ctx.log(f"program_trace: clock offset from {len(offs)} anchors, "
                f"spread (max - min) {(max(offs) - min(offs)) / 1e3:.3f} us")
        return statistics.median(offs)
    return _cached(ctx, "_pt_offset", make)


def recover_requests(ctx):
    """[(root span, [child spans])] of the program's ``recover`` calls in
    the window, or ``None`` where there are none."""
    def make():
        recs = program_records(ctx)
        if recs is None:
            return None
        spans = [r for r in recs if hasattr(r, "t1")]
        roots = {r.id: (r, []) for r in spans
                 if r.name == "recover" and r.parent is None}
        for r in spans:
            if r.root in roots and r.id != r.root:
                roots[r.root][1].append(r)
        return list(roots.values()) or None
    return _cached(ctx, "_pt_recover", make)


def child_ms_per_request(ctx, names) -> Optional[float]:
    """Mean per ``recover`` call of the summed duration of its child
    spans called ``names``, in ms."""
    reqs = recover_requests(ctx)
    if not reqs:
        return None
    tot = sum(c.t1 - c.t0 for _, kids in reqs for c in kids
              if c.name in names)
    return 1e3 * tot / len(reqs)


def recover_idle(ctx) -> Optional[float]:
    """Mean per request and chip of the device idle time inside the
    program's ``recover`` spans (ns); logs the window's idle time by the
    innermost program span it fell in."""
    def make():
        reqs = recover_requests(ctx)
        off = clock_offset(ctx) if reqs else None
        if off is None:
            return None
        spans = []
        for root, kids in reqs:
            spans.append((ROOT_SELF, root.t0 * 1e9 + off,
                          root.t1 * 1e9 + off, 0))
            spans.extend((k.name, k.t0 * 1e9 + off, k.t1 * 1e9 + off, 1)
                         for k in kids)
        red = ctx.trace
        total: Dict[str, int] = {}
        for dev in ctx.devices:
            ops = red.trace.devices[dev].get(trace_mod.OPS_LINE, [])
            gaps = trace_mod.idle_gaps(ops, red.w0, red.w1)
            for k, v in idle_by_span(gaps, spans).items():
                total[k] = total.get(k, 0) + v
        inside = sum(v for k, v in total.items() if k != NO_SPAN)
        n = len(reqs) * len(ctx.devices)
        ctx.log("recover idle by program span, ms per request and chip: "
                + ", ".join(f"{k} {v / 1e6 / n:.4f}" for k, v in
                            sorted(total.items(), key=lambda kv: -kv[1])))
        named = inside - total.get(ROOT_SELF, 0)
        if inside:
            ctx.log(f"recover idle in named child spans: "
                    f"{100.0 * named / inside:.2f}%")
        return inside / n
    return _cached(ctx, "_pt_recover_idle", make)


def count_events(ctx, name) -> Optional[int]:
    recs = program_records(ctx)
    if recs is None:
        return None
    return sum(r.n for r in recs if not hasattr(r, "t1") and r.name == name)


def sim_scope_map(ctx) -> Optional[Dict[str, str]]:
    """Scope of each instruction of the cell's batch program, compiled with
    the generator's arguments at each horizon the window ran; ``None``
    where the program lacks any of the simulator's scopes."""
    def make():
        import jax
        import jax.numpy as jnp

        from chipbench import run as run_mod
        from chipbench.generators.sim import scenario
        from repro.core import engine, policies

        spec = run_mod.resolve(run_mod.load_benchmark(), ctx.workload)
        config, traffic = spec["config"], spec["traffic"]
        cfg = scenario(config, traffic)
        policy = policies.get(traffic["policy"])
        keys = jax.ShapeDtypeStruct((int(traffic["reps_per_batch"]), 2),
                                    jnp.uint32)
        out = {}
        for M in ctx.work["horizons"]:
            lower = functools.partial(engine._sim_batch_jit.lower, keys, cfg,
                                      int(config["R"]), int(M), policy)
            lowered = lower()
            found = {scope_of(loc) for loc in _LOC.findall(
                lowered.as_text(debug_info=True))}
            if not set(SIM_SCOPES) <= found:
                ctx.log(f"program_trace: the batch program has no scopes "
                        f"{sorted(set(SIM_SCOPES) - found)}; the simulator "
                        f"readers report nothing")
                return None
            t = time.perf_counter()
            smap = scope_map(lowered.compile().as_text())
            if not set(SIM_SCOPES) <= set(smap.values()):
                # The persistent cache's key leaves the op_name metadata
                # out, so the executable that ran can come from a program
                # compiled before the scopes existed (an older checkout
                # sharing the cache).  Its instruction names are those of
                # a compile with the scopes: key one on the metadata too,
                # past the in-memory caches.
                smap = _compile_with_metadata(lower)
            ctx.log(f"program_trace: batch program at M={M} compiled (or "
                    f"loaded) in {time.perf_counter() - t:.3f} s")
            for k, v in smap.items():
                out.setdefault(k, v)
        return out
    return _cached(ctx, "_pt_sim_map", make)


def _compile_with_metadata(lower) -> Dict[str, str]:
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    jax.clear_caches()
    try:
        return scope_map(lower().compile().as_text())
    finally:
        jax.config.update(flag, before)


def sim_scope_ns(ctx) -> Optional[Dict[Optional[str], int]]:
    """Device ns of the batch program inside the window by scope (``None``
    key: unscoped); logs the five longest operations of each scope."""
    def make():
        smap = sim_scope_map(ctx)
        if smap is None:
            return None
        red = ctx.trace
        dev = ctx.devices[0]
        lines = red.trace.devices[dev]
        modules = [e for e in lines.get(trace_mod.MODULES_LINE, [])
                   if SIM_MODULE in e[0]]
        by_scope, per_op = split_by_scope(lines.get(trace_mod.OPS_LINE, []),
                                          modules, smap, red.w0, red.w1)
        n_mod, mod_ns = red.module(SIM_MODULE, dev)
        for scope in list(SIM_SCOPES) + [None]:
            top = sorted(per_op.get(scope, {}).items(),
                         key=lambda kv: -kv[1])[:5]
            ctx.log(f"scope {scope or 'unscoped'}: "
                    f"{by_scope.get(scope, 0) / 1e9:.6f} s; longest ops "
                    + ", ".join(f"{n} {v / 1e9:.6f}" for n, v in top))
        scoped = sum(v for k, v in by_scope.items() if k is not None)
        if mod_ns:
            ctx.log(f"scoped ops cover {100.0 * scoped / mod_ns:.2f}% of "
                    f"{n_mod} {SIM_MODULE} runs ({mod_ns / 1e9:.6f} s); "
                    f"unscoped remainder {(mod_ns - scoped) / 1e9:.6f} s")
        return by_scope
    return _cached(ctx, "_pt_sim_ns", make)


def sim_scope_ms_per_krep(ctx, scope) -> Optional[float]:
    by_scope = sim_scope_ns(ctx)
    reps = ctx.result["attempted"]
    if by_scope is None or not reps:
        return None
    return by_scope.get(scope, 0) / 1e6 / (reps / 1000.0)
