"""Device time of the coded expert layer's programs by named scope.

The layer (``repro.core.coded_moe``) runs four programs a request
(``jit_moe_route``, ``jit_moe_gate_up``, ``jit_moe_down``,
``jit_moe_combine``, one compiled variant per column buffer size) and tags
their operations with the scopes ``moe.route``, ``moe.swiglu`` and
``moe.combine``.  The generator hands the compiled variants' HLO text to
the readers (``result["programs"]``); each instruction's scope comes from
its ``op_name`` metadata, and the device time of each program's
operations inside the window is split by scope (outermost operations
only, so a fusion and what it calls count once).  Where the result holds
no programs, the readers report nothing.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench import program_trace as pt
from chipbench import trace as trace_mod

SCOPES = ("moe.route", "moe.swiglu", "moe.combine")


def scope_ns(ctx) -> Optional[Dict[Optional[str], int]]:
    """Device ns inside the window by scope (``None``: unscoped), summed
    over the layer's programs on the cell's chip."""
    def make():
        programs = ctx.result.get("programs")
        if not programs:
            return None
        red = ctx.trace
        lines = red.trace.devices[ctx.devices[0]]
        total: Dict[Optional[str], int] = {}
        for module, texts in sorted(programs.items()):
            smap, clash = {}, 0
            for text in texts:
                for name, scope in pt.scope_map(text, SCOPES).items():
                    clash += smap.setdefault(name, scope) != scope
            runs = [e for e in lines.get(trace_mod.MODULES_LINE, [])
                    if module in e[0]]
            by_scope, per_op = pt.split_by_scope(
                lines.get(trace_mod.OPS_LINE, []), runs, smap, red.w0, red.w1)
            for k, v in by_scope.items():
                total[k] = total.get(k, 0) + v
            ctx.log(f"{module}: {len(runs)} runs, {len(texts)} variants, "
                    f"{clash} scope clashes; by scope " + ", ".join(
                        f"{k or 'unscoped'} {v / 1e9:.6f} s"
                        for k, v in sorted(by_scope.items(),
                                           key=lambda kv: -kv[1])))
        return total
    return pt._cached(ctx, "_moe_scope_ns", make)


def ms_per_product(ctx, scopes) -> Optional[float]:
    ns = scope_ns(ctx)
    n = ctx.result["attempted"]
    if ns is None or not n:
        return None
    return sum(ns.get(s, 0) for s in scopes) / 1e6 / n


def span_ms_per_product(ctx, name) -> Optional[float]:
    """Summed duration of the program's ``name`` spans in the window, per
    product, in ms."""
    recs = pt.program_records(ctx)
    n = ctx.result["attempted"]
    if recs is None or not n:
        return None
    spans = [r for r in recs if hasattr(r, "t1") and r.name == name]
    if not spans:
        return None
    by = {}
    for r in spans:
        key = r.attrs.get("product", "")
        by[key] = by.get(key, 0.0) + (r.t1 - r.t0)
    ctx.log(f"program spans {name}: {len(spans)} in the window, ms per "
            "product " + ", ".join(f"{k or name} {1e3 * v / n:.4f}"
                                   for k, v in sorted(by.items())))
    return 1e3 * sum(by.values()) / n


def recover_child_ms_per_product(ctx, names) -> Optional[float]:
    """Summed duration of the child spans called ``names`` of the program's
    ``recover`` calls in the window (two a product), per product, in ms."""
    reqs = pt.recover_requests(ctx)
    n = ctx.result["attempted"]
    if not reqs or not n:
        return None
    return 1e3 * sum(c.t1 - c.t0 for _, kids in reqs for c in kids
                     if c.name in names) / n


def recover_calls_per_product(ctx) -> Optional[float]:
    reqs = pt.recover_requests(ctx)
    n = ctx.result["attempted"]
    return len(reqs) / n if reqs and n else None
