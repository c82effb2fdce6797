"""Shared by the span readers (``span_ratio``, ``span_self_ms``,
``span_attr_peak``, ``span_count``): which spans a term takes, what each is
worth, and whether the program's span ring still holds the whole window.

A *term* is ``{"span": name, "where": {attr: value or [values]}, "under":
parent's name, "value": expr}``.  ``expr`` is ``"duration_s"``, ``"count"``
(1 a span), an attribute's name, or ``[op, expr, expr, ...]`` with ``op`` one
of ``*``, ``+``, ``/``.  A span that lacks an attribute the term names is left
out.

Only whole spans count: the harness hands over the spans that *began* inside
the window, and one that ends after it — the step or the call the window's end
cuts through — would be counted without the children that began too late.
With ``under``, a span counts only as the child (``parent_id``) of a whole
span of that name, so a ratio of children to parents is taken over the same
steps above and below.
"""

from __future__ import annotations

import math
from typing import Optional


def window_covered(ctx) -> bool:
    """False when the program's ring has overwritten spans and the oldest
    one the harness still got starts later than the window's first second:
    a share over what is left would be a share of the window's end only."""
    from k8s_llm_monitor_tpu.observability.tracing import get_tracer

    if getattr(get_tracer(), "overwritten", 0) <= 0 or not ctx.spans:
        return True
    return min(s["start_mono"] for s in ctx.spans) <= ctx.window.t0 + 1.0


def whole(ctx) -> list[dict]:
    """The spans that began and ended inside the window."""
    t1 = ctx.window.t1
    return [s for s in ctx.spans if s["start_mono"] + s["duration_s"] <= t1]


def _matches(span: dict, name: str, where: Optional[dict]) -> bool:
    if span["name"] != name:
        return False
    for attr, want in (where or {}).items():
        wanted = want if isinstance(want, list) else [want]
        if span["attrs"].get(attr) not in wanted:
            return False
    return True


def value_of(span: dict, expr) -> Optional[float]:
    if isinstance(expr, list):
        op, *parts = expr
        values = [value_of(span, p) for p in parts]
        if any(v is None for v in values):
            return None
        if op == "*":
            return math.prod(values)
        if op == "+":
            return sum(values)
        if op == "/" and len(values) == 2:
            return values[0] / values[1] if values[1] else None
        raise ValueError(f"unknown operation {op!r} in {expr!r}")
    if expr == "count":
        return 1.0
    if expr == "duration_s":
        return float(span["duration_s"])
    value = span["attrs"].get(expr)
    return None if value is None else float(value)


def values(ctx, term: dict) -> list[float]:
    """The term's value for every whole span it takes."""
    spans = whole(ctx)
    parents = ({s["span_id"] for s in spans if s["name"] == term["under"]}
               if "under" in term else None)
    out = []
    for span in spans:
        if parents is not None and span["parent_id"] not in parents:
            continue
        if _matches(span, term["span"], term.get("where")):
            value = value_of(span, term.get("value", "count"))
            if value is not None:
                out.append(value)
    return out
