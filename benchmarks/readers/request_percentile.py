"""A percentile over the window's sample of a per-request quantity from
``harness/stats.py`` (``late_ms``, ``ttft_ms``, ``tpot_ms``)."""

from benchmarks.harness import stats


def read(ctx, *, field: str, q: float):
    quantity = getattr(stats, field)
    values = [v for v in (quantity(r) for r in ctx.window.sample) if v is not None]
    return stats.percentile(values, q) if values else None
