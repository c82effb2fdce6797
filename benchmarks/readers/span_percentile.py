"""A percentile, in ms, of the durations of the program's spans of one name
that began inside the window."""

from benchmarks.harness import stats


def read(ctx, *, span: str, q: float):
    values = [s["duration_s"] * 1e3 for s in ctx.spans if s["name"] == span]
    return stats.percentile(values, q) if values else None
