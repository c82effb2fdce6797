"""A percentile, in ms, over the spans named ``span`` of their duration
minus that of their children named in ``minus`` (children by ``parent_id``):
the time a step spent outside the phases taken out."""

from benchmarks.harness import stats
from benchmarks.readers import span_terms


def read(ctx, *, span: str, minus: list, q: float):
    if not span_terms.window_covered(ctx):
        return None
    spans = span_terms.whole(ctx)  # a whole step has all its children
    taken: dict[str, float] = {}
    for s in spans:
        if s["name"] in minus:
            taken[s["parent_id"]] = taken.get(s["parent_id"], 0.0) + s["duration_s"]
    own = [max(0.0, s["duration_s"] - taken.get(s["span_id"], 0.0)) * 1e3
           for s in spans if s["name"] == span]
    return stats.percentile(own, q) if own else None
