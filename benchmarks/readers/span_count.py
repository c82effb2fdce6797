"""How many spans of the given names began inside the window.  0 is a
reading; None only when the program recorded no span at all (tracing off)
or the ring no longer holds the window."""

from benchmarks.readers import span_terms


def read(ctx, *, spans: list):
    if not ctx.spans or not span_terms.window_covered(ctx):
        return None
    return float(sum(1 for s in span_terms.whole(ctx) if s["name"] in spans))
