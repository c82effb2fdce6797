"""Sum of one term over sum of another, over the program's spans that began
and ended inside the window (terms: ``span_terms.py``).  None when no span feeds the
denominator, or when the ring no longer holds the window."""

from benchmarks.readers import span_terms


def read(ctx, *, numerator: dict, denominator: dict):
    if not span_terms.window_covered(ctx):
        return None
    below = span_terms.values(ctx, denominator)
    total = sum(below)
    if not below or total <= 0:
        return None
    return sum(span_terms.values(ctx, numerator)) / total
