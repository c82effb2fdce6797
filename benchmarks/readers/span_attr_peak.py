"""The largest value one term takes over the program's spans that began
and ended inside the window (terms: ``span_terms.py``)."""

from benchmarks.readers import span_terms


def read(ctx, *, term: dict):
    if not span_terms.window_covered(ctx):
        return None
    found = span_terms.values(ctx, term)
    return max(found) if found else None
