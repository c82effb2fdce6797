"""Mean or maximum of a series the harness samples every 50 ms inside the
window of a traced run (``active_slots``, ``kv_used_share``, ``in_flight``)."""


def read(ctx, *, series: str, stat: str):
    values = [s[series] for s in ctx.samples]
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "max":
        return max(values)
    raise ValueError(f"unknown stat {stat!r}")
