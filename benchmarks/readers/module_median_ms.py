"""Median device duration, in ms, of the executions of the XLA modules whose
name matches ``pattern``, over the traced sub-window and every chip."""

from benchmarks.harness import reduce_trace


def read(ctx, *, pattern: str):
    if ctx.trace is None:
        return None
    modules = [ev for dev in ctx.trace["devices"].values() for ev in dev["modules"]]
    return reduce_trace.module_median_ms(modules, pattern)
