"""One of the counts the harness takes around the window."""


def read(ctx, *, name: str):
    return ctx.counters.get(name)
