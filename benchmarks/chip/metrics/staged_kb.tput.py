"""KiB copied host to device per dispatch in the window: the counter
``EngineMetrics.staged_bytes`` over ``num_dispatches``.  None unless
each dispatch has its ``alloc.pack`` span, whose ``bytes`` the counter
sums."""


def read(ctx):
    n = ctx.counters.get("num_dispatches", 0)
    staged = ctx.counters.get("staged_bytes")
    if not n or staged is None or ctx.trace.count("alloc.pack") != n:
        return None
    return staged / n / 1024
