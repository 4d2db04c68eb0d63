"""Self time of ``alloc.state`` per build: laying out the whole
cluster's tiles and copying them to the device, once per engine.  None
unless the window has one span per build that
``EngineMetrics.state_builds`` counts."""


def read(ctx):
    n = ctx.counters.get("state_builds", 0)
    if not n or ctx.trace.count("alloc.state") != n:
        return None
    return ctx.trace.self_ms(("alloc.state",)) / n
