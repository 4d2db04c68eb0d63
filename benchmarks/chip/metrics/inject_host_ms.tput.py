"""Self time of ``engine.inject`` per span: one workflow injected."""


def read(ctx):
    n = ctx.trace.count("engine.inject")
    return ctx.trace.self_ms(("engine.inject",)) / n if n else None
