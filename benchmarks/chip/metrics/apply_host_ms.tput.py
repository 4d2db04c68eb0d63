"""Self time of ``engine.apply`` (the per-row result loop) per dispatch."""
from _common import self_ms_per_dispatch


def read(ctx):
    return self_ms_per_dispatch(ctx, ("engine.apply",))
