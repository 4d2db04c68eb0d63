"""Self time of ``engine.stage`` and ``alloc.pack`` (building a
dispatch's inputs and copying them to the device) per dispatch."""
from _common import self_ms_per_dispatch


def read(ctx):
    return self_ms_per_dispatch(ctx, ("engine.stage", "alloc.pack"))
