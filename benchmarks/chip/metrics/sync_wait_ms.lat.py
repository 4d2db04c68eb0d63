"""Self time of ``alloc.launch`` and ``alloc.wait`` (the jitted call and
the blocking fetch of its decisions) per dispatch."""
from _common import self_ms_per_dispatch


def read(ctx):
    return self_ms_per_dispatch(ctx, ("alloc.launch", "alloc.wait"))
