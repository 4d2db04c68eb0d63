"""What a per-layer metric reader is handed.

Each file ``metrics/<metric name>.py`` defines ``read(ctx)`` and returns
a number, or ``None`` when the run holds nothing for it to read (the
harness then leaves the metric out of the result line).  ``ctx`` is a
:class:`Context`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class Context:
    trace: object  # tracing.Reduction of the traced window
    dispatch_rows: List[int]  # rows of every dispatch inside the window
    # The window's change in every integer field of ``EngineMetrics``
    # (``num_dispatches``, ``staged_bytes``, ...), summed over episodes.
    counters: Dict[str, int]
    nodes: int  # padded nodes a kernel call walks (kernel_cost.kernel_nodes)
    engine: dict  # the configuration's engine block
    peaks: Optional[dict]  # peaks.peaks(device_kind)


def host_ms(ctx: Context):
    return ctx.trace.host_ms_per_step(0)


def dispatch_device_ms(ctx: Context):
    busy = ctx.trace.busy_s(0)
    if busy is None or not ctx.dispatch_rows:
        return None
    return busy / len(ctx.dispatch_rows) * 1e3


def idle_pct(ctx: Context):
    idle = ctx.trace.idle_share(0)
    return None if idle is None else idle * 100.0


def self_ms_per_dispatch(ctx: Context, names: Sequence[str]):
    """Summed self time of the named program spans per dispatch in the
    window; None unless each name has one span per dispatch."""
    n = ctx.counters.get("num_dispatches", 0)
    if not n or any(ctx.trace.count(name) != n for name in names):
        return None
    return ctx.trace.self_ms(names) / n
