"""What a per-layer metric reader is handed.

Each file ``metrics/<metric name>.py`` defines ``read(ctx)`` and returns
a number, or ``None`` when the run holds nothing for it to read (the
harness then leaves the metric out of the result line).  ``ctx`` is a
:class:`Context`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class Context:
    trace: object  # tracing.Reduction of the traced window
    dispatch_rows: List[int]  # rows of every dispatch inside the window
    nodes: int  # nodes of one cluster of the configuration
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
