"""Profiler trace of a measured window, and its reduction to numbers.

``capture`` records the window with JAX's profiler (Python tracer off)
into a scratch directory under ``$TMPDIR`` and reads the ``.xplane.pb``
back with ``jax.profiler.ProfileData``.  Only what the reduction needs
is kept, as plain tuples ``(plane, line, name, start_ns, duration_ns)``:
the device planes' op and module lines, and the host spans that the
driver itself opens (``window``, ``step:<kind>``, ``pace_wait``,
``episode_reset``).

``Reduction`` turns those events into the quantities the per-layer
metric readers use.  Busy time on a device is the union of the
intervals in which an XLA op ran there, clipped to the ``window`` span;
idle is the rest of the window.  A step's host time is its span minus
the device-busy union inside it.  The fused step and the kernel are
found by name; a name that is not there is reported missing, never as
zero.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_SPANS = ("window", "step:", "pace_wait", "episode_reset")
# The fused maintain-and-decide dispatch (repro.core.allocator._state_step;
# an engine's first dispatch, which creates the device state, runs
# _state_dispatch) and, on a federation, the re-pad dispatch's
# sequential core (_core_dispatch, a jit of alloc_scan).
FUSED_STEP = re.compile(r"_state_step|_state_dispatch|jit_alloc_scan")
# The Pallas sequential core: its custom call carries the kernel's name.
KERNEL = re.compile(r"_scan_kernel|alloc_scan_pallas")


@contextlib.contextmanager
def capture(events: List[Event]):
    """Trace the enclosed block; fills ``events`` when it closes."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        events.extend(load(files[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load(path: str) -> List[Event]:
    """The events the reduction reads, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if host and not name.startswith(HOST_SPANS):
                    continue
                out.append((plane.name, line.name, name, int(ev.start_ns),
                            int(ev.duration_ns)))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted ``[start, end)`` intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` covered by merged intervals."""
    import bisect

    total = 0
    i = max(bisect.bisect_right(merged, (lo, lo)) - 1, 0)
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0, min(e, hi) - max(s, lo))
        i += 1
    return total


def _base(name: str) -> str:
    """An op's name: the HLO instruction name (the trace gives the whole
    instruction text) without its ``%`` and instance suffix."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


class Reduction:
    """Numbers from the events of one traced window."""

    def __init__(self, events: Sequence[Event]):
        windows = [(s, s + d) for _, _, n, s, d in events if n == "window"]
        if len(windows) != 1:
            raise ValueError(f"expected one 'window' span, found "
                             f"{len(windows)}")
        self.lo, self.hi = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        ops: Dict[int, list] = defaultdict(list)
        modules: Dict[int, list] = defaultdict(list)
        self.steps: List[Tuple[str, int, int]] = []
        self.host: List[Tuple[str, int, int]] = []
        for plane, line, name, s, d in events:
            m = DEVICE_PLANE.match(plane)
            if m:
                dev = int(m.group(1))
                if s + d <= self.lo or s >= self.hi:
                    continue
                (ops if line == OP_LINE else modules)[dev].append(
                    (name, s, s + d))
            elif name != "window" and s < self.hi and s + d > self.lo:
                self.host.append((name, s, s + d))
                if name.startswith("step:"):
                    self.steps.append((name, s, s + d))
        self.devices = sorted(set(ops) | set(modules))
        self.ops = ops
        self.modules = modules
        self.busy = {dev: union(clip([(s, e) for _, s, e in ops[dev]],
                                     self.lo, self.hi))
                     for dev in sorted(ops)}

    # --------------------------------------------------------- device
    def busy_s(self, dev: int = 0) -> Optional[float]:
        if dev not in self.busy:
            return None
        return sum(e - s for s, e in self.busy[dev]) * 1e-9

    def idle_share(self, dev: int = 0) -> Optional[float]:
        busy = self.busy_s(dev)
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    def fused_steps(self, dev: int = 0) -> List[Tuple[str, int, int]]:
        return [m for m in self.modules.get(dev, ())
                if FUSED_STEP.search(m[0])]

    def kernel_events(self, dev: int = 0) -> List[Tuple[str, int, int]]:
        return [o for o in self.ops.get(dev, ()) if KERNEL.search(o[0])]

    # ----------------------------------------------------------- host
    def host_ms_per_step(self, dev: int = 0) -> Optional[float]:
        """Mean over step spans of span minus device busy inside it."""
        if not self.steps or dev not in self.busy:
            return None
        busy = self.busy[dev]
        total = 0
        for _, s, e in self.steps:
            s, e = max(s, self.lo), min(e, self.hi)
            total += (e - s) - covered(busy, s, e)
        return total / len(self.steps) * 1e-6

    # ------------------------------------------------------ breakdown
    def top_ops(self, dev: int = 0, n: int = 10) -> List[list]:
        tot: Dict[str, int] = defaultdict(int)
        for name, s, e in self.ops.get(dev, ()):
            tot[_base(name)] += min(e, self.hi) - max(s, self.lo)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, dev: int = 0, n: int = 10) -> List[list]:
        """Idle time of the device, summed by what the host was doing."""
        busy = self.busy.get(dev, [])
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        # Host spans do not overlap one another (the driver opens them
        # one after the other), so one forward sweep attributes the gaps.
        spans = sorted(self.host, key=lambda h: h[1])
        merged = union((s, e) for _, s, e in spans)
        by: Dict[str, int] = defaultdict(int)
        j = 0
        for gs, ge in gaps:
            while j < len(spans) and spans[j][2] <= gs:
                j += 1
            k = j
            while k < len(spans) and spans[k][1] < ge:
                name, s, e = spans[k]
                by[name] += max(0, min(e, ge) - max(s, gs))
                k += 1
            by["harness"] += (ge - gs) - covered(merged, gs, ge)
        top = sorted(((k, v) for k, v in by.items() if v > 0),
                     key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]
