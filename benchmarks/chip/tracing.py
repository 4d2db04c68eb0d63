"""Profiler trace of a measured window, and its reduction to numbers.

``capture`` records the window with JAX's profiler (Python tracer off)
into a scratch directory under ``$TMPDIR``, opens a ``gc`` span over
each garbage collection inside it, and reads the ``.xplane.pb`` back
with ``jax.profiler.ProfileData``.  Only what the reduction needs is
kept, as plain tuples ``(plane, line, name, start_ns, duration_ns)``:
the device planes' op and module lines; the host spans that the driver
itself opens (``window``, ``step:<kind>``, ``pace_wait``,
``episode_reset``); and, with their metadata as a sixth element, the
program's own spans, named ``<layer>.<name>`` (``engine.apply``,
``alloc.wait``, ...), and the ``gc`` spans.

``Reduction`` turns those events into the quantities the per-layer
metric readers use.  Busy time on a device is the union of the
intervals in which an XLA op ran there, clipped to the ``window`` span;
idle is the rest of the window.  A step's host time is its span minus
the device-busy union inside it.  A program span's self time is its
duration less its direct program children (a ``gc`` span inside it is
not subtracted), and the device's idle time is named by the innermost
span open over it.  The fused step and the kernel are found by name; a
name that is not there is reported missing, never as zero.
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import glob
import os
import re
import shutil
import statistics
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# (plane, line, name, start_ns, duration_ns), and the span's metadata
# as a sixth element on the program's spans.
Event = tuple
# (name, start_ns, end_ns, metadata), clipped to the window.
Span = Tuple[str, int, int, dict]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_SPANS = ("window", "step:", "pace_wait", "episode_reset")
# The program's span convention, ``<layer>.<name>``: any span a later
# change opens is read without an edit here.
PROGRAM_SPAN = re.compile(r"^[a-z]+\.[a-z_]+$")
GC = "gc"
# The fused maintain-and-decide dispatch (repro.core.allocator._state_step;
# an engine's first dispatch, which creates the device state, runs
# _state_dispatch) and, on a federation, the re-pad dispatch's
# sequential core (_core_dispatch, a jit of alloc_scan).
FUSED_STEP = re.compile(r"_state_step|_state_dispatch|jit_alloc_scan")
# The Pallas sequential core, matched against the op's own HLO name (an
# op whose operands name the kernel's output is another op).
KERNEL = re.compile(r"_scan_kernel|alloc_scan_pallas")


class GcSpans:
    """Opens a ``gc`` trace span over each garbage collection."""

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._open = None

    def __call__(self, phase, info):
        if phase == "start":
            self._open = self._annotation(GC)
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


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
            with GcSpans():
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
                event = (plane.name, line.name, name, int(ev.start_ns),
                         int(ev.duration_ns))
                if host and (name == GC or PROGRAM_SPAN.match(name)):
                    event += (dict(ev.stats),)
                elif host and not name.startswith(HOST_SPANS):
                    continue
                out.append(event)
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


def _attribute(pieces, named, by) -> List[Tuple[int, int]]:
    """Add to ``by`` the overlap of each sorted, disjoint ``(start,
    end)`` piece with the sorted, disjoint ``(start, end, name)``
    intervals, by name; returns the parts of the pieces they leave."""
    rest: List[Tuple[int, int]] = []
    j = 0
    for s, e in pieces:
        while j < len(named) and named[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(named) and named[k][0] < e:
            a, b = max(named[k][0], t), min(named[k][1], e)
            if a > t:
                rest.append((t, a))
            by[named[k][2]] += b - a
            t = b
            k += 1
        if t < e:
            rest.append((t, e))
    return rest


class Reduction:
    """Numbers from the events of one traced window."""

    def __init__(self, events: Sequence[Event]):
        windows = [(e[3], e[3] + e[4]) for e in events if e[2] == "window"]
        if len(windows) != 1:
            raise ValueError(f"expected one 'window' span, found "
                             f"{len(windows)}")
        self.lo, self.hi = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        ops: Dict[int, list] = defaultdict(list)
        modules: Dict[int, list] = defaultdict(list)
        self.steps: List[Tuple[str, int, int]] = []
        self.host: List[Tuple[str, int, int]] = []
        program: List[Span] = []
        for plane, line, name, s, d, *meta in events:
            m = DEVICE_PLANE.match(plane)
            if m:
                dev = int(m.group(1))
                if s + d <= self.lo or s >= self.hi:
                    continue
                (ops if line == OP_LINE else modules)[dev].append(
                    (name, s, s + d))
            elif name == "window" or s >= self.hi or s + d <= self.lo:
                continue
            elif name == GC or PROGRAM_SPAN.match(name):
                program.append((name, max(s, self.lo), min(s + d, self.hi),
                                meta[0] if meta else {}))
            else:
                self.host.append((name, s, s + d))
                if name.startswith("step:"):
                    self.steps.append((name, s, s + d))
        self.devices = sorted(set(ops) | set(modules))
        self.ops = ops
        self.modules = modules
        self.busy = {dev: union(clip([(s, e) for _, s, e in ops[dev]],
                                     self.lo, self.hi))
                     for dev in sorted(ops)}
        # The program's spans and ``gc``, outer first on a tie; they nest,
        # where the harness's spans follow one another.
        self.program = sorted(program, key=lambda sp: (sp[1], -sp[2]))
        # Self time: a program span less its direct program children,
        # found by one sweep; gc spans are left inside their parent, and
        # their time is summed by that parent's name.
        self.self_ns: List[int] = []
        self.gc_in: Dict[str, int] = defaultdict(int)
        stack: List[int] = []
        for i, (name, s, e, _) in enumerate(self.program):
            self.self_ns.append(e - s)
            while stack and self.program[stack[-1]][2] <= s:
                stack.pop()
            if name == GC:
                self.gc_in[self.program[stack[-1]][0] if stack
                           else "none"] += e - s
                continue
            if stack:
                self.self_ns[stack[-1]] -= e - s
            stack.append(i)

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
        return [o for o in self.ops.get(dev, ())
                if KERNEL.search(_base(o[0]))]

    def gaps(self, dev: int = 0) -> List[Tuple[int, int]]:
        """The window's intervals in which the device ran no op."""
        out, t = [], self.lo
        for s, e in self.busy.get(dev, []):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.hi:
            out.append((t, self.hi))
        return out

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

    # -------------------------------------------------- program spans
    def count(self, name: str) -> int:
        return sum(1 for sp in self.program if sp[0] == name)

    def self_ms(self, names: Sequence[str]) -> float:
        """Summed self time of the named spans, clipped to the window."""
        return sum(t for sp, t in zip(self.program, self.self_ns)
                   if sp[0] in names) * 1e-6

    def meta(self, name: str, key: str) -> List:
        return [sp[3][key] for sp in self.program
                if sp[0] == name and key in sp[3]]

    def innermost(self) -> List[Tuple[int, int, str]]:
        """Disjoint intervals, each named by the innermost program or
        ``gc`` span open over it (the one that opened last)."""
        edges = sorted({t for _, s, e, _ in self.program for t in (s, e)})
        out: List[Tuple[int, int, str]] = []
        open_: List[Span] = []
        j = 0
        for a, b in zip(edges, edges[1:]):
            while j < len(self.program) and self.program[j][1] <= a:
                open_.append(self.program[j])
                j += 1
            open_ = [sp for sp in open_ if sp[2] > a]
            if open_:
                name = max(open_, key=lambda sp: sp[1])[0]
                if out and out[-1][2] == name and out[-1][1] == a:
                    out[-1] = (out[-1][0], b, name)
                else:
                    out.append((a, b, name))
        return out

    def idle_in_steps(self, dev: int = 0) -> Tuple[int, int]:
        """Idle time (ns) inside ``step:`` spans, and the part of it in
        which no program span or ``gc`` was open."""
        steps = union((s, e) for _, s, e in self.steps)
        gaps = self.gaps(dev)
        bare = _attribute(gaps, self.innermost(), defaultdict(int))
        return (sum(covered(steps, s, e) for s, e in gaps),
                sum(covered(steps, s, e) for s, e in bare))

    def round_trip(self, dev: int = 0) -> Optional[Dict[str, float]]:
        """Medians (ms) over dispatches: from ``alloc.launch``'s return
        to the fused step's start on the device, the step's module on
        the device, and from its end to ``alloc.wait``'s return.  None
        unless launches, waits and modules pair one for one."""
        launches = [sp for sp in self.program if sp[0] == "alloc.launch"]
        waits = [sp for sp in self.program if sp[0] == "alloc.wait"]
        modules = sorted(self.fused_steps(dev), key=lambda m: m[1])
        if not modules or not len(launches) == len(waits) == len(modules):
            return None
        med = statistics.median
        return {
            "queue_ms": med(m[1] - sp[2] for sp, m in zip(launches, modules))
            * 1e-6,
            "device_ms": med(m[2] - m[1] for m in modules) * 1e-6,
            "return_ms": med(sp[2] - m[2] for sp, m in zip(waits, modules))
            * 1e-6,
        }

    def step_split(self) -> Dict[str, dict]:
        """Per kind of step: its spans' time, the part that top-level
        program spans cover, and the rest (ms)."""
        program = union((s, e) for n, s, e, _ in self.program if n != GC)
        out: Dict[str, dict] = {}
        for name, s, e in self.steps:
            s, e = max(s, self.lo), min(e, self.hi)
            row = out.setdefault(name, {"count": 0, "span_ms": 0.0,
                                        "program_ms": 0.0})
            row["count"] += 1
            row["span_ms"] += (e - s) * 1e-6
            row["program_ms"] += covered(program, s, e) * 1e-6
        for row in out.values():
            row["rest_ms"] = row["span_ms"] - row["program_ms"]
        return out

    # ------------------------------------------------------ breakdown
    def top_ops(self, dev: int = 0, n: int = 10) -> List[list]:
        tot: Dict[str, int] = defaultdict(int)
        for name, s, e in self.ops.get(dev, ()):
            tot[_base(name)] += min(e, self.hi) - max(s, self.lo)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, dev: int = 0, n: int = 10) -> List[list]:
        """Idle time of the device, summed by the innermost span open: a
        program span or ``gc``, else the harness's span, else
        ``harness``."""
        by: Dict[str, int] = defaultdict(int)
        rest = _attribute(self.gaps(dev), self.innermost(), by)
        # The harness's spans do not overlap one another.
        harness = sorted((s, e, name) for name, s, e in self.host)
        rest = _attribute(rest, harness, by)
        by["harness"] += sum(e - s for s, e in rest)
        top = sorted(((k, v) for k, v in by.items() if v > 0),
                     key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]
