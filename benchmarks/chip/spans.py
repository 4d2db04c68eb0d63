#!/usr/bin/env python3
"""Split a traced window's host time by the program's own spans.

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \
        --seconds <s>

The program opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries (README, "Tracing the scheduler"): ``engine.inject``,
``engine.fold``, ``engine.stage``, ``alloc.pack``, ``alloc.launch``,
``alloc.wait`` and ``engine.apply``, the per-dispatch ones tagged with
the engine's dispatch index.  ``tracing.py`` keeps only the spans the
harness opens itself, so this tool runs one cell as ``run.py --trace 1``
does, keeps the program's spans as well (its loader wraps
``tracing.load`` for the one run) and opens a ``gc`` span over each
garbage collection.  It prints one JSON line:

* ``per_dispatch_ms``: self time per dispatch in the window of the fold
  (``engine.fold``), the staging (``engine.stage`` + ``alloc.pack``),
  the round trip to the device (``alloc.launch`` + ``alloc.wait``) and
  the apply loop (``engine.apply``); ``inject_ms``: self time per
  injected workflow;
* ``staged_kb``: mean ``bytes`` of ``alloc.pack`` (the bytes a dispatch
  copies host to device, ``EngineMetrics.staged_bytes``) over 1,024;
* ``round_trip``: per dispatch, the medians of the wait from the
  launch's return to the fused step's start on the device, the step on
  the device, and from its end to the wait's return;
* ``gc_in_s``: garbage collection by the program span it fell in;
* ``steps``: per kind of step, its spans' time, the part program spans
  cover and the rest;
* ``idle_gaps``: the device's idle time by the innermost span open: a
  program span or ``gc``, else the harness's span, else ``harness``;
* ``traced``: the end-to-end values of the traced window (bind lag
  percentiles, pods per second), which less an untraced run's is the
  cost of tracing.

A span's self time is its duration minus the union of its child program
spans; a ``gc`` span inside it is not subtracted.  Without a TPU the
cell is rehearsed at a tiny size on the CPU and the run exits 2.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import tracing  # noqa: E402

# (name, start_ns, end_ns, metadata)
Span = Tuple[str, int, int, dict]

PROGRAM = ("engine.", "alloc.")
GC = "gc"
PARTS = {"fold": ("engine.fold",),
         "stage": ("engine.stage", "alloc.pack"),
         "sync": ("alloc.launch", "alloc.wait"),
         "apply": ("engine.apply",)}


def load(path: str) -> List[Span]:
    """The program's spans and ``gc`` spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM) or ev.name == GC:
                    start = int(ev.start_ns)
                    out.append((ev.name, start, start + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


class Split:
    """The program's spans of one traced window, beside its reduction."""

    def __init__(self, red: tracing.Reduction, spans: Sequence[Span]):
        self.red = red
        inside = sorted(
            ((n, max(s, red.lo), min(e, red.hi), meta)
             for n, s, e, meta in spans if s < red.hi and e > red.lo),
            key=lambda sp: (sp[1], -sp[2]))
        self.spans = inside
        # Self time: a program span less its direct program children,
        # found by one sweep over the spans ordered by start (outer
        # first on a tie); gc spans are left inside their parent, and
        # their time is summed by that parent's name.
        self.self_ns: List[int] = []
        self.gc_in: Dict[str, int] = defaultdict(int)
        stack: List[int] = []
        for i, (name, s, e, _) in enumerate(inside):
            self.self_ns.append(e - s)
            while stack and inside[stack[-1]][2] <= s:
                stack.pop()
            parent = inside[stack[-1]][0] if stack else None
            if name == GC:
                self.gc_in[parent or "none"] += e - s
                continue
            if stack:
                self.self_ns[stack[-1]] -= e - s
            stack.append(i)

    def count(self, name: str) -> int:
        return sum(1 for sp in self.spans if sp[0] == name)

    def self_ms(self, names: Sequence[str]) -> float:
        """Summed self time of the named spans, clipped to the window."""
        return sum(t for sp, t in zip(self.spans, self.self_ns)
                   if sp[0] in names) * 1e-6

    def meta(self, name: str, key: str) -> List:
        return [sp[3][key] for sp in self.spans
                if sp[0] == name and key in sp[3]]

    def innermost(self) -> List[Tuple[int, int, str]]:
        """Disjoint intervals, each named by the innermost program or
        ``gc`` span open over it (the one that opened last)."""
        edges = sorted({t for _, s, e, _ in self.spans for t in (s, e)})
        out: List[Tuple[int, int, str]] = []
        open_: List[Span] = []
        j = 0
        for a, b in zip(edges, edges[1:]):
            while j < len(self.spans) and self.spans[j][1] <= a:
                open_.append(self.spans[j])
                j += 1
            open_ = [sp for sp in open_ if sp[2] > a]
            if open_:
                name = max(open_, key=lambda sp: sp[1])[0]
                if out and out[-1][2] == name and out[-1][1] == a:
                    out[-1] = (out[-1][0], b, name)
                else:
                    out.append((a, b, name))
        return out

    def gaps(self, dev: int = 0) -> List[Tuple[int, int]]:
        """The window's intervals in which the device ran no op."""
        out, t = [], self.red.lo
        for s, e in self.red.busy.get(dev, []):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.red.hi:
            out.append((t, self.red.hi))
        return out

    def idle_gaps(self, dev: int = 0) -> Dict[str, int]:
        """Idle time of the device (ns) by the innermost span open: a
        program span or ``gc``, else the harness's span, else none."""
        by: Dict[str, int] = defaultdict(int)
        rest = _attribute(self.gaps(dev), self.innermost(), by)
        # The harness's spans do not overlap one another.
        harness = sorted((s, e, name) for name, s, e in self.red.host)
        rest = _attribute(rest, harness, by)
        by["harness"] += sum(e - s for s, e in rest)
        return dict(by)

    def idle_in_steps(self, dev: int = 0) -> Tuple[int, int]:
        """Idle time (ns) inside ``step:`` spans, and the part of it in
        which no program span or ``gc`` was open."""
        steps = tracing.union((s, e) for _, s, e in self.red.steps)
        gaps = self.gaps(dev)
        bare = _attribute(gaps, self.innermost(), defaultdict(int))
        return (sum(tracing.covered(steps, s, e) for s, e in gaps),
                sum(tracing.covered(steps, s, e) for s, e in bare))

    def round_trip(self, dev: int = 0) -> Optional[Dict[str, float]]:
        """Medians (ms) over dispatches: from ``alloc.launch``'s return
        to the fused step's start on the device, the step's module on
        the device, and from its end to ``alloc.wait``'s return.  None
        unless launches, waits and modules pair one for one."""
        launches = [sp for sp in self.spans if sp[0] == "alloc.launch"]
        waits = [sp for sp in self.spans if sp[0] == "alloc.wait"]
        modules = sorted(self.red.fused_steps(dev), key=lambda m: m[1])
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

    def steps(self) -> Dict[str, dict]:
        """Per kind of step: its spans' time, the part that top-level
        program spans cover, and the rest (ms)."""
        program = tracing.union((s, e) for n, s, e, _ in self.spans
                                if n != GC)
        out: Dict[str, dict] = {}
        for name, s, e in self.red.steps:
            s, e = max(s, self.red.lo), min(e, self.red.hi)
            row = out.setdefault(name, {"count": 0, "span_ms": 0.0,
                                        "program_ms": 0.0})
            row["count"] += 1
            row["span_ms"] += (e - s) * 1e-6
            row["program_ms"] += tracing.covered(program, s, e) * 1e-6
        for row in out.values():
            row["rest_ms"] = row["span_ms"] - row["program_ms"]
        return out


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


def summary(split: Split, dispatches: int) -> dict:
    """The JSON line's numbers from one window's spans."""
    injects = split.count("engine.inject")
    staged = split.meta("alloc.pack", "bytes")
    in_steps, bare = split.idle_in_steps(0)
    gaps = split.idle_gaps(0)
    return {
        "dispatches": dispatches,
        "counts": {name: split.count(name) for name in sorted(
            {sp[0] for sp in split.spans})},
        "per_dispatch_ms": {part: split.self_ms(names) / dispatches
                            if dispatches else None
                            for part, names in PARTS.items()},
        "inject_ms": (split.self_ms(("engine.inject",)) / injects
                      if injects else None),
        "staged_kb": sum(staged) / len(staged) / 1024 if staged else None,
        "round_trip": split.round_trip(0),
        "gc_in_s": {k: v * 1e-9 for k, v in sorted(split.gc_in.items())},
        "steps": split.steps(),
        "idle_in_steps_s": in_steps * 1e-9,
        "idle_in_steps_bare_share": bare / in_steps if in_steps else None,
        "idle_gaps": sorted(([k, v * 1e-9] for k, v in gaps.items()
                             if v > 0), key=lambda kv: -kv[1]),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import repro.api  # noqa: F401  (loads the kernel package in order)

    dev = jax.devices()[0]
    tiny = dev.platform != "tpu"
    if tiny:
        run.log("no TPU: rehearsing the cell at a tiny size on the CPU")
    harness: List[tuple] = []
    program: List[Span] = []
    harness_load = tracing.load

    def load_both(path):
        program.extend(load(path))
        harness[:] = harness_load(path)
        return harness

    tracing.load = load_both
    try:
        with GcSpans():
            result, window = run.execute(
                cell, args.seed, min(args.seconds, 2.0) if tiny
                else args.seconds, True, tiny=tiny)
    finally:
        tracing.load = harness_load
    split = Split(tracing.Reduction(harness), program)
    line = {"workload": args.workload, "seed": args.seed,
            "correct": result["correct"], "device": result["device"],
            **summary(split, window.dispatches)}
    traced = {}
    if window.lags:
        lags_ms = [x * 1e3 for x in window.lags]
        traced.update(bind_p50_ms=run.percentile(lags_ms, 50),
                      bind_p95_ms=run.percentile(lags_ms, 95))
    if window.binds_in_window:
        traced["pods_per_s"] = window.binds_in_window / args.seconds
    line["traced"] = traced
    print(json.dumps(line), flush=True)
    return 2 if tiny else 0


if __name__ == "__main__":
    sys.exit(main())
