#!/usr/bin/env python3
"""Split a traced window's host time by the program's own spans.

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \
        --seconds <s>

Runs one cell as ``run.py --trace 1`` does and prints one JSON line from
the window's ``tracing.Reduction``, which keeps the program's spans
(``engine.inject``, ``engine.fold``, ``engine.stage``, ``alloc.pack``,
``alloc.launch``, ``alloc.wait``, ``engine.apply``) and a ``gc`` span
over each garbage collection:

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
* ``idle_gaps``: the device's idle time by the innermost span open;
* ``traced``: the end-to-end values of the traced window (bind lag
  percentiles, pods per second), which less an untraced run's is the
  cost of tracing.

Without a TPU the cell is rehearsed at a tiny size on the CPU and the
run exits 2.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import tracing  # noqa: E402

PARTS = {"fold": ("engine.fold",),
         "stage": ("engine.stage", "alloc.pack"),
         "sync": ("alloc.launch", "alloc.wait"),
         "apply": ("engine.apply",)}


def summary(red: tracing.Reduction, dispatches: int) -> dict:
    """The JSON line's numbers from one window's reduction."""
    injects = red.count("engine.inject")
    staged = red.meta("alloc.pack", "bytes")
    in_steps, bare = red.idle_in_steps(0)
    return {
        "dispatches": dispatches,
        "counts": {name: red.count(name) for name in sorted(
            {sp[0] for sp in red.program})},
        "per_dispatch_ms": {part: red.self_ms(names) / dispatches
                            if dispatches else None
                            for part, names in PARTS.items()},
        "inject_ms": (red.self_ms(("engine.inject",)) / injects
                      if injects else None),
        "staged_kb": sum(staged) / len(staged) / 1024 if staged else None,
        "round_trip": red.round_trip(0),
        "gc_in_s": {k: v * 1e-9 for k, v in sorted(red.gc_in.items())},
        "steps": red.step_split(),
        "idle_in_steps_s": in_steps * 1e-9,
        "idle_in_steps_bare_share": bare / in_steps if in_steps else None,
        "idle_gaps": red.idle_gaps(0, n=100),
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

    tiny = jax.devices()[0].platform != "tpu"
    if tiny:
        run.log("no TPU: rehearsing the cell at a tiny size on the CPU")
    seconds = min(args.seconds, 2.0) if tiny else args.seconds
    result, window = run.execute(cell, args.seed, seconds, True, tiny=tiny)
    line = {"workload": args.workload, "seed": args.seed,
            "correct": result["correct"], "device": result["device"],
            **summary(window.trace, window.dispatches)}
    traced = {}
    if window.lags:
        lags_ms = [x * 1e3 for x in window.lags]
        traced.update(bind_p50_ms=run.percentile(lags_ms, 50),
                      bind_p95_ms=run.percentile(lags_ms, 95))
    if window.binds_in_window:
        traced["pods_per_s"] = window.binds_in_window / seconds
    line["traced"] = traced
    print(json.dumps(line), flush=True)
    return 2 if tiny else 0


if __name__ == "__main__":
    sys.exit(main())
