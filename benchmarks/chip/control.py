#!/usr/bin/env python3
"""Readings of the check's control: the reference one precision lower.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed, the reference runs the cell's traffic at the cell's own
size, standalone, with every stored quantity and every sum in bfloat16
(the configurations state float32).  Its binds are then handed to the
float32 check in the program's place, over the horizon a run of
``run_seconds`` covers.  Each seed prints one JSON line with the
numbers the check compares; a sound control reads as not correct.

This runs on the host only; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))

import traffic  # noqa: E402
from reference import Reference  # noqa: E402


def horizon_of(mix: dict, stream, seconds: float):
    """(horizon, pods) of the decisions one run of the cell checks."""
    kind = mix["drive"]
    scale = float(mix.get("time_scale", 1.0))
    if kind == "paced":
        t_w = stream[int(mix["warmup"]["arrivals"]) - 1][0]
        return t_w + seconds * scale, None
    return float("inf"), traffic.pod_count(stream)


def readings(engine: dict, mix: dict, seed: int, seconds: float,
             dtype: str = "bfloat16") -> dict:
    stream = traffic.arrivals(mix, seed, seconds)
    horizon, pods = horizon_of(mix, stream, seconds)
    t0 = time.perf_counter()
    decided = Reference(engine, stream, dtype=dtype).decide_all(horizon, pods)
    r = Reference(engine, stream).follow(decided, horizon=horizon, pods=pods)
    return {"seed": seed, "dtype": dtype, "decisions": r.decisions,
            "mismatches": r.mismatches, "never_bound": r.missing,
            "quota_gap": r.quota_gap, "overcommits": r.overcommits,
            "ambiguous": r.ambiguous, "seconds": time.perf_counter() - t0,
            "first": r.first_mismatch}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    engine = json.loads((ROOT / config["file"]).read_text())["engine"]
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    for seed in args.seeds:
        out = readings(engine, mix, seed, float(bench["run_seconds"]),
                       args.dtype)
        print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
