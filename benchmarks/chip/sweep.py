#!/usr/bin/env python3
"""Find the highest arrival rate a paced cell sustains: one sweep.

    python3 benchmarks/chip/sweep.py --config k8s-5k --traffic poisson \
        --rates 300 340 380 420 460 --seconds 20 --seed 5000000001

For each rate, the configuration serves the traffic mix (a paced one)
once as ``run.py`` runs a cell (warm-up, then
the paced window), with its Poisson rate replaced.  Each line gives the
bind lag's median and 95th percentile, the mean lag of the window's
first and last quarter (a lag that grows through the window means the
scheduler falls behind), how late the loop ran, and the device.  The
rate in the cell's traffic file is set once from such a sweep; runs
never search.  A sweep measures the chip only: without a TPU it exits 2
and prints no line.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import repro.api  # noqa: F401

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"no TPU ({device}): a sweep measures the chip only",
              file=sys.stderr)
        return 2
    for rate in args.rates:
        cell = run.mix_cell(args.config, args.traffic)
        cell["mix"]["arrivals"]["rate_per_s"] = rate
        result, window = run.execute(cell, args.seed, args.seconds, False,
                                     tiny=False)
        lags = [x * 1e3 for x in window.lags]
        q = max(len(lags) // 4, 1)
        print(json.dumps({
            "rate_per_s": rate,
            "pods": len(lags),
            "bind_p50_ms": run.percentile(lags, 50),
            "bind_p95_ms": run.percentile(lags, 95),
            "first_quarter_mean_ms": statistics.fmean(lags[:q]),
            "last_quarter_mean_ms": statistics.fmean(lags[-q:]),
            "late_ms": window.late_s * 1e3,
            "overrun_s": window.overrun_s,
            "correct": result["correct"],
            "device": device,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
