"""Benchmark harness: one entry per paper table/figure + roofline.

Each benchmark prints ``name,us_per_call,derived`` CSV lines.
``python -m benchmarks.run [--quick]``.
"""
from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the full Table-2 matrix (CI mode)")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache

    use_compile_cache(Path(__file__).resolve().parents[1])
    from benchmarks import (
        allocator_scale,
        fig1_lifecycle,
        fig9_oom,
        roofline,
        table2,
        usage_curves,
    )

    benches = [
        ("fig1_lifecycle", fig1_lifecycle.main),
        ("fig9_oom", fig9_oom.main),
        ("allocator_scale", allocator_scale.main),
        ("usage_curves", usage_curves.main),
        ("roofline", roofline.main),
    ]
    if not args.quick:
        benches.insert(0, ("table2", table2.main))

    failures = []
    for name, fn in benches:
        print(f"== {name} ==", flush=True)
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"FAILED benches: {failures}")
        sys.exit(1)
    print("all benches complete")


if __name__ == "__main__":
    main()
