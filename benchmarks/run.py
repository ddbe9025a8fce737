"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Default mode keeps runtimes
CPU-friendly (fewer Monte-Carlo seeds / requests / sweep points);
``--full`` reproduces the paper-scale settings.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args, _ = ap.parse_known_args()

    # a suite that cannot be imported fails the run here, loudly
    from benchmarks import (engine_validation, fig_sweeps, kernel_bench,
                            optimality_gap, roofline, table4_clustered,
                            table5_scattered, table6_runtime)
    suites = [
        ("table4", table4_clustered.run),
        ("table5", table5_scattered.run),
        ("table6", table6_runtime.run),
        ("figs", fig_sweeps.run),
        ("optgap", optimality_gap.run),
        ("roofline", roofline.run),
        ("engine_validation", engine_validation.run),
        ("kernels", kernel_bench.run),
    ]

    only = set(args.only.split(",")) if args.only else None
    failures = 0
    for name, fn in suites:
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            fn(full=args.full)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# suite {name} FAILED", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
