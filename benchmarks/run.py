"""Benchmark harness entry point: one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only NAME]
Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from . import (
    engine_overhead, fig1_schedules, fig34_grouping, fig56_matmul_study,
)

SUITES = {
    "fig1": fig1_schedules.run,
    "fig34": fig34_grouping.run,
    "fig56": fig56_matmul_study.run,
    "engine": engine_overhead.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(SUITES))
    args = ap.parse_args()

    failures = 0
    print("name,us_per_call,derived")
    for name, suite in SUITES.items():
        if args.only and name != args.only:
            continue
        try:
            for row, us, derived in suite():
                print(f'{row},{us:.1f},"{derived}"')
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f'{name},FAILED,""')
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
