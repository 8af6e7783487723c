#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. The last line on stdout is the result object; a run that cannot
measure (no TPU, too few chips, the program missing) prints none and exits
non-zero. See `bench_harness.py`.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = bench_harness.run(HERE.parents[1], args.workload, args.seed,
                                args.seconds, bool(args.trace), T_PROCESS)
    except bench_harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
