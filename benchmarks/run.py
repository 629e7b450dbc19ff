"""The benchmark's entry:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, and hands them to ``drive_<kind>.py`` (the traffic file's ``kind``).
Prints one JSON object as the last line of standard output. Exits 3, with no
result, when jax finds no TPU or fewer chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx = common.load_cell(args.workload)
    dirs = common.cache_dirs()
    devices = common.require_chips(int(ctx["cell"]["chips"]))
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    result = driver.drive(ctx, args.seed, args.seconds, bool(args.trace), T_PROCESS, devices, dirs)
    common.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
