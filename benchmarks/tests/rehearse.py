"""Run a cell's driver here on the CPU at a tiny size and print its result
line: `python benchmarks/tests/rehearse.py <workload> [seconds] [trace]`."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
T0 = time.perf_counter()
import tiny  # noqa: E402
import common  # noqa: E402
import importlib  # noqa: E402


def main():
    workload = sys.argv[1]
    seconds = float(sys.argv[2]) if len(sys.argv) > 2 else 2.0
    trace = bool(int(sys.argv[3])) if len(sys.argv) > 3 else False
    import jax

    ctx = tiny.tiny_ctx(workload)
    dirs = common.cache_dirs()
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    result = driver.drive(ctx, 2**31 + 12345, seconds, trace, T0, jax.devices(), dirs,
                          scale=0.02 * int(ctx["cell"]["chips"]))
    common.emit(result)


if __name__ == "__main__":
    main()
