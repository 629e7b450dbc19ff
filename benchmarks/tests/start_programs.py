"""By hand, on the chip: one run of a cell as ``run.py`` makes it, with the
program's by-name table of what it traced, lowered and compiled or fetched
(``compile_plane.compile_programs()``) read at the window's close, beside the
``compile_total`` of the result line.

    python3 benchmarks/tests/start_programs.py --workload <cell> --seed <n> --seconds <s> \
        --out <name> [--trace 1] [--cold] [--cost]

``--cold`` starts from an empty compile cache directory; ``--cost`` sums the
seconds spent inside the program's three ``jax.monitoring`` listeners. Prints
the result line like ``run.py`` and writes ``chiprun_out/<name>.start.json``:
the table (``t_first`` moved to seconds since the process began), the
totals, the sums over the table, ``setup_s`` and its stages."""

import time

T_PROCESS, T_WALL = time.perf_counter(), time.time()

import argparse
import importlib
import json
import os
import shutil
import sys
from unittest import mock

import tiny  # noqa: F401  (puts benchmarks/ and the repo root on the path)
import common

PHASES = (("trace_s", "trace_s"), ("lower_s", "lower_s"), ("backend_compile_s", "backend_s"))


def timed(fn, spent):
    def wrapper(*a, **k):
        t = time.perf_counter()
        try:
            return fn(*a, **k)
        finally:
            spent[0] += time.perf_counter() - t
            spent[1] += 1

    return wrapper


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cold", action="store_true")
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args(argv)
    if args.cold:
        cold = os.path.join(common.CACHE_BASE, "xla_cold")
        shutil.rmtree(cold, ignore_errors=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cold
    ctx = common.load_cell(args.workload)
    dirs = common.cache_dirs()
    devices = common.require_chips(int(ctx["cell"]["chips"]))

    from hydragnn_tpu.train import compile_plane as cp

    spent = [0.0, 0]  # seconds inside the listeners, calls
    if args.cost:
        for name in ("_on_event", "_on_scalar", "_on_duration"):
            setattr(cp, name, timed(getattr(cp, name), spent))
    tables = []
    read_metrics = cp.compile_metrics

    def metrics_and_table():
        tables.append(cp.compile_programs())  # the last call closes the window
        return read_metrics()

    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    with mock.patch.object(cp, "compile_metrics", metrics_and_table):
        result = driver.drive(ctx, args.seed, args.seconds, bool(args.trace), T_PROCESS, devices, dirs)
    info, programs = result["info"], tables[-1]
    installed_at = cp._T_INSTALLED - T_WALL
    for row in programs.values():
        row["t_first"] += installed_at
    total = info["compile_total"]
    out = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace, "cold": args.cold,
        "setup_s": info["setup_s"], "stages": info["stages"], "listeners_installed_at_s": installed_at,
        "compile_total": total, "compile_in_window": info["compile_in_window"],
        "sums_over_programs": {t: sum(r[f] for r in programs.values()) for t, f in PHASES},
        "n_over_programs": sum(r["n"] for r in programs.values()),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()
                    if k.startswith(("start_", "compile", "time_to_first", "setup_s"))},
        "listener_s": spent[0] if args.cost else None, "listener_calls": spent[1] if args.cost else None,
        "programs": programs,
    }
    os.makedirs(os.path.join(common.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(common.ROOT, "chiprun_out", f"{args.out}.start.json"), "w") as f:
        json.dump(out, f, indent=1)
    cost = lambda r: r["trace_s"] + r["lower_s"] + r["backend_s"]
    for name, r in sorted(programs.items(), key=lambda kv: cost(kv[1]), reverse=True)[:10]:
        print(f"program {name}: n {r['n']} trace_s {r['trace_s']:.3f} lower_s {r['lower_s']:.3f} "
              f"backend_s {r['backend_s']:.3f} t_first {r['t_first']:.2f}", file=sys.stderr)
    common.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
