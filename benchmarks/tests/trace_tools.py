"""By hand, on the chip: a look at what a trace holds before trusting the
reduction, and the dump that ``make_fixture.py`` cuts a fixture from.

    python3 benchmarks/tests/trace_tools.py <workload> <seed> <seconds> <out.json>

Runs the cell's driver with ``--trace 1``, keeps the profiler's trace, and
writes its structure (planes, lines, commonest events), the reduction, half a
second of rows around the longest idle gap and the first rows of both planes
(for checking that they share one clock)."""

import glob
import json
import os
import sys
import time
from typing import Dict, List, Tuple

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts benchmarks/ and the repo root on the path)
import common  # noqa: E402
import tracing  # noqa: E402


def sample_rows(rows: List[tracing.Row], seconds: float,
                annotations: Tuple[str, ...] = ("dataload", "train_step")) -> List[tracing.Row]:
    """``seconds`` of a trace around its longest idle gap, device rows and
    named host rows only: small enough to keep as a fixture, and it holds a
    gap worth naming."""
    ops = [r for r in rows if r[0].startswith(tracing.DEVICE_PREFIX) and r[1] == tracing.OPS_LINE]
    if not ops:
        return []
    _, merged = tracing.union_length((r[3], r[3] + r[4]) for r in ops)
    gaps = [(b[0] - a[1], (a[1] + b[0]) // 2) for a, b in zip(merged, merged[1:])]
    mid = max(gaps)[1] if gaps else (merged[0][0] + merged[-1][1]) // 2
    lo, hi = mid - int(seconds * 5e8), mid + int(seconds * 5e8)
    keep = []
    for r in rows:
        if r[3] < lo or r[3] + r[4] > hi:
            continue
        if r[0].startswith(tracing.DEVICE_PREFIX) or r[2] in annotations or r[2].startswith("PjitFunction"):
            keep.append(r)
    return keep


def alignment_rows(rows: List[tracing.Row], limit: int = 30) -> Dict[str, list]:
    """The first step programs on the device and the first host annotations,
    times relative to the first device op: for checking by hand that both
    planes share one clock."""
    ops = [r for r in rows if r[0].startswith(tracing.DEVICE_PREFIX) and r[1] == tracing.OPS_LINE]
    if not ops:
        return {}
    lo = min(r[3] for r in ops)
    mods = sorted((r[3] - lo, r[4], r[2][:32]) for r in rows
                  if r[0].startswith(tracing.DEVICE_PREFIX) and r[1] == tracing.MODULES_LINE)[:limit]
    host = sorted((r[3] - lo, r[4], r[2]) for r in rows
                  if not r[0].startswith("/device:")
                  and (r[2] in ("dataload", "train_step") or r[2].startswith("PjitFunction")))[:2 * limit]
    return {"modules": mods, "host": host}


def structure(trace_dir: str, limit: int = 40) -> Dict:
    """What a trace holds (planes, lines, commonest event names): for looking
    at a trace by hand before trusting the reduction."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out: Dict = {"files": paths, "planes": []}
    if not paths:
        return out
    data = jax.profiler.ProfileData.from_file(paths[-1])
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            counts: Dict[str, List[float]] = {}
            sample_stats = None
            n = 0
            for ev in line.events:
                n += 1
                c = counts.setdefault(ev.name, [0, 0.0])
                c[0] += 1
                c[1] += ev.duration_ns / 1e9
                if sample_stats is None:
                    try:
                        sample_stats = {str(k): str(v)[:200] for k, v in ev.stats}
                    except Exception as e:  # noqa: BLE001 - a look, not a measurement
                        sample_stats = {"error": repr(e)}
            top = sorted(counts.items(), key=lambda kv: -kv[1][1])[:limit]
            p["lines"].append({"name": line.name, "events": n, "top": top, "sample_stats": sample_stats})
        out["planes"].append(p)
    return out


def main():
    import importlib

    workload, seed, seconds, out = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
    ctx = common.load_cell(workload)
    dirs = common.cache_dirs()
    devices = common.require_chips(int(ctx["cell"]["chips"]))
    tracing.TraceSpan.keep_trace = True
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    result = driver.drive(ctx, seed, seconds, True, T0, devices, dirs)
    trace_dir = os.path.join(dirs["trace"], ctx["cell"]["name"])
    rows = tracing.read_xplane(trace_dir)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump({"structure": structure(trace_dir), "reduced": result.get("breakdown"),
                   "rows_sample": sample_rows(rows, 0.5), "alignment": alignment_rows(rows)}, f)
    common.emit(result)


if __name__ == "__main__":
    main()
