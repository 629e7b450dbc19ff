"""What every driver of the benchmark shares: where things are, how a cell is
found from ``BENCHMARK.json`` by name, the table of peaks, the device stamp,
the per-layer readers and the result line. No list of cells, metrics or
models lives here: all of them are files found by name."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_BASE = os.path.join(ROOT, "logs", "benchmarks_cache")


def cache_dirs() -> Dict[str, str]:
    """Compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, else at one
    fixed path in the checkout (the program adopts the variable, so it is set
    here before jax is imported); the dataset cache beside it."""
    xla = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(CACHE_BASE, "xla")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = xla
    # every program of a run is cached, however quickly it compiled
    os.environ.setdefault("HYDRAGNN_COMPILE_CACHE_MIN_SECS", "0")
    data = os.path.join(CACHE_BASE, "data")
    for d in (xla, data):
        os.makedirs(d, exist_ok=True)
    return {"xla": xla, "data": data, "trace": os.path.join(CACHE_BASE, "trace")}


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    return cell_from_files(cells[name], bench)


def cell_from_files(cell: Dict[str, Any], bench: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """A cell is {name, config, traffic, chips}: its two files by name."""
    return {
        "cell": cell,
        "bench": bench or {"end_to_end": [], "per_layer": []},
        "config": load_json(BENCH_DIR, "configs", f"{cell['config']}.json"),
        "traffic": load_json(BENCH_DIR, "traffic", f"{cell['traffic']}.json"),
    }


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def require_chips(chips: int):
    """The devices of this run, or exit 3 before any data is built."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"benchmark needs {chips} tpu chip(s); jax found {len(devs)} x {devs[0].platform}",
            file=sys.stderr,
        )
        raise SystemExit(3)
    return devs


def device_stamp(devices, chips: int) -> Dict[str, Any]:
    """The device as jax reports it. The TPU runtime counts live arrays under
    ``peak_bytes_in_use`` and the scratch memory of running programs under
    ``peak_bytes_reserved`` (a 4 GiB temporary shows only there): the peak is
    their sum, on the fullest chip."""
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }


def metric_applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_per_layer(ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of ``BENCHMARK.json`` that lists this cell is
    read by ``metrics/<name>.py``'s ``read(ctx)``; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in ctx["bench"]["per_layer"]:
        if not metric_applies(m, ctx["cell"]["name"]):
            continue
        path = os.path.join(BENCH_DIR, "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location("bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(ctx: Dict[str, Any], values: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in ctx["bench"]["end_to_end"]:
        if metric_applies(m, ctx["cell"]["name"]) and m["name"] in values:
            out[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    return out


def fill_result(result: Dict[str, Any], ctx: Dict[str, Any], device: Dict[str, Any], traced: bool,
                values: Dict[str, float]) -> None:
    """``metrics``, ``device`` and ``breakdown`` of a result line: the
    per-layer readers' numbers in a traced run, the end-to-end ``values``
    otherwise."""
    if traced:
        trace = ctx["trace"]
        result["metrics"] = read_per_layer(ctx)
        device["busy_s"] = trace["busy_s"] if trace else 0.0
        device["window_s"] = trace["window_s"] if trace else 0.0
        if trace:
            result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    else:
        result["metrics"] = end_to_end(ctx, values)
    result["device"] = device


def emit(result: Dict[str, Any]) -> None:
    """Numbers compared, each beside its limit: last lines on standard
    error, and last key of the one result line on standard output."""
    compared = result.pop("compared", {})
    for name, row in compared.items():
        print(f"compared {name}: value {row['value']!r} limit {row['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = compared
    print(json.dumps(result), flush=True)
