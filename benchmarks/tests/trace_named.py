"""By hand, on the chip: one traced run of a cell with the trace kept, read
for what the result line's reduction does not keep.

    python3 benchmarks/tests/trace_named.py <workload> <seed> <seconds> <out.json>

Writes (a) every host event of the program's regions, by thread, with its
``batch`` and ``epoch`` (how many annotations a span holds, and on which
thread) and every region's count and seconds over the window; (b) every device op's time with the ``op_name`` the compiled step
gives it (the ``hg_`` scopes of ``hydragnn_tpu.utils.tracer``), and device
milliseconds a step by phase: forward, backward, remat recompute, optimizer,
guard, cast, each kernel and each kernel's tangent; (c) a second of rows
around the longest idle gap, host rows included, that a fixture is cut from.
"""

import glob
import json
import os
import re
import sys
import time
from typing import Dict, List

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts benchmarks/ and the repo root on the path)
import common  # noqa: E402
import tracing  # noqa: E402

REGIONS = ("dataload", "rng_split", "train_step", "dispatch", "epoch_restart", "epoch_drain",
           "batch_build", "h2d_stage")
KERNELS = ("hg_fused_edge", "hg_sorted_segment", "hg_multi_agg", "hg_flash_attention")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def host_events(trace_dir: str, lo_ns: int) -> Dict[str, Dict]:
    """Per host thread: count and seconds by event name (longest 25), and
    every event of the program's regions as [start - lo, duration, batch,
    epoch] in nanoseconds."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out: Dict[str, Dict] = {}
    if not paths:
        return out
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):  # python threads share one line name
            by_name: Dict[str, List[float]] = {}
            regions: Dict[str, list] = {}
            for ev in line.events:
                c = by_name.setdefault(ev.name, [0, 0.0])
                c[0] += 1
                c[1] += ev.duration_ns / 1e9
                if ev.name in REGIONS:
                    stats = {str(k): str(v) for k, v in ev.stats}
                    regions.setdefault(ev.name, []).append(
                        [int(ev.start_ns) - lo_ns, int(ev.duration_ns), stats.get("batch"), stats.get("epoch")])
            if by_name:
                top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:25]
                out[f"{plane.name}|{line.name}|{i}"] = {"events": top, "regions": regions}
    return out


def op_key(text: str) -> str:
    """`%name type[shape]` of an HLO instruction: the same in a trace event's
    name and in the compiled module's text."""
    m = _INSTR.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:60]


def op_names_of(hlo_text: str) -> Dict[str, str]:
    """`%name type[shape]` -> op_name metadata, from a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m, n = _INSTR.match(line), _OP_NAME.search(line)
        if m and n:
            out.setdefault(f"{m.group(1)} {m.group(2)}", n.group(1))
    return out


def phase_of(op_name: str, mosaic: bool) -> str:
    """One phase a device op: the kernel scopes first, then the step's
    phases; `.remat` marks the recompute of a `jax.checkpoint` in the
    backward pass (a kernel called again there is `<kernel>.remat`)."""
    remat = ".remat" if "rematted_computation" in op_name else ""
    for k in KERNELS:
        if k + "_tangent" in op_name:
            return k + "_tangent" + remat
    for k in KERNELS:
        if k in op_name:
            return (k if mosaic else k + ".prep") + remat
    for scope in ("hg_optimizer", "hg_guard", "hg_cast"):
        if scope in op_name:
            return scope
    if "hg_loss" in op_name:
        if remat:
            return "hg_loss.remat"
        return "hg_loss.backward" if "transpose(" in op_name else "hg_loss.forward"
    return "other" if op_name else "unnamed"


class ShapeLog:
    """Wraps the compiled step: keeps the abstract arguments of the first
    call with each batch shape, for compiling the same programs again after
    the run (from the compile cache) and reading their text."""

    def __init__(self, step):
        self.step, self.seen = step, {}

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, state, batch, rng):
        import jax

        key = tuple(batch.node_mask.shape) + tuple(batch.edge_mask.shape)
        if key not in self.seen:
            self.seen[key] = jax.tree_util.tree_map(abstract, (state, batch, rng))
        return self.step(state, batch, rng)


def abstract(x):
    import jax

    aval = jax.api_util.shaped_abstractify(x)  # python scalars of a fresh state are weak-typed
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type)


def main():
    import importlib

    import jax

    import drive_train
    import trace_tools

    workload, seed, seconds, out = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
    rehearsal = len(sys.argv) > 5 and sys.argv[5] == "tiny"  # here on the CPU: paths and keys only
    ctx = tiny.tiny_ctx(workload) if rehearsal else common.load_cell(workload)
    dirs = common.cache_dirs()
    devices = jax.devices() if rehearsal else common.require_chips(int(ctx["cell"]["chips"]))
    tracing.TraceSpan.keep_trace = True
    logs: List[ShapeLog] = []
    setup = drive_train.setup

    def logged_setup(*a, **k):
        env = setup(*a, **k)
        if hasattr(env.raw_step, "lower"):
            env.raw_step = ShapeLog(env.raw_step)
            logs.append(env.raw_step)
        return env

    drive_train.setup = logged_setup
    # the driver reads the recorder's totals at the window's two ends: keep both
    from hydragnn_tpu.utils import tracer as tr

    reads, get_regions = [], tr.get_regions

    def logged_regions():
        reads.append(get_regions())
        return reads[-1]

    tr.get_regions = logged_regions
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    extra = {"scale": 0.02} if rehearsal else {}
    result = driver.drive(ctx, seed, seconds, True, T0, devices, dirs, **extra)
    trace_dir = os.path.join(dirs["trace"], ctx["cell"]["name"])
    rows = tracing.read_xplane(trace_dir)
    ops = [r for r in rows if r[0].startswith(tracing.DEVICE_PREFIX) and r[1] == tracing.OPS_LINE]
    lo = min((r[3] for r in ops), default=min((r[3] for r in rows), default=0))
    steps = len([r for r in rows if r[0].startswith(tracing.DEVICE_PREFIX)
                 and r[1] == tracing.MODULES_LINE and r[2].startswith("jit_train_step")])

    # ---- the compiled programs' op names, one module a batch shape
    names: Dict[str, str] = {}
    modules = []
    for log in logs:
        for key, args in log.seen.items():
            t = time.perf_counter()
            text = log.step.lower(*args).compile().as_text()
            mine = op_names_of(text)
            clash = sum(1 for k, v in mine.items() if names.get(k, v) != v)
            for k, v in mine.items():
                names.setdefault(k, v)
            modules.append({"shape": list(key), "instructions": len(mine), "clashes": clash,
                            "compile_s": time.perf_counter() - t})

    by_op: Dict[str, List[float]] = {}
    for r in ops:
        c = by_op.setdefault(r[2], [0, 0.0])
        c[0] += 1
        c[1] += r[4] / 1e9
    phases: Dict[str, float] = {}
    device_ops = []
    for text, (count, sec) in sorted(by_op.items(), key=lambda kv: -kv[1][1]):
        op_name = names.get(op_key(text), "")
        phase = phase_of(op_name, tracing.is_mosaic(text))
        phases[phase] = phases.get(phase, 0.0) + sec
        device_ops.append([tracing.short(text), count, sec, phase, op_name[-220:]])
    per_step = {k: 1e3 * v / max(steps, 1) for k, v in sorted(phases.items(), key=lambda kv: -kv[1])}

    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump({
            "steps_in_span": steps, "modules": modules,
            "phase_ms_per_step": per_step,
            "device_ops": device_ops[:400],
            "host": host_events(trace_dir, lo),
            "reduced": result.get("breakdown"),
            "window": {k: result["info"][k] for k in ("window_s", "steps", "epochs")},
            "window_regions": {
                k: {f: v[f] - reads[0].get(k, {}).get(f, 0.0) for f in ("count", "total")}
                for k, v in reads[-1].items()} if len(reads) >= 2 else None,
            "rows_sample": trace_tools.sample_rows(rows, 1.0, REGIONS),
        }, f)
    print("phase_ms_per_step", json.dumps(per_step), file=sys.stderr)
    common.emit(result)


if __name__ == "__main__":
    main()
