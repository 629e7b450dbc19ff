"""From a profiler trace to numbers: device busy and idle time, time by
operation, Mosaic (Pallas) call time, the interval between steps, and each
long idle gap named by the host annotation that covers it.

``reduce_events`` works on plain rows so that it can be checked against a
small recorded trace (``fixtures/``); ``read_xplane`` turns the profiler's
``.xplane.pb`` into those rows with nothing but jax.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import threading
from typing import Dict, Iterable, List, Optional, Tuple

# a row: (plane, line, name, start_ns, duration_ns)
Row = Tuple[str, str, str, int, int]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class TraceSpan:
    """A few seconds of profiler trace inside a window (not all of it: traces
    are large and tracing slows the host), started and stopped by timers so
    that the window's loop is left alone. Device ops and TraceMe annotations
    only: the Python tracer slows the host it is meant to observe."""

    keep_trace = False

    def __init__(self, trace_dir: str, window_seconds: float):
        self.dir = trace_dir
        self._lock, self._on = threading.Lock(), False
        span = min(3.0, 0.3 * window_seconds)
        self._timers = [threading.Timer(0.25 * window_seconds, self._start),
                        threading.Timer(0.25 * window_seconds + span, self._stop)]
        shutil.rmtree(trace_dir, ignore_errors=True)

    def _start(self) -> None:
        import jax

        with self._lock:
            if not self._on:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(self.dir, profiler_options=options)
                self._on = True

    def _stop(self) -> None:
        import jax

        with self._lock:
            if self._on:
                jax.profiler.stop_trace()
                self._on = False

    def start(self) -> None:
        for timer in self._timers:
            timer.start()

    def finish(self, step_prefix: str, chips: int, annotations: Tuple[str, ...]) -> Optional[Dict]:
        """Stop, reduce, remove the trace (``keep_trace`` leaves it for a
        look by hand: tests/trace_tools.py)."""
        for timer in self._timers:
            timer.join()
        self._stop()
        reduced = reduce_events(read_xplane(self.dir), step_prefix, chips, annotations)
        if not self.keep_trace:
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def read_xplane(trace_dir: str) -> List[Row]:
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    rows: List[Row] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return rows


def union_length(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered length and the merged intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


_HLO = re.compile(r"^(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])[^ ]* .*?([a-z][a-z0-9_-]*)\(")


def short(name: str) -> str:
    """An op's HLO text cut to `%name type[shape] opcode` (Mosaic calls
    marked), so that a breakdown line stays readable."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    op = "mosaic-custom-call" if is_mosaic(name) else m.group(3)
    return f"{m.group(1)} {m.group(2)} {op}"


def is_mosaic(name: str) -> bool:
    n = name.lower()
    return "mosaic" in n or "tpu_custom_call" in n or "pallas" in n


def _median(xs: List[float]) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    k = len(xs) // 2
    return xs[k] if len(xs) % 2 else 0.5 * (xs[k - 1] + xs[k])


def reduce_events(rows: List[Row], step_prefix: str, chips: int = 1,
                  annotations: Tuple[str, ...] = ("dataload", "train_step")) -> Optional[Dict]:
    """The reduction. ``step_prefix`` names the step's program on the
    modules line (``jit_train_step``). None when no device op was traced."""
    planes = sorted({r[0] for r in rows if r[0].startswith(DEVICE_PREFIX)})[:chips]
    if not planes:
        return None
    ops = [r for r in rows if r[0] in planes and r[1] == OPS_LINE]
    if not ops:
        return None
    t_lo = min(r[3] for r in ops)
    t_hi = max(r[3] + r[4] for r in ops)
    busy_total, by_op, mosaic_total, gaps = 0, {}, 0, []
    for plane in planes:
        mine = [r for r in ops if r[0] == plane]
        busy, merged = union_length((r[3], r[3] + r[4]) for r in mine)
        busy_total += busy
        for r in mine:
            by_op[r[2]] = by_op.get(r[2], 0) + r[4]
            if is_mosaic(r[2]):
                mosaic_total += r[4]
        if plane == planes[0]:
            edges = [(t_lo, t_lo)] + merged + [(t_hi, t_hi)]
            gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    # host annotations that cover a gap's midpoint name it
    host = [r for r in rows if not r[0].startswith("/device:")]
    named = [r for r in host if r[2] in annotations or r[2].startswith("PjitFunction")]
    gap_rows = []
    for s, e in gaps:
        mid = (s + e) // 2
        label = "no_span"
        best = None
        for r in named:
            if r[3] <= mid <= r[3] + r[4] and (best is None or r[4] < best[4]):
                best = r
        if best is not None:
            label = best[2]
        gap_rows.append((label, (e - s) / 1e9))
    sums: Dict[str, float] = {}
    for label, sec in gap_rows:
        sums[label] = sums.get(label, 0.0) + sec
    idle_gaps = [[f"sum:{k}", v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:5]]
    idle_gaps += [[f"gap:{k}", v] for k, v in sorted(gap_rows, key=lambda kv: -kv[1])[:5]]
    mods = sorted(
        (r[3] + r[4] for r in rows
         if r[0] == planes[0] and r[1] == MODULES_LINE and r[2].startswith(step_prefix)))
    step_ms = _median([(b - a) / 1e6 for a, b in zip(mods, mods[1:])])
    ann = {}
    for r in host:
        if r[2] in annotations:
            lo, hi = max(r[3], t_lo), min(r[3] + r[4], t_hi)
            if hi > lo:
                ann[r[2]] = ann.get(r[2], 0.0) + (hi - lo) / 1e9
    n = len(planes)
    return {
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": busy_total / 1e9 / n,
        "mosaic_s": mosaic_total / 1e9 / n,
        "ops_s": sum(by_op.values()) / 1e9 / n,
        "step_ms_p50": step_ms,
        "steps": len(mods),
        "annotation_s": ann,
        "device_ops": [[short(k), v / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "mosaic_ops": [[short(k), v / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])
                       if is_mosaic(k)],
        "idle_gaps": idle_gaps[:10],
    }
