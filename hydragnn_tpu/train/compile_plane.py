"""Compile plane: the zero-recompile steady state.

On TPU the dominant non-step cost of this framework is XLA compilation: a
``num_pad_buckets=4`` SpecLadder (config/config.py) means up to 4 train + 4
eval step specializations per run, each one stalling the step loop mid-epoch
on its first visit — and the fault-tolerance work (rollback, SIGTERM resume,
preemption; docs/ROBUSTNESS.md) made restarts routine, so every recovery
used to repay the full compile bill from zero. Three mechanisms close that:

1. **Persistent compilation cache** (``setup_compile_cache``): jax's
   disk-backed executable cache, placed by ONE rule
   (``compile_cache_dir``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
   ``<checkout>/logs/xla_cache``); ``Training.compile_cache_dir: false``
   or ``HYDRAGNN_COMPILE_CACHE=0`` switch it off. Restarts, rollbacks,
   and mid-epoch resumes deserialize compiled executables instead of
   recompiling them.

2. **Background AOT warm-up** (``CompilePlane``): the loaders' SpecLadder
   pad shapes are enumerated up front (``GraphLoader.spec_template_batches``
   — shapes are fully determined by the ladder, no epoch needs to run), and
   every (train, eval) x bucket specialization is ``lower().compile()``d in
   a worker thread while epoch 0 runs (``Training.precompile:
   off | blocking | background``). The AOT compile lands in the persistent
   cache, so the step loop's first organic visit to each bucket pays a
   cache *retrieval* instead of a full XLA compile. Lowering shares jax's
   trace cache with the call path, so warm-up also absorbs the Python
   tracing cost. Without a
   persistent cache directory the warm-up executables would be unreachable
   from the call path — the plane then degrades to ``off`` (AOT work whose
   results nothing can reuse is pure waste).

3. **Retrace sentinel**: every step builder's traced body calls
   ``note_trace(name, args)``, which records the call's abstract signature
   (shape/dtype/weak_type per leaf) — executed once per trace, by
   construction. Once warm-up has covered the ladder the sentinel is
   *armed*: any later trace whose signature is not among the known
   specializations is a silent-retrace bug (the PR 3 incident — one
   int32/weak-type flip on a counter silently doubled every
   specialization's compile bill), reported with the aval diff against the
   nearest known signature and handled per ``Training.retrace_policy:
   warn (default) | error``.

Observability: ``jax.monitoring`` reports the three phases of every program
jax builds (trace, lower, backend compile or cache fetch) with the program's
name, and cache hits, misses and retrieval seconds. The listeners here keep
them as process-wide counters (``compile_metrics()``: flat, numeric), by
program (``compile_programs()``), and as regions of ``utils.tracer``
(``compile_trace`` / ``compile_lower`` / ``compile_backend``, on the thread
that compiles, so a recompile is a named host span in a device trace). The
plane's ``report()`` takes their differences over a run and prints them with
time-to-first-step (``format_report``); the benchmark's readers under
``benchmarks/metrics/`` (``start_*``, ``compile_seconds_in_window``,
``compiles_in_window``) read ``compile_metrics()`` through the driver.
"""

from __future__ import annotations

import os
import re
import sys
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
from ..utils import envflags
from ..utils import tracer as tr

PRECOMPILE_MODES = ("off", "blocking", "background", "analysis")
RETRACE_POLICIES = ("warn", "error")

# how long finish() waits for a still-running warm-up worker before leaking
# the daemon thread with a warning (a wedged XLA compile must not hang run
# teardown); module-level so tests can pin it
_WORKER_JOIN_TIMEOUT_S = 30.0


class RetraceError(RuntimeError):
    """An armed retrace sentinel saw a trace outside the known
    specialization set — a silent-recompile bug (``Training.retrace_policy:
    error``). The message carries the aval diff against the nearest known
    specialization."""


# ---------------------------------------------------------------------------
# compile metrics: process-wide counters fed by jax.monitoring events
# ---------------------------------------------------------------------------

_METRICS_LOCK = threading.Lock()
_METRICS = {
    "cache_hits": 0,
    "cache_misses": 0,
    "backend_compile_s": 0.0,
    "cache_retrieval_s": 0.0,
    "trace_s": 0.0,
    "lower_s": 0.0,
    "programs": 0,
}
# compile_programs(): fun_name -> {n, trace_s, lower_s, backend_s, t_first}
_PROGRAMS: Dict[str, Dict[str, float]] = {}
_LISTENERS_INSTALLED = False
_T_INSTALLED = 0.0  # time.time() at installation: jax stamps its events with that clock

_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
# the three phases of every program jax builds (jax/_src/dispatch.py
# log_elapsed_time: a scalar with fun_name= at entry, a duration with
# fun_name= at exit): event -> (key of compile_metrics(), key of a
# compile_programs() row, region)
_PHASES = {
    _TRACE_EVENT: ("trace_s", "trace_s", tr.COMPILE_TRACE),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_s", "lower_s", tr.COMPILE_LOWER),
    _BACKEND_EVENT: ("backend_compile_s", "backend_s", tr.COMPILE_BACKEND),
}
# the same program is `step` in its trace event and `jit(step)` in the other two
_WRAPPED_NAME_RE = re.compile(r"^\w+\((.*)\)$")


class _OpenPhases(threading.local):
    """One thread's open phases, innermost last, as ``[event, counted,
    seconds of the counted phases that closed inside it]``. Phases nest: a
    trace encloses the traces of the jitted functions it calls (a decoder
    step's, hundreds) and a lowering encloses the traces Mosaic makes of a
    kernel's helpers; such an inner trace is not counted, its seconds are
    its parent's. A program compiled eagerly inside a trace is counted, and
    its seconds are taken out of that trace's: every phase's seconds are
    its own, so the sums never pass the thread's wall clock."""

    def __init__(self):
        self.stack: list = []


_OPEN = _OpenPhases()


def _on_event(name: str, **kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        with _METRICS_LOCK:
            _METRICS["cache_hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        with _METRICS_LOCK:
            _METRICS["cache_misses"] += 1
            misses = _METRICS["cache_misses"]
        # a miss in steady state is a full XLA compile paid — record it as
        # a typed incident (obs/events.py; misses are rare by design, so
        # the emission cost is irrelevant)
        try:
            from ..obs.events import EV_CACHE_MISS
            from ..obs.events import emit as _emit_event

            _emit_event(EV_CACHE_MISS, severity="warn", total=misses)
        except Exception:
            pass


def _on_scalar(name: str, value: float, **kw) -> None:
    """A phase opens (jax records its start time as a scalar under the
    duration event's name): push it, and open its region if it will be
    counted. Never raises into jax."""
    phase = _PHASES.get(name)
    if phase is None:
        return
    try:
        stack = _OPEN.stack
        counted = not (stack and name == _TRACE_EVENT)
        stack.append([name, counted, 0.0])
        if counted:
            # sync=False: draining the device would run a program from
            # inside the compile of another
            tr.start(phase[2], sync=False, fun_name=_program_key(kw.get("fun_name")))
    except Exception:
        pass


def _on_duration(name: str, secs: float, **kw) -> None:
    if name == _RETRIEVAL_EVENT:
        with _METRICS_LOCK:
            _METRICS["cache_retrieval_s"] += float(secs)
        return
    phase = _PHASES.get(name)
    if phase is None:
        return
    try:
        _close_phase(name, phase, float(secs), kw.get("fun_name"))
    except Exception:
        pass  # a listener never fails the compile it watches


def _close_phase(name: str, phase: Tuple[str, str, str], secs: float, fun_name) -> None:
    stack = _OPEN.stack
    # an exit whose entry the listeners did not see (installed inside the
    # phase) is counted whole
    _, counted, inner_s = stack.pop() if stack and stack[-1][0] == name else (name, True, 0.0)
    if stack:
        stack[-1][2] += secs if counted else inner_s
    if not counted:
        return
    total_key, row_key, region = phase
    tr.stop(region, sync=False)
    own_s = max(secs - inner_s, 0.0)
    key = _program_key(fun_name)  # here, not for each of a step's hundreds of inner traces
    with _METRICS_LOCK:
        row = _PROGRAMS.get(key)
        if row is None:
            row = _PROGRAMS[key] = {
                "n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                "t_first": time.time() - secs - _T_INSTALLED,
            }
        _METRICS[total_key] += own_s
        row[row_key] += own_s
        if name == _BACKEND_EVENT:
            _METRICS["programs"] += 1
            row["n"] += 1


def _program_key(fun_name) -> str:
    name = str(fun_name)
    m = _WRAPPED_NAME_RE.match(name)
    return m.group(1) if m else name


def install_metrics_listeners() -> None:
    """Idempotently subscribe the counters to jax.monitoring. Must run
    before the compiles it should observe; listeners cannot be removed, so
    there is exactly one registration per process."""
    global _LISTENERS_INSTALLED, _T_INSTALLED
    with _METRICS_LOCK:
        if _LISTENERS_INSTALLED:
            return
        _LISTENERS_INSTALLED = True
        _T_INSTALLED = time.time()
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_metrics() -> Dict[str, float]:
    """Snapshot of the process-wide compile counters, a flat dict of
    numbers (callers take differences over every key): cache hits and
    misses, ``programs`` built or fetched (backend-compile events), and
    cumulative seconds of tracing (``trace_s``), lowering (``lower_s``),
    ``compile_or_get_cached`` (``backend_compile_s``: the XLA compile cold,
    the fetch warm) and, inside that, cache retrieval."""
    with _METRICS_LOCK:
        return dict(_METRICS)


def compile_programs() -> Dict[str, Dict[str, float]]:
    """The same seconds by program: ``fun_name -> {n, trace_s, lower_s,
    backend_s, t_first}``: executables built or fetched, the three phases'
    sums (over programs they equal ``compile_metrics()``'s) and seconds from
    the listeners' installation to the program's first phase. Process-wide
    like ``compile_metrics()``. Retrieval seconds carry no name in jax and
    stay a total."""
    with _METRICS_LOCK:
        return {k: dict(v) for k, v in _PROGRAMS.items()}


def _metrics_delta(before: Dict[str, float]) -> Dict[str, float]:
    now = compile_metrics()
    return {k: now[k] - before.get(k, 0) for k in now}


def _programs_delta(before: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """``compile_programs()`` since ``before``: the programs that ran a
    phase since, with that interval's calls and seconds."""
    out = {}
    for name, row in compile_programs().items():
        was = before.get(name, {})
        d = {k: row[k] - was.get(k, 0) for k in ("n", "trace_s", "lower_s", "backend_s")}
        if any(d.values()):
            out[name] = d
    return out


# ---------------------------------------------------------------------------
# communication accounting: collective ops + bytes from the compiled HLO
# ---------------------------------------------------------------------------

# per-chip ICI bandwidth by TPU generation, bytes/second (public figures,
# same table discipline as PEAK_FLOPS in obs/telemetry.py) — the divisor of
# the collective-time estimate. CPU/unknown gets a deliberately modest
# figure so the estimate stays an ESTIMATE, never a claim.
ICI_BYTES_PER_S = {
    "v6": 400e9,
    "v5p": 600e9,
    "v5": 200e9,  # v5e / "TPU v5 lite"
    "v4": 300e9,
}


def ici_bytes_per_s(device_kind: str) -> float:
    kind = str(device_kind).lower()
    for key, val in ICI_BYTES_PER_S.items():
        if key in kind:
            return val
    return 50e9


# result-shape + op-name of one collective instruction in optimized HLO
# text. Async pairs count once: the `-start` op is matched, the matching
# `-done` never is (after the base op name only `-start(` or `(` match).
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+(?P<shape>\([^)]*\)|[a-zA-Z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?P<start>-start)?\("
)
_HLO_SHAPE_TOKEN_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(shape: str, largest_only: bool = False) -> int:
    """Bytes of one HLO result shape (scalar, array, or tuple).
    ``largest_only`` keeps just the biggest tuple component — the async
    ``-start`` forms return ``(operand, destination, ...)`` tuples whose
    operand entries alias buffers already counted, so summing them would
    roughly double the sync form's figure."""
    sizes = []
    for dtype, dims in _HLO_SHAPE_TOKEN_RE.findall(shape):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _HLO_DTYPE_BYTES.get(dtype, 4))
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


def collective_census(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Count collective instructions and their per-device result bytes in
    an optimized HLO module: ``{op: {"count": n, "bytes": b}}``. The text
    is the PER-DEVICE SPMD program, so bytes are what each device's
    collective touches per step — the figure the ICI/DCN estimate divides.
    """
    out: Dict[str, Dict[str, float]] = {}
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        op = m.group("op")
        entry = out.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _shape_bytes(
            m.group("shape"), largest_only=m.group("start") is not None
        )
    return out


def summarize_comm(
    census: Dict[str, Dict[str, float]],
    flops: Optional[float],
    device_kind: str,
) -> Dict[str, Any]:
    """One spec's collective table + the compute-vs-comm step-time
    decomposition: ``comm_time_est_s`` = bytes / per-chip ICI bandwidth,
    ``compute_time_est_s`` = XLA-counted FLOPs / chip peak,
    ``comm_fraction_est`` their ratio — the direct instrument for the MFU
    hunt (a spec whose fraction dominates is bandwidth-bound, and no
    kernel fusion will move it)."""
    from ..obs.telemetry import peak_flops

    bytes_total = float(sum(e["bytes"] for e in census.values()))
    ops_total = int(sum(e["count"] for e in census.values()))
    comm_t = bytes_total / ici_bytes_per_s(device_kind)
    peak = peak_flops(device_kind)  # None off the listed TPU generations
    compute_t = float(flops) / peak if flops and peak else None
    fraction = None
    if compute_t is not None and (comm_t + compute_t) > 0:
        fraction = comm_t / (comm_t + compute_t)
    return {
        "collectives": {k: dict(v) for k, v in sorted(census.items())},
        "ops_total": ops_total,
        "bytes_total": int(bytes_total),
        "comm_time_est_s": comm_t,
        "compute_time_est_s": compute_t,
        "comm_fraction_est": fraction,
    }


# ---------------------------------------------------------------------------
# persistent compilation cache wiring
# ---------------------------------------------------------------------------


def cache_dir_active() -> Optional[str]:
    """The persistent cache directory jax currently writes to, or None."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def _reset_jax_cache_object() -> None:
    """jax materializes its persistent-cache object at most once per
    process (``compilation_cache._get_cache``), silently ignoring later
    ``jax_compilation_cache_dir`` changes — reset it so a re-pointed
    directory actually takes effect (tests, the BENCH_COMPILE cold/warm
    A/B)."""
    from jax.experimental.compilation_cache import compilation_cache as _jcc

    _jcc.reset_cache()


def set_cache_dir(
    path: Optional[str], min_compile_secs: Optional[float] = None
) -> Optional[str]:
    """Point jax's persistent compilation cache at ``path`` (created).
    ``min_compile_secs`` lowers the write threshold (jax default: 1s — CPU
    test compiles would never be cached without 0). ``None`` path disables
    the cache. The program's own entry points go through
    ``setup_compile_cache``; calling this directly with a path is for tests
    that need a private directory."""
    import jax

    if path is None:
        if cache_dir_active() is not None:
            jax.config.update("jax_compilation_cache_dir", None)
            _reset_jax_cache_object()
        return None
    os.makedirs(path, exist_ok=True)
    if cache_dir_active() != path:
        jax.config.update("jax_compilation_cache_dir", path)
        _reset_jax_cache_object()
    if min_compile_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_compile_secs)
        )
        # a 0-second threshold means "cache everything" — drop the entry-size
        # floor too, or trivial test-sized executables still skip the disk
        if float(min_compile_secs) <= 0:
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_metrics_listeners()
    return path


# <checkout>/logs/xla_cache: anchored on this file, so the place a later
# process looks does not move with the working directory, the run name, the
# pid or the clock
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "logs",
    "xla_cache",
)


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives — the ONE placement
    rule, shared by every entry point (api.py, bench.py, chip_smoke.py):
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax has
    already adopted it; the program then issues no
    ``jax_compilation_cache_dir`` update), else ``<checkout>/logs/xla_cache``.
    A cache whose path moves with a hyper-parameter never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def setup_compile_cache(
    training: Optional[Dict[str, Any]] = None,
) -> Optional[str]:
    """Activate the persistent compilation cache at ``compile_cache_dir()``.

    ``HYDRAGNN_COMPILE_CACHE=0/off/none/false`` or
    ``Training.compile_cache_dir: false`` disable it (and DEACTIVATE a
    directory a previous run in this process pointed jax at);
    ``HYDRAGNN_COMPILE_CACHE=1`` forces it back on over a config ``false``.
    ``HYDRAGNN_COMPILE_CACHE_MIN_SECS`` lowers jax's min-compile-time write
    threshold (the smokes pin 0 so CPU-sized compiles are cached too).
    Returns the active directory, or None."""
    off = ("0", "off", "none", "false", "")
    env = envflags.env_str("HYDRAGNN_COMPILE_CACHE")
    env = None if env is None else env.strip().lower()
    cfg = (training or {}).get("compile_cache_dir")
    if isinstance(cfg, str):
        cfg = False if cfg.strip().lower() in off else cfg
    if env not in (None, "1", *off) or cfg not in (None, True, False):
        # outside input: a path here used to MOVE the cache; silently
        # ignoring it would leave the user looking in the wrong directory
        raise ValueError(
            "HYDRAGNN_COMPILE_CACHE / Training.compile_cache_dir only switch "
            f"the cache on or off (got {env!r} / {cfg!r}); place it with "
            "JAX_COMPILATION_CACHE_DIR"
        )
    if env in off or (cfg is False and env != "1"):
        return set_cache_dir(None)
    min_secs = envflags.env_str("HYDRAGNN_COMPILE_CACHE_MIN_SECS")
    return set_cache_dir(
        compile_cache_dir(), float(min_secs) if min_secs is not None else None
    )


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------

# one leaf of a trace signature: (tree path, shape, dtype, weak_type)
_Leaf = Tuple[str, Tuple[int, ...], str, bool]
_Sig = Tuple[_Leaf, ...]


def _signature_of(args) -> _Sig:
    """Abstract signature of a (pytree of) traced argument(s): per-leaf
    (path, shape, dtype, weak_type). Called from inside traced function
    bodies, where leaves are tracers carrying ``.aval``."""
    import jax

    leaves = []
    for path, x in jax.tree_util.tree_flatten_with_path(args)[0]:
        aval = getattr(x, "aval", None)
        if aval is not None:
            shape = tuple(getattr(aval, "shape", ()))
            dtype = str(getattr(aval, "dtype", type(x).__name__))
            weak = bool(getattr(aval, "weak_type", False))
        else:  # non-array leaf (should not happen under jit; be tolerant)
            shape = tuple(np.shape(x))
            dtype = str(np.asarray(x).dtype) if np.ndim(x) else type(x).__name__
            weak = isinstance(x, (int, float, complex, bool))
        leaves.append((jax.tree_util.keystr(path), shape, dtype, weak))
    return tuple(leaves)


def _diff_sigs(got: _Sig, ref: _Sig, limit: int = 8) -> List[str]:
    """Human-readable per-leaf diff of two signatures (by tree path)."""
    ref_by_path = {p: (s, d, w) for p, s, d, w in ref}
    got_paths = {p for p, *_ in got}
    out = []
    for p, s, d, w in got:
        have = ref_by_path.get(p)
        if have is None:
            out.append(f"  {p}: NEW leaf {d}{list(s)}{' weak' if w else ''}")
        elif have != (s, d, w):
            rs, rd, rw = have
            out.append(
                f"  {p}: {rd}{list(rs)}{' weak' if rw else ''} -> "
                f"{d}{list(s)}{' weak' if w else ''}"
            )
    for p, s, d, w in ref:
        if p not in got_paths:
            out.append(f"  {p}: leaf DROPPED ({d}{list(s)})")
    if len(out) > limit:
        out = out[:limit] + [f"  ... {len(out) - limit} more differing leaves"]
    return out


class _TraceSentinel:
    """Process-wide trace counter per step builder, armable against a known
    specialization set. ``note`` is called from traced function bodies —
    i.e. exactly once per jit trace — so its counts ARE the retrace
    census."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sigs: Dict[str, List[_Sig]] = {}
        self._armed = False
        self._policy = "warn"
        self._known: Dict[str, set] = {}
        self._violations: List[str] = []

    def note(self, name: str, args) -> None:
        sig = _signature_of(args)
        with self._lock:
            self._sigs.setdefault(name, []).append(sig)
            if not self._armed:
                return
            known = self._known.get(name, set())
            if sig in known:
                # a re-trace of a known specialization: jit caches make this
                # impossible for a live builder — it means a builder was
                # rebuilt or a cache was invalidated mid-run. Beyond the
                # ladder budget either way.
                msg = (
                    f"retrace sentinel: {name} re-traced an already-known "
                    "specialization after warm-up (rebuilt step function or "
                    "invalidated jit cache?) — one extra XLA compile"
                )
            else:
                msg = self._unknown_sig_message(name, sig, known)
            # number the message: a recurring violation (step rebuilt every
            # epoch) would otherwise emit byte-identical warnings that
            # Python's default filter dedups down to ONE — silencing every
            # repeat of an each-time-paid recompile
            msg = f"{msg} [violation #{len(self._violations) + 1}]"
            self._violations.append(msg)
            policy = self._policy
            n_violations = len(self._violations)
        # structured incident record (obs/events.py) with the active trace
        # context — a violation inside a sampled serving request carries the
        # request's trace_id into the flight-recorder window
        try:
            from ..obs.events import EV_RETRACE_VIOLATION
            from ..obs.events import emit as _emit_event

            _emit_event(
                EV_RETRACE_VIOLATION,
                severity="error" if policy == "error" else "warn",
                builder=name,
                violation=n_violations,
            )
        except Exception:
            pass
        if policy == "error":
            raise RetraceError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    @staticmethod
    def _unknown_sig_message(name: str, sig: _Sig, known: set) -> str:
        nearest = None
        best = None
        for k in known:
            d = len(_diff_sigs(sig, k, limit=10 ** 6))
            if best is None or d < best:
                best, nearest = d, k
        lines = [
            f"retrace sentinel: {name} traced a specialization outside the "
            "warmed ladder budget after warm-up completed — a silent "
            "recompile (one full XLA compile per occurrence)."
        ]
        if nearest is not None:
            lines.append(
                f"aval diff vs the nearest known specialization "
                f"({best} differing leaves):"
            )
            lines.extend(_diff_sigs(sig, nearest))
        else:
            lines.append(f"no known specializations recorded for {name!r}")
        return "\n".join(lines)

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {k: len(v) for k, v in self._sigs.items()}

    def arm(self, policy: str) -> None:
        """Freeze every signature seen so far as the known set; later traces
        are violations handled per ``policy``."""
        with self._lock:
            self._known = {k: set(v) for k, v in self._sigs.items()}
            self._policy = policy
            self._armed = True

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    @property
    def armed(self) -> bool:
        return self._armed

    def violations(self) -> List[str]:
        with self._lock:
            return list(self._violations)

    def reset(self) -> None:
        with self._lock:
            self._sigs.clear()
            self._known.clear()
            self._violations.clear()
            self._armed = False
            self._policy = "warn"


_SENTINEL = _TraceSentinel()


def sentinel() -> _TraceSentinel:
    return _SENTINEL


def note_trace(name: str, args) -> None:
    """Record one trace of step builder ``name`` (call from the traced
    function body — it executes exactly once per trace). No-op cost at run
    time: the call does not appear in the jaxpr."""
    _SENTINEL.note(name, args)


def serve_warmup(
    fn,
    state,
    templates,
    policy: str = "error",
    label: str = "serve",
) -> Tuple[List[Tuple[str, float]], List[Tuple[str, str]], float]:
    """Serving-side blocking warm-up: CALL the jit object on one template
    batch per ladder level and block until each executes.

    Unlike the training plane's ``lower().compile()`` jobs (whose AOT
    executables are only reachable from the call path through the persistent
    cache), calling the jit object directly lands every specialization in
    its OWN executable cache — so once this returns, the serve loop's first
    organic visit to any level is a pure cache hit regardless of persistent-
    cache configuration (the persistent cache still buys down *restarts*).
    Readiness == zero-retrace steady state by construction.

    On full coverage the retrace sentinel is armed at ``policy`` (serving
    default ``error``: an unknown specialization under live traffic is a
    correctness bug). Returns ``(compiled, errors, last_exec_s)`` where
    ``compiled`` is [(label, seconds)] per level, ``errors`` the failures
    (arming is skipped if any), and ``last_exec_s`` the warm re-execution
    time of the final (worst-case) level — the serving-latency seed for the
    shed estimator."""
    if policy not in RETRACE_POLICIES:
        raise ValueError(
            f"retrace policy {policy!r} must be one of {RETRACE_POLICIES}"
        )
    import jax

    install_metrics_listeners()
    compiled: List[Tuple[str, float]] = []
    errors: List[Tuple[str, str]] = []
    last_exec_s = 0.0
    for spec, tmpl in templates:
        name = f"{label}:{spec.n_nodes}n/{spec.n_edges}e"
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(fn(state, tmpl))
        except Exception as e:  # noqa: BLE001 — reported, never raised here
            errors.append((name, f"{type(e).__name__}: {e}"))
            continue
        compiled.append((name, time.perf_counter() - t0))
    if templates and not errors:
        # warm re-execution of the worst level: compile excluded, pure step
        spec, tmpl = templates[-1]
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(fn(state, tmpl))
            last_exec_s = time.perf_counter() - t0
        except Exception:  # pragma: no cover - first call succeeded above
            pass
        _SENTINEL.arm(policy)
    return compiled, errors, last_exec_s


def attach_lower_fn(fn, jitted, batch_transform: Optional[Callable] = None,
                    batch_argnum: int = 1):
    """Mark a step-fn *wrapper* as AOT-lowerable: ``fn`` is what the loop
    calls (e.g. the mesh path's ``lambda s, b, r: _pstep(s, promote_batch(b,
    mesh), r)``), ``jitted`` the underlying jit object, ``batch_transform``
    the wrapper's batch preprocessing. The compile plane lowers through the
    SAME jit object and transform the loop uses, so the warmed executable is
    byte-identical to the organic one."""

    def _lower(*args):
        if batch_transform is not None:
            args = list(args)
            args[batch_argnum] = batch_transform(args[batch_argnum])
        return jitted.lower(*args)

    fn._compile_plane_lower = _lower
    return fn


def _aval_like(x):
    """Abstract stand-in for one (about-to-be-donated) argument leaf:
    shape/dtype/weak_type via the aval, plus the committed sharding when
    one exists — everything ``jit.lower`` specializes on, so a program
    lowered from these is identical to the organic call's."""
    import jax

    aval = jax.typeof(x)
    sharding = getattr(x, "sharding", None)
    # only MESH shardings are program-relevant; a plain array's implicit
    # SingleDeviceSharding must stay implicit (an explicit one would mark
    # the aval committed and lower a different — device-pinned — program
    # than the organic call compiled)
    if isinstance(sharding, jax.sharding.NamedSharding):
        return jax.ShapeDtypeStruct(
            aval.shape, aval.dtype, sharding=sharding,
            weak_type=bool(getattr(aval, "weak_type", False)),
        )
    return aval


def _lower_fn_of(fn) -> Optional[Callable]:
    lower = getattr(fn, "_compile_plane_lower", None)
    if lower is not None:
        return lower
    return getattr(fn, "lower", None)


# ---------------------------------------------------------------------------
# the plane
# ---------------------------------------------------------------------------


class CompilePlane:
    """Per-run orchestrator: collect the ladder's warm-up jobs, run them
    (inline or in a worker thread), arm the sentinel when coverage is
    complete, and report compile observability at run end."""

    def __init__(
        self,
        mode: str = "background",
        retrace_policy: str = "warn",
        log_name: str = "run",
        remat_policy: str = "full",
    ):
        if mode not in PRECOMPILE_MODES:
            raise ValueError(
                f"precompile mode {mode!r} must be one of {PRECOMPILE_MODES}"
            )
        if retrace_policy not in RETRACE_POLICIES:
            raise ValueError(
                f"retrace_policy {retrace_policy!r} must be one of "
                f"{RETRACE_POLICIES}"
            )
        self.mode = mode
        self.retrace_policy = retrace_policy
        self.log_name = log_name
        # Training.remat_policy, carried so the flops/MFU accounting below
        # records WHICH recompute schedule its XLA-counted step FLOPs were
        # measured under (remat changes the counted FLOPs — a policy A/B
        # without this field would bank incomparable MFU numbers)
        self.remat_policy = remat_policy
        self.cache_dir: Optional[str] = None
        self.jobs: List[Tuple[str, Callable]] = []
        self.compiled: List[Tuple[str, float]] = []  # (label, secs)
        self.errors: List[Tuple[str, str]] = []
        # XLA-counted FLOPs per warmed specialization (label -> flops),
        # harvested from the AOT-compiled executables' cost_analysis — the
        # flops-audit recipe (run-scripts/flops_audit.py) at zero extra
        # compile cost. The telemetry plane's MFU gauge reads this table
        # (obs/telemetry.py attach_flops); dict writes are atomic under the
        # GIL, so the background worker publishes lock-free.
        self.flops_by_spec: Dict[str, float] = {}
        # HBM accounting (obs/memory.py): memory_analysis() figures per
        # warmed specialization, harvested beside the flops — argument /
        # output / temp / peak bytes. Published as hydragnn_hbm_* gauges
        # and rendered in report(); the flight recorder dumps the process
        # table as its OOM-forensics section.
        self.memory_by_spec: Dict[str, Dict[str, float]] = {}
        # communication accounting (collective_census): per warmed
        # specialization, the collective ops + per-device bytes walked out
        # of the compiled HLO and the compute-vs-comm decomposition —
        # published as hydragnn_comm_* gauges, rendered in report(), and
        # read per window by the telemetry layer (attach_comm). Dict
        # writes are atomic under the GIL like flops_by_spec.
        self.comm_by_spec: Dict[str, Dict[str, Any]] = {}
        # MFU-estimate fallback (obs/telemetry.py attach_flops consumer):
        # with precompile off nothing fills flops_by_spec — when armed via
        # enable_flops_fallback(), the first organic step's executable is
        # lowered + compiled through the persistent cache and its
        # cost/memory analysis harvested instead
        self._organic_flops = False
        self.time_to_first_step: Optional[float] = None
        self._t0: Optional[float] = None
        self._m0: Dict[str, float] = {}
        self._p0: Dict[str, Dict[str, float]] = {}
        self._counts0: Dict[str, int] = {}
        self._viol0 = 0
        self._worker: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- job collection ----------------------------------------------------

    def _collect_jobs(self, step_fn, eval_fn, state, train_loader,
                      val_loader, test_loader, rng) -> None:
        lower_step = _lower_fn_of(step_fn) if step_fn is not None else None
        lower_eval = _lower_fn_of(eval_fn) if eval_fn is not None else None

        def template_list(loader):
            fn = getattr(loader, "spec_template_batches", None)
            return fn() if fn is not None else []

        if lower_step is not None and train_loader is not None:
            for spec, tmpl in template_list(train_loader):
                self.jobs.append(
                    (
                        f"train:{spec.n_nodes}n/{spec.n_edges}e",
                        lambda t=tmpl: lower_step(state, t, rng),
                    )
                )
        if lower_eval is not None:
            seen = set()
            for loader in (val_loader, test_loader):
                if loader is None:
                    continue
                for spec, tmpl in template_list(loader):
                    if spec in seen:
                        continue  # val/test share the ladder (api.py)
                    seen.add(spec)
                    self.jobs.append(
                        (
                            f"eval:{spec.n_nodes}n/{spec.n_edges}e",
                            lambda t=tmpl: lower_eval(state, t),
                        )
                    )

    # -- lifecycle ---------------------------------------------------------

    def launch(self, step_fn, eval_fn, state, train_loader,
               val_loader=None, test_loader=None, rng=None, skip_eval=False):
        """Start the plane for one run. Returns ``step_fn`` instrumented
        with a first-step timer; warm-up runs per ``self.mode``. Without an
        active persistent cache directory ``blocking``/``background``
        degrade to ``off``: the call path could never reuse the AOT
        executables, so warm-up would burn a core for nothing. Mode
        ``analysis`` is the explicit exception — it runs the (blocking)
        warm-up regardless, accepting that without a cache the
        executables are unreachable, because the harvests are the point:
        the FLOPs/HBM/collective tables and the MFU gauge where a
        persistent cache is switched off (shared-FS quota; the cache-less
        children of run-scripts/fleet_smoke.py)."""
        from ..utils.timers import Timer

        install_metrics_listeners()
        self.cache_dir = cache_dir_active()
        self._t0 = time.perf_counter()
        # started HERE so blocking-mode warm-up is inside the span, exactly
        # like the report's time_to_first_step field (both measure launch ->
        # first completed step)
        ttfs_timer = Timer("time_to_first_step").start()
        self._m0 = compile_metrics()
        self._p0 = compile_programs()
        self._counts0 = _SENTINEL.counts()
        # the sentinel is process-global; baseline its violation count so
        # this plane's report never attributes an earlier run's retraces
        # to itself (in-process HPO trials, repeated run_training)
        self._viol0 = len(_SENTINEL.violations())
        if self.mode in ("blocking", "background") and self.cache_dir is None:
            self.mode = "off"
        if self.mode != "off":
            import jax

            if rng is None:
                rng = jax.random.PRNGKey(0)
            self._collect_jobs(
                step_fn, None if skip_eval else eval_fn, state,
                train_loader, val_loader, test_loader, rng,
            )
            if self.mode in ("blocking", "analysis"):
                with Timer("compile_plane_warmup"):
                    self._run_jobs()
                self._maybe_arm()
            elif self.jobs:
                self._worker = threading.Thread(
                    target=self._worker_main, daemon=True,
                    name="compile-plane-warmup",
                )
                self._worker.start()

        # first-step timer: time from plane launch to the first completed
        # optimizer step (the restart-latency metric the cache is buying
        # down); one flag check per call afterwards. The Timer entry
        # "time_to_first_step" records the same launch-to-done span as the
        # report field (started at launch above, stopped after the first
        # step; never stopped — so never recorded — if no step runs); the
        # tracer region "first_step" covers only the step call itself (a
        # launch-scoped xprof annotation would span half of epoch 0 and
        # break the tracer's LIFO unwind for regions opened in between).
        done = {"first": True}
        plane = self

        def instrumented(st, batch, step_rng, _fn=step_fn):
            if not done["first"]:
                return _fn(st, batch, step_rng)
            import jax

            # organic-executable harvest (enable_flops_fallback): the
            # donated STATE's buffers are dead after the step, so its
            # avals (shape/dtype/weak_type + committed sharding — pure
            # metadata, no trace, no copy) are captured here; the actual
            # lower()+compile() happens AFTER the first step, off the
            # time_to_first_step measurement (lowering is a full second
            # Python trace — on the critical path it would inflate the
            # first-step latency the bench gate bounds). batch/rng are
            # not donated, so they lower live.
            state_avals = None
            if plane._organic_flops:
                try:
                    state_avals = jax.tree_util.tree_map(_aval_like, st)
                except Exception:
                    state_avals = None
            tr.start("first_step")
            out = _fn(st, batch, step_rng)
            jax.block_until_ready(out[1])
            tr.stop("first_step")
            done["first"] = False
            plane.time_to_first_step = time.perf_counter() - plane._t0
            ttfs_timer.stop()
            if state_avals is not None:
                try:
                    lower = _lower_fn_of(_fn)
                    # compile() is a persistent-cache retrieval of the
                    # entry the organic call just wrote (aval-faithful
                    # lowering: weak types + shardings preserved, so the
                    # program is byte-identical to the organic one)
                    plane._harvest_analyses(
                        plane._batch_label(batch),
                        lower(state_avals, batch, step_rng).compile(),
                    )
                except Exception as e:
                    warnings.warn(
                        "organic FLOPs/HBM harvest failed "
                        f"({type(e).__name__}: {e}); the MFU gauge stays "
                        "unpublished for this run",
                        RuntimeWarning,
                        stacklevel=2,
                    )
            return out

        return instrumented

    def _run_jobs(self) -> None:
        for label, thunk in self.jobs:
            if self._stop.is_set():
                return
            t0 = time.perf_counter()
            try:
                compiled = thunk().compile()
            except Exception as e:  # warm-up must never kill training
                self.errors.append((label, f"{type(e).__name__}: {e}"))
                continue
            self.compiled.append((label, time.perf_counter() - t0))
            self._harvest_analyses(label, compiled)

    def _harvest_analyses(self, label: str, compiled) -> None:
        """Best-effort cost (FLOPs) + memory (HBM) harvest from one
        compiled executable — the zero-extra-compile observability dividend
        of holding the executable at all."""
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            flops = float(cost.get("flops", 0.0))
            if flops > 0:
                self.flops_by_spec[label] = flops
        except Exception:  # cost analysis is best-effort observability
            pass
        try:
            from ..obs import memory as obs_memory

            stats = obs_memory.record(label, compiled)
            if stats is not None:
                self.memory_by_spec[label] = stats
        except Exception:  # memory analysis availability is backend-bound
            pass
        # collective census: walk the compiled per-device HLO for
        # collective ops + bytes. Multi-device programs only — a
        # single-device executable has no collectives, and its (possibly
        # tens of MB) HLO text is not worth materializing to prove it.
        try:
            import jax

            if jax.device_count() > 1:
                census = collective_census(compiled.as_text())
                summary = summarize_comm(
                    census,
                    self.flops_by_spec.get(label),
                    jax.devices()[0].device_kind,
                )
                self.comm_by_spec[label] = summary
                self._publish_comm(label, summary)
        except Exception:  # the census is best-effort observability
            pass

    @staticmethod
    def _publish_comm(label: str, summary: Dict[str, Any]) -> None:
        """hydragnn_comm_* gauges for one spec (best-effort)."""
        try:
            from ..obs.registry import registry

            reg = registry()
            g_ops = reg.gauge(
                "hydragnn_comm_collectives",
                "Collective instructions per compiled specialization "
                "(HLO census, train/compile_plane.py)",
                labelnames=("spec", "collective"),
            )
            g_bytes = reg.gauge(
                "hydragnn_comm_bytes",
                "Per-device bytes each collective touches per step",
                labelnames=("spec", "collective"),
            )
            for op, entry in summary["collectives"].items():
                g_ops.set(entry["count"], spec=label, collective=op)
                g_bytes.set(entry["bytes"], spec=label, collective=op)
            reg.gauge(
                "hydragnn_comm_bytes_total",
                "Per-device collective bytes per step, all collectives",
                labelnames=("spec",),
            ).set(summary["bytes_total"], spec=label)
            if summary["comm_fraction_est"] is not None:
                reg.gauge(
                    "hydragnn_comm_fraction_est",
                    "Estimated fraction of step time inside collectives "
                    "(bytes/ICI-bandwidth vs FLOPs/peak)",
                    labelnames=("spec",),
                ).set(summary["comm_fraction_est"], spec=label)
        except Exception:
            pass

    def _worker_main(self) -> None:
        from ..utils.timers import Timer

        with Timer("compile_plane_warmup"):
            self._run_jobs()
        self._maybe_arm()

    def _maybe_arm(self) -> None:
        # arm only on FULL coverage: a failed warm-up job means its organic
        # visit will legitimately trace later — flagging it would turn a
        # warm-up hiccup into a spurious (possibly fatal) sentinel report
        if self.jobs and not self.errors and not self._stop.is_set():
            _SENTINEL.arm(self.retrace_policy)

    def train_flops_for(self, key: Tuple[int, int]) -> Optional[float]:
        """FLOPs of the train-step specialization padded to ``key`` =
        (per-shard nodes, edges), or None while warm-up has not compiled
        it (background mode fills the table as it goes)."""
        return self.flops_by_spec.get(f"train:{key[0]}n/{key[1]}e")

    def train_comm_for(self, key: Tuple[int, int]) -> Optional[Dict[str, Any]]:
        """Collective table of the train-step specialization padded to
        ``key`` (obs/telemetry.py ``attach_comm`` consumer), or None while
        its HLO has not been walked."""
        return self.comm_by_spec.get(f"train:{key[0]}n/{key[1]}e")

    def enable_flops_fallback(self) -> None:
        """Arm the organic cost/memory harvest for ``precompile: off``
        runs (the loop calls this when telemetry wants an MFU estimate):
        ``flops_by_spec`` is otherwise populated only by AOT warm-up, so
        mode ``off`` silently zeroed the MFU gauge. With a persistent
        cache active, the first organic step's program is lowered (one
        extra Python trace) and ``compile()``d through the cache (a
        retrieval, not a recompile — the organic call just wrote the
        entry) purely to hold its analyses. Without a cache the fallback
        would pay a FULL duplicate XLA compile, so it warns once naming
        the cause instead."""
        if self.mode != "off":
            return  # warm-up fills the table; nothing to fall back from
        if self.cache_dir is None:
            warnings.warn(
                "telemetry MFU estimate has no FLOPs source: "
                "Training.precompile is 'off' (or degraded to off because "
                "no persistent compilation cache is active) and no cache "
                "directory is available to harvest the organic executable "
                "through — hydragnn_mfu_estimate will not be published. "
                "Enable Training.precompile or the compile cache.",
                RuntimeWarning,
                stacklevel=2,
            )
            return
        self._organic_flops = True

    @staticmethod
    def _batch_label(batch, prefix: str = "train") -> str:
        """Spec label of a (possibly device-stacked) batch from its mask
        shapes — the same per-shard (nodes, edges) key the telemetry
        layer's flops lookup uses (obs/telemetry.py _batch_census)."""
        return (
            f"{prefix}:{int(batch.node_mask.shape[-1])}n/"
            f"{int(batch.edge_mask.shape[-1])}e"
        )

    def finish(self, verbosity: int = 0) -> Dict[str, Any]:
        """End the run: stop/join the worker, disarm the sentinel, return
        (and at verbosity > 0 print) the report."""
        if self._worker is not None and self._worker.is_alive():
            # a still-compiling worker gets the FULL grace to drain the
            # queue — the remaining AOT compiles populate the persistent
            # cache for the next restart, which is the whole point (the
            # compile smoke's cold leg asserts full ladder coverage on a
            # run shorter than its warm-up). Only after the grace expires
            # is the stop flag set: the leaked daemon thread then exits at
            # its next job boundary instead of hanging teardown on a
            # wedged XLA compile.
            self._worker.join(timeout=_WORKER_JOIN_TIMEOUT_S)
            if self._worker.is_alive():
                self._stop.set()
                warnings.warn(
                    "compile-plane warm-up worker still compiling "
                    f"{_WORKER_JOIN_TIMEOUT_S}s after the run ended; "
                    "leaking the daemon thread",
                    RuntimeWarning,
                    stacklevel=2,
                )
        rep = self.report()
        _SENTINEL.disarm()
        if verbosity > 0:
            for line in format_report(rep).splitlines():
                print(f"[{self.log_name}] {line}", file=sys.stderr)
        return rep

    def report(self) -> Dict[str, Any]:
        delta = _metrics_delta(self._m0) if self._m0 else compile_metrics()
        counts = _SENTINEL.counts()
        traces = {
            k: v - self._counts0.get(k, 0)
            for k, v in counts.items()
            if v - self._counts0.get(k, 0)
        }
        return {
            "mode": self.mode,
            "cache_dir": self.cache_dir,
            "remat_policy": self.remat_policy,
            "specializations": len(self.jobs),
            "precompiled": len(self.compiled),
            "compile_time_s": round(
                sum(s for _, s in self.compiled) or delta["backend_compile_s"], 3
            ),
            "backend_compile_s": round(delta["backend_compile_s"], 3),
            "cache_hits": int(delta["cache_hits"]),
            "cache_misses": int(delta["cache_misses"]),
            # where the start's compile seconds went (jax.monitoring's three
            # phases, by program name): tracing, lowering, and above as
            # backend_compile_s the XLA compile or, warm, the cache fetch
            "trace_s": round(delta["trace_s"], 3),
            "lower_s": round(delta["lower_s"], 3),
            "cache_retrieval_s": round(delta["cache_retrieval_s"], 3),
            "programs": int(delta["programs"]),
            "top_programs": top_programs(_programs_delta(self._p0), 5),
            "time_to_first_step": (
                round(self.time_to_first_step, 3)
                if self.time_to_first_step is not None
                else None
            ),
            "traces": traces,
            "violations": len(_SENTINEL.violations()) - self._viol0,
            "warmup_errors": list(self.errors),
            # per-spec HBM table (memory_analysis harvest, obs/memory.py):
            # peak bytes per warmed specialization + the run's worst case —
            # the headroom figure that used to be guesswork before an OOM
            "hbm_by_spec": {
                label: int(stats["peak_bytes"])
                for label, stats in sorted(self.memory_by_spec.items())
            },
            "hbm_peak_bytes": (
                max(
                    int(s["peak_bytes"]) for s in self.memory_by_spec.values()
                )
                if self.memory_by_spec
                else None
            ),
            # per-spec collective table (HLO census): bytes + op count +
            # the compute-vs-comm decomposition — ROADMAP item 4's direct
            # instrument (a comm-bound spec shows up HERE, not in a guess)
            "comm_by_spec": {
                label: {
                    "bytes_total": int(c["bytes_total"]),
                    "ops_total": int(c["ops_total"]),
                    "comm_fraction_est": (
                        round(c["comm_fraction_est"], 6)
                        if c["comm_fraction_est"] is not None
                        else None
                    ),
                }
                for label, c in sorted(self.comm_by_spec.items())
            },
            "comm_bytes_peak": (
                max(
                    int(c["bytes_total"]) for c in self.comm_by_spec.values()
                )
                if self.comm_by_spec
                else None
            ),
            # accelerator memory capacity (None on backends that expose
            # no memory_stats, e.g. CPU): the denominator the run
            # doctor's HBM-pressure rule divides hbm_peak_bytes by
            "device_bytes_limit": device_bytes_limit(),
        }


def device_bytes_limit() -> Optional[float]:
    """Per-device memory capacity (obs/memory.py owns the helper — it
    also rides every flight dump's memory.json); kept as a best-effort
    delegate so the report never fails on an obs import problem."""
    try:
        from ..obs.memory import device_bytes_limit as _limit

        return _limit()
    except Exception:
        return None


def top_programs(programs: Dict[str, Dict[str, float]], k: int) -> List[Dict[str, Any]]:
    """The ``k`` costliest rows of a ``compile_programs()`` table by
    ``trace_s + lower_s + backend_s``, as ``{name, n, <the three>}``."""
    cost = lambda row: row["trace_s"] + row["lower_s"] + row["backend_s"]
    rows = sorted(programs.items(), key=lambda kv: cost(kv[1]), reverse=True)[:k]
    return [
        {"name": name, "n": int(row["n"]),
         **{key: round(row[key], 3) for key in ("trace_s", "lower_s", "backend_s")}}
        for name, row in rows
    ]


def format_report(rep: Dict[str, Any]) -> str:
    """Two grep-able lines: the one the chaos/compile smokes parse, then
    where the start's compile seconds went, with the costliest programs as
    ``name*n:trace+lower+backend`` seconds."""
    ttfs = rep.get("time_to_first_step")
    hbm = rep.get("hbm_peak_bytes")
    comm = rep.get("comm_bytes_peak")
    comm_specs = rep.get("comm_by_spec") or {}
    fracs = [
        c["comm_fraction_est"]
        for c in comm_specs.values()
        if c.get("comm_fraction_est") is not None
    ]
    return (
        f"compile plane: mode={rep['mode']} "
        f"remat={rep.get('remat_policy', 'full')} "
        f"precompiled={rep['precompiled']}/{rep['specializations']} "
        f"compile_time_s={rep['compile_time_s']} "
        f"cache_hits={rep['cache_hits']} cache_misses={rep['cache_misses']} "
        f"time_to_first_step={ttfs if ttfs is not None else 'n/a'}s "
        f"traces={sum(rep['traces'].values())} "
        f"violations={rep['violations']} "
        f"hbm_peak={hbm if hbm is not None else 'n/a'} "
        f"comm_bytes_peak={comm if comm is not None else 'n/a'} "
        f"comm_frac_est={round(max(fracs), 4) if fracs else 'n/a'}"
        + (f" warmup_errors={len(rep['warmup_errors'])}"
           if rep["warmup_errors"] else "")
        + "\n"
        f"compile plane start: programs={rep['programs']} "
        f"trace_s={rep['trace_s']} lower_s={rep['lower_s']} "
        f"backend_compile_s={rep['backend_compile_s']} "
        f"cache_retrieval_s={rep['cache_retrieval_s']} top="
        + (",".join(
            f"{p['name']}*{p['n']}:{p['trace_s']}+{p['lower_s']}+{p['backend_s']}"
            for p in rep["top_programs"]
        ) or "n/a")
    )
