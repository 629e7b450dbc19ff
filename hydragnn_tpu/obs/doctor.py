"""Run doctor: automated diagnosis over the observability streams
(docs/OBSERVABILITY.md "Run doctor").

Four PRs built observability *producers* — per-step telemetry
(metrics.jsonl, r7), OTLP spans + events + flight dumps (r8), in-graph
numerics/HBM (r12), fleet aggregation + comm accounting (r13) — and until
now the only consumer was a human hand-correlating six files. The doctor
closes the loop: it ingests every stream a run emits and applies a
rulebook of pathologies the codebase can already exhibit, emitting typed
findings — each with a severity, the concrete evidence records that
triggered it, and a remediation naming the exact config knob.

Modes (``python -m hydragnn_tpu.obs.doctor``):

- ``<run_dir>`` — diagnose one run (also accepts a single flight-dump
  directory: the crash-forensics path works from the black box alone).
  Exit 0 = zero findings, 1 = findings, 2 = usage/IO error.
- ``diff <A> <B>`` — cross-run regression diff: completed-config diff +
  metric / trace-percentile / finding deltas. ``A``/``B`` are run dirs
  or committed ``BENCH_r*.json`` rounds (per-cell deltas; ``--gate``
  cross-checks bench_gate.py's ``gate_verdict.json``). This is the
  promotion-gate primitive for ROADMAP items 3/5.
- ``watch <run_dir>`` — tail a live run's streams and print findings as
  they fire.
- ``trace <trace.jsonl>`` — span-decomposition report (the successor of
  run-scripts/analyze_trace.py for the r8 span plane).

Every stream is parsed through obs/schema.py; invalid or truncated
records degrade to parse warnings, never crashes — a half-written flight
dump is still evidence. The correctness loop is fault-drill-verified:
run-scripts/doctor_smoke.py drives every ``HYDRAGNN_FAULT_*`` injection
point through real runs and asserts the doctor names exactly the planted
pathology, and that a clean run yields zero findings (the false-positive
gate every threshold below is tuned against).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .events import (
    EV_DATA_SKIP,
    EV_ELASTIC_GROW,
    EV_ELASTIC_SHRINK,
    EV_FLEET_DESYNC,
    EV_FLEET_HOST_STALE,
    EV_FLEET_STRAGGLER,
    EV_GUARD_ROLLBACK,
    EV_GUARD_SKIP,
    EV_LOADER_STALL,
    EV_MIX_DEMOTE,
    EV_NUMERICS_PROVENANCE,
    EV_BREAKER_CLOSE,
    EV_BREAKER_OPEN,
    EV_QUANT_DRIFT,
    EV_QUEUE_FULL,
    EV_RELOAD_ROLLBACK,
    EV_REPLICA_BENCHED,
    EV_REPLICA_EXIT,
    EV_REPLICA_RESTART,
    EV_RETRACE_VIOLATION,
    EV_SHED,
    EV_WEDGE,
    severity_rank,
)
from .schema import (
    percentile as _percentile,
    span_duration_ms,
    validate_event_record,
    validate_metrics_record,
    validate_span_record,
)

DOCTOR_SCHEMA_VERSION = 1

# -- finding vocabulary (the rulebook's stable kind names) -------------------
F_INPUT_BOUND = "input_bound"            # host batch build dominates the step
F_RETRACE_STORM = "retrace_storm"        # silent recompiles kept firing
F_PADDING_WASTE = "padding_waste"        # a pad bucket burns its slots
F_NAN_DIVERGENCE = "nan_divergence"      # non-finite steps, with provenance
F_LR_ROLLBACK_LOOP = "lr_rollback_loop"  # rollback policy kept restoring
F_STRAGGLER = "straggler"                # one host's steps are slow
F_DESYNC = "desync"                      # fleet progress skew past bound
F_STALE_HOST = "stale_host"              # host heartbeats went silent
F_HBM_PRESSURE = "hbm_pressure"          # peak HBM near the device limit
F_COMM_DOMINANT = "comm_dominant"        # collectives dominate step time
F_SHED_SPIRAL = "shed_spiral"            # serving kept shedding load
F_QUEUE_SATURATION = "queue_saturation"  # queue wait dominates latency
F_QUARANTINE_ROT = "quarantine_rot"      # data rot: quarantine/demotions
F_LOADER_STALL = "loader_stall"          # loader watchdog fired
F_WEDGED_STEP = "wedged_step"            # serving device step wedged
F_COLD_START = "compile_cold_start"      # warm path regressed to recompiles
F_CRASH = "crash"                        # unexplained crash dump
F_ELASTIC_SHRINK = "elastic_shrink"      # fleet re-laid-out onto fewer hosts
F_ELASTIC_GROW = "elastic_grow"          # fleet re-grew to more hosts
F_REPLICA_FLAP = "replica_flap"          # serving replica crash-looped
F_BREAKER_OPEN = "breaker_open"          # router circuit breaker tripped
F_RELOAD_ROLLBACK = "reload_rollback"    # rolling reload auto-rolled back
F_QUANT_DRIFT = "quant_drift"            # int8 accuracy gate refused a state
F_CACHE_INEFFECTIVE = "cache_ineffective"  # prediction cache barely hitting

FINDING_KINDS = (
    F_INPUT_BOUND, F_RETRACE_STORM, F_PADDING_WASTE, F_NAN_DIVERGENCE,
    F_LR_ROLLBACK_LOOP, F_STRAGGLER, F_DESYNC, F_STALE_HOST,
    F_HBM_PRESSURE, F_COMM_DOMINANT, F_SHED_SPIRAL, F_QUEUE_SATURATION,
    F_QUARANTINE_ROT, F_LOADER_STALL, F_WEDGED_STEP, F_COLD_START,
    F_CRASH, F_ELASTIC_SHRINK, F_ELASTIC_GROW,
    F_REPLICA_FLAP, F_BREAKER_OPEN, F_RELOAD_ROLLBACK,
    F_QUANT_DRIFT, F_CACHE_INEFFECTIVE,
)

_EVIDENCE_CAP = 16  # per finding; a shed spiral does not need 300 records


@dataclass
class DoctorConfig:
    """Rule thresholds. The defaults are tuned against the false-positive
    gate (doctor_smoke's clean leg must yield ZERO findings on a CPU toy
    run) while still firing on every injected drill."""

    # input-bound: host batch build p50 must exceed this multiple of the
    # device dispatch p50, over at least min_span_samples sampled steps
    input_bound_factor: float = 2.0
    min_span_samples: int = 5
    # retrace storm: violations below this are a one-off, not a storm
    retrace_storm_min: int = 3
    # padding waste: a bucket above this fraction, observed over at least
    # this many steps (toy CPU ladders legitimately idle ~40% of slots)
    padding_waste_threshold: float = 0.75
    padding_waste_min_steps: int = 4
    # straggler: worst host's median step time vs the other hosts' median
    straggler_factor: float = 2.0
    # HBM: peak within this fraction of the device limit is pressure
    hbm_headroom_fraction: float = 0.92
    # comm: estimated collective fraction of step time above this
    comm_fraction_threshold: float = 0.4
    # serving
    shed_spiral_min: int = 5
    queue_full_min: int = 5
    queue_wait_fraction: float = 0.5
    # fleet: one supervisor restart is recovery, this many is instability
    # (benching fires the finding regardless of this threshold)
    replica_flap_min_restarts: int = 3
    # rollbacks: 1 recovers, this many is a loop
    rollback_loop_min: int = 2
    # prediction cache: judge efficacy only after this many lookups (a
    # fleet that barely ran has no verdict), and call it ineffective when
    # the hit rate sits below the floor — a cache-enabled fleet paying
    # key-hash + disk probes per request for almost no reuse
    cache_min_lookups: int = 100
    cache_hit_rate_min: float = 0.05
    # diff mode: time_to_first_step growth beyond this factor with fresh
    # cache misses is a cold-start regression
    cold_start_factor: float = 1.5


@dataclass
class Finding:
    """One diagnosed pathology: what, how bad, the records that prove it,
    and the config knob that fixes it."""

    kind: str
    severity: str
    summary: str
    remediation: str
    evidence: List[Dict[str, Any]] = field(default_factory=list)
    data: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "severity": self.severity,
            "summary": self.summary,
            "remediation": self.remediation,
            "evidence": self.evidence[:_EVIDENCE_CAP],
            "evidence_total": len(self.evidence),
            "data": self.data,
        }


# ---------------------------------------------------------------------------
# stream ingestion
# ---------------------------------------------------------------------------


def _read_jsonl(path: str, validate: Callable[[Any], List[str]],
                warnings_out: List[str]) -> List[Dict[str, Any]]:
    """Parse one JSONL stream through a schema validator. Malformed lines
    (incl. a torn final line from a crash) and schema-invalid records
    become warnings, not exceptions."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path) as fh:
            lines = fh.readlines()
    except OSError as e:
        warnings_out.append(f"{os.path.basename(path)}: unreadable ({e})")
        return out
    bad = 0
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            # a torn final line is the expected crash artifact; mid-file
            # corruption is worth one warning per file either way
            bad += 1
            continue
        errs = validate(rec)
        if errs:
            bad += 1
            if bad == 1:
                warnings_out.append(
                    f"{os.path.basename(path)}: line {i + 1}: {errs[0]}"
                )
            continue
        out.append(rec)
    if bad:
        warnings_out.append(
            f"{os.path.basename(path)}: {bad} malformed/invalid record(s) "
            "skipped"
        )
    return out


def _read_json(path: str, warnings_out: List[str],
               label: Optional[str] = None) -> Optional[Any]:
    """Best-effort JSON file read; a truncated/partial file degrades to a
    warning (the half-written-flight-dump contract)."""
    label = label or os.path.basename(path)
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError) as e:
        warnings_out.append(f"{label}: unreadable/truncated ({e})")
        return None


def _event_key(rec: Dict[str, Any]) -> Tuple:
    return (rec.get("ts"), rec.get("kind"), rec.get("trace_id"),
            tuple(sorted((k, str(v)) for k, v in rec.items()
                         if k not in ("ts", "kind", "trace_id"))))


@dataclass
class RunStreams:
    """Everything one run (or one flight dump) emitted, parsed and
    schema-checked: the doctor's working set."""

    target: str
    source: str  # "run_dir" | "flight_dump"
    metrics: List[Dict[str, Any]] = field(default_factory=list)
    spans: List[Dict[str, Any]] = field(default_factory=list)
    events: List[Dict[str, Any]] = field(default_factory=list)
    quarantine: List[Dict[str, Any]] = field(default_factory=list)
    dumps: List[Dict[str, Any]] = field(default_factory=list)
    config: Optional[Dict[str, Any]] = None
    memory: Optional[Dict[str, Any]] = None
    # obs/sharding.py snapshot a flight dump carried (label -> report):
    # per-leaf PartitionSpec tables + replication audit
    sharding: Optional[Dict[str, Any]] = None
    parse_warnings: List[str] = field(default_factory=list)

    # -- derived views -------------------------------------------------------

    def events_of(self, *kinds: str) -> List[Dict[str, Any]]:
        want = set(kinds)
        return [e for e in self.events if e.get("kind") in want]

    def records_of(self, kind: str) -> List[Dict[str, Any]]:
        return [r for r in self.metrics if r.get("kind") == kind]

    def compile_report(self) -> Optional[Dict[str, Any]]:
        reps = self.records_of("compile_report")
        return reps[-1] if reps else None

    @classmethod
    def load(cls, target: str) -> "RunStreams":
        """Auto-detect: a directory with a ``meta.json``/``events.json``
        (and no metrics stream) is a flight dump; anything else is a run
        directory."""
        if os.path.isfile(os.path.join(target, "meta.json")) or (
            os.path.isfile(os.path.join(target, "events.json"))
            and not os.path.isfile(os.path.join(target, "metrics.jsonl"))
        ):
            return cls.from_flight_dump(target)
        return cls.from_run_dir(target)

    @classmethod
    def from_run_dir(cls, run_dir: str) -> "RunStreams":
        s = cls(target=run_dir, source="run_dir")
        w = s.parse_warnings
        # metrics.jsonl + per-host fleet streams
        for path in sorted(
            glob.glob(os.path.join(run_dir, "metrics.jsonl"))
            + glob.glob(os.path.join(run_dir, "metrics-h*.jsonl"))
        ):
            s.metrics.extend(_read_jsonl(path, validate_metrics_record, w))
        # trace.jsonl + per-host fleet streams
        for path in sorted(
            glob.glob(os.path.join(run_dir, "trace.jsonl"))
            + glob.glob(os.path.join(run_dir, "trace-h*.jsonl"))
        ):
            s.spans.extend(_read_jsonl(path, validate_span_record, w))
        # events.jsonl (r14 persistent sink) + per-host streams
        event_paths = sorted(
            glob.glob(os.path.join(run_dir, "events.jsonl"))
            + glob.glob(os.path.join(run_dir, "events-h*.jsonl"))
        )
        for path in event_paths:
            s.events.extend(_read_jsonl(path, validate_event_record, w))
        # quarantine manifest (data/validate.py)
        man = os.path.join(run_dir, "quarantine", "manifest.jsonl")
        if os.path.isfile(man):
            s.quarantine.extend(_read_jsonl(man, lambda r: [], w))
        # completed config (config.save_config)
        s.config = _read_json(os.path.join(run_dir, "config.json"), w)
        # flight dumps: meta always; events only as the fallback source
        # for pre-r14 runs (an events.jsonl already holds the superset —
        # double-ingesting the ring would double every event-derived
        # evidence list)
        seen = {_event_key(e) for e in s.events}
        for d in sorted(glob.glob(os.path.join(run_dir, "flightrec", "*"))):
            if not os.path.isdir(d) or os.path.basename(d).startswith("."):
                continue
            meta = _read_json(os.path.join(d, "meta.json"), w,
                              label=f"flightrec/{os.path.basename(d)}/meta")
            s.dumps.append({"dir": d, "meta": meta or {}})
            if s.memory is None:
                s.memory = _read_json(os.path.join(d, "memory.json"), w)
            if s.sharding is None:
                s.sharding = _read_json(
                    os.path.join(d, "sharding.json"), w
                )
            if not event_paths:
                for ev in (_read_json(
                    os.path.join(d, "events.json"), w,
                    label=f"flightrec/{os.path.basename(d)}/events",
                ) or []):
                    if validate_event_record(ev):
                        continue
                    key = _event_key(ev)
                    if key not in seen:
                        seen.add(key)
                        s.events.append(ev)
        s.events.sort(key=lambda e: e.get("ts", 0))
        return s

    @classmethod
    def from_flight_dump(cls, dump_dir: str) -> "RunStreams":
        """The crash-forensics path: diagnose from a black box alone. A
        truncated/partially-written dump degrades to parse warnings."""
        s = cls(target=dump_dir, source="flight_dump")
        w = s.parse_warnings
        meta = _read_json(os.path.join(dump_dir, "meta.json"), w)
        s.dumps.append({"dir": dump_dir, "meta": meta or {}})
        for ev in (_read_json(os.path.join(dump_dir, "events.json"), w)
                   or []):
            errs = validate_event_record(ev)
            if errs:
                w.append(f"events.json: {errs[0]}")
                continue
            s.events.append(ev)
        for sp in (_read_json(os.path.join(dump_dir, "spans.json"), w)
                   or []):
            if validate_span_record(sp):
                continue
            s.spans.append(sp)
        s.memory = _read_json(os.path.join(dump_dir, "memory.json"), w)
        s.sharding = _read_json(os.path.join(dump_dir, "sharding.json"), w)
        return s


def _tail_jsonl(
    path: str,
    offset: int,
    validate: Callable[[Any], List[str]],
    warnings_out: List[str],
) -> Tuple[List[Dict[str, Any]], int]:
    """Parse the COMPLETE lines appended to ``path`` since ``offset``;
    returns (records, new offset). A trailing line without its newline is
    left unconsumed — the producer is mid-write and the next tick picks
    it up whole (watch mode must not mis-parse a torn tail as corruption)."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path, "rb") as fh:
            fh.seek(offset)
            chunk = fh.read()
    except OSError as e:
        warnings_out.append(f"{os.path.basename(path)}: unreadable ({e})")
        return out, offset
    end = chunk.rfind(b"\n")
    if end < 0:
        return out, offset
    consumed = chunk[: end + 1]
    for line in consumed.split(b"\n"):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line.decode("utf-8", errors="replace"))
        except json.JSONDecodeError:
            warnings_out.append(
                f"{os.path.basename(path)}: malformed record skipped"
            )
            continue
        if validate(rec):
            warnings_out.append(
                f"{os.path.basename(path)}: invalid record skipped"
            )
            continue
        out.append(rec)
    return out, offset + len(consumed)


class StreamTail:
    """Incremental run-dir ingester for watch mode: per-file byte
    offsets mean each tick parses only what was appended since the last
    one, instead of re-reading (and re-validating) the whole history —
    a multi-hour live run would otherwise make every 2-second tick
    linear in total stream size. New files (a fleet host joining, the
    first flight dump) are picked up by re-globbing; dumps and the
    config are scanned once each."""

    def __init__(self, run_dir: str):
        self.run_dir = run_dir
        self._offsets: Dict[str, int] = {}
        self.streams = RunStreams(target=run_dir, source="run_dir")

    def refresh(self) -> "RunStreams":
        s = self.streams
        w = s.parse_warnings
        for pattern, validate, sink in (
            ("metrics*.jsonl", validate_metrics_record, s.metrics),
            ("trace*.jsonl", validate_span_record, s.spans),
            ("events*.jsonl", validate_event_record, s.events),
            (os.path.join("quarantine", "manifest.jsonl"),
             lambda r: [], s.quarantine),
        ):
            for path in sorted(
                glob.glob(os.path.join(self.run_dir, pattern))
            ):
                recs, off = _tail_jsonl(
                    path, self._offsets.get(path, 0), validate, w
                )
                self._offsets[path] = off
                sink.extend(recs)
        known = {d["dir"] for d in s.dumps}
        for d in sorted(glob.glob(os.path.join(self.run_dir,
                                               "flightrec", "*"))):
            if (not os.path.isdir(d) or os.path.basename(d).startswith(".")
                    or d in known):
                continue
            meta = _read_json(
                os.path.join(d, "meta.json"), w,
                label=f"flightrec/{os.path.basename(d)}/meta",
            )
            s.dumps.append({"dir": d, "meta": meta or {}})
        if s.config is None:
            # no warning sink: the config legitimately appears late
            s.config = _read_json(
                os.path.join(self.run_dir, "config.json"), []
            )
        return s


# ---------------------------------------------------------------------------
# span decomposition (the analyze_trace successor)
# ---------------------------------------------------------------------------


def span_decomposition(
    spans: Iterable[Dict[str, Any]],
) -> Dict[str, Dict[str, float]]:
    """Per-span-name duration stats: count, p50/p99, total — the stage
    decomposition the input-bound rule and the diff mode consume."""
    durs: Dict[str, List[float]] = {}
    for rec in spans:
        ms = span_duration_ms(rec)
        if ms is None:
            continue
        durs.setdefault(str(rec.get("name", "?")), []).append(ms)
    out: Dict[str, Dict[str, float]] = {}
    for name, vals in durs.items():
        vals.sort()
        out[name] = {
            "count": len(vals),
            "p50_ms": round(_percentile(vals, 0.50), 4),
            "p99_ms": round(_percentile(vals, 0.99), 4),
            "total_ms": round(sum(vals), 3),
        }
    return out


def step_phase_verdict(
    decomp: Dict[str, Dict[str, float]], cfg: DoctorConfig
) -> Optional[Dict[str, Any]]:
    """Input-bound vs compute-bound decomposition of the sampled training
    steps (``train/host_batch_build`` vs ``train/device_dispatch``
    children of ``train/step``). None when there are not enough samples
    to say anything."""
    hb = decomp.get("train/host_batch_build")
    dd = decomp.get("train/device_dispatch")
    if not hb or not dd:
        return None
    n = min(hb["count"], dd["count"])
    if n < cfg.min_span_samples:
        return None
    ratio = hb["p50_ms"] / max(dd["p50_ms"], 1e-9)
    verdict = (
        "input_bound" if ratio > cfg.input_bound_factor
        else "compute_bound" if ratio < 1.0 / cfg.input_bound_factor
        else "balanced"
    )
    return {
        "verdict": verdict,
        "host_batch_build_p50_ms": hb["p50_ms"],
        "device_dispatch_p50_ms": dd["p50_ms"],
        "ratio": round(ratio, 3),
        "samples": n,
    }


# ---------------------------------------------------------------------------
# the rulebook
# ---------------------------------------------------------------------------

Rule = Callable[[RunStreams, DoctorConfig], List[Finding]]
_RULES: List[Rule] = []


def rule(fn: Rule) -> Rule:
    _RULES.append(fn)
    return fn


@rule
def r_input_bound(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    decomp = span_decomposition(s.spans)
    phase = step_phase_verdict(decomp, cfg)
    if phase is None or phase["verdict"] != "input_bound":
        return []
    return [Finding(
        F_INPUT_BOUND, "warn",
        f"training is input-bound: host batch build p50 "
        f"{phase['host_batch_build_p50_ms']:.1f}ms is "
        f"{phase['ratio']:.1f}x the device dispatch p50 "
        f"{phase['device_dispatch_p50_ms']:.1f}ms over {phase['samples']} "
        "sampled steps — the accelerator is waiting on the host",
        "raise Training.double_buffer (device staging depth) and the "
        "loader prefetch; if batch *construction* dominates, enable "
        "Dataset.lappe_cache / move featurization offline",
        evidence=[{"span_stats": {k: decomp[k] for k in
                                  ("train/host_batch_build",
                                   "train/device_dispatch")}}],
        data=phase,
    )]


@rule
def r_retrace_storm(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_RETRACE_VIOLATION)
    rep = s.compile_report()
    violations = max(
        len(evs), int(rep["violations"]) if rep is not None else 0
    )
    if violations < cfg.retrace_storm_min:
        return []
    return [Finding(
        F_RETRACE_STORM, "error",
        f"retrace storm: {violations} sentinel violations — a step "
        "specialization keeps silently recompiling (each one is a full "
        "XLA compile on the critical path)",
        "set Training.precompile: blocking so warm-up covers the full "
        "ladder before epoch 0, and Training.retrace_policy: error to "
        "fail fast at the violating aval (the report names the per-leaf "
        "diff vs the nearest known specialization)",
        evidence=evs,
        data={"violations": violations},
    )]


@rule
def r_padding_waste(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    # aggregate per pad bucket over every step_window
    buckets: Dict[str, Dict[str, float]] = {}
    for wrec in s.records_of("step_window"):
        for label, b in (wrec.get("buckets") or {}).items():
            if not isinstance(b, dict):
                continue
            agg = buckets.setdefault(label, {"steps": 0, "waste_x_steps": 0.0})
            steps = int(b.get("steps", 0))
            agg["steps"] += steps
            agg["waste_x_steps"] += float(b.get("padding_waste", 0.0)) * steps
    bad = {}
    for label, agg in buckets.items():
        if agg["steps"] < cfg.padding_waste_min_steps:
            continue
        waste = agg["waste_x_steps"] / max(agg["steps"], 1)
        if waste > cfg.padding_waste_threshold:
            bad[label] = {"steps": agg["steps"], "padding_waste": round(waste, 4)}
    if not bad:
        return []
    worst = max(bad.items(), key=lambda kv: kv[1]["padding_waste"])
    return [Finding(
        F_PADDING_WASTE, "warn",
        f"padding waste above {cfg.padding_waste_threshold:.0%} in "
        f"{len(bad)} pad bucket(s) — worst: {worst[0]} at "
        f"{worst[1]['padding_waste']:.0%} over {worst[1]['steps']} steps "
        "(those node slots burn FLOPs on masked garbage)",
        "raise Training.num_pad_buckets (finer ladder levels) or lower "
        "Training.batch_size for the offending shapes; packed batching "
        "(Dataset pack mode) eliminates the tail for skewed graph sizes",
        evidence=[{"bucket": k, **v} for k, v in sorted(bad.items())],
        data={"buckets": bad},
    )]


@rule
def r_nan_divergence(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    prov = s.events_of(EV_NUMERICS_PROVENANCE)
    skips = s.events_of(EV_GUARD_SKIP)
    if not prov and not skips:
        return []
    total_skips = sum(int(e.get("new_skips", e.get("total", 1)) or 0)
                      for e in skips) or len(skips)
    layers = sorted({str(e.get("layer")) for e in prov
                     if e.get("layer") and e.get("layer") != "<unreproduced>"})
    sources: set = set()
    for e in prov + skips:
        sv = e.get("sources")
        if sv:
            sources.update(str(x) for x in str(sv).split(","))
    chain = ""
    if layers:
        chain += f"; first non-finite tensor: {', '.join(layers[:4])}"
    if sources:
        chain += (
            f"; implicated mixture source id(s): "
            f"{', '.join(sorted(sources)[:8])}"
        )
    remediation = (
        "lower NeuralNetwork.Training.Optimizer.learning_rate (or set "
        "Training.non_finite_policy: rollback for automatic LR backoff)"
    )
    if sources:
        remediation += (
            "; the implicated sources suggest data rot — set "
            "Dataset.bad_sample_policy: quarantine and/or lower "
            "Mixture.demote_after to demote them"
        )
    if layers:
        remediation += (
            "; Telemetry.numerics window stats for the named layer show "
            "whether it saturated gradually (LR) or spiked (data)"
        )
    return [Finding(
        F_NAN_DIVERGENCE, "error",
        f"non-finite divergence: {total_skips} guarded step skip(s), "
        f"{len(prov)} NaN provenance drill-down(s){chain}",
        remediation,
        evidence=prov + skips,
        data={"skips": total_skips, "layers": layers,
              "sources": sorted(sources)},
    )]


@rule
def r_lr_rollback_loop(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_GUARD_ROLLBACK)
    if not evs:
        return []
    loop = len(evs) >= cfg.rollback_loop_min
    return [Finding(
        F_LR_ROLLBACK_LOOP, "error" if loop else "warn",
        f"{len(evs)} guard rollback(s) restored a verified checkpoint"
        + (" — a sustained LR-too-hot divergence loop, each iteration "
           "loses the epochs since the last checkpoint" if loop else ""),
        "lower NeuralNetwork.Training.Optimizer.learning_rate at the "
        "recipe level; Training.non_finite_lr_backoff compounds per "
        "rollback, so a loop that is not converging means the base LR is "
        "far past stable — also check Training.non_finite_max_rollbacks "
        "before the run turns fatal",
        evidence=evs,
        data={"rollbacks": len(evs)},
    )]


@rule
def r_straggler(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_FLEET_STRAGGLER)
    # metrics-derived detection: per-host median window step time (works
    # post-hoc from the host-suffixed streams even when no collector ran)
    per_host: Dict[int, List[float]] = {}
    for wrec in s.records_of("step_window"):
        per_host.setdefault(int(wrec.get("host", 0)), []).append(
            float(wrec["step_time_ms"])
        )
    skew = None
    if len(per_host) >= 2:
        medians = {
            h: _percentile(sorted(v), 0.5) for h, v in per_host.items()
        }
        worst_host = max(medians, key=lambda h: medians[h])
        others = [v for h, v in medians.items() if h != worst_host]
        baseline = _percentile(sorted(others), 0.5)
        if baseline > 0 and medians[worst_host] > cfg.straggler_factor * baseline:
            skew = {
                "host": worst_host,
                "median_step_ms": round(medians[worst_host], 3),
                "fleet_median_step_ms": round(baseline, 3),
                "factor": round(medians[worst_host] / baseline, 2),
            }
    if not evs and skew is None:
        return []
    hosts = sorted({str(e.get("host")) for e in evs if e.get("host")
                    is not None} | ({str(skew["host"])} if skew else set()))
    summary = (
        f"straggler host(s) {', '.join(hosts) or '?'}: "
        + (f"{len(evs)} fleet watchdog detection(s)" if evs else "")
        + (" and " if evs and skew else "")
        + (f"median step {skew['median_step_ms']}ms is {skew['factor']}x "
           f"the other hosts' {skew['fleet_median_step_ms']}ms" if skew
           else "")
    )
    return [Finding(
        F_STRAGGLER, "warn", summary,
        "inspect the named host (thermals, input pipeline, noisy "
        "neighbor); Telemetry.fleet_straggler_factor tunes the watchdog "
        "threshold and the coordinated flight dumps carry each host's "
        "registry snapshot for the moment of detection",
        evidence=evs or [{"step_time_skew": skew}],
        data={"hosts": hosts, **({"skew": skew} if skew else {})},
    )]


@rule
def r_desync(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_FLEET_DESYNC)
    if not evs:
        return []
    return [Finding(
        F_DESYNC, "error",
        f"fleet desync: {len(evs)} progress-skew detection(s) — hosts "
        "disagree on the step index beyond Telemetry.fleet_max_step_lag "
        "(a collective will eventually deadlock or mispair)",
        "find what stalled the lagging host (its coordinated flight dump "
        "is keyed by the same fleet step); raise "
        "Telemetry.fleet_max_step_lag only if the skew is benign by "
        "construction (e.g. uneven per-host batch counts)",
        evidence=evs,
        data={"detections": len(evs)},
    )]


@rule
def r_stale_host(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_FLEET_HOST_STALE)
    if not evs:
        return []
    hosts = sorted({str(e.get("host")) for e in evs if e.get("host")
                    is not None})
    return [Finding(
        F_STALE_HOST, "warn",
        f"stale fleet host(s) {', '.join(hosts) or '?'}: heartbeats went "
        f"silent past the staleness threshold ({len(evs)} detection(s)) — "
        "their series were retired from the fleet aggregates",
        "check whether the host process died (its metrics-h<N>.jsonl tail "
        "names the last completed step) or only its collector route; "
        "Telemetry.fleet_stale_after_s tunes the threshold",
        evidence=evs,
        data={"hosts": hosts},
    )]


def _elastic_findings(s: "RunStreams", kind: str, fkind: str,
                      severity: str, what: str, action: str) -> List[Finding]:
    """Shared body of the elastic shrink/grow rules: one finding per
    re-layout event, with the event's before/after layouts, the measured
    progress loss, and the run's recorded sharding tables as evidence."""
    evs = s.events_of(kind)
    out: List[Finding] = []
    stale = s.events_of(EV_FLEET_HOST_STALE)
    for e in evs:
        before = e.get("before") or {}
        after = e.get("after") or {}
        lost = e.get("progress_lost_steps")
        evidence: List[Dict[str, Any]] = [e]
        if stale:
            evidence.extend(stale)
        if s.sharding:
            # the re-layout's placement record: the rule table's sharding
            # tables as recorded AFTER the survivor re-laid-out
            evidence.append({"sharding_tables": sorted(s.sharding)})
        out.append(Finding(
            fkind, severity,
            f"{what}: {before.get('host_count', '?')} -> "
            f"{after.get('host_count', '?')} host(s) "
            f"(trigger: {e.get('trigger', '?')}, progress lost: "
            + (f"{lost} step(s)" if lost is not None
               else "bounded by the checkpoint cadence") + ")",
            action,
            evidence=evidence,
            data={
                "before": before, "after": after,
                **({"progress_lost_steps": int(lost)}
                   if lost is not None else {}),
            },
        ))
    return out


@rule
def r_elastic_shrink(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    return _elastic_findings(
        s, EV_ELASTIC_SHRINK, F_ELASTIC_SHRINK, "warn",
        "elastic shrink: the fleet re-laid-out onto fewer hosts after a "
        "host loss and resumed from the coordinated checkpoint",
        "the run is healthy but degraded — re-grow when the host returns "
        "(the mixture re-deals its draw stripes either way); if shrinks "
        "recur, check the stale-host findings for the failing host and "
        "Training.elastic.min_hosts for the capacity floor",
    )


@rule
def r_elastic_grow(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    return _elastic_findings(
        s, EV_ELASTIC_GROW, F_ELASTIC_GROW, "info",
        "elastic re-grow: the fleet returned to a larger topology and "
        "resumed from the coordinated checkpoint",
        "no action needed — verify steady-state retraces stayed at zero "
        "after the re-layout (the compile cache makes the re-grown step "
        "a cache hit); the paired elastic_shrink finding names what was "
        "lost in between",
    )


@rule
def r_hbm_pressure(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    rep = s.compile_report()
    peak = limit = None
    by_spec: Dict[str, Any] = {}
    if rep is not None:
        peak = rep.get("hbm_peak_bytes")
        limit = rep.get("device_bytes_limit")
        by_spec = rep.get("hbm_by_spec") or {}
    if (peak is None or limit is None) and s.memory:
        specs = s.memory.get("hbm_by_spec") or {}
        peaks = [v.get("peak_bytes") for v in specs.values()
                 if isinstance(v, dict) and v.get("peak_bytes")]
        if peaks and peak is None:
            peak = max(peaks)
            by_spec = {k: v.get("peak_bytes") for k, v in specs.items()
                       if isinstance(v, dict)}
        if limit is None:
            limit = s.memory.get("device_bytes_limit")
    if not peak or not limit:
        return []
    frac = float(peak) / float(limit)
    if frac < cfg.hbm_headroom_fraction:
        return []
    worst = max(by_spec.items(), key=lambda kv: kv[1] or 0)[0] if by_spec \
        else "?"
    return [Finding(
        F_HBM_PRESSURE, "warn",
        f"HBM peak {peak / 1e9:.2f}GB is {frac:.0%} of the device limit "
        f"{float(limit) / 1e9:.2f}GB (worst spec: {worst}) — one ladder "
        "level up or a fragmentation spike from here is an OOM",
        "set Training.remat_policy: full (recompute instead of stash), "
        "lower Training.batch_size, or shard the optimizer state "
        "(Optimizer.zero_stage); the per-spec table names which pad "
        "bucket to shrink",
        evidence=[{"hbm_by_spec": by_spec}],
        data={"peak_bytes": int(peak), "limit_bytes": int(limit),
              "fraction": round(frac, 4)},
    )]


@rule
def r_comm_dominant(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    rep = s.compile_report()
    bad: Dict[str, float] = {}
    if rep is not None:
        for spec, c in (rep.get("comm_by_spec") or {}).items():
            frac = (c or {}).get("comm_fraction_est")
            if frac is not None and float(frac) > cfg.comm_fraction_threshold:
                bad[spec] = float(frac)
    # window-level confirmation/fallback (attach_comm step records)
    fracs = [r["comm_fraction_est"] for r in s.records_of("step_window")
             if r.get("comm_fraction_est") is not None]
    window_mean = sum(fracs) / len(fracs) if fracs else None
    if not bad and (window_mean is None
                    or window_mean <= cfg.comm_fraction_threshold):
        return []
    worst = max(bad.items(), key=lambda kv: kv[1]) if bad else (
        "window_mean", window_mean)
    return [Finding(
        F_COMM_DOMINANT, "warn",
        f"collectives dominate: estimated comm fraction {worst[1]:.0%} "
        f"({worst[0]}) exceeds {cfg.comm_fraction_threshold:.0%} of step "
        "time — the mesh is paying more in gradient movement than it "
        "earns in parallel compute",
        "lower Optimizer.zero_stage (stage 3 all-gathers weights every "
        "step), grow the per-host batch to amortize the fixed collective "
        "cost, or re-shard via the mesh layout; the compile report's "
        "comm_by_spec table names bytes per specialization",
        evidence=[{"comm_by_spec": (rep or {}).get("comm_by_spec")},
                  {"window_comm_fraction_mean": window_mean}],
        data={"specs": bad, "window_mean": window_mean},
    )]


def _fleet_serve_latest(s: RunStreams) -> Optional[Dict[str, Any]]:
    """Last fleet-aggregated serving window (serve/fleet.py writes them
    ~1/s; counters in them are cumulative, so the last record carries the
    fleet totals). None for single-server runs."""
    recs = s.records_of("fleet_serve")
    return recs[-1] if recs else None


def _per_replica_breakdown(rec: Dict[str, Any], key: str) -> Dict[str, float]:
    return {
        f"replica{h}": float(v.get(key, 0.0))
        for h, v in (rec.get("per_replica") or {}).items()
        if isinstance(v, dict)
    }


@rule
def r_shed_spiral(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    # fleet deployments: judge the AGGREGATED shed total from the
    # manager's fleet_serve records so fleet-wide overload is ONE finding
    # with a per-replica breakdown, not one finding per replica stream
    fleet = _fleet_serve_latest(s)
    if fleet is not None:
        sheds = int(fleet.get("shed_total", 0))
        if sheds < cfg.shed_spiral_min:
            return []
        breakdown = _per_replica_breakdown(fleet, "shed")
        return [Finding(
            F_SHED_SPIRAL, "warn",
            f"fleet-wide shed spiral: {sheds} SLO load sheds across "
            f"{fleet.get('replicas')} replicas ({fleet.get('ready')} "
            "ready) — offered load is persistently above what the FLEET "
            "can finish inside Serving.slo_p99_s",
            "scale out (raise Serving.fleet_replicas) or raise "
            "Serving.micro_batch_graphs for better per-replica device "
            "utilization; if sheds concentrate on one replica (see "
            "breakdown) its device set is the straggler",
            evidence=[fleet],
            data={"sheds": sheds, "per_replica": breakdown},
        )]
    evs = s.events_of(EV_SHED)
    if len(evs) < cfg.shed_spiral_min:
        return []
    return [Finding(
        F_SHED_SPIRAL, "warn",
        f"serve shed spiral: {len(evs)} SLO load sheds — offered load is "
        "persistently above what the server can finish inside "
        "Serving.slo_p99_s (projected queue wait at admission kept "
        "exceeding the SLO)",
        "scale out (more replicas) or raise Serving.micro_batch_graphs "
        "toward the warmed ladder's batch size for better device "
        "utilization; raising Serving.slo_p99_s trades latency for "
        "goodput only if clients tolerate it",
        evidence=evs,
        data={"sheds": len(evs)},
    )]


@rule
def r_queue_saturation(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    fleet = _fleet_serve_latest(s)
    if fleet is not None:
        # same aggregation argument as r_shed_spiral: one fleet verdict
        qfull = int(fleet.get("queue_full_total", 0))
        if qfull < cfg.queue_full_min:
            return []
        breakdown = _per_replica_breakdown(fleet, "queue_full")
        return [Finding(
            F_QUEUE_SATURATION, "warn",
            f"fleet-wide queue saturation: {qfull} queue-full rejections "
            f"across {fleet.get('replicas')} replicas (mean depth "
            f"{fleet.get('queue_depth_mean')}, max "
            f"{fleet.get('queue_depth_max')})",
            "the device step is the bottleneck, not admission: add "
            "capacity (Serving.fleet_replicas / bigger "
            "Serving.micro_batch_graphs) rather than raising "
            "Serving.max_queue_requests — a deeper queue only adds "
            "latency to the same throughput",
            evidence=[fleet],
            data={"queue_full": qfull, "per_replica": breakdown},
        )]
    evs = s.events_of(EV_QUEUE_FULL)
    decomp = span_decomposition(s.spans)
    qw = decomp.get("serve/queue_wait")
    req = decomp.get("serve/request")
    wait_frac = None
    if qw and req and req["p99_ms"] > 0 and \
            req["count"] >= cfg.min_span_samples:
        wait_frac = qw["p99_ms"] / req["p99_ms"]
    if len(evs) < cfg.queue_full_min and (
        wait_frac is None or wait_frac < cfg.queue_wait_fraction
    ):
        return []
    parts = []
    if len(evs) >= cfg.queue_full_min:
        parts.append(f"{len(evs)} queue-full rejections")
    if wait_frac is not None and wait_frac >= cfg.queue_wait_fraction:
        parts.append(
            f"queue wait explains {wait_frac:.0%} of request p99 "
            f"({qw['p99_ms']:.1f}ms of {req['p99_ms']:.1f}ms)"
        )
    return [Finding(
        F_QUEUE_SATURATION, "warn",
        "serve queue saturation: " + "; ".join(parts),
        "the device step is the bottleneck, not admission: add capacity "
        "(replicas / bigger Serving.micro_batch_graphs) rather than "
        "raising Serving.max_queue_requests — a deeper queue only adds "
        "latency to the same throughput",
        evidence=evs[:_EVIDENCE_CAP] or [{"span_stats": {
            "serve/queue_wait": qw, "serve/request": req}}],
        data={"queue_full": len(evs), "queue_wait_fraction": wait_frac},
    )]


@rule
def r_replica_flap(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    """A benched replica is a finding by itself (the supervisor only
    benches after fleet_flap_max_restarts deaths inside the window —
    restarts cannot fix it), and restarts short of the bench threshold
    still get surfaced once they repeat."""
    benched = s.events_of(EV_REPLICA_BENCHED)
    restarts = s.events_of(EV_REPLICA_RESTART)
    if benched:
        idxs = sorted({e.get("replica") for e in benched})
        return [Finding(
            F_REPLICA_FLAP, "error",
            f"replica(s) {idxs} BENCHED by the flap breaker: each died "
            "fleet_flap_max_restarts times inside fleet_flap_window_s — "
            "a crash loop restarts cannot fix (bad device set, corrupt "
            "checkpoint, OOM on warm-up)",
            "read logs/<run>/replica_<i>.log for the crash cause; the "
            "fleet keeps serving on the remaining replicas but at reduced "
            "capacity until the fleet is restarted",
            evidence=(benched + s.events_of(EV_REPLICA_EXIT))[:_EVIDENCE_CAP],
            data={"benched": idxs, "restarts": len(restarts)},
        )]
    if len(restarts) < cfg.replica_flap_min_restarts:
        return []
    per = {}
    for e in restarts:
        per[e.get("replica")] = per.get(e.get("replica"), 0) + 1
    return [Finding(
        F_REPLICA_FLAP, "warn",
        f"{len(restarts)} replica restart(s) this run "
        f"(per replica: {per}) — the supervisor recovered each time, but "
        "repeated deaths mean the workers are unstable",
        "check replica_<i>.log for the exit cause; if deaths cluster on "
        "one replica its device set or host is suspect",
        evidence=restarts[:_EVIDENCE_CAP],
        data={"restarts": len(restarts), "per_replica": per},
    )]


@rule
def r_breaker_open(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    opens = s.events_of(EV_BREAKER_OPEN)
    if not opens:
        return []
    closes = s.events_of(EV_BREAKER_CLOSE)
    still_open = len(opens) > len(closes)
    return [Finding(
        F_BREAKER_OPEN, "error" if still_open else "warn",
        f"router circuit breaker tripped {len(opens)} time(s)"
        + ("" if not still_open else
           f" and {len(opens) - len(closes)} breaker(s) never re-closed")
        + " — a replica kept failing typed-retryable requests and the "
        "router stopped sending it traffic",
        "breakers that re-closed mean the half-open probe found the "
        "replica healthy again (transient); a breaker still open at run "
        "end means the replica stayed broken — cross-check replica_flap "
        "and the replica's log",
        evidence=(opens + closes)[:_EVIDENCE_CAP],
        data={"opens": len(opens), "closes": len(closes),
              "still_open": still_open},
    )]


@rule
def r_reload_rollback(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_RELOAD_ROLLBACK)
    if not evs:
        return []
    last = evs[-1]
    return [Finding(
        F_RELOAD_ROLLBACK, "error",
        f"rolling reload rolled back: first reloaded replica's probe "
        f"error rate {last.get('error_rate')} crossed "
        "Serving.reload_error_spike, so the fleet was restored to "
        f"checkpoint {last.get('rolled_back_to')!r} and the rollout "
        "aborted (the regressed checkpoint reached at most one replica)",
        "the candidate checkpoint is the problem, not the fleet: inspect "
        f"the regressed entry {last.get('regressed')!r} (training-side "
        "divergence, wrong export) before re-publishing the pointer",
        evidence=evs,
        data={"rollbacks": len(evs), "last": last},
    )]


@rule
def r_quant_drift(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    """Every quant_drift event is a refused install: the int8 accuracy
    gate (serve/quantize.py) caught a quantized state whose predictions
    drifted past the configured bound. One refusal is already a finding —
    a candidate that would have served wrong answers reached the gate."""
    evs = s.events_of(EV_QUANT_DRIFT)
    if not evs:
        return []
    last = evs[-1]
    candidates = sorted(
        {str(e.get("candidate")) for e in evs if e.get("candidate")}
    )
    return [Finding(
        F_QUANT_DRIFT, "error",
        f"int8 accuracy gate refused {len(evs)} quantized state(s) "
        f"(mode {last.get('mode')!r}): relative max error "
        f"{last.get('max_error')} crossed the "
        f"Serving.quantization.max_error={last.get('limit')} bound "
        f"(worst heads: {last.get('per_head')}); the previous weights "
        "kept serving",
        "the checkpoint's weight distribution no longer quantizes within "
        "the bound: widen Serving.quantization.max_error only if the "
        "drift is acceptable, exclude the worst layers via "
        "Serving.quantization.exclude, drop Serving.quantization.mode "
        "from w8a8 to weight_only, or serve this run at "
        "weights_dtype bfloat16",
        evidence=evs,
        data={"refusals": len(evs), "candidates": candidates,
              "last": last},
    )]


@rule
def r_cache_ineffective(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    """A cache-enabled fleet whose hit rate stays on the floor: every
    request pays the content hash + disk probe and almost none reuse an
    entry. Judged from the manager's aggregated fleet_serve window
    (counters are cumulative — the last record is the fleet total)."""
    fleet = _fleet_serve_latest(s)
    if fleet is None or not fleet.get("cache_enabled"):
        return []
    hits = int(fleet.get("cache_hits", 0))
    misses = int(fleet.get("cache_misses", 0))
    lookups = hits + misses
    if lookups < cfg.cache_min_lookups:
        return []
    rate = hits / lookups
    if rate >= cfg.cache_hit_rate_min:
        return []
    return [Finding(
        F_CACHE_INEFFECTIVE, "warn",
        f"prediction cache is ineffective: {hits} hit(s) in {lookups} "
        f"lookups ({rate:.1%}, floor {cfg.cache_hit_rate_min:.0%}) across "
        f"{fleet.get('replicas')} replica(s) — the traffic's graphs "
        "almost never repeat bit-identically under the current cache "
        "context",
        "disable Serving.prediction_cache for this traffic (the cache "
        "only pays off on repeated identical inputs), or check for a "
        "context churn source: every checkpoint swap and weights_dtype/"
        "quantization change namespaces the keys, so a flapping rollout "
        "orphans all prior entries",
        evidence=[fleet],
        data={"hits": hits, "misses": misses, "hit_rate": round(rate, 4),
              "entries": int(fleet.get("cache_entries", 0)),
              "bytes": int(fleet.get("cache_bytes", 0))},
    )]


@rule
def r_quarantine_rot(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    demotes = s.events_of(EV_MIX_DEMOTE)
    skips = s.events_of(EV_DATA_SKIP)
    manifest = s.quarantine
    if not demotes and not skips and not manifest:
        return []
    sids = sorted({str(e.get("source")) for e in demotes
                   if e.get("source") is not None})
    parts = []
    if manifest:
        reasons = sorted({str(m.get("reason")) for m in manifest})
        parts.append(
            f"{len(manifest)} quarantined sample(s) "
            f"({', '.join(reasons[:4])})"
        )
    if skips:
        parts.append(f"{len(skips)} validator skip event(s)")
    if demotes:
        parts.append(f"source(s) {', '.join(sids)} quarantine-DEMOTED")
    return [Finding(
        F_QUARANTINE_ROT, "error" if demotes else "warn",
        "data rot: " + "; ".join(parts),
        "inspect quarantine/manifest.jsonl for the per-sample reasons; "
        "Dataset.bad_sample_policy picks the response (quarantine keeps "
        "the audit trail) and Mixture.demote_after bounds how much rot a "
        "mixture source may show before demotion — re-ingest or drop the "
        "named sources",
        evidence=(demotes + skips + manifest)[:_EVIDENCE_CAP * 2],
        data={"quarantined": len(manifest), "skip_events": len(skips),
              "demoted_sources": sids},
    )]


@rule
def r_loader_stall(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_LOADER_STALL)
    if not evs:
        return []
    causes = sorted({str(e.get("cause")) for e in evs if e.get("cause")})
    return [Finding(
        F_LOADER_STALL, "error",
        f"loader stall: the prefetch watchdog fired {len(evs)} time(s) "
        f"(cause(s): {', '.join(causes) or '?'}) — a producer thread "
        "wedged or died without its end sentinel",
        "check the storage path / remote store the producer reads "
        "(HYDRAGNN_DDSTORE_* retry knobs bound transient drops); "
        "Training.loader_stall_timeout tunes how long an alive-but-"
        "silent producer may hold the step loop before the typed error",
        evidence=evs,
        data={"stalls": len(evs), "causes": causes},
    )]


@rule
def r_wedged_step(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    evs = s.events_of(EV_WEDGE)
    if not evs:
        return []
    return [Finding(
        F_WEDGED_STEP, "error",
        f"wedged device step: the serve watchdog abandoned {len(evs)} "
        "hung step(s) and recycled the runner — an XLA program stopped "
        "making progress mid-dispatch",
        "Serving.step_timeout_s bounds the watchdog; a recurring wedge "
        "at the same pad bucket points at a pathological shape — check "
        "the flight dump the wedge triggered (spans carry the batch "
        "index) and warm that level explicitly",
        evidence=evs,
        data={"wedges": len(evs)},
    )]


@rule
def r_cold_start(s: RunStreams, cfg: DoctorConfig) -> List[Finding]:
    """Single-run variant: a RESUMED run (Training.continue) that still
    paid compile-cache misses regressed its restart latency — the cache
    the resume was supposed to be warm from did not serve. The cross-run
    variant (time_to_first_step growth) lives in diff mode."""
    rep = s.compile_report()
    if rep is None or not s.config:
        return []
    training = (s.config.get("NeuralNetwork") or {}).get("Training") or {}
    resumed = bool(training.get("continue"))
    misses = int(rep.get("cache_misses") or 0)
    if not resumed or misses <= 0:
        return []
    return [Finding(
        F_COLD_START, "warn",
        f"compile-cache cold start on a resumed run: {misses} cache "
        f"miss(es) (hits: {rep.get('cache_hits')}) — the restart paid "
        f"time_to_first_step={rep.get('time_to_first_step')}s in "
        "recompilation the persistent cache should have absorbed",
        "check both runs resolved the SAME cache directory "
        "(JAX_COMPILATION_CACHE_DIR, else <checkout>/logs/xla_cache — "
        "train/compile_plane.py compile_cache_dir) and that "
        "Training.compile_cache_dir / HYDRAGNN_COMPILE_CACHE did not "
        "switch it off; a jax/jaxlib upgrade also invalidates every key",
        evidence=[{"compile_report": {
            k: rep.get(k) for k in ("cache_hits", "cache_misses",
                                    "time_to_first_step", "mode")}}],
        data={"cache_misses": misses,
              "time_to_first_step": rep.get("time_to_first_step")},
    )]


# exception types a kind-specific rule already explains: the crash rule
# folds those dumps into the existing finding instead of double-reporting
_EXPLAINED_EXC = {
    "LoaderStallError": F_LOADER_STALL,
    "WedgedStepError": F_WEDGED_STEP,
    "RetraceError": F_RETRACE_STORM,
    "MixtureExhaustedError": F_QUARANTINE_ROT,
}
_CRASH_REASON_RE = re.compile(
    r"unhandled_exception|train_exception|thread_exception|fatal_guard"
)


def r_crash(s: RunStreams, cfg: DoctorConfig,
            findings: List[Finding]) -> List[Finding]:
    """Runs AFTER the rulebook (it needs the other findings): crash dumps
    whose exception an existing finding explains become its evidence;
    anything else is an unexplained crash of its own."""
    by_kind = {f.kind: f for f in findings}
    out: List[Finding] = []
    for dump in s.dumps:
        meta = dump.get("meta") or {}
        reason = str(meta.get("reason", ""))
        exc = meta.get("exception") or {}
        if not exc and not _CRASH_REASON_RE.search(reason):
            continue
        exc_type = str(exc.get("type", ""))
        mapped = _EXPLAINED_EXC.get(exc_type)
        if mapped is None and reason == "fatal_guard":
            mapped = F_NAN_DIVERGENCE
        if mapped is not None and mapped in by_kind:
            f = by_kind[mapped]
            f.evidence.append({"flight_dump": dump["dir"], "meta": meta})
            f.data["crash_dump"] = dump["dir"]
            continue
        out.append(Finding(
            F_CRASH, "error",
            f"crash dump {os.path.basename(dump['dir'])}: "
            + (f"{exc_type}: {exc.get('message', '')}" if exc_type
               else f"reason={reason}"),
            "read the dump's meta.json traceback; events.json holds the "
            "last incidents before death ranked by severity, spans.json "
            "the causal trace, metrics.prom every counter at the moment "
            "of death",
            evidence=[{"flight_dump": dump["dir"], "meta": meta}],
            data={"reason": reason, "exception_type": exc_type},
        ))
    return out


# ---------------------------------------------------------------------------
# diagnosis driver
# ---------------------------------------------------------------------------


def diagnose(
    streams: RunStreams, cfg: Optional[DoctorConfig] = None
) -> Tuple[List[Finding], Dict[str, Any]]:
    """Apply the rulebook. Returns (findings sorted most-severe-first,
    report dict with the span decomposition + stream census)."""
    cfg = cfg or DoctorConfig()
    findings: List[Finding] = []
    for r in _RULES:
        try:
            findings.extend(r(streams, cfg))
        except Exception as e:  # a broken rule must not mask the others
            streams.parse_warnings.append(
                f"rule {r.__name__} failed: {type(e).__name__}: {e}"
            )
    findings.extend(r_crash(streams, cfg, findings))
    findings.sort(key=lambda f: (-severity_rank(f.severity), f.kind))
    decomp = span_decomposition(streams.spans)
    report = {
        "target": streams.target,
        "source": streams.source,
        "streams": {
            "metrics_records": len(streams.metrics),
            "spans": len(streams.spans),
            "events": len(streams.events),
            "quarantined": len(streams.quarantine),
            "flight_dumps": len(streams.dumps),
        },
        "span_decomposition": decomp,
        "step_phase": step_phase_verdict(decomp, cfg),
        "parse_warnings": list(streams.parse_warnings),
    }
    return findings, report


def static_findings_record(root: Optional[str] = None) -> Dict[str, Any]:
    """The graftlint verdict for doctor.json (HYDRAGNN_DOCTOR teardown):
    whether the tree the diagnosed binary ran from was clean under
    ``python -m hydragnn_tpu.analysis``, so post-hoc forensics can rule
    convention rot in or out before chasing runtime causes. Analysis is
    pure host-side AST work (no jax import); any failure degrades to an
    ``error`` field — the verdict hook must never take teardown down."""
    try:
        from .. import analysis

        findings = analysis.analyze(root)
        summary = analysis.summarize(findings)
        rec: Dict[str, Any] = {
            "v": analysis.ANALYSIS_SCHEMA_VERSION,
            "clean": summary["clean"],
            "active": summary["active"],
            "waived": summary["waived"],
            "by_checker": summary["by_checker"],
        }
        if summary["active"]:
            rec["findings"] = [
                f.to_dict() for f in findings if not f.waived
            ][:50]  # bounded: doctor.json is a forensic record, not a report
        return rec
    except Exception as e:  # noqa: BLE001 — degrade, never raise
        return {"error": f"{type(e).__name__}: {e}"}


def run_summary(streams: RunStreams) -> Dict[str, Any]:
    """Comparable scalar summary of one run (the diff mode's per-side
    metric table)."""
    out: Dict[str, Any] = {}
    windows = streams.records_of("step_window")
    if windows:
        steps = sum(int(w["steps"]) for w in windows)
        out["steps"] = steps
        out["step_time_ms_mean"] = round(
            sum(float(w["step_time_ms"]) * int(w["steps"]) for w in windows)
            / max(steps, 1), 3)
        out["graphs_per_sec_mean"] = round(
            sum(float(w["graphs_per_sec"]) * int(w["steps"])
                for w in windows) / max(steps, 1), 2)
        out["padding_waste_mean"] = round(
            sum(float(w["padding_waste"]) * int(w["steps"])
                for w in windows) / max(steps, 1), 4)
        mfus = [w["mfu_est"] for w in windows if w.get("mfu_est") is not None]
        out["mfu_est_last"] = mfus[-1] if mfus else None
    epochs = streams.records_of("epoch")
    if epochs:
        real = [e for e in epochs if not e.get("filler")]
        last = (real or epochs)[-1]
        out["epochs"] = len(epochs)
        for k in ("train", "val", "test", "lr"):
            if k in last:
                out[f"loss_{k}_final"] = last[k]
    rep = streams.compile_report()
    if rep is not None:
        for k in ("time_to_first_step", "cache_hits", "cache_misses",
                  "violations", "hbm_peak_bytes", "comm_bytes_peak"):
            out[k] = rep.get(k)
    return out


# ---------------------------------------------------------------------------
# diff mode
# ---------------------------------------------------------------------------

_BENCH_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")
_BENCH_PRIMARY = ("value", "mfu", "vs_baseline")
_BENCH_AUX_RE = re.compile(r"graphs_per_sec")


def _is_number(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v)


def load_bench_cells(path: str) -> Tuple[int, Dict[str, float]]:
    """Parse one committed BENCH_r*.json round into gated cells — the
    SAME keying as run-scripts/bench_gate.py (primary keys namespaced by
    the metric string; *graphs_per_sec* auxiliaries by name), so a doctor
    diff and a gate verdict over the same rounds name the same cells."""
    m = _BENCH_ROUND_RE.search(os.path.basename(path))
    if not m:
        raise ValueError(f"{path!r} is not a BENCH_r*.json round")
    with open(path) as fh:
        doc = json.load(fh)
    parsed = doc.get("parsed")
    if not isinstance(parsed, dict):
        raise ValueError(f"{path!r} has no parsed cell object")
    if int(doc.get("rc", 0)) != 0 or "error" in parsed:
        raise ValueError(f"{path!r} is not a valid round (rc/error)")
    metric = str(parsed.get("metric", ""))
    cells: Dict[str, float] = {}
    for key, val in parsed.items():
        if not _is_number(val) or val <= 0:
            continue
        if key in _BENCH_PRIMARY:
            cells[f"{metric} :: {key}"] = float(val)
        elif _BENCH_AUX_RE.search(key):
            cells[key] = float(val)
    return int(m.group(1)), cells


def _flatten(cfg: Any, prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if isinstance(cfg, dict) and cfg:
        for k, v in cfg.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    else:
        out[prefix[:-1]] = cfg
    return out


def config_diff(a: Optional[Dict], b: Optional[Dict]) -> Dict[str, Any]:
    """Changed/added/removed keys between two completed configs
    (dot-path flattened; lists compare as values)."""
    if a is None or b is None:
        return {"available": False}
    fa, fb = _flatten(a), _flatten(b)
    changed = {
        k: {"a": fa[k], "b": fb[k]}
        for k in sorted(set(fa) & set(fb))
        if fa[k] != fb[k]
    }
    return {
        "available": True,
        "changed": changed,
        "added": sorted(set(fb) - set(fa)),
        "removed": sorted(set(fa) - set(fb)),
    }


def sharding_diff(
    a: Optional[Dict[str, Any]], b: Optional[Dict[str, Any]]
) -> Dict[str, Any]:
    """Placement regression diff between two runs' ``sharding.json``
    snapshots (obs/sharding.py reports the flight recorder dumped): per
    label, every leaf whose PartitionSpec changed, leaves present in only
    one run, and the replicated/per-device byte deltas — the oracle pair
    for rule-table edits (docs/PARALLELISM.md "Auditing a table"). The
    ``rule_audit`` entry (unmatched-leaf lists) diffs as path sets."""
    if a is None or b is None:
        return {"available": False}

    def _leaves(report: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
        return {
            e["path"]: e
            for table in (report.get("sections") or {}).values()
            for e in table
        }

    labels: Dict[str, Dict[str, Any]] = {}
    for label in sorted(set(a) & set(b)):
        ra, rb = a[label], b[label]
        if label == "rule_audit" or "summary" not in ra or "summary" not in rb:
            continue
        la, lb = _leaves(ra), _leaves(rb)
        changed = {
            p: {"a": la[p].get("spec"), "b": lb[p].get("spec")}
            for p in sorted(set(la) & set(lb))
            if la[p].get("spec") != lb[p].get("spec")
        }
        sa_, sb_ = ra["summary"], rb["summary"]
        deltas = {
            k: {"a": sa_.get(k), "b": sb_.get(k),
                "delta": (sb_.get(k) or 0) - (sa_.get(k) or 0)}
            for k in ("replicated_bytes", "per_device_bytes",
                      "sharded_bytes", "sharded_leaves")
        }
        labels[label] = {
            "builder": {
                "a": (ra.get("builder") or {}).get("name"),
                "b": (rb.get("builder") or {}).get("name"),
            },
            "mesh": {"a": ra.get("mesh"), "b": rb.get("mesh")},
            "spec_changed": changed,
            "only_in_a": sorted(set(la) - set(lb)),
            "only_in_b": sorted(set(lb) - set(la)),
            "summary": deltas,
            "audit_warnings": {
                "a": len(ra.get("audit") or ()),
                "b": len(rb.get("audit") or ()),
            },
        }
    ua = set((a.get("rule_audit") or {}).get("unmatched") or ())
    ub = set((b.get("rule_audit") or {}).get("unmatched") or ())
    return {
        "available": True,
        "labels": labels,
        "unmatched_new_in_b": sorted(ub - ua),
        "unmatched_resolved_in_b": sorted(ua - ub),
    }


def diff_runs(
    a: str,
    b: str,
    cfg: Optional[DoctorConfig] = None,
    gate_verdict: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Cross-run regression diff — the promotion-gate primitive. ``a``
    and ``b`` are run directories (stream diff) or BENCH_r*.json rounds
    (per-cell delta diff); ``gate_verdict`` (bench_gate.py --verdict-out)
    is cross-checked per cell when given."""
    cfg = cfg or DoctorConfig()
    a_bench = bool(_BENCH_ROUND_RE.search(os.path.basename(a)))
    b_bench = bool(_BENCH_ROUND_RE.search(os.path.basename(b)))
    if a_bench != b_bench:
        raise ValueError(
            f"cannot diff a bench round against a run dir ({a!r} vs {b!r})"
        )
    if a_bench:
        round_a, cells_a = load_bench_cells(a)
        round_b, cells_b = load_bench_cells(b)
        cells: Dict[str, Dict[str, Any]] = {}
        for name in sorted(set(cells_a) | set(cells_b)):
            va, vb = cells_a.get(name), cells_b.get(name)
            entry: Dict[str, Any] = {"a": va, "b": vb}
            if va is not None and vb is not None and va > 0:
                entry["delta_frac"] = round((vb - va) / va, 6)
            cells[name] = entry
        out: Dict[str, Any] = {
            "mode": "bench_rounds",
            "a": {"path": a, "round": round_a},
            "b": {"path": b, "round": round_b},
            "cells": cells,
        }
        if gate_verdict is not None:
            out["gate"] = _check_gate_consistency(
                cells, round_a, gate_verdict
            )
        return out

    sa, sb = RunStreams.load(a), RunStreams.load(b)
    fa, _ = diagnose(sa, cfg)
    fb, _ = diagnose(sb, cfg)
    sum_a, sum_b = run_summary(sa), run_summary(sb)
    metrics: Dict[str, Dict[str, Any]] = {}
    for key in sorted(set(sum_a) | set(sum_b)):
        va, vb = sum_a.get(key), sum_b.get(key)
        entry: Dict[str, Any] = {"a": va, "b": vb}
        if _is_number(va) and _is_number(vb) and va:
            entry["delta_frac"] = round((vb - va) / abs(va), 6)
        metrics[key] = entry
    da = span_decomposition(sa.spans)
    db = span_decomposition(sb.spans)
    trace: Dict[str, Dict[str, Any]] = {}
    for name in sorted(set(da) & set(db)):
        trace[name] = {
            q: {
                "a": da[name][q], "b": db[name][q],
                "delta_frac": (
                    round((db[name][q] - da[name][q]) / da[name][q], 4)
                    if da[name][q] else None
                ),
            }
            for q in ("p50_ms", "p99_ms")
        }
    kinds_a = {f.kind for f in fa}
    kinds_b = {f.kind for f in fb}
    diff_findings: List[Dict[str, Any]] = []
    # cross-run cold-start: run B paid recompiles run A's warm path did not
    ttfs_a, ttfs_b = sum_a.get("time_to_first_step"), sum_b.get(
        "time_to_first_step")
    if (
        _is_number(ttfs_a) and _is_number(ttfs_b) and ttfs_a > 0
        and ttfs_b > cfg.cold_start_factor * ttfs_a
        and int(sum_b.get("cache_misses") or 0)
        > int(sum_a.get("cache_misses") or 0)
    ):
        diff_findings.append(Finding(
            F_COLD_START, "warn",
            f"compile-cache cold-start regression: time_to_first_step "
            f"{ttfs_b}s vs {ttfs_a}s "
            f"({ttfs_b / ttfs_a:.1f}x) with cache misses "
            f"{sum_b.get('cache_misses')} vs {sum_a.get('cache_misses')}",
            "run B recompiled what run A served from cache — check "
            "JAX_COMPILATION_CACHE_DIR stability across the two runs "
            "and whether the step program changed (the retrace sentinel "
            "report names the differing avals)",
            data={"ttfs_a": ttfs_a, "ttfs_b": ttfs_b},
        ).to_dict())
    return {
        "mode": "run_dirs",
        "a": {"path": a, "summary": sum_a,
              "findings": [f.to_dict() for f in fa]},
        "b": {"path": b, "summary": sum_b,
              "findings": [f.to_dict() for f in fb]},
        "config_diff": config_diff(sa.config, sb.config),
        "sharding": sharding_diff(sa.sharding, sb.sharding),
        "metrics": metrics,
        "trace": trace,
        "findings_new_in_b": sorted(kinds_b - kinds_a),
        "findings_resolved_in_b": sorted(kinds_a - kinds_b),
        "diff_findings": diff_findings,
    }


def _check_gate_consistency(
    cells: Dict[str, Dict[str, Any]],
    round_a: int,
    verdict: Dict[str, Any],
) -> Dict[str, Any]:
    """Cross-check the doctor's per-cell deltas against a
    ``gate_verdict.json`` (bench_gate.py). Only cells the gate baselined
    against round ``a`` are comparable — the gate walks back to the most
    recent prior round carrying each cell, which may be older than A."""
    checked = 0
    mismatches: List[str] = []
    statuses: Dict[str, str] = {}
    for entry in verdict.get("cells", []):
        name = entry.get("cell")
        statuses[name] = entry.get("status", "?")
        if entry.get("baseline_round") != round_a:
            continue
        mine = cells.get(name, {})
        dv, dm = entry.get("delta_frac"), mine.get("delta_frac")
        if dv is None or dm is None:
            continue
        checked += 1
        if abs(float(dv) - float(dm)) > 1e-6:
            mismatches.append(
                f"{name}: doctor delta {dm:+.4f} vs gate {float(dv):+.4f}"
            )
    return {
        "gate_rc": verdict.get("rc"),
        "cells_checked": checked,
        "consistent": not mismatches,
        "mismatches": mismatches,
        "statuses": statuses,
    }


# ---------------------------------------------------------------------------
# watch mode
# ---------------------------------------------------------------------------


def watch(
    run_dir: str,
    interval_s: float = 2.0,
    max_seconds: Optional[float] = None,
    cfg: Optional[DoctorConfig] = None,
    exit_on_finding: bool = False,
    out=None,
) -> List[Finding]:
    """Tail a live run's streams: re-diagnose every ``interval_s`` and
    print each finding once, the moment it first fires. Returns every
    finding seen. Stops on ``max_seconds``, ``exit_on_finding`` (first
    finding), or KeyboardInterrupt."""
    out = out or sys.stdout
    cfg = cfg or DoctorConfig()
    seen: Dict[str, Finding] = {}
    t0 = time.monotonic()
    tail = StreamTail(run_dir)
    print(f"doctor[watch]: tailing {run_dir} (interval {interval_s}s)",
          file=out, flush=True)
    try:
        while True:
            try:
                findings, _ = diagnose(tail.refresh(), cfg)
            except Exception as e:  # a mid-write race must not kill watch
                print(f"doctor[watch]: ingest failed ({e}); retrying",
                      file=out, flush=True)
                findings = []
            fired = False
            for f in findings:
                if f.kind in seen:
                    seen[f.kind] = f  # keep the freshest evidence
                    continue
                seen[f.kind] = f
                fired = True
                print(
                    f"doctor[watch] FINDING [{f.severity}] {f.kind}: "
                    f"{f.summary}\n  remediation: {f.remediation}",
                    file=out, flush=True,
                )
            if exit_on_finding and fired:
                break
            if max_seconds is not None and \
                    time.monotonic() - t0 >= max_seconds:
                break
            time.sleep(interval_s)
    except KeyboardInterrupt:
        pass
    print(f"doctor[watch]: done ({len(seen)} finding kind(s))",
          file=out, flush=True)
    return list(seen.values())


# ---------------------------------------------------------------------------
# rendering + CLI
# ---------------------------------------------------------------------------


def render_findings(findings: List[Finding], report: Dict[str, Any],
                    out=None) -> None:
    out = out or sys.stdout
    st = report.get("streams", {})
    print(
        f"doctor: {report.get('target')} [{report.get('source')}] — "
        f"{st.get('metrics_records', 0)} metric records, "
        f"{st.get('spans', 0)} spans, {st.get('events', 0)} events, "
        f"{st.get('quarantined', 0)} quarantined, "
        f"{st.get('flight_dumps', 0)} flight dump(s)",
        file=out,
    )
    phase = report.get("step_phase")
    if phase:
        print(
            f"doctor: step decomposition: {phase['verdict']} "
            f"(host_batch_build p50 {phase['host_batch_build_p50_ms']}ms "
            f"vs device_dispatch p50 {phase['device_dispatch_p50_ms']}ms "
            f"over {phase['samples']} sampled steps)",
            file=out,
        )
    for wmsg in report.get("parse_warnings", []):
        print(f"doctor: warning: {wmsg}", file=out)
    if not findings:
        print("doctor: 0 findings — no known pathology detected", file=out)
        return
    print(f"doctor: {len(findings)} finding(s):", file=out)
    for f in findings:
        print(f"  [{f.severity.upper():5s}] {f.kind}: {f.summary}",
              file=out)
        print(f"          remediation: {f.remediation}", file=out)
        print(f"          evidence: {len(f.evidence)} record(s)", file=out)


def render_span_report(decomp: Dict[str, Dict[str, float]],
                       out=None) -> None:
    out = out or sys.stdout
    if not decomp:
        print("doctor[trace]: no spans found", file=out)
        return
    total = sum(v["total_ms"] for v in decomp.values())
    print(f"doctor[trace]: {sum(v['count'] for v in decomp.values())} "
          f"spans, {total:.1f}ms total span time", file=out)
    print(f"  {'span':<28} {'count':>6} {'p50 ms':>10} {'p99 ms':>10} "
          f"{'total ms':>11} {'share':>6}", file=out)
    for name, v in sorted(decomp.items(), key=lambda kv: -kv[1]["total_ms"]):
        share = v["total_ms"] / total if total else 0.0
        print(
            f"  {name:<28} {v['count']:>6} {v['p50_ms']:>10.3f} "
            f"{v['p99_ms']:>10.3f} {v['total_ms']:>11.2f} {share:>6.1%}",
            file=out,
        )


def _write_json(path: str, doc: Dict[str, Any]) -> None:
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, default=str)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(
        prog="python -m hydragnn_tpu.obs.doctor",
        description=__doc__.splitlines()[0],
    )
    sub = ap.add_subparsers(dest="mode")
    d = sub.add_parser("diagnose", help="diagnose one run dir / flight dump")
    d.add_argument("target")
    d.add_argument("--json", default=None, metavar="PATH",
                   help="also write the findings as JSON")
    df = sub.add_parser("diff", help="cross-run regression diff")
    df.add_argument("a")
    df.add_argument("b")
    df.add_argument("--gate", default=None, metavar="PATH",
                    help="gate_verdict.json (bench_gate.py --verdict-out) "
                         "to cross-check per-cell deltas against")
    df.add_argument("--json", default=None, metavar="PATH")
    wt = sub.add_parser("watch", help="tail a live run, print findings")
    wt.add_argument("target")
    wt.add_argument("--interval", type=float, default=2.0)
    wt.add_argument("--max-seconds", type=float, default=None)
    wt.add_argument("--exit-on-finding", action="store_true")
    tr = sub.add_parser("trace", help="span-decomposition report")
    tr.add_argument("trace_jsonl")
    # bare `doctor <run_dir>` is the diagnose shorthand
    if argv and argv[0] not in ("diagnose", "diff", "watch", "trace",
                                "-h", "--help"):
        argv = ["diagnose"] + argv
    args = ap.parse_args(argv)

    if args.mode == "diagnose":
        if not os.path.isdir(args.target):
            print(f"doctor: {args.target!r} is not a directory",
                  file=sys.stderr)
            return 2
        streams = RunStreams.load(args.target)
        findings, report = diagnose(streams)
        render_findings(findings, report)
        if args.json:
            _write_json(args.json, {
                "v": DOCTOR_SCHEMA_VERSION, "mode": "diagnose",
                "target": args.target,
                "findings": [f.to_dict() for f in findings],
                "report": report,
            })
        return 1 if findings else 0

    if args.mode == "diff":
        for p in (args.a, args.b):
            if not os.path.exists(p):
                print(f"doctor: {p!r} not found", file=sys.stderr)
                return 2
        gate = None
        if args.gate:
            warnings_: List[str] = []
            gate = _read_json(args.gate, warnings_)
            if gate is None:
                print(f"doctor: cannot read gate verdict {args.gate!r}: "
                      f"{warnings_}", file=sys.stderr)
                return 2
        try:
            result = diff_runs(args.a, args.b, gate_verdict=gate)
        except (ValueError, OSError, json.JSONDecodeError) as e:
            print(f"doctor: diff failed: {e}", file=sys.stderr)
            return 2
        if result["mode"] == "bench_rounds":
            print(f"doctor[diff]: BENCH r{result['a']['round']:02d} -> "
                  f"r{result['b']['round']:02d}")
            for name, entry in result["cells"].items():
                delta = entry.get("delta_frac")
                print(f"  {name!r}: {entry['a']} -> {entry['b']}"
                      + (f" ({delta:+.1%})" if delta is not None else ""))
            gate_res = result.get("gate")
            if gate_res is not None:
                print(
                    f"doctor[diff]: gate verdict rc={gate_res['gate_rc']} "
                    f"cells_checked={gate_res['cells_checked']} "
                    f"consistent={gate_res['consistent']}"
                )
                for mm in gate_res["mismatches"]:
                    print(f"  MISMATCH {mm}", file=sys.stderr)
        else:
            cd = result["config_diff"]
            if cd.get("available"):
                print(f"doctor[diff]: config: {len(cd['changed'])} "
                      f"changed, {len(cd['added'])} added, "
                      f"{len(cd['removed'])} removed")
                for k, v in list(cd["changed"].items())[:20]:
                    print(f"  {k}: {v['a']!r} -> {v['b']!r}")
            sh = result["sharding"]
            if sh.get("available"):
                for label, entry in sh["labels"].items():
                    dv = entry["summary"]["replicated_bytes"]
                    print(
                        f"doctor[diff]: sharding[{label}] "
                        f"builder {entry['builder']['a']} -> "
                        f"{entry['builder']['b']}, "
                        f"{len(entry['spec_changed'])} leaf spec(s) "
                        f"changed, replicated_bytes {dv['a']} -> "
                        f"{dv['b']} ({dv['delta']:+d}), audit warnings "
                        f"{entry['audit_warnings']['a']} -> "
                        f"{entry['audit_warnings']['b']}"
                    )
                    for p, v in list(entry["spec_changed"].items())[:20]:
                        print(f"  {p}: {v['a']!r} -> {v['b']!r}")
                if sh["unmatched_new_in_b"]:
                    print(
                        "doctor[diff]: rule_audit unmatched leaves new in "
                        f"B: {sh['unmatched_new_in_b']}"
                    )
            for key, entry in result["metrics"].items():
                delta = entry.get("delta_frac")
                print(f"  {key}: {entry['a']} -> {entry['b']}"
                      + (f" ({delta:+.1%})" if delta is not None else ""))
            for name, qs in result["trace"].items():
                print(f"  trace {name}: p50 {qs['p50_ms']['a']} -> "
                      f"{qs['p50_ms']['b']}ms, p99 {qs['p99_ms']['a']} -> "
                      f"{qs['p99_ms']['b']}ms")
            print(f"doctor[diff]: findings new in B: "
                  f"{result['findings_new_in_b'] or 'none'}; resolved: "
                  f"{result['findings_resolved_in_b'] or 'none'}")
            for fd in result["diff_findings"]:
                print(f"  [{fd['severity'].upper()}] {fd['kind']}: "
                      f"{fd['summary']}")
        if args.json:
            _write_json(args.json, {
                "v": DOCTOR_SCHEMA_VERSION, "mode": "diff", **result,
            })
        gate_res = result.get("gate")
        if gate_res is not None and not gate_res["consistent"]:
            return 1
        return 0

    if args.mode == "watch":
        if not os.path.isdir(args.target):
            print(f"doctor: {args.target!r} is not a directory",
                  file=sys.stderr)
            return 2
        watch(args.target, interval_s=args.interval,
              max_seconds=args.max_seconds,
              exit_on_finding=args.exit_on_finding)
        return 0

    if args.mode == "trace":
        warnings_: List[str] = []
        spans = _read_jsonl(args.trace_jsonl, validate_span_record,
                            warnings_)
        for wmsg in warnings_:
            print(f"doctor[trace]: warning: {wmsg}")
        if not spans and not os.path.exists(args.trace_jsonl):
            print(f"doctor: {args.trace_jsonl!r} not found",
                  file=sys.stderr)
            return 2
        render_span_report(span_decomposition(spans))
        return 0

    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
