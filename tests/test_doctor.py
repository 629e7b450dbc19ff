"""Run doctor (obs/doctor.py) + stream schemas (obs/schema.py): the
producer-drift tests (every record kind the planes emit must validate
against the one-source-of-truth schemas), the rulebook over synthesized
streams, flight-dump ingestion (identical findings live vs dump-only,
truncated dumps degrading to warnings), diff mode (run dirs and bench
rounds + gate_verdict cross-check), watch mode, and the per-kind event
severity defaults."""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from hydragnn_tpu.obs.doctor import (
    DoctorConfig,
    RunStreams,
    diagnose,
    diff_runs,
    load_bench_cells,
    span_decomposition,
    watch,
)
from hydragnn_tpu.obs.events import (
    DEFAULT_SEVERITY,
    EVENT_KINDS,
    attach_stream,
    detach_stream,
    emit,
    events,
    severity_rank,
)
from hydragnn_tpu.obs.schema import (
    METRICS_KINDS,
    validate_event_record,
    validate_metrics_record,
    validate_span_record,
)
from hydragnn_tpu.obs.telemetry import StepTelemetry, resolve_telemetry

_NOW = time.time()


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")


def _window(host=0, step_ms=5.0, waste=0.3, bucket="64n/128e",
            bucket_waste=None, comm_frac=None, step=10):
    return {
        "v": 1, "ts": _NOW, "kind": "step_window", "host": host,
        "step": step, "steps": 10, "step_time_ms": step_ms,
        "graphs_per_sec": 100.0, "nodes_per_sec": 1e3,
        "edges_per_sec": 1e4, "padding_waste": waste,
        "padding_waste_graphs": 0.1, "padding_waste_edges": waste,
        "mfu_est": None, "comm_bytes_per_step": None,
        "comm_fraction_est": comm_frac,
        "buckets": {bucket: {
            "steps": 10,
            "padding_waste": waste if bucket_waste is None else bucket_waste,
        }},
    }


def _epoch(epoch=0, filler=False):
    return {"v": 1, "ts": _NOW, "kind": "epoch", "host": 0, "epoch": epoch,
            "train": 1.0, "val": 1.1, "test": 1.2, "lr": 0.01,
            "filler": filler}


def _compile_report(**over):
    rec = {
        "v": 1, "ts": _NOW, "kind": "compile_report", "host": 0,
        "mode": "background", "precompiled": 4, "specializations": 4,
        "cache_hits": 4, "cache_misses": 0, "violations": 0,
        "time_to_first_step": 1.2, "hbm_by_spec": {},
        "hbm_peak_bytes": None, "comm_by_spec": {},
        "comm_bytes_peak": None, "device_bytes_limit": None,
    }
    rec.update(over)
    return rec


def _event(kind, severity="warn", **attrs):
    return {"ts": _NOW, "kind": kind, "severity": severity, **attrs}


def _span(name, dur_ms, trace_id="t" * 32, host=0, start=None):
    start = _NOW if start is None else start
    return {
        "v": 1, "traceId": trace_id, "spanId": os.urandom(8).hex(),
        "name": name, "startTimeUnixNano": str(int(start * 1e9)),
        "endTimeUnixNano": str(int((start + dur_ms / 1e3) * 1e9)),
        "host": host,
    }


def _clean_run(tmp_path, name="clean"):
    d = str(tmp_path / name)
    _write_jsonl(os.path.join(d, "metrics.jsonl"),
                 [_window(), _window(), _epoch(), _compile_report()])
    return d


# ---------------------------------------------------------------------------
# schema drift: what the REAL producers emit must validate
# ---------------------------------------------------------------------------


class _FakeBatch:
    """Loader-shaped batch: the three masks _batch_census reads."""

    def __init__(self, n_graphs=4, n_nodes=32, n_edges=64):
        self.graph_mask = np.array([True] * (n_graphs - 1) + [False])
        self.node_mask = np.array([True] * (n_nodes - 8) + [False] * 8)
        self.edge_mask = np.array([True] * (n_edges - 16) + [False] * 16)


def pytest_schema_drift_step_telemetry_records(tmp_path):
    """Every metrics.jsonl kind StepTelemetry emits — step_window, epoch,
    numerics, run, compile_report — validates against obs/schema.py."""
    settings = resolve_telemetry(
        {"Telemetry": {"enabled": True, "interval_steps": 2,
                       "profile_trigger": False}}
    )
    telem = StepTelemetry(settings, "doctor_drift", log_path=str(tmp_path))
    telem.attach_flops(lambda key: 1e9)
    telem.attach_numerics({"act_names": ["embed"], "grad_names": ["conv"]})
    stats = np.array([[1.0, 2.0, 3.0, 0.0, 0.0]])
    for _ in range(2):
        telem.on_step(_FakeBatch(), 0.01, real_graphs=3,
                      numerics={"act": stats, "grad": stats})
    telem.on_epoch(0, {"train": 0.5, "val": 0.4, "test": 0.3, "lr": 0.01})
    from hydragnn_tpu.train.compile_plane import CompilePlane

    telem.compile_record(
        CompilePlane(mode="off", retrace_policy="warn",
                     log_name="doctor_drift").report()
    )
    telem.run_record({
        "log_name": "doctor_drift", "epochs": 1, "global_step": 2,
        "endpoint_port": None,
        "compile": {"precompiled": 0, "specializations": 0,
                    "cache_hits": 0, "cache_misses": 0, "violations": 0,
                    "time_to_first_step": None},
    })
    telem.close()
    # the fleet_serve kind's real producer is the ReplicaManager's
    # aggregate-window writer — drive it into the same stream without
    # spawning a fleet
    from hydragnn_tpu.serve.fleet import ReplicaManager

    class _Slot:
        benched = False

    mgr = ReplicaManager.__new__(ReplicaManager)
    mgr.n = 2
    mgr.run_dir = str(tmp_path / "doctor_drift")
    mgr._replicas = {1: _Slot(), 2: _Slot()}
    mgr._metrics_fh = None
    mgr._write_metrics_record(
        2, 3.0, 2, 1, 0, 42,
        {"1": {"queue_depth": 1, "shed": 1, "queue_full": 0, "ready": True},
         "2": {"queue_depth": 2, "shed": 0, "queue_full": 0, "ready": True}},
    )
    mgr._metrics_fh.close()
    records = [
        json.loads(l)
        for l in open(tmp_path / "doctor_drift" / "metrics.jsonl")
    ]
    kinds = {r["kind"] for r in records}
    # the drift gate proper: every kind of the producer validates, and
    # every kind the schema knows is actually exercised here
    assert kinds >= set(METRICS_KINDS), kinds
    for r in records:
        assert validate_metrics_record(r) == [], (r["kind"], r)


def pytest_schema_drift_tracer_spans(tmp_path):
    from hydragnn_tpu.obs.trace import Tracer

    tracer = Tracer(str(tmp_path), sample=1.0)
    with tracer.span("train/step", batch_index=0) as sp:
        tracer.emit_completed("train/host_batch_build", time.time() - 0.01,
                              0.01, parent=sp)
        sp.add_link("f" * 32, "a" * 16)
    root = tracer.begin("serve/request")
    from hydragnn_tpu.obs.trace import STATUS_ERROR

    root.set_status(STATUS_ERROR, "boom")
    tracer.finish(root)
    tracer.flush()
    tracer.close()
    spans = [json.loads(l) for l in open(tmp_path / "trace.jsonl")]
    assert len(spans) == 3
    for s in spans:
        assert validate_span_record(s) == [], s


def pytest_schema_drift_event_kinds_and_severity_defaults():
    """Every event kind in the vocabulary has a severity default, and a
    default-emitted record of each kind validates and carries it."""
    assert set(DEFAULT_SEVERITY) == set(EVENT_KINDS)
    events().clear()
    for kind in EVENT_KINDS:
        rec = emit(kind, detail="drift")
        assert validate_event_record(rec) == [], rec
        assert rec["severity"] == DEFAULT_SEVERITY[kind], rec
    # explicit severity still wins over the table
    rec = emit("retrace_violation", severity="error")
    assert rec["severity"] == "error"
    assert severity_rank("fatal") > severity_rank("error") > \
        severity_rank("warn") > severity_rank("info")
    events().clear()


def pytest_schema_rejects_malformed_records():
    good = _window()
    assert validate_metrics_record(good) == []
    bad = dict(good)
    del bad["step_time_ms"]
    assert any("step_time_ms" in e for e in validate_metrics_record(bad))
    bad2 = dict(good)
    bad2["steps"] = True  # bool is not an int here
    assert validate_metrics_record(bad2)
    bad3 = dict(good)
    bad3["mfu_est"] = "NaN"  # strings don't pass numeric fields
    assert validate_metrics_record(bad3)
    assert validate_metrics_record({"v": 1})  # missing envelope
    assert validate_span_record({"v": 1})  # missing everything
    assert validate_event_record({"ts": 1.0, "kind": "x",
                                  "severity": "catastrophic"})
    # unknown kinds validate envelope-only (forward compatibility)
    assert validate_metrics_record(
        {"v": 1, "ts": 1.0, "kind": "new_kind", "host": 0}) == []


def pytest_events_jsonl_sink_roundtrip(tmp_path):
    events().clear()
    path = attach_stream(str(tmp_path))
    assert path == str(tmp_path / "events.jsonl")
    try:
        emit("loader_stall", cause="test", batch_index=3)
        emit("serve_shed", request_id=1)
    finally:
        detach_stream()
    recs = [json.loads(l) for l in open(path)]
    assert [r["kind"] for r in recs] == ["loader_stall", "serve_shed"]
    assert recs[0]["severity"] == "error"  # the kind table ranked it
    for r in recs:
        assert validate_event_record(r) == []
    events().clear()


# ---------------------------------------------------------------------------
# rulebook
# ---------------------------------------------------------------------------


def pytest_doctor_clean_run_zero_findings(tmp_path):
    d = _clean_run(tmp_path)
    findings, report = diagnose(RunStreams.from_run_dir(d))
    assert findings == []
    assert report["parse_warnings"] == []
    assert report["streams"]["metrics_records"] == 4


def pytest_doctor_nan_divergence_chains_provenance(tmp_path):
    d = str(tmp_path / "nan")
    _write_jsonl(os.path.join(d, "metrics.jsonl"), [_window()])
    _write_jsonl(os.path.join(d, "events.jsonl"), [
        _event("numerics_provenance", layer="conv1.bn", sources="3,7"),
        _event("guard_skip", new_skips=2, total=2, sources="3"),
    ])
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    assert [f.kind for f in findings] == ["nan_divergence"]
    f = findings[0]
    assert f.severity == "error"
    assert "conv1.bn" in f.summary  # chained to the provenance layer
    assert "3" in f.summary  # and the mixture source ids
    assert "learning_rate" in f.remediation
    assert "Dataset.bad_sample_policy" in f.remediation
    assert len(f.evidence) == 2


def pytest_doctor_input_bound_vs_compute_bound(tmp_path):
    cfg = DoctorConfig()
    d = str(tmp_path / "ib")
    spans = []
    for _ in range(8):
        spans.append(_span("train/host_batch_build", 30.0))
        spans.append(_span("train/device_dispatch", 5.0))
    _write_jsonl(os.path.join(d, "trace.jsonl"), spans)
    findings, report = diagnose(RunStreams.from_run_dir(d), cfg)
    assert [f.kind for f in findings] == ["input_bound"]
    assert report["step_phase"]["verdict"] == "input_bound"
    assert "double_buffer" in findings[0].remediation
    # the flipped ratio is the healthy state: decomposition reported,
    # but no finding
    d2 = str(tmp_path / "cb")
    spans2 = []
    for _ in range(8):
        spans2.append(_span("train/host_batch_build", 2.0))
        spans2.append(_span("train/device_dispatch", 30.0))
    _write_jsonl(os.path.join(d2, "trace.jsonl"), spans2)
    findings2, report2 = diagnose(RunStreams.from_run_dir(d2), cfg)
    assert findings2 == []
    assert report2["step_phase"]["verdict"] == "compute_bound"


def pytest_doctor_straggler_from_per_host_metrics(tmp_path):
    d = str(tmp_path / "fleet")
    _write_jsonl(os.path.join(d, "metrics.jsonl"),
                 [_window(host=0, step_ms=5.0)] * 3)
    _write_jsonl(os.path.join(d, "metrics-h1.jsonl"),
                 [_window(host=1, step_ms=40.0)] * 3)
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    assert [f.kind for f in findings] == ["straggler"]
    assert "1" in findings[0].data["hosts"] or \
        findings[0].data["skew"]["host"] == 1


def pytest_doctor_threshold_rules(tmp_path):
    """Padding waste / retrace storm / HBM pressure / comm dominance /
    shed spiral / queue saturation / rollback loop each fire on streams
    past their thresholds — and each names its remediation knob."""
    d = str(tmp_path / "bad")
    _write_jsonl(os.path.join(d, "metrics.jsonl"), [
        _window(bucket="999n/9999e", bucket_waste=0.9),
        _window(bucket="999n/9999e", bucket_waste=0.9),
        _epoch(),
        _compile_report(
            violations=4,
            hbm_by_spec={"train:999n/9999e": 9_000_000_000},
            hbm_peak_bytes=9_000_000_000,
            device_bytes_limit=9_500_000_000.0,
            comm_by_spec={"train:999n/9999e": {
                "bytes_total": 1 << 20, "ops_total": 4,
                "comm_fraction_est": 0.55}},
            comm_bytes_peak=1 << 20,
        ),
    ])
    _write_jsonl(os.path.join(d, "events.jsonl"),
                 [_event("serve_shed", request_id=i) for i in range(6)]
                 + [_event("serve_queue_full", request_id=i)
                    for i in range(6)]
                 + [_event("guard_rollback", severity="error", rollback=k)
                    for k in (1, 2)])
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    by_kind = {f.kind: f for f in findings}
    assert set(by_kind) == {
        "padding_waste", "retrace_storm", "hbm_pressure", "comm_dominant",
        "shed_spiral", "queue_saturation", "lr_rollback_loop",
    }
    assert "num_pad_buckets" in by_kind["padding_waste"].remediation
    assert "precompile" in by_kind["retrace_storm"].remediation
    assert "remat_policy" in by_kind["hbm_pressure"].remediation
    assert "zero_stage" in by_kind["comm_dominant"].remediation
    assert by_kind["lr_rollback_loop"].severity == "error"  # >= 2 = loop
    # severity ordering: errors lead the findings list
    ranks = [severity_rank(f.severity) for f in findings]
    assert ranks == sorted(ranks, reverse=True)


def pytest_doctor_quarantine_rot_and_mix_demotion(tmp_path):
    d = str(tmp_path / "rot")
    _write_jsonl(os.path.join(d, "quarantine", "manifest.jsonl"), [
        {"index": 3, "dataset_id": "ds0", "reason": "nonfinite_features"},
        {"index": 9, "dataset_id": "ds0", "reason": "bad_edge_index"},
    ])
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    assert [f.kind for f in findings] == ["quarantine_rot"]
    assert findings[0].severity == "warn"
    assert "Mixture.demote_after" in findings[0].remediation
    # a demoted mixture source escalates to error
    _write_jsonl(os.path.join(d, "events.jsonl"),
                 [_event("mix_demote", source=3, reason="rot")])
    findings2, _ = diagnose(RunStreams.from_run_dir(d))
    assert findings2[0].severity == "error"
    assert findings2[0].data["demoted_sources"] == ["3"]


def pytest_doctor_cold_start_on_resumed_run(tmp_path):
    d = str(tmp_path / "resume")
    _write_jsonl(os.path.join(d, "metrics.jsonl"),
                 [_compile_report(cache_hits=0, cache_misses=6)])
    with open(os.path.join(d, "config.json"), "w") as fh:
        json.dump({"NeuralNetwork": {"Training": {"continue": 1}}}, fh)
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    assert [f.kind for f in findings] == ["compile_cold_start"]
    assert "compile_cache_dir" in findings[0].remediation
    # the same misses on a FRESH run are expected — no finding
    with open(os.path.join(d, "config.json"), "w") as fh:
        json.dump({"NeuralNetwork": {"Training": {}}}, fh)
    findings2, _ = diagnose(RunStreams.from_run_dir(d))
    assert findings2 == []


# ---------------------------------------------------------------------------
# flight-dump ingestion (the crash-forensics path)
# ---------------------------------------------------------------------------


def _dump_dir(tmp_path, events_list, meta=None, name="dump"):
    d = str(tmp_path / name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "meta.json"), "w") as fh:
        json.dump(meta or {"reason": "sigusr2", "ts": _NOW, "pid": 1,
                           "host": 0, "dump_index": 1}, fh)
    with open(os.path.join(d, "events.json"), "w") as fh:
        json.dump(events_list, fh)
    with open(os.path.join(d, "spans.json"), "w") as fh:
        json.dump([], fh)
    return d


def pytest_doctor_identical_findings_live_vs_dump_only(tmp_path):
    """The crash-forensics contract: the doctor reaches the same verdict
    from a live run dir and from only its flightrec dump."""
    evs = [
        _event("numerics_provenance", layer="heads.0", sources="5"),
        _event("guard_skip", new_skips=1, total=1),
        _event("serve_wedge", severity="error", batch_index=2),
    ]
    live = str(tmp_path / "live")
    _write_jsonl(os.path.join(live, "events.jsonl"), evs)
    dump = _dump_dir(tmp_path, evs)
    f_live, _ = diagnose(RunStreams.from_run_dir(live))
    f_dump, _ = diagnose(RunStreams.from_flight_dump(dump))
    assert [(f.kind, f.severity, f.summary) for f in f_live] == \
        [(f.kind, f.severity, f.summary) for f in f_dump]
    assert {f.kind for f in f_live} == {"nan_divergence", "wedged_step"}
    # RunStreams.load auto-detects the dump shape
    assert RunStreams.load(dump).source == "flight_dump"
    assert RunStreams.load(live).source == "run_dir"


def pytest_doctor_truncated_dump_degrades_to_warning(tmp_path):
    d = str(tmp_path / "torn")
    os.makedirs(d)
    with open(os.path.join(d, "meta.json"), "w") as fh:
        fh.write('{"reason": "unhandled_exc')  # torn mid-write
    with open(os.path.join(d, "events.json"), "w") as fh:
        fh.write('[{"ts": 1.0, "kind": "serve_wedge", "severity"')
    streams = RunStreams.from_flight_dump(d)
    findings, report = diagnose(streams)
    assert report["parse_warnings"], "truncation must surface as warnings"
    assert all(f.kind != "crash" or f.evidence for f in findings)


def pytest_doctor_crash_dump_folds_into_explaining_finding(tmp_path):
    d = str(tmp_path / "crashed")
    _write_jsonl(os.path.join(d, "events.jsonl"),
                 [_event("loader_stall", severity="error", cause="stall")])
    dump = os.path.join(d, "flightrec", "20260804-000000-01-train_exception-h0")
    os.makedirs(dump)
    with open(os.path.join(dump, "meta.json"), "w") as fh:
        json.dump({"reason": "train_exception",
                   "exception": {"type": "LoaderStallError",
                                 "message": "no batch for 1.0s"}}, fh)
    findings, _ = diagnose(RunStreams.from_run_dir(d))
    # ONE finding: the stall explains the crash, the dump rides as evidence
    assert [f.kind for f in findings] == ["loader_stall"]
    assert findings[0].data.get("crash_dump") == dump
    # an unexplained exception stays its own crash finding
    with open(os.path.join(dump, "meta.json"), "w") as fh:
        json.dump({"reason": "unhandled_exception",
                   "exception": {"type": "ValueError", "message": "?"}}, fh)
    findings2, _ = diagnose(RunStreams.from_run_dir(d))
    assert sorted(f.kind for f in findings2) == ["crash", "loader_stall"]


def pytest_flightrec_meta_carries_severity_census(tmp_path):
    from hydragnn_tpu.obs.flightrec import FlightRecorder

    events().clear()
    emit("serve_wedge", batch_index=1)  # error via the kind table
    emit("checkpoint_write", seconds=0.1)  # info
    rec = FlightRecorder(str(tmp_path))
    out = rec.dump("census_test")
    meta = json.load(open(os.path.join(out, "meta.json")))
    assert meta["events_by_severity"]["error"] >= 1
    assert meta["events_by_severity"]["info"] >= 1
    assert meta["worst_severity"] == "error"
    # the capacity denominator rides every dump (None on CPU, but the
    # KEY must exist — the doctor's dump-only HBM verdict reads it)
    mem = json.load(open(os.path.join(out, "memory.json")))
    assert "device_bytes_limit" in mem
    events().clear()


def pytest_doctor_hbm_pressure_from_dump_alone(tmp_path):
    """The OOM-forensics contract: a flight dump's memory.json carries
    both the per-spec peaks and the device limit, so the HBM-pressure
    verdict is reachable with no metrics stream at all."""
    d = str(tmp_path / "oomdump")
    os.makedirs(d)
    with open(os.path.join(d, "meta.json"), "w") as fh:
        json.dump({"reason": "sigusr2"}, fh)
    with open(os.path.join(d, "memory.json"), "w") as fh:
        json.dump({
            "hbm_by_spec": {"train:999n/9999e": {"peak_bytes": 9.4e9}},
            "device_memory_peak_bytes": {},
            "device_bytes_limit": 1e10,
        }, fh)
    findings, _ = diagnose(RunStreams.from_flight_dump(d))
    assert [f.kind for f in findings] == ["hbm_pressure"]
    assert findings[0].data["limit_bytes"] == int(1e10)


def pytest_stream_tail_consumes_only_complete_lines(tmp_path):
    from hydragnn_tpu.obs.doctor import StreamTail

    d = str(tmp_path / "tailed")
    os.makedirs(d)
    path = os.path.join(d, "events.jsonl")
    tail = StreamTail(d)
    with open(path, "w") as fh:
        fh.write(json.dumps(_event("serve_shed", request_id=1)) + "\n")
        fh.write('{"ts": 1.0, "kind": "serve_sh')  # torn mid-write
    s = tail.refresh()
    assert len(s.events) == 1 and not s.parse_warnings
    with open(path, "a") as fh:  # the producer finishes the line
        fh.write('ed", "severity": "warn"}\n')
    s = tail.refresh()
    assert len(s.events) == 2, s.events  # no loss, no double-ingest
    s = tail.refresh()
    assert len(s.events) == 2  # idempotent at EOF


def pytest_percentile_shared_between_gate_and_doctor():
    """One implementation (obs/schema.py) behind both trace-percentile
    consumers — a drift here would make the bench gate's baseline and
    the doctor's report disagree on identical data."""
    from hydragnn_tpu.obs.schema import percentile

    bg = _load_bench_gate()
    assert bg._percentile is percentile
    from hydragnn_tpu.obs import doctor as doctor_mod

    assert doctor_mod._percentile is percentile


# ---------------------------------------------------------------------------
# diff mode
# ---------------------------------------------------------------------------


def pytest_doctor_diff_run_dirs(tmp_path):
    a = str(tmp_path / "a")
    _write_jsonl(os.path.join(a, "metrics.jsonl"), [
        _window(step_ms=5.0), _epoch(),
        _compile_report(time_to_first_step=1.0, cache_hits=4),
    ])
    with open(os.path.join(a, "config.json"), "w") as fh:
        json.dump({"NeuralNetwork": {"Training": {"batch_size": 8}}}, fh)
    _write_jsonl(os.path.join(a, "trace.jsonl"),
                 [_span("train/step", 10.0) for _ in range(4)])
    b = str(tmp_path / "b")
    _write_jsonl(os.path.join(b, "metrics.jsonl"), [
        _window(step_ms=10.0), _epoch(),
        _compile_report(time_to_first_step=9.0, cache_misses=6),
    ])
    with open(os.path.join(b, "config.json"), "w") as fh:
        json.dump({"NeuralNetwork": {"Training": {"batch_size": 16}}}, fh)
    _write_jsonl(os.path.join(b, "trace.jsonl"),
                 [_span("train/step", 20.0) for _ in range(4)])
    result = diff_runs(a, b)
    assert result["mode"] == "run_dirs"
    cd = result["config_diff"]
    assert cd["changed"]["NeuralNetwork.Training.batch_size"] == \
        {"a": 8, "b": 16}
    assert result["metrics"]["step_time_ms_mean"]["delta_frac"] == \
        pytest.approx(1.0)
    assert result["trace"]["train/step"]["p50_ms"]["delta_frac"] == \
        pytest.approx(1.0, abs=0.01)
    # ttfs blew past the factor WITH fresh cache misses: cold start
    kinds = [f["kind"] for f in result["diff_findings"]]
    assert kinds == ["compile_cold_start"]


def _load_bench_gate():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_gate_doctor", os.path.join(repo, "run-scripts",
                                          "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_round(path, n, value, aux):
    with open(path, "w") as fh:
        json.dump({
            "rc": 0,
            "parsed": {"metric": "synthetic throughput", "value": value,
                       "synthetic_pna_graphs_per_sec": aux},
        }, fh)


def pytest_doctor_diff_bench_rounds_consistent_with_gate(tmp_path):
    """diff over two bench rounds must report the same per-cell deltas
    bench_gate.py banked in gate_verdict.json — the acceptance contract
    of the promotion-gate primitive."""
    repo = str(tmp_path)
    a, b = os.path.join(repo, "BENCH_r07.json"), \
        os.path.join(repo, "BENCH_r08.json")
    _bench_round(a, 7, 100.0, 5000.0)
    _bench_round(b, 8, 80.0, 6000.0)  # value regressed 20%, aux improved
    bg = _load_bench_gate()
    verdict_path = os.path.join(repo, "gate_verdict.json")
    rc = bg.main(["--repo", repo, "--verdict-out", verdict_path])
    assert rc == 1  # the 20% drop fails the 8% gate
    verdict = json.load(open(verdict_path))
    assert verdict["rc"] == 1
    statuses = {c["cell"]: c["status"] for c in verdict["cells"]}
    assert "fail" in statuses.values() and "pass" in statuses.values()
    result = diff_runs(a, b, gate_verdict=verdict)
    assert result["mode"] == "bench_rounds"
    gate = result["gate"]
    assert gate["cells_checked"] == 2
    assert gate["consistent"], gate["mismatches"]
    # and the doctor's own delta math matches the raw numbers
    cell = result["cells"]["synthetic throughput :: value"]
    assert cell["delta_frac"] == pytest.approx(-0.2)


def pytest_doctor_diff_rounds_and_cells(tmp_path):
    """Round artifacts of the driver's shape parse through the same cell
    keying as bench_gate (valid rounds only; invalid rounds refuse)."""
    repo = str(tmp_path)
    _bench_round(os.path.join(repo, "BENCH_r01.json"), 1, 68055.28, 68055.28)
    _bench_round(os.path.join(repo, "BENCH_r05.json"), 5, 861.25, 183004.03)
    with open(os.path.join(repo, "BENCH_r02.json"), "w") as fh:
        json.dump({"rc": 0, "parsed": {
            "metric": "synthetic throughput", "value": 0.0,
            "error": "device unreachable"}}, fh)
    n1, cells1 = load_bench_cells(os.path.join(repo, "BENCH_r01.json"))
    n5, cells5 = load_bench_cells(os.path.join(repo, "BENCH_r05.json"))
    assert n1 == 1 and n5 == 5 and cells1 and cells5
    with pytest.raises(ValueError, match="not a valid round"):
        load_bench_cells(os.path.join(repo, "BENCH_r02.json"))
    result = diff_runs(os.path.join(repo, "BENCH_r01.json"),
                       os.path.join(repo, "BENCH_r05.json"))
    assert result["mode"] == "bench_rounds"
    assert set(result["cells"]) == set(cells1) | set(cells5)


# ---------------------------------------------------------------------------
# watch mode
# ---------------------------------------------------------------------------


def pytest_doctor_watch_fires_on_new_finding(tmp_path, capsys):
    d = _clean_run(tmp_path, "watched")

    def _inject():
        time.sleep(0.3)
        _write_jsonl(os.path.join(d, "events.jsonl"),
                     [_event("loader_stall", severity="error",
                             cause="stall")])

    t = threading.Thread(target=_inject)
    t.start()
    found = watch(d, interval_s=0.1, max_seconds=10.0,
                  exit_on_finding=True)
    t.join()
    assert [f.kind for f in found] == ["loader_stall"]
    out = capsys.readouterr().out
    assert "FINDING [error] loader_stall" in out
    assert "loader_stall_timeout" in out  # remediation printed


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def pytest_doctor_cli_modes(tmp_path, capsys):
    from hydragnn_tpu.obs.doctor import main

    clean = _clean_run(tmp_path, "cli_clean")
    assert main([clean]) == 0
    out = capsys.readouterr().out
    assert "0 findings" in out
    bad = str(tmp_path / "cli_bad")
    _write_jsonl(os.path.join(bad, "events.jsonl"),
                 [_event("serve_wedge", severity="error", batch_index=0)])
    json_out = str(tmp_path / "doctor.json")
    assert main([bad, "--json", json_out]) == 1
    doc = json.load(open(json_out))
    assert doc["findings"][0]["kind"] == "wedged_step"
    assert main(["/nonexistent-dir-xyz"]) == 2
    capsys.readouterr()
    # trace subcommand: the analyze_trace successor
    tr = str(tmp_path / "t.jsonl")
    _write_jsonl(tr, [_span("train/step", 10.0) for _ in range(3)])
    assert main(["trace", tr]) == 0
    out = capsys.readouterr().out
    assert "train/step" in out and "p50" in out
