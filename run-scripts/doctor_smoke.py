#!/usr/bin/env python
"""CI run-doctor smoke (docs/OBSERVABILITY.md "Run doctor"; wired into
ci.sh): every existing ``HYDRAGNN_FAULT_*`` injection point becomes
ground truth for the diagnosis engine. Real runs (fresh interpreters,
CPU JAX, scrubbed env, temp workdirs — the telemetry_smoke recipe) are
driven through planted faults, and the doctor must name EXACTLY the
planted pathology, with evidence records attached:

1. **clean leg** (false-positive gate): a 2-epoch telemetry+trace run
   with no faults must yield ZERO findings, zero parse warnings, and a
   ``HYDRAGNN_DOCTOR=1`` end-of-run verdict line + ``doctor.json``.
2. **NaN drill** (``HYDRAGNN_FAULT_NAN_STEP``, numerics on): exactly
   ``nan_divergence``, its summary chained to the located tensor; the
   SAME finding from only the flightrec dump (crash-forensics path);
   ``watch`` mode tails the live run and fires the finding while the
   run is still going.
3. **loader stall drill** (``HYDRAGNN_FAULT_LOADER_STALL``): the run
   dies with LoaderStallError; exactly ``loader_stall``, with the crash
   dump folded into the finding instead of double-reported.
4. **corrupt sample drill** (``HYDRAGNN_FAULT_SAMPLE_NAN`` under
   ``Dataset.bad_sample_policy: quarantine``): exactly
   ``quarantine_rot``, manifest entries as evidence.
5. **serve wedge drill** (``HYDRAGNN_FAULT_SERVE_WEDGE``): exactly
   ``wedged_step`` over the serving run dir.
6. **straggler drill** (``HYDRAGNN_FAULT_STRAGGLE`` on simulated host 1
   of a 2-host run dir): exactly ``straggler``, from the per-host
   metrics streams alone.
7. **diff leg**: a synthetic degraded round pair proves ``doctor diff``'s
   per-cell deltas agree with ``bench_gate.py``'s ``gate_verdict.json``
   to the digit (gate consistency check).

Exit 0 = diagnosis engine healthy; nonzero with a diagnostic otherwise.
"""

import glob
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# generic training child: scenario picked via DOCTOR_SCENARIO
# ---------------------------------------------------------------------------

_TRAIN_CHILD = """
import os
import sys

sys.path.insert(0, {repo!r})
import jax

import hydragnn_tpu

scen = os.environ["DOCTOR_SCENARIO"]
cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "doctor_" + scen,
        "format": "synthetic",
        "synthetic": {{"number_configurations": 96}},
        "node_features": {{"name": ["x", "x2", "x3"], "dim": [1, 1, 1]}},
        "graph_features": {{"name": ["s"], "dim": [1]}},
    }},
    "NeuralNetwork": {{
        "Architecture": {{
            "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
            "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
            "output_heads": {{"graph": {{"num_sharedlayers": 1,
                                        "dim_sharedlayers": 8,
                                        "num_headlayers": 2,
                                        "dim_headlayers": [8, 8]}}}},
        }},
        "Variables_of_interest": {{
            "input_node_features": [0],
            "output_names": ["s"], "output_index": [0],
            "type": ["graph"], "denormalize_output": False,
        }},
        "Training": {{
            "num_epoch": 2, "batch_size": 8, "seed": 11,
            "num_pad_buckets": 3,
            "precompile": "blocking",
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Telemetry": {{"enabled": True, "interval_steps": 2,
                   "trace": True, "trace_interval_steps": 2}},
}}
if scen == "nan":
    cfg["Telemetry"]["numerics"] = True
if scen == "corrupt":
    cfg["Dataset"]["bad_sample_policy"] = "quarantine"
if scen == "stall":
    cfg["NeuralNetwork"]["Training"]["loader_stall_timeout"] = 2.0

try:
    hydragnn_tpu.run_training(cfg)
except BaseException as e:
    print("CHILD_TRAIN_RAISED %s: %s" % (type(e).__name__, e), flush=True)
    sys.exit(3)
print("CHILD_TRAIN_OK", flush=True)
"""

# ---------------------------------------------------------------------------
# serve child: fresh-init server driven into an injected wedge
# ---------------------------------------------------------------------------

_SERVE_CHILD = """
import os
import sys
import warnings

sys.path.insert(0, {repo!r})
import jax

# wedge batch 1 for 3s against a 0.5s step watchdog
os.environ["HYDRAGNN_FAULT_SERVE_WEDGE"] = "1:3"

import hydragnn_tpu
from hydragnn_tpu.serve import RequestError

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "doctor_wedge",
        "format": "synthetic",
        "synthetic": {{"number_configurations": 48}},
        "node_features": {{"name": ["x", "x2", "x3"], "dim": [1, 1, 1]}},
        "graph_features": {{"name": ["s"], "dim": [1]}},
    }},
    "NeuralNetwork": {{
        "Architecture": {{
            "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
            "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
            "output_heads": {{"graph": {{"num_sharedlayers": 1,
                                        "dim_sharedlayers": 8,
                                        "num_headlayers": 2,
                                        "dim_headlayers": [8, 8]}}}},
        }},
        "Variables_of_interest": {{
            "input_node_features": [0],
            "output_names": ["s"], "output_index": [0],
            "type": ["graph"], "denormalize_output": False,
        }},
        "Training": {{
            "num_epoch": 1, "batch_size": 8, "seed": 11,
            "num_pad_buckets": 1,
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Telemetry": {{"enabled": True, "trace": True, "trace_sample": 1.0}},
    "Serving": {{
        "batch_window_s": 0.001,
        "step_timeout_s": 0.5,
        "http_port": -1,
    }},
}}

with warnings.catch_warnings():
    warnings.simplefilter("ignore")  # fresh-init fallback is the plan
    server = hydragnn_tpu.run_server(cfg)
try:
    assert server.wait_ready(300), server.failed
    graphs = server._template_graphs
    (out,) = server.predict([graphs[0]], timeout=60)  # batch 0: clean
    wedged = server.submit(graphs[1])                 # batch 1: wedged
    err = wedged.error(timeout=60)
    assert err is not None and err.code == "wedged_step", err
    (out2,) = server.predict([graphs[2]], timeout=60)  # recycled runner
finally:
    server.close()
print("CHILD_SERVE_OK", flush=True)
"""


# cache-less children (smoke_env.py)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from smoke_env import child_env as _env  # noqa: E402


def _fail(tag, out, rc=None):
    print(f"doctor_smoke FAIL [{tag}]"
          + (f" (rc={rc})" if rc is not None else "") + f":\n{out[-4000:]}")
    return 1


def _run_dir_of(workdir, marker="metrics.jsonl"):
    hits = glob.glob(os.path.join(workdir, "logs", "*", marker))
    assert hits, f"no run dir (by {marker}) under {workdir}/logs"
    return os.path.dirname(hits[0])


def _train(workdir, scenario, extra_env=None, expect_rc=0):
    script = os.path.join(workdir, f"child_{scenario}.py")
    with open(script, "w") as f:
        f.write(_TRAIN_CHILD.format(repo=_REPO))
    env = _env({"DOCTOR_SCENARIO": scenario, **(extra_env or {})})
    proc = subprocess.run(
        [sys.executable, script], cwd=workdir, env=env,
        capture_output=True, text=True, timeout=900,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != expect_rc:
        raise AssertionError(
            f"[{scenario}] child rc={proc.returncode} (wanted "
            f"{expect_rc}):\n{out[-4000:]}"
        )
    return out


def _doctor(workdir, *args):
    """Run the doctor CLI in the child's workdir; returns (rc, output,
    parsed doctor.json when --json was passed)."""
    json_path = None
    argv = list(args)
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    proc = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.obs.doctor"] + argv,
        cwd=workdir, env=_env(), capture_output=True, text=True,
        timeout=300,
    )
    doc = None
    if json_path is not None and os.path.exists(
            os.path.join(workdir, json_path)):
        with open(os.path.join(workdir, json_path)) as fh:
            doc = json.load(fh)
    return proc.returncode, proc.stdout + proc.stderr, doc


def _expect_exact(tag, doc, kinds, rc, out):
    got = [f["kind"] for f in doc["findings"]]
    assert got == kinds, (
        f"[{tag}] doctor named {got}, wanted exactly {kinds}\n{out[-2500:]}"
    )
    for f in doc["findings"]:
        assert f["evidence_total"] >= 1, f"[{tag}] finding without evidence: {f}"
        assert f["remediation"], f
    assert (rc == 1) == bool(kinds), (tag, rc, kinds)


def main() -> int:  # noqa: C901 — one linear drill script
    t0 = time.time()

    # ---- leg 1: clean run, zero findings (false-positive gate) ------------
    wd = tempfile.mkdtemp(prefix="doctor_clean_")
    try:
        out = _train(wd, "clean", extra_env={"HYDRAGNN_DOCTOR": "1"})
    except AssertionError as e:
        return _fail("clean/train", str(e))
    if "run doctor: 0 finding(s)" not in out:
        return _fail("clean/verdict-line", out)
    run_dir = _run_dir_of(wd)
    if not os.path.exists(os.path.join(run_dir, "doctor.json")):
        return _fail("clean/doctor.json", out)
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "clean_doctor.json")
    if rc != 0 or doc["findings"]:
        return _fail("clean/doctor", dout + json.dumps(doc["findings"]), rc)
    if doc["report"]["parse_warnings"]:
        return _fail("clean/parse-warnings",
                     json.dumps(doc["report"]["parse_warnings"]))
    if not os.path.exists(os.path.join(run_dir, "events.jsonl")):
        return _fail("clean/events.jsonl", "events sink never armed")
    print(f"LEG1_CLEAN_OK zero findings ({time.time() - t0:.0f}s)",
          flush=True)

    # ---- leg 2: NaN drill + dump-only ingestion + watch mode --------------
    wd = tempfile.mkdtemp(prefix="doctor_nan_")
    script = os.path.join(wd, "child_nan.py")
    with open(script, "w") as f:
        f.write(_TRAIN_CHILD.format(repo=_REPO))
    child = subprocess.Popen(
        [sys.executable, script], cwd=wd,
        env=_env({"DOCTOR_SCENARIO": "nan",
                  "HYDRAGNN_FAULT_NAN_STEP": "3+"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # watch the live run: wait for the run dir to appear, then tail it
    run_dir = None
    deadline = time.time() + 300
    while time.time() < deadline:
        hits = glob.glob(os.path.join(wd, "logs", "*", "metrics.jsonl"))
        if hits:
            run_dir = os.path.dirname(hits[0])
            break
        time.sleep(0.5)
    if run_dir is None:
        child.kill()
        return _fail("nan/run-dir", child.communicate()[0] or "")
    wrc, wout, _ = _doctor(wd, "watch", os.path.relpath(run_dir, wd),
                           "--interval", "1", "--max-seconds", "240",
                           "--exit-on-finding")
    child_out = child.communicate(timeout=600)[0] or ""
    if child.returncode != 0:
        return _fail("nan/train", child_out, child.returncode)
    if wrc != 0 or "FINDING" not in wout or "nan_divergence" not in wout:
        return _fail("nan/watch", wout, wrc)
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "nan_doctor.json")
    try:
        _expect_exact("nan", doc, ["nan_divergence"], rc, dout)
        f = doc["findings"][0]
        assert "first non-finite tensor" in f["summary"], f["summary"]
        assert f["severity"] == "error", f
    except AssertionError as e:
        return _fail("nan/doctor", str(e))
    # crash-forensics path: the flightrec dump ALONE reaches the verdict
    dumps = [d for d in glob.glob(os.path.join(run_dir, "flightrec", "*"))
             if os.path.isdir(d)]
    if not dumps:
        return _fail("nan/no-dump", dout)
    rc2, dout2, doc2 = _doctor(wd, os.path.relpath(dumps[0], wd),
                               "--json", "nan_dump_doctor.json")
    try:
        _expect_exact("nan/dump", doc2, ["nan_divergence"], rc2, dout2)
    except AssertionError as e:
        return _fail("nan/dump-doctor", str(e))
    print(f"LEG2_NAN_OK live+dump+watch agree ({time.time() - t0:.0f}s)",
          flush=True)

    # ---- leg 3: loader stall drill (run dies; crash folds into finding) ---
    wd = tempfile.mkdtemp(prefix="doctor_stall_")
    try:
        out = _train(wd, "stall", expect_rc=3,
                     extra_env={"HYDRAGNN_FAULT_LOADER_STALL": "2:30"})
    except AssertionError as e:
        return _fail("stall/train", str(e))
    if "LoaderStallError" not in out:
        return _fail("stall/exception", out)
    run_dir = _run_dir_of(wd)
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "stall_doctor.json")
    try:
        _expect_exact("stall", doc, ["loader_stall"], rc, dout)
        assert doc["findings"][0]["data"].get("crash_dump"), (
            "the train_exception dump was not folded into the finding"
        )
    except AssertionError as e:
        return _fail("stall/doctor", str(e))
    print(f"LEG3_STALL_OK crash folded ({time.time() - t0:.0f}s)",
          flush=True)

    # ---- leg 4: corrupt-sample drill (quarantine manifest evidence) -------
    wd = tempfile.mkdtemp(prefix="doctor_corrupt_")
    try:
        _train(wd, "corrupt",
               extra_env={"HYDRAGNN_FAULT_SAMPLE_NAN": "3,7"})
    except AssertionError as e:
        return _fail("corrupt/train", str(e))
    run_dir = _run_dir_of(wd)
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "corrupt_doctor.json")
    try:
        _expect_exact("corrupt", doc, ["quarantine_rot"], rc, dout)
        f = doc["findings"][0]
        assert f["data"]["quarantined"] == 2, f["data"]
        assert "bad_sample_policy" in f["remediation"]
    except AssertionError as e:
        return _fail("corrupt/doctor", str(e))
    print(f"LEG4_CORRUPT_OK 2 quarantined ({time.time() - t0:.0f}s)",
          flush=True)

    # ---- leg 5: serve wedge drill -----------------------------------------
    wd = tempfile.mkdtemp(prefix="doctor_wedge_")
    script = os.path.join(wd, "child_serve.py")
    with open(script, "w") as f:
        f.write(_SERVE_CHILD.format(repo=_REPO))
    proc = subprocess.run(
        [sys.executable, script], cwd=wd, env=_env(),
        capture_output=True, text=True, timeout=900,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0 or "CHILD_SERVE_OK" not in out:
        return _fail("wedge/serve", out, proc.returncode)
    # a pure serving run writes no metrics.jsonl — find it by its events
    run_dir = _run_dir_of(wd, marker="events.jsonl")
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "wedge_doctor.json")
    try:
        _expect_exact("wedge", doc, ["wedged_step"], rc, dout)
        assert "step_timeout_s" in doc["findings"][0]["remediation"]
    except AssertionError as e:
        return _fail("wedge/doctor", str(e))
    print(f"LEG5_WEDGE_OK ({time.time() - t0:.0f}s)", flush=True)

    # ---- leg 6: straggler drill (2 simulated hosts, one run dir) ----------
    wd = tempfile.mkdtemp(prefix="doctor_straggle_")
    try:
        _train(wd, "straggle",
               extra_env={"HYDRAGNN_FLEET_HOST_INDEX": "0",
                          "HYDRAGNN_FLEET_HOST_COUNT": "2"})
        _train(wd, "straggle",
               extra_env={"HYDRAGNN_FLEET_HOST_INDEX": "1",
                          "HYDRAGNN_FLEET_HOST_COUNT": "2",
                          "HYDRAGNN_FAULT_STRAGGLE": "0+:0.05"})
    except AssertionError as e:
        return _fail("straggle/train", str(e))
    run_dir = _run_dir_of(wd)
    if not os.path.exists(os.path.join(run_dir, "metrics-h1.jsonl")):
        return _fail("straggle/h1-stream",
                     str(os.listdir(run_dir)))
    rc, dout, doc = _doctor(wd, os.path.relpath(run_dir, wd),
                            "--json", "straggle_doctor.json")
    try:
        _expect_exact("straggle", doc, ["straggler"], rc, dout)
        assert "1" in doc["findings"][0]["data"]["hosts"], doc["findings"][0]
    except AssertionError as e:
        return _fail("straggle/doctor", str(e))
    print(f"LEG6_STRAGGLER_OK host 1 named ({time.time() - t0:.0f}s)",
          flush=True)

    # ---- leg 7: diff mode over bench rounds + gate consistency ------------
    # synthetic degraded pair: the deltas must agree with the verdict to
    # the digit, and the regression must show as a failed cell
    wd = tempfile.mkdtemp(prefix="doctor_diff_")
    for n, val in ((11, 100.0), (12, 70.0)):
        with open(os.path.join(wd, f"BENCH_r{n}.json"), "w") as fh:
            json.dump({"rc": 0, "parsed": {
                "metric": "doctor smoke throughput", "value": val,
                "synthetic_pna_graphs_per_sec": 1000.0 * n}}, fh)
    verdict2 = os.path.join(wd, "gate_verdict_syn.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "run-scripts", "bench_gate.py"),
         "--repo", wd, "--verdict-out", verdict2],
        cwd=wd, env=_env(), capture_output=True, text=True, timeout=120,
    )
    if proc.returncode != 1:  # the 30% drop must fail the gate
        return _fail("diff/syn-gate", proc.stdout + proc.stderr,
                     proc.returncode)
    vdoc = json.load(open(verdict2))
    statuses = {c["cell"]: c["status"] for c in vdoc["cells"]}
    if statuses.get("doctor smoke throughput :: value") != "fail":
        return _fail("diff/syn-status", json.dumps(vdoc["cells"]))
    rc, dout, _ = _doctor(
        wd, "diff", os.path.join(wd, "BENCH_r11.json"),
        os.path.join(wd, "BENCH_r12.json"), "--gate", verdict2,
    )
    if rc != 0 or "consistent=True" not in dout or "-30.0%" not in dout:
        return _fail("diff/syn-doctor", dout, rc)
    print(f"LEG7_DIFF_OK gate-consistent ({time.time() - t0:.0f}s)",
          flush=True)

    print("DOCTOR_SMOKE_OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
