#!/usr/bin/env python
"""CI tracing-plane smoke (docs/OBSERVABILITY.md; wired into ci.sh).

One subprocess leg (fresh interpreter, CPU JAX, scrubbed env, temp workdir
— the compile_smoke recipe) asserting the r8 tentpole's acceptance
contract end-to-end:

1. **training leg**: a 2-epoch run with ``Telemetry.trace`` on
   (every-step sampling) must produce ``logs/<run>/trace.jsonl`` whose
   ``train/step`` roots carry ``train/host_batch_build`` +
   ``train/device_dispatch`` children with correct parentage (same
   traceId, parentSpanId = the root's spanId), plus a standalone
   ``train/checkpoint_write`` span from the final save.
2. **serving leg**: ``run_server`` with ``trace_sample: 1`` under
   injected queue pressure (requests admitted during warm-up) must yield
   a single trace per request covering admit → queue_wait → (linked
   serve/step: batch_form / bucket_select / device_step / respond) whose
   queue-wait span explains the measured request latency within 10%;
   then an injected wedged step (``HYDRAGNN_FAULT_SERVE_WEDGE`` past
   ``Serving.step_timeout_s``) must produce a flight-recorder dump
   containing the wedge event with its trace_id and the registry
   snapshot.
3. **overhead A/B**: the same step loop driven with tracing on vs off
   must show <= 2% step-time regression (best-of-3 blocks of interleaved
   trials — the telemetry_smoke measurement design).
4. **bench gate self-check**: ``bench_gate.py`` exits 0 on the repo's
   committed rounds, 1 on a synthetically degraded copy, and its trace
   gate round-trips a baseline derived from leg 1's trace (pass
   unchanged, fail against a 10x-shrunk baseline).

Exit 0 = tracing plane healthy; nonzero with a diagnostic otherwise.
"""

import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json
import os
import sys
import time

sys.path.insert(0, {repo!r})
import jax
import numpy as np

import hydragnn_tpu
from hydragnn_tpu.config import get_log_name_config

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "trace_smoke",
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
            "precompile": "background",
            # best-val checkpointing ON so a checkpoint write happens
            # INSIDE the traced loop (epoch 0 always improves on inf)
            "Checkpoint": True,
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Telemetry": {{
        "enabled": True, "interval_steps": 4,
        "trace": True, "trace_interval_steps": 1, "trace_sample": 1.0,
    }},
    "Serving": {{
        "batch_window_s": 0.001,
        "max_queue_requests": 512,
        "http_port": -1,
    }},
}}


def spans_of(run_dir):
    path = os.path.join(run_dir, "trace.jsonl")
    assert os.path.exists(path), f"no trace.jsonl in {{run_dir}}"
    return [json.loads(l) for l in open(path) if l.strip()]


def attr(rec, key):
    for a in rec.get("attributes", []):
        if a["key"] == key:
            v = a["value"]
            return v.get("intValue", v.get("doubleValue",
                         v.get("stringValue", v.get("boolValue"))))
    return None


def dur_s(rec):
    return (int(rec["endTimeUnixNano"]) - int(rec["startTimeUnixNano"])) / 1e9


# ---- leg 1: training span parentage -----------------------------------------
model, state, hist, cfg_out, loaders, mm = hydragnn_tpu.run_training(cfg)
run_dir = os.path.join("logs", get_log_name_config(cfg_out))
recs = spans_of(run_dir)
by_id = {{r["spanId"]: r for r in recs}}
roots = [r for r in recs if r["name"] == "train/step"]
assert roots, "no train/step root spans (every-step sampling was on)"
checked = 0
for root in roots:
    kids = [r for r in recs
            if r.get("parentSpanId") == root["spanId"]
            and r["traceId"] == root["traceId"]]
    names = {{k["name"] for k in kids}}
    assert "train/host_batch_build" in names, (root, names)
    assert "train/device_dispatch" in names, (root, names)
    assert "parentSpanId" not in root, "train/step must be a trace root"
    checked += 1
assert any(r["name"] == "train/checkpoint_write" for r in recs), (
    "final save emitted no checkpoint span"
)
assert any(r["name"] == "train/guard_verdict" for r in recs), (
    "epoch boundary emitted no guard-verdict span"
)
print(f"LEG1_TRAIN_SPANS_OK roots={{len(roots)}} checked={{checked}}",
      flush=True)
trace_len_after_training = len(recs)

# ---- leg 2: serving lifecycle + wedge flight dump ---------------------------
server = hydragnn_tpu.run_server(cfg)
try:
    # injected queue pressure: admissions are open while the ladder warms,
    # so requests submitted now wait out the warm-up in the queue — their
    # latency IS queue wait, which the queue_wait span must explain
    graphs = loaders[2].graphs
    handles = [server.submit(g) for g in graphs[:6]]
    assert server.wait_ready(300), f"serve warm-up failed: {{server.failed}}"
    for h in handles:
        assert h.error(120) is None
    lat0 = handles[0].done_at - handles[0].submitted_at
finally:
    server.close()

recs = spans_of(run_dir)
serve_recs = recs[trace_len_after_training:]
reqs = [r for r in serve_recs if r["name"] == "serve/request"]
assert len(reqs) >= 6, f"expected >=6 request traces, got {{len(reqs)}}"
req0 = [r for r in reqs if attr(r, "request_id") == "0"][0]
kids0 = {{r["name"] for r in serve_recs
         if r.get("parentSpanId") == req0["spanId"]
         and r["traceId"] == req0["traceId"]}}
assert {{"serve/admit", "serve/queue_wait"}} <= kids0, kids0
steps = [r for r in serve_recs if r["name"] == "serve/step"
         and r["traceId"] == req0["traceId"]]
assert steps, "lead request's trace is missing the serve/step span"
step_kids = {{r["name"] for r in serve_recs
             if r.get("parentSpanId") == steps[0]["spanId"]}}
assert {{"serve/batch_form", "serve/bucket_select", "serve/device_step",
        "serve/respond"}} <= step_kids, step_kids
# co-batched requests in other traces link to the shared step span
linked = [r for r in reqs if r["traceId"] != req0["traceId"] and any(
    l["spanId"] == steps[0]["spanId"] for l in r.get("links", []))]
qw = [r for r in serve_recs if r["name"] == "serve/queue_wait"
      and r["traceId"] == req0["traceId"]][0]
ratio = dur_s(qw) / max(dur_s(req0), 1e-9)
print(f"LEG2_SERVE_SPANS_OK requests={{len(reqs)}} linked={{len(linked)}} "
      f"queue_wait={{dur_s(qw)*1e3:.1f}}ms request={{dur_s(req0)*1e3:.1f}}ms "
      f"measured={{lat0*1e3:.1f}}ms ratio={{ratio:.2%}}", flush=True)
assert ratio > 0.90, (
    f"queue-wait span explains only {{ratio:.1%}} of the request latency "
    "(acceptance: within 10% under queue pressure)"
)

# wedged step -> flight-recorder dump: a fresh server whose batch 0 wedges
# past a tight watchdog budget
cfg["Serving"]["step_timeout_s"] = 0.5
os.environ["HYDRAGNN_FAULT_SERVE_WEDGE"] = "0:3"
from hydragnn_tpu.serve import WedgedStepError

server2 = hydragnn_tpu.run_server(cfg)
try:
    assert server2.wait_ready(300), server2.failed
    h = server2.submit(graphs[0])
    err = h.error(60)
    assert isinstance(err, WedgedStepError), err
finally:
    server2.close()
    del os.environ["HYDRAGNN_FAULT_SERVE_WEDGE"]

flight_root = os.path.join(run_dir, "flightrec")
dumps = sorted(d for d in os.listdir(flight_root) if not d.startswith("."))
wedge_dumps = [d for d in dumps if "serve_wedge" in d]
assert wedge_dumps, f"no serve_wedge flight dump in {{dumps}}"
dump = os.path.join(flight_root, wedge_dumps[-1])
evs = json.load(open(os.path.join(dump, "events.json")))
wedge_evs = [e for e in evs if e["kind"] == "serve_wedge"]
assert wedge_evs, "dump is missing the wedge event"
assert wedge_evs[-1].get("trace_id"), "wedge event carries no trace_id"
prom = open(os.path.join(dump, "metrics.prom")).read()
assert "hydragnn_serve_events_total" in prom, "dump registry snapshot empty"
assert json.load(open(os.path.join(dump, "meta.json")))["reason"] == "serve_wedge"
print(f"LEG2_WEDGE_DUMP_OK dump={{os.path.basename(dump)}}", flush=True)

# ---- leg 3: overhead A/B (tracing on vs off) --------------------------------
from hydragnn_tpu.data import GraphLoader
from hydragnn_tpu.obs.trace import Tracer
from hydragnn_tpu.train.loop import make_train_step, train_epoch
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.models import create_model, init_model

os.environ["HYDRAGNN_DEVICE_PREFETCH"] = "0"
train_loader = GraphLoader(
    loaders[0].graphs, 8, spec=loaders[0].ladder, seed=0, prefetch=0
)
ab_model = create_model(cfg_out)
variables = init_model(ab_model, next(iter(train_loader)), seed=0)
tx = make_optimizer(cfg_out["NeuralNetwork"]["Training"]["Optimizer"])
step = make_train_step(ab_model, tx)
tracer = Tracer(os.path.join(run_dir, "ab_trace"), every_n_steps=10)
rng = jax.random.PRNGKey(0)
ab_state = TrainState.create(variables, tx)
ab_state, _, _, rng, _ = train_epoch(train_loader, step, ab_state, rng)
n_batches = len(train_loader)
# best-of-3 interleaved blocks (the telemetry_smoke measurement design: a
# real additive per-step cost inflates the on-leg in EVERY block, machine
# drift cannot hit all three the same way)
ratios = []
for block in range(3):
    times = {{"off": [], "on": []}}
    for trial in range(8):
        for leg in ("off", "on"):
            t0 = time.perf_counter()
            ab_state, _, _, rng, _ = train_epoch(
                train_loader, step, ab_state, rng,
                tracer=tracer if leg == "on" else None,
            )
            times[leg].append((time.perf_counter() - t0) / n_batches)
    off_s = float(np.median(times["off"]))
    on_s = float(np.median(times["on"]))
    ratios.append(on_s / max(off_s, 1e-12))
    print(f"LEG3_AB block {{block}}: off={{off_s*1e3:.3f}}ms "
          f"on={{on_s*1e3:.3f}}ms delta={{(on_s/off_s-1)*100:+.2f}}%",
          flush=True)
tracer.close()
best = min(ratios)
print(f"LEG3_AB overhead={{(best-1)*100:.2f}}% (best of {{len(ratios)}}; "
      f"all: {{[round((r-1)*100, 2) for r in ratios]}})", flush=True)
assert best <= 1.02, (
    f"tracing overhead {{(best-1)*100:.2f}}% exceeds the 2% budget in "
    "EVERY block — a real per-step regression, not measurement noise"
)

# ---- leg 4: bench gate self-check -------------------------------------------
import shutil
import subprocess

gate = os.path.join({repo!r}, "run-scripts", "bench_gate.py")
rc = subprocess.run([sys.executable, gate, "--repo", {repo!r}]).returncode
assert rc == 0, f"bench_gate failed on the checkout (rc={{rc}})"
tmp = "bench_gate_degraded"
os.makedirs(tmp, exist_ok=True)
doc = {{"n": 5, "rc": 0, "parsed": {{
    "metric": "trace smoke throughput", "value": 100.0,
    "unit": "graphs/sec/chip"}}}}
json.dump(doc, open(os.path.join(tmp, "BENCH_r05.json"), "w"))
doc["parsed"]["value"] *= 0.5
doc["n"] = 6
json.dump(doc, open(os.path.join(tmp, "BENCH_r06.json"), "w"))
rc = subprocess.run([sys.executable, gate, "--repo", tmp]).returncode
assert rc == 1, f"bench_gate missed a 50% degraded cell (rc={{rc}})"
# trace gate round trip: baseline from leg 1's trace -> pass; 10x-shrunk
# baseline -> fail
trace_path = os.path.join(run_dir, "trace.jsonl")
base_path = os.path.join(tmp, "trace_baseline.json")
rc = subprocess.run([sys.executable, gate, "--repo", tmp,
                     "--trace", trace_path,
                     "--write-trace-baseline", base_path]).returncode
assert rc == 1, "degraded rounds must still fail while writing a baseline"
rc = subprocess.run([sys.executable, gate, "--repo", {repo!r},
                     "--trace", trace_path,
                     "--trace-baseline", base_path]).returncode
assert rc == 0, f"trace gate failed against its own baseline (rc={{rc}})"
# the reserved _meta key carries the trace's host-count topology (the
# fleet-plane comparability guard) — shrink only the stage entries
shrunk = {{k: (v if k == "_meta"
              else {{**v, "p50_ms": v["p50_ms"] / 10,
                     "p99_ms": v["p99_ms"] / 10}})
          for k, v in json.load(open(base_path)).items()}}
json.dump(shrunk, open(base_path, "w"))
rc = subprocess.run([sys.executable, gate, "--repo", {repo!r},
                     "--trace", trace_path,
                     "--trace-baseline", base_path]).returncode
assert rc == 1, f"trace gate missed a 10x stage regression (rc={{rc}})"
print("LEG4_BENCH_GATE_OK", flush=True)

print("TRACE_SMOKE_OK", flush=True)
"""


def _env(workdir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
    env["HYDRAGNN_COMPILE_CACHE_MIN_SECS"] = "0"
    return env


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="trace_smoke_")
    script = os.path.join(workdir, "child.py")
    with open(script, "w") as f:
        f.write(_CHILD.format(repo=_REPO))
    proc = subprocess.run(
        [sys.executable, script], cwd=workdir, env=_env(workdir),
        capture_output=True, text=True, timeout=900,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0 or "TRACE_SMOKE_OK" not in out:
        print(f"trace_smoke FAIL (rc={proc.returncode}):\n{out[-4000:]}")
        return 1
    for line in out.splitlines():
        if line.startswith(("LEG1_", "LEG2_", "LEG3_", "LEG4_", "TRACE_")):
            print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
