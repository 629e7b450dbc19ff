#!/usr/bin/env python
"""CI telemetry-plane smoke (docs/OBSERVABILITY.md; wired into ci.sh).

One subprocess leg (fresh interpreter, CPU JAX, scrubbed env, temp
workdir — the compile_smoke recipe) that exercises the whole plane
end-to-end and asserts the acceptance contract of the r7 tentpole:

1. **training leg**: a 2-epoch CPU run with the ``Telemetry`` section
   enabled must produce a versioned ``metrics.jsonl`` stream whose
   ``step_window`` records carry step time / goodput / padding waste /
   MFU estimate (schema-asserted), ``epoch`` records marked non-filler,
   and health counters routed into ``scalars.jsonl`` (guard skips,
   data-plane skips, compile cache hits/misses, retrace violations).
2. **serving leg**: ``run_server`` over the trained run must expose
   ``/metrics`` + ``/healthz`` + ``/readyz`` (readiness flipping only
   after the full-ladder warm-up), and a load burst against a tiny p99
   SLO must shed — after which every named series of the catalog (step
   time, padding waste, MFU estimate, queue depth, shed count, cache
   hits, guard skips) is present in one scrape.
3. **overhead A/B**: the same step loop driven with telemetry on vs off
   must show <= 2% mean step-time regression (min-of-means over
   interleaved trials, so machine drift hits both legs).
4. **double-buffer A/B**: ``Training.double_buffer`` on vs off through
   the same loop — the prefetch-depth gauge must read the configured
   depth in each leg (the knob reaches the staging path) and the
   double-buffered leg must stay within 1.5x of the inline one (the
   thread handoff is bounded; its H2D win is a hardware-round number).
5. **numerics leg** (own single-device child): a training run with
   ``Telemetry.numerics`` on and an injected gradient NaN
   (``HYDRAGNN_FAULT_NAN_STEP``, utils/faultinject.py) must produce
   typed ``numerics_provenance`` events naming the poisoned tensor, a
   ``guard_skip`` event carrying batch provenance, a flight-recorder
   dump with the OOM-forensics ``memory.json``, ``numerics`` records in
   metrics.jsonl, and a populated HBM table — then a clean numerics-on
   vs numerics-off A/B must hold the same <= 2% step-time budget.

Exit 0 = telemetry plane healthy; nonzero with a diagnostic otherwise.
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
import urllib.request

sys.path.insert(0, {repo!r})
import jax
import numpy as np

import hydragnn_tpu
from hydragnn_tpu.config import get_log_name_config

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "telemetry_smoke",
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
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Telemetry": {{"enabled": True, "interval_steps": 2}},
    "Serving": {{
        "batch_window_s": 0.001,
        "max_queue_requests": 512,
        "slo_p99_s": 0.02,
        "expected_latency_per_graph_s": 0.05,
        "http_port": 0,
    }},
}}

# ---- leg 1: training --------------------------------------------------------
model, state, hist, cfg_out, loaders, mm = hydragnn_tpu.run_training(cfg)
run_dir = os.path.join("logs", get_log_name_config(cfg_out))

records = [json.loads(l) for l in open(os.path.join(run_dir, "metrics.jsonl"))]
assert records, "metrics.jsonl is empty"
for r in records:
    assert r["v"] == 1 and "ts" in r and "kind" in r, f"bad schema: {{r}}"
windows = [r for r in records if r["kind"] == "step_window"]
epochs = [r for r in records if r["kind"] == "epoch"]
runs = [r for r in records if r["kind"] == "run"]
assert windows and epochs and runs, (len(windows), len(epochs), len(runs))
for w in windows:
    for key in ("step", "steps", "step_time_ms", "graphs_per_sec",
                "nodes_per_sec", "edges_per_sec", "padding_waste",
                "mfu_est", "buckets"):
        assert key in w, f"step_window missing {{key}}: {{w}}"
    assert 0.0 <= w["padding_waste"] < 1.0, w
    assert w["step_time_ms"] > 0 and w["graphs_per_sec"] > 0, w
# this child runs on the CPU, which has no listed peak FLOP/s: the FLOPs
# source is wired (precompile harvest), yet no window may carry an MFU
assert all(w["mfu_est"] is None for w in windows), (
    "an MFU estimate was published on a device with no listed peak"
)
for e in epochs:
    assert e["filler"] is False and np.isfinite(e["val"]), e
assert len(epochs) == 2 and runs[-1]["epochs"] == 2, (epochs, runs)
assert runs[-1]["compile"]["specializations"] > 0, runs[-1]

scalar_tags = {{json.loads(l)["tag"]
               for l in open(os.path.join(run_dir, "scalars.jsonl"))}}
for tag in ("guard/skipped_steps", "data/skipped_samples",
            "compile/cache_hits", "compile/cache_misses",
            "compile/retrace_violations", "telemetry/step_time_ms",
            "telemetry/padding_waste", "loss/train"):
    assert tag in scalar_tags, f"scalars.jsonl missing {{tag}}: {{sorted(scalar_tags)}}"
print("LEG1_TRAINING_OK windows=%d" % len(windows), flush=True)

# ---- leg 2: serving endpoint + load burst -----------------------------------
def get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()

server = hydragnn_tpu.run_server(cfg)
try:
    assert server.http_port, "Serving.http_port=0 did not bind an endpoint"
    base = f"http://127.0.0.1:{{server.http_port}}"
    first_ready, _ = get(base + "/readyz")
    assert server.wait_ready(300), f"serve warm-up failed: {{server.failed}}"
    ready_after, _ = get(base + "/readyz")
    assert ready_after == 200, ready_after
    # the poll racing warm-up normally sees not-ready, but a fully cached
    # ladder (leg 1 populated the compile cache) can legitimately warm up
    # before the first GET — the deterministic wiring proof is the drain
    # flip below plus tests/test_obs.py; only an impossible status fails
    assert first_ready in (200, 503), first_ready
    if first_ready == 200:
        print("note: warm-up finished before the first /readyz poll "
              "(cached ladder); flip-before-ready not observed this run",
              flush=True)
    health, _ = get(base + "/healthz")
    assert health == 200, health

    graphs = loaders[2].graphs
    from hydragnn_tpu.serve import RequestError

    # completions first, one at a time: with the tiny SLO armed, a zero
    # backlog is the only admissible state, so each request must finish
    # before the next is submitted
    for g in graphs[:8]:
        (out,) = server.predict([g], timeout=60)
        assert isinstance(out, dict), out
    # burst: flood far past the tiny p99 SLO — the server must shed
    handles, shed = [], 0
    for i in range(300):
        try:
            handles.append(server.submit(graphs[i % len(graphs)]))
        except RequestError as e:
            shed += 1 if e.code in ("shed", "queue_full") else 0
    for h in handles:
        h.wait(120)
    stats = server.stats()
    assert shed > 0 and stats["shed"] > 0, (shed, stats)
    assert stats["completed"] > 0, stats

    code, text = get(base + "/metrics")
    assert code == 200, code
    named = [
        'hydragnn_step_time_seconds_count{{phase="train"}}',
        "hydragnn_padding_waste_fraction",
        "hydragnn_mfu_estimate",
        "hydragnn_serve_queue_depth",
        'hydragnn_serve_events_total{{event="shed"}}',
        "hydragnn_compile_cache_hits_total",
        "hydragnn_guard_skipped_steps_total",
        "hydragnn_serve_batch_latency_seconds_count",
        "hydragnn_checkpoint_seconds_count",
        "hydragnn_loader_prefetch_depth",
    ]
    for series in named:
        assert series in text, f"/metrics missing {{series}}"
    shed_line = [l for l in text.splitlines()
                 if l.startswith('hydragnn_serve_events_total{{event="shed"}}')]
    assert shed_line and float(shed_line[0].split()[-1]) > 0, shed_line
    # a draining server must fall out of its load balancer
    server.initiate_drain()
    draining_ready, _ = get(base + "/readyz")
    assert draining_ready == 503, draining_ready
finally:
    server.close()
print("LEG2_SERVING_OK shed=%d" % stats["shed"], flush=True)

# ---- leg 3: overhead A/B (telemetry on vs off) ------------------------------
from hydragnn_tpu.data import GraphLoader
from hydragnn_tpu.obs.telemetry import StepTelemetry, resolve_telemetry
from hydragnn_tpu.train.loop import make_train_step, train_epoch
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.models import create_model, init_model

# single-threaded loop for the A/B: the prefetch threads add multi-percent
# step-time jitter that would swamp a 2% budget; the telemetry bill being
# measured is identical either way
os.environ["HYDRAGNN_DEVICE_PREFETCH"] = "0"
train_loader = GraphLoader(
    loaders[0].graphs, 8, spec=loaders[0].ladder, seed=0, prefetch=0
)
ab_model = create_model(cfg_out)
variables = init_model(ab_model, next(iter(train_loader)), seed=0)
tx = make_optimizer(cfg_out["NeuralNetwork"]["Training"]["Optimizer"])
step = make_train_step(ab_model, tx)
telem = StepTelemetry(
    resolve_telemetry({{"Telemetry": {{"enabled": True}}}}),
    "telemetry_smoke_ab",
)
rng = jax.random.PRNGKey(0)
ab_state = TrainState.create(variables, tx)
# warm both paths (compile everything) before timing
ab_state, _, _, rng, _ = train_epoch(train_loader, step, ab_state, rng)
n_batches = len(train_loader)
# Measurement design: this box's NULL A/B (off vs off, identical code)
# shows ~±1.5% systematic drift between interleaved legs — above the
# ~0.5% true telemetry bill. So the gate is best-of-3 independent blocks
# of interleaved pairs: a REAL >2% per-step overhead inflates the on-leg
# in EVERY block (it is an additive per-step cost), while a contention
# burst cannot hit all three the same way. Medians within a block absorb
# per-epoch spikes.
ratios = []
for block in range(3):
    times = {{"off": [], "on": []}}
    for trial in range(10):
        for leg in ("off", "on"):
            t0 = time.perf_counter()
            ab_state, _, _, rng, _ = train_epoch(
                train_loader, step, ab_state, rng,
                telemetry=telem if leg == "on" else None,
            )
            times[leg].append((time.perf_counter() - t0) / n_batches)
    off_s = float(np.median(times["off"]))
    on_s = float(np.median(times["on"]))
    ratios.append((on_s + 0.0) / max(off_s, 1e-12))
    print(f"LEG3_AB block {{block}}: off={{off_s*1e3:.3f}}ms "
          f"on={{on_s*1e3:.3f}}ms delta={{(on_s/off_s-1)*100:+.2f}}%",
          flush=True)
telem.close()
best = min(ratios)
print(f"LEG3_AB overhead={{(best-1)*100:.2f}}% (best of {{len(ratios)}} "
      f"blocks; all: {{[round((r-1)*100, 2) for r in ratios]}})", flush=True)
assert best <= 1.02, (
    f"telemetry overhead {{(best-1)*100:.2f}}% exceeds the 2% budget in "
    f"EVERY block (per-block deltas "
    f"{{[round((r-1)*100, 2) for r in ratios]}}%) — a real per-step "
    "regression, not measurement noise"
)
print("TELEMETRY_SMOKE_OK", flush=True)
"""

# ---- leg 4 child: Training.double_buffer A/B --------------------------------
# its OWN subprocess on ONE CPU device: the staging path deactivates on
# multi-device processes, so under ci.sh's forced 8-device mesh the main
# child's gauge would read 0 in both legs and the A/B would be vacuous —
# legs 1-3 keep their historical 8-device environment untouched
_DB_CHILD = """
import os
import sys
import time

sys.path.insert(0, {repo!r})
import jax
import numpy as np

from hydragnn_tpu.data import (
    GraphLoader, MinMax, VariablesOfInterest, deterministic_graph_dataset,
    extract_variables,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.obs.registry import registry
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.loop import make_train_step, train_epoch
from hydragnn_tpu.config import update_config

assert jax.local_device_count() == 1, jax.devices()
graphs = MinMax.fit(g := deterministic_graph_dataset(64, seed=3)).apply(g)
voi = VariablesOfInterest([0], ["s"], ["graph"], [0], [1, 1, 1], [1])
graphs = [extract_variables(x, voi) for x in graphs]
cfg = {{
    "Dataset": {{"node_features": {{"dim": [1, 1, 1]}},
                 "graph_features": {{"dim": [1]}}}},
    "NeuralNetwork": {{
        "Architecture": {{"mpnn_type": "GIN", "hidden_dim": 8,
                          "num_conv_layers": 2, "task_weights": [1.0],
                          "output_heads": {{"graph": {{
                              "num_sharedlayers": 1, "dim_sharedlayers": 8,
                              "num_headlayers": 2, "dim_headlayers": [8, 8]}}}}}},
        "Variables_of_interest": {{"input_node_features": [0],
                                   "output_names": ["s"], "output_index": [0],
                                   "type": ["graph"]}},
        "Training": {{"batch_size": 8,
                      "Optimizer": {{"type": "AdamW",
                                     "learning_rate": 0.01}}}},
    }},
}}
cfg = update_config(cfg, graphs, graphs[:4], graphs[:4])
loader = GraphLoader(graphs, 8, seed=0, prefetch=0)
model = create_model(cfg)
variables = init_model(model, next(iter(loader)), seed=0)
tx = make_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
step = make_train_step(model, tx)
state = TrainState.create(variables, tx)
rng = jax.random.PRNGKey(0)
state, _, _, rng, _ = train_epoch(loader, step, state, rng)  # compile warm
n_batches = len(loader)
os.environ.pop("HYDRAGNN_DEVICE_PREFETCH", None)  # let the knob decide
times = {{}}
for leg, depth in (("off", 0), ("on", 2)):
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, _, _, rng, _ = train_epoch(
            loader, step, state, rng, prefetch_depth=depth,
        )
        samples.append((time.perf_counter() - t0) / n_batches)
    times[leg] = float(np.median(samples))
    gauge = registry().get("hydragnn_device_prefetch_depth")
    assert gauge is not None and gauge.value() == float(depth), (
        "double_buffer leg %r: prefetch-depth gauge reads %s, wanted %d "
        "— the config knob did not reach the staging path"
        % (leg, gauge and gauge.value(), depth)
    )
ratio = times["on"] / max(times["off"], 1e-12)
print("LEG4_DB off=%.3fms on=%.3fms ratio=%.3f"
      % (times["off"] * 1e3, times["on"] * 1e3, ratio), flush=True)
assert ratio <= 1.5, (
    "double-buffered staging is %.2fx the inline loop — the staging "
    "thread is costing far more than a queue handoff should" % ratio
)
print("LEG4_DOUBLE_BUFFER_OK", flush=True)
"""


# ---- leg 5 child: numerics observatory + NaN provenance ---------------------
# its OWN single-device subprocess: the injected-fault env must not leak
# into legs 1-4, and the A/B wants the deterministic single-device loop
_NUM_CHILD = """
import json
import os
import sys
import time

sys.path.insert(0, {repo!r})
import jax
import numpy as np

# armed BEFORE the first step traces: poison_grads reads the env at trace
# time; "3+" keeps the condition true at diagnosis time too
os.environ["HYDRAGNN_FAULT_NAN_STEP"] = "3+"

import hydragnn_tpu
from hydragnn_tpu.config import get_log_name_config

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "numerics_smoke",
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
            "num_pad_buckets": 1,
            "precompile": "blocking",
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Telemetry": {{"enabled": True, "interval_steps": 2, "numerics": True}},
}}

model, state, hist, cfg_out, loaders, mm = hydragnn_tpu.run_training(cfg)
run_dir = os.path.join("logs", get_log_name_config(cfg_out))

from hydragnn_tpu.obs.events import events

evs = events().snapshot()
prov = [e for e in evs if e["kind"] == "numerics_provenance"]
assert prov, "no numerics_provenance event despite injected NaN"
named = [e for e in prov if e.get("layer") and e["layer"] != "<unreproduced>"]
assert named, f"provenance never named a tensor: {{prov[:3]}}"
assert named[0].get("tensor_kind") == "gradient", named[0]
assert named[0].get("level"), named[0]
print("LEG5_PROVENANCE_OK layer=%s events=%d"
      % (named[0]["layer"], len(prov)), flush=True)

gs = [e for e in evs if e["kind"] == "guard_skip"]
assert gs, "no guard_skip event despite injected NaN"
assert any(e.get("layers") or e.get("batches") for e in gs), (
    "guard_skip events carry no batch provenance: %r" % gs
)

fdir = os.path.join(run_dir, "flightrec")
dumps = [d for d in os.listdir(fdir) if "numerics_provenance" in d]
assert dumps, os.listdir(fdir)
mem = json.load(open(os.path.join(fdir, dumps[0], "memory.json")))
assert "hbm_by_spec" in mem, mem
dump_evs = json.load(open(os.path.join(fdir, dumps[0], "events.json")))
assert any(e["kind"] == "numerics_provenance" for e in dump_evs)

recs = [json.loads(l) for l in open(os.path.join(run_dir, "metrics.jsonl"))]
nrecs = [r for r in recs if r["kind"] == "numerics"]
assert nrecs, "metrics.jsonl has no numerics records"
assert any(
    any(g["nonfinite"] > 0 for g in r["gradients"].values()) for r in nrecs
), "no numerics record shows the injected non-finite gradients"

# HBM table: blocking precompile harvested memory_analysis on this backend
from hydragnn_tpu.obs import memory as obs_memory

snap = obs_memory.snapshot()
assert any(k.startswith("train:") for k in snap), snap
assert all(v["peak_bytes"] > 0 for v in snap.values()), snap
print("LEG5_FORENSICS_OK dumps=%d numerics_records=%d hbm_specs=%d"
      % (len(dumps), len(nrecs), len(snap)), flush=True)

# ---- numerics on/off overhead A/B ------------------------------------------
# clean steps (fault disarmed; poison is read at trace time, so the fresh
# builders below compile the identity). Production-representative shape:
# ~60-node BCC cells, batch 32 (~2300 padded nodes / ~70k edges), hidden
# 128 — the probes' single fused stat-reduce per tensor must disappear
# into a real step's compute, not into a 1 ms dispatch-bound toy step
del os.environ["HYDRAGNN_FAULT_NAN_STEP"]
os.environ["HYDRAGNN_DEVICE_PREFETCH"] = "0"
from hydragnn_tpu.data import (
    GraphLoader, MinMax, VariablesOfInterest, deterministic_graph_dataset,
    extract_variables,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.obs.numerics import NanWatch
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.loop import make_train_step, train_epoch
from hydragnn_tpu.config import update_config

graphs = MinMax.fit(g := deterministic_graph_dataset(
    64, unit_cell_x_range=(3, 5), unit_cell_y_range=(3, 5),
    unit_cell_z_range=(2, 4), seed=3)).apply(g)
voi = VariablesOfInterest([0], ["s"], ["graph"], [0], [1, 1, 1], [1])
graphs = [extract_variables(x, voi) for x in graphs]
ab_cfg = {{
    "Dataset": {{"node_features": {{"dim": [1, 1, 1]}},
                 "graph_features": {{"dim": [1]}}}},
    "NeuralNetwork": {{
        "Architecture": {{"mpnn_type": "GIN", "hidden_dim": 128,
                          "num_conv_layers": 3, "task_weights": [1.0],
                          "output_heads": {{"graph": {{
                              "num_sharedlayers": 1, "dim_sharedlayers": 128,
                              "num_headlayers": 2,
                              "dim_headlayers": [128, 128]}}}}}},
        "Variables_of_interest": {{"input_node_features": [0],
                                   "output_names": ["s"], "output_index": [0],
                                   "type": ["graph"]}},
        "Training": {{"batch_size": 32,
                      "Optimizer": {{"type": "AdamW",
                                     "learning_rate": 0.01}}}},
    }},
}}
ab_cfg = update_config(ab_cfg, graphs, graphs[:4], graphs[:4])
loader = GraphLoader(graphs, 32, seed=0, prefetch=0)
ab_model = create_model(ab_cfg)
variables = init_model(ab_model, next(iter(loader)), seed=0)
tx = make_optimizer(ab_cfg["NeuralNetwork"]["Training"]["Optimizer"])
step_off = make_train_step(ab_model, tx, numerics=False)
step_on = make_train_step(ab_model, tx, numerics=True)
rng = jax.random.PRNGKey(0)
ab_state = TrainState.create(variables, tx)
# warm BOTH programs before timing (they compile differently by design)
ab_state, _, _, rng, _ = train_epoch(loader, step_off, ab_state, rng)
ab_state, _, _, rng, _ = train_epoch(
    loader, step_on, ab_state, rng,
    nan_watch=NanWatch(diagnose=step_on._nan_diagnose),
)
n_batches = len(loader)
# same gate design as leg 3: best-of-3 blocks of interleaved medians — a
# real additive per-step cost inflates the on leg in EVERY block
ratios = []
for block in range(3):
    times = {{"off": [], "on": []}}
    for trial in range(8):
        for leg in ("off", "on"):
            watch = (
                NanWatch(diagnose=step_on._nan_diagnose)
                if leg == "on" else None
            )
            t0 = time.perf_counter()
            ab_state, _, _, rng, _ = train_epoch(
                loader, step_on if leg == "on" else step_off, ab_state,
                rng, nan_watch=watch,
            )
            times[leg].append((time.perf_counter() - t0) / n_batches)
    off_s = float(np.median(times["off"]))
    on_s = float(np.median(times["on"]))
    ratios.append(on_s / max(off_s, 1e-12))
    print(f"LEG5_AB block {{block}}: off={{off_s*1e3:.3f}}ms "
          f"on={{on_s*1e3:.3f}}ms delta={{(on_s/off_s-1)*100:+.2f}}%",
          flush=True)
best = min(ratios)
print(f"LEG5_AB overhead={{(best-1)*100:.2f}}% (best of {{len(ratios)}}; "
      f"all: {{[round((r-1)*100, 2) for r in ratios]}})", flush=True)
assert best <= 1.02, (
    f"numerics overhead {{(best-1)*100:.2f}}% exceeds the 2% budget in "
    f"EVERY block ({{[round((r-1)*100, 2) for r in ratios]}}%) — the "
    "in-graph probes are costing more than fused reductions should"
)
print("LEG5_NUMERICS_OK", flush=True)
"""


def _env(workdir, single_device=False):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if single_device:
        # the double-buffer child needs ONE device (the staging path
        # deactivates on multi-device processes); strip ci.sh's forced
        # 8-device mesh flag
        env["XLA_FLAGS"] = " ".join(
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        )
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    # a cache private to this smoke; CPU-sized compiles beat jax's default
    # 1s cache-write floor, so the cache-hit series has real hits to show
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
    env["HYDRAGNN_COMPILE_CACHE_MIN_SECS"] = "0"
    return env


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="telemetry_smoke_")
    script = os.path.join(workdir, "child.py")
    with open(script, "w") as f:
        f.write(_CHILD.format(repo=_REPO))
    proc = subprocess.run(
        [sys.executable, script], cwd=workdir, env=_env(workdir),
        capture_output=True, text=True, timeout=900,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0 or "TELEMETRY_SMOKE_OK" not in out:
        print(
            f"telemetry_smoke FAIL (rc={proc.returncode}):\n{out[-4000:]}"
        )
        return 1
    db_script = os.path.join(workdir, "db_child.py")
    with open(db_script, "w") as f:
        f.write(_DB_CHILD.format(repo=_REPO))
    db = subprocess.run(
        [sys.executable, db_script], cwd=workdir,
        env=_env(workdir, single_device=True),
        capture_output=True, text=True, timeout=600,
    )
    db_out = db.stdout + db.stderr
    if db.returncode != 0 or "LEG4_DOUBLE_BUFFER_OK" not in db_out:
        print(
            f"telemetry_smoke FAIL leg4 (rc={db.returncode}):\n{db_out[-3000:]}"
        )
        return 1
    num_script = os.path.join(workdir, "num_child.py")
    with open(num_script, "w") as f:
        f.write(_NUM_CHILD.format(repo=_REPO))
    num = subprocess.run(
        [sys.executable, num_script], cwd=workdir,
        env=_env(workdir, single_device=True),
        capture_output=True, text=True, timeout=900,
    )
    num_out = num.stdout + num.stderr
    if num.returncode != 0 or "LEG5_NUMERICS_OK" not in num_out:
        print(
            f"telemetry_smoke FAIL leg5 (rc={num.returncode}):\n{num_out[-4000:]}"
        )
        return 1
    for line in (out + db_out + num_out).splitlines():
        if line.startswith(("LEG1_", "LEG2_", "LEG3_", "LEG4_", "LEG5_",
                            "TELEMETRY_")):
            print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
