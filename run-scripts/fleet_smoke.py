#!/usr/bin/env python
"""CI fleet-plane smoke (docs/OBSERVABILITY.md "Fleet"; wired into ci.sh).

A 2-process **simulated fleet** on CPU (independent subprocess hosts with
``HYDRAGNN_FLEET_HOST_INDEX``/``_COUNT`` identities sharing one workdir —
the shared-filesystem model) plus an isolation leg, asserting the r13
tentpole's acceptance contract:

1. **fleet legs** (two concurrent host children, host 0 running the
   rank-0 collector, both on the 2-device zero-2 mesh step): a warm run
   populates a SHARED compilation cache, a file barrier lines both hosts
   up, then the fleet run proper. Host 1 is armed with the new
   ``HYDRAGNN_FAULT_STRAGGLE`` point. Afterwards each host asserts:
   aggregated ``hydragnn_fleet_*`` gauges on host 0 (min/mean/max,
   per-host step + step-lag, pushes from BOTH hosts), the injected
   straggler detected as a typed ``fleet_straggler`` event on BOTH hosts
   with a coordinated, host-disambiguated (``-h<rank>``) flight dump
   keyed by the same fleet step, a populated per-spec collective table
   (``hydragnn_comm_*`` + ``comm_bytes_per_step`` in step_window
   records), and host-stamped metrics/trace streams.
2. **stitch leg**: ``python -m hydragnn_tpu.obs.fleet`` merges both
   hosts' trace streams into one time-ordered run-level view carrying
   both host identities.
3. **inspector + isolation leg** (own child): the sharding inspector on
   a zero-3-placed real-model state shows optimizer moments AND large
   params sharded, and flags an injected over-replicated leaf; the
   fleet-on vs fleet-off step programs lower byte-identically (the
   plane is host-side only); and a fleet-on vs fleet-off step-loop A/B
   holds the established <= 2% overhead budget.

Exit 0 = fleet plane healthy; nonzero with a diagnostic otherwise.
"""

import os
import socket
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HOST_CHILD = """
import json
import os
import sys
import time

sys.path.insert(0, {repo!r})
import jax
import numpy as np

HOST = int(os.environ["HYDRAGNN_FLEET_HOST_INDEX"])
assert jax.device_count() == 2, jax.devices()

import hydragnn_tpu
from hydragnn_tpu.config import get_log_name_config


def make_cfg(fleet, num_epoch):
    return {{
        "Verbosity": {{"level": 1}},
        "Dataset": {{
            "name": "fleet_h%d" % HOST,
            "format": "synthetic",
            "synthetic": {{"number_configurations": 96}},
            "node_features": {{"name": ["x", "x2", "x3"], "dim": [1, 1, 1]}},
            "graph_features": {{"name": ["s"], "dim": [1]}},
        }},
        "NeuralNetwork": {{
            "Architecture": {{
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 64, "num_conv_layers": 2,
                "task_weights": [1.0],
                "output_heads": {{"graph": {{"num_sharedlayers": 1,
                                            "dim_sharedlayers": 64,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [64, 64]}}}},
            }},
            "Variables_of_interest": {{
                "input_node_features": [0],
                "output_names": ["s"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            }},
            "Training": {{
                "num_epoch": num_epoch, "batch_size": 8, "seed": 11,
                "num_pad_buckets": 2,
                # "analysis": blocking AOT warm-up WITHOUT a persistent
                # cache (the children run cache-less, smoke_env.py); the
                # analysis mode still fills the FLOPs/HBM/collective
                # tables the smoke asserts
                "precompile": "analysis" if fleet else "off",
                # zero-2 engages the mesh step on the 2-device CPU mesh:
                # real psum/reduce-scatter collectives in the HLO
                "Optimizer": {{"type": "AdamW", "learning_rate": 0.01,
                               "zero_stage": 2}},
            }},
        }},
        "Telemetry": {{
            "enabled": True, "interval_steps": 2,
            "trace": fleet, "trace_interval_steps": 4,
            "fleet": fleet,
            "fleet_straggler_factor": 1.5,
            "fleet_max_step_lag": 8,
            "fleet_stale_after_s": 120.0,
        }},
        "Visualization": {{"create_plots": False}},
    }}


# ---- warm leg: pay the one-time import/data/compile costs (fleet off)
# so both hosts' fleet runs start stepping nearly simultaneously after
# the barrier below — the straggler detection window needs overlap
os.environ["HYDRAGNN_FLEET"] = "0"
hydragnn_tpu.run_training(make_cfg(False, 1))
print("WARM_OK host=%d" % HOST, flush=True)

# ---- barrier: both hosts warmed, start the fleet runs together
open("ready-h%d" % HOST, "w").close()
deadline = time.time() + 300
other = "ready-h%d" % (1 - HOST)
while not os.path.exists(other):
    if time.time() > deadline:
        raise SystemExit("barrier timeout waiting for " + other)
    time.sleep(0.1)

# ---- fleet run proper -------------------------------------------------------
os.environ["HYDRAGNN_FLEET"] = "1"
if HOST == 1:
    # the injected straggler: 250ms of host-side sleep per step from
    # step 2 on. Detection baselines each host against the OTHER hosts'
    # median, so with factor 1.5 this needs t0 + 0.25 > 1.5 * t0 — true
    # for any clean step time t0 < 500ms: wide margin over ~10-50ms CPU
    # steps even on a loaded CI box
    os.environ["HYDRAGNN_FAULT_STRAGGLE"] = "2+:0.25"

from hydragnn_tpu.obs.events import events
from hydragnn_tpu.obs.prometheus import render_text
from hydragnn_tpu.obs.registry import registry

# Adaptive lifetimes instead of timing guesses: each host trains "forever"
# (epoch budget far beyond the deadline) and SIGTERMs itself — the
# preemption plane's graceful stop — once BOTH hosts have seen the
# straggler event (file handshake in the shared workdir). Detection needs
# the hosts stepping CONCURRENTLY; this makes the overlap a postcondition
# instead of a race against compile-time skew between the children.
import signal
import threading


def _watcher():
    me = "straggler-seen-h%d" % HOST
    other = "straggler-seen-h%d" % (1 - HOST)
    deadline = time.time() + 240
    while True:
        if not os.path.exists(me) and any(
            e["kind"] == "fleet_straggler" for e in events().snapshot()
        ):
            open(me, "w").close()
        if (os.path.exists(me) and os.path.exists(other)) or (
            time.time() > deadline
        ):
            os.kill(os.getpid(), signal.SIGTERM)
            return
        time.sleep(0.25)


threading.Thread(target=_watcher, daemon=True).start()
model, state, hist, cfg_out, loaders, mm = hydragnn_tpu.run_training(
    make_cfg(True, 500)
)
run_dir = os.path.join("logs", get_log_name_config(cfg_out))

# -- straggler detected with a typed event on THIS host (both hosts run
# this assert: host 0 via its own push response, host 1 the same way)
evs = events().snapshot()
stragglers = [e for e in evs if e["kind"] == "fleet_straggler"]
assert stragglers, "host %d never saw a fleet_straggler event: %r" % (
    HOST, [e["kind"] for e in evs])
assert stragglers[0]["offender"] == 1, stragglers[0]
step_key = stragglers[0]["step"]

# -- coordinated, host-disambiguated flight dump keyed by the fleet step
fdir = os.path.join(run_dir, "flightrec")
dumps = os.listdir(fdir)
match = [d for d in dumps
         if "fleet_straggler_step" in d and d.endswith("-h%d" % HOST)]
assert match, (HOST, dumps)
assert any(("step%d" % step_key) in d for d in match), (step_key, match)

# -- per-spec collective table populated on the mesh builder
text = render_text()
assert 'hydragnn_comm_bytes_total{{spec="train:' in text, (
    "no per-spec comm table in the registry")
assert 'hydragnn_comm_collectives{{spec="train:' in text
assert 'collective="all-reduce"' in text or (
    'collective="reduce-scatter"' in text), text[-2000:]

# -- host-stamped metrics stream (host 1 writes its own suffixed file)
mname = "metrics.jsonl" if HOST == 0 else "metrics-h1.jsonl"
recs = [json.loads(l) for l in open(os.path.join(run_dir, mname))]
assert recs and all(r["host"] == HOST for r in recs), mname
windows = [r for r in recs if r["kind"] == "step_window"]
assert windows, "no step_window records"
assert any(w.get("comm_bytes_per_step") for w in windows), (
    "no step_window ever carried collective bytes")

# -- host-stamped trace stream
tname = "trace.jsonl" if HOST == 0 else "trace-h1.jsonl"
spans = [json.loads(l) for l in open(os.path.join(run_dir, tname))]
assert spans and all(s["host"] == HOST for s in spans), tname

if HOST == 0:
    # -- collector-side: across-host aggregates + per-host step/lag, with
    # pushes absorbed from BOTH hosts
    assert "hydragnn_fleet_mean{{" in text and "hydragnn_fleet_max{{" in text
    assert 'hydragnn_fleet_host_step{{host="0"}}' in text
    assert 'hydragnn_fleet_host_step{{host="1"}}' in text, (
        "host 1 never pushed to the collector")
    assert 'hydragnn_fleet_step_lag{{host="1"}}' in text
    for h in ("0", "1"):
        c = registry().get("hydragnn_fleet_pushes_total")
        assert c.value(host=h) >= 1, (h, c and c.value(host=h))
    # every scalar series aggregates: spot-check a core gauge rode the push
    assert 'hydragnn_fleet_max{{series="hydragnn_goodput_per_second' in text

print("FLEET_HOST_OK host=%d straggler_step=%d windows=%d"
      % (HOST, step_key, len(windows)), flush=True)
"""


_INSPECT_CHILD = """
import os
import sys
import time

sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
import numpy as np

assert jax.device_count() == 2, jax.devices()

from hydragnn_tpu.config import update_config
from hydragnn_tpu.data import (
    GraphLoader, MinMax, VariablesOfInterest, deterministic_graph_dataset,
    extract_variables,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.obs import sharding as obs_sharding
from hydragnn_tpu.obs.fleet import FleetPlane
from hydragnn_tpu.obs.telemetry import StepTelemetry, resolve_telemetry
from hydragnn_tpu.parallel import (
    make_mesh, replicate_state, shard_optimizer_state,
)
from hydragnn_tpu.parallel.dp import make_parallel_train_step
from hydragnn_tpu.parallel.mesh import shard_params_zero3
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.loop import train_epoch

graphs = MinMax.fit(g := deterministic_graph_dataset(64, seed=3)).apply(g)
voi = VariablesOfInterest([0], ["s"], ["graph"], [0], [1, 1, 1], [1])
graphs = [extract_variables(x, voi) for x in graphs]
cfg = {{
    "Dataset": {{"node_features": {{"dim": [1, 1, 1]}},
                 "graph_features": {{"dim": [1]}}}},
    "NeuralNetwork": {{
        "Architecture": {{"mpnn_type": "GIN", "hidden_dim": 64,
                          "num_conv_layers": 2, "task_weights": [1.0],
                          "output_heads": {{"graph": {{
                              "num_sharedlayers": 1, "dim_sharedlayers": 64,
                              "num_headlayers": 2,
                              "dim_headlayers": [64, 64]}}}}}},
        "Variables_of_interest": {{"input_node_features": [0],
                                   "output_names": ["s"], "output_index": [0],
                                   "type": ["graph"]}},
        "Training": {{"batch_size": 8,
                      "Optimizer": {{"type": "AdamW",
                                     "learning_rate": 0.01}}}},
    }},
}}
cfg = update_config(cfg, graphs, graphs[:4], graphs[:4])
mesh = make_mesh()
loader = GraphLoader(graphs, 8, seed=0, num_shards=jax.device_count())
model = create_model(cfg)
variables = init_model(model, jax.tree_util.tree_map(
    lambda x: x[0], next(iter(loader))), seed=0)
tx = make_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
state = TrainState.create(variables, tx)

# ---- zero-3 placement -> inspector: moments AND large params sharded --------
state = replicate_state(state, mesh)
state = state.replace(
    opt_state=shard_optimizer_state(state.opt_state, mesh, min_size=1024),
    params=shard_params_zero3(state.params, mesh, min_size=1024),
)
obs_sharding.note_builder("parallel_train_step", dict(mesh.shape),
                          zero2=True, zero3=True)
report = obs_sharding.inspect_state(
    state, threshold_bytes=1 << 20, label="fleet_smoke_zero3", mesh=mesh)
opt_entries = report["sections"]["opt_state"]
sharded_opt = [e for e in opt_entries if not e["replicated"]]
assert sharded_opt, "zero3 placement left every optimizer leaf replicated"
param_entries = report["sections"]["params"]
assert any(not e["replicated"] for e in param_entries), (
    "zero3 placement left every param leaf replicated")
assert report["audit"] == [], report["audit"]
text = obs_sharding.format_report(report)
assert "SHARDED" in text and "builder=parallel_train_step" in text

# inject an over-replicated leaf: clobber one large param back to fully
# replicated (the exact regression a rule-table refactor could introduce)
big = max(param_entries, key=lambda e: e["total_bytes"])
def _clobber(tree, path):
    import jax.sharding as shd
    flat = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for p, leaf in flat[0]:
        if ("params" + jax.tree_util.keystr(p)) == path:
            leaf = jax.device_put(
                leaf, shd.NamedSharding(mesh, shd.PartitionSpec()))
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(flat[1], leaves)
state = state.replace(params=_clobber(state.params, big["path"]))
report2 = obs_sharding.inspect_state(
    state, threshold_bytes=big["total_bytes"], label="fleet_smoke_audit",
    mesh=mesh)
flagged = {{f["path"] for f in report2["audit"]}}
assert big["path"] in flagged, (big["path"], flagged)
print("INSPECTOR_OK sharded_opt=%d flagged=%s"
      % (len(sharded_opt), sorted(flagged)), flush=True)

# ---- fleet on/off programs lower byte-identically ---------------------------
state = replicate_state(state, mesh)  # clean replicated state for the A/B
step = make_parallel_train_step(model, tx, mesh)
batch = next(iter(loader))
rng = jax.random.PRNGKey(0)
os.environ["HYDRAGNN_FLEET"] = "0"
off_text = step.lower(state, batch, rng).as_text()
os.environ["HYDRAGNN_FLEET"] = "1"
plane = FleetPlane.from_settings(
    resolve_telemetry({{"Telemetry": {{"enabled": True, "fleet": True}}}}))
assert plane is not None and plane.pusher is not None
try:
    on_text = step.lower(state, batch, rng).as_text()
finally:
    plane.close()
assert on_text == off_text, (
    "fleet on/off lowered DIFFERENT step programs (%d vs %d chars) — the "
    "fleet plane must stay host-side only" % (len(on_text), len(off_text)))
del os.environ["HYDRAGNN_FLEET"]
print("BYTE_IDENTICAL_OK chars=%d" % len(on_text), flush=True)

# ---- fleet on/off overhead A/B ----------------------------------------------
# same gate design as telemetry_smoke leg 3: best-of-3 blocks of
# interleaved medians — a real additive per-step cost inflates the
# fleet-on leg in EVERY block, a contention burst cannot hit all three
os.environ["HYDRAGNN_DEVICE_PREFETCH"] = "0"
def make_telem(fleet):
    return StepTelemetry(
        resolve_telemetry({{"Telemetry": {{
            "enabled": True, "interval_steps": 2, "jsonl": False,
            "profile_trigger": False, "fleet": fleet}}}}),
        "fleet_ab_%s" % ("on" if fleet else "off"))
state, _, _, rng, _ = train_epoch(loader, step, state, rng)  # warm
n_batches = len(loader)
telems = {{"off": make_telem(False), "on": make_telem(True)}}
assert telems["on"].fleet is not None and telems["off"].fleet is None
ratios = []
for block in range(3):
    times = {{"off": [], "on": []}}
    for trial in range(8):
        for leg in ("off", "on"):
            t0 = time.perf_counter()
            state, _, _, rng, _ = train_epoch(
                loader, step, state, rng, telemetry=telems[leg])
            times[leg].append((time.perf_counter() - t0) / n_batches)
    off_s = float(np.median(times["off"]))
    on_s = float(np.median(times["on"]))
    ratios.append(on_s / max(off_s, 1e-12))
    print("FLEET_AB block %d: off=%.3fms on=%.3fms delta=%+.2f%%"
          % (block, off_s * 1e3, on_s * 1e3, (on_s / off_s - 1) * 100),
          flush=True)
for t in telems.values():
    t.close()
best = min(ratios)
print("FLEET_AB overhead=%.2f%% (best of %d; all: %s)"
      % ((best - 1) * 100, len(ratios),
         [round((r - 1) * 100, 2) for r in ratios]), flush=True)
assert best <= 1.02, (
    "fleet overhead %.2f%% exceeds the 2%% budget in EVERY block (%s) — "
    "the push path is leaking onto the step loop"
    % ((best - 1) * 100, [round((r - 1) * 100, 2) for r in ratios]))
print("FLEET_INSPECT_OK", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from smoke_env import child_env  # noqa: E402


def _env(extra=None):
    # 2 virtual devices: the zero-2 mesh step with real collectives,
    # independent of ci.sh's 8-device flag. Cache-less children
    # (smoke_env.py); precompile "analysis" keeps the harvests.
    return child_env(
        {"HYDRAGNN_COMPILE_CACHE_MIN_SECS": "0", **(extra or {})},
        device_count=2,
    )


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="fleet_smoke_")
    port = _free_port()
    script = os.path.join(workdir, "host_child.py")
    with open(script, "w") as f:
        f.write(_HOST_CHILD.format(repo=_REPO))

    procs = []
    for host in (0, 1):
        procs.append(
            subprocess.Popen(
                [sys.executable, script],
                cwd=workdir,
                env=_env(
                    {
                        "HYDRAGNN_FLEET_HOST_INDEX": str(host),
                        "HYDRAGNN_FLEET_HOST_COUNT": "2",
                        "HYDRAGNN_FLEET_COLLECTOR": f"127.0.0.1:{port}",
                    }
                ),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for host, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            proc.kill()
            out = (proc.communicate()[0] or "") + "\n<timeout>"
        outs.append(out)
    failed = False
    for host, (proc, out) in enumerate(zip(procs, outs)):
        if proc.returncode != 0 or "FLEET_HOST_OK" not in out:
            print(
                f"fleet_smoke FAIL host {host} "
                f"(rc={proc.returncode}):\n{out[-4000:]}"
            )
            failed = True
    if failed:
        return 1

    # ---- stitch leg: the run-level view carries both host identities.
    # Both hosts trained the SAME model config into one shared run dir
    # (the shared-filesystem scenario the host-suffixed streams exist
    # for): host 0 wrote trace.jsonl, host 1 trace-h1.jsonl beside it.
    import glob

    h0s = glob.glob(os.path.join(workdir, "logs", "*", "trace.jsonl"))
    h1s = glob.glob(os.path.join(workdir, "logs", "*", "trace-h1.jsonl"))
    if not h0s or not h1s:
        print(
            f"fleet_smoke FAIL: per-host trace streams missing "
            f"(trace.jsonl: {h0s}, trace-h1.jsonl: {h1s})"
        )
        return 1
    h0, h1 = h0s[0], h1s[0]
    merged = os.path.join(workdir, "merged_trace.jsonl")
    stitch = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.obs.fleet", merged, h0, h1],
        cwd=workdir, env=_env(), capture_output=True, text=True, timeout=300,
    )
    if stitch.returncode != 0 or "hosts: [0, 1]" not in stitch.stdout:
        print(
            f"fleet_smoke FAIL stitch (rc={stitch.returncode}):\n"
            f"{stitch.stdout}\n{stitch.stderr}"
        )
        return 1
    import json as _json

    starts = [
        int(_json.loads(l)["startTimeUnixNano"]) for l in open(merged)
    ]
    if starts != sorted(starts) or not starts:
        print("fleet_smoke FAIL: stitched trace is not time-ordered")
        return 1
    print(f"STITCH_OK spans={len(starts)} ({stitch.stdout.strip()})")

    # ---- inspector + isolation leg
    iscript = os.path.join(workdir, "inspect_child.py")
    with open(iscript, "w") as f:
        f.write(_INSPECT_CHILD.format(repo=_REPO))
    ins = subprocess.run(
        [sys.executable, iscript], cwd=workdir, env=_env(),
        capture_output=True, text=True, timeout=900,
    )
    ins_out = ins.stdout + ins.stderr
    if ins.returncode != 0 or "FLEET_INSPECT_OK" not in ins_out:
        print(
            f"fleet_smoke FAIL inspect leg (rc={ins.returncode}):\n"
            f"{ins_out[-4000:]}"
        )
        return 1
    for out in outs + [ins_out]:
        for line in out.splitlines():
            if line.startswith(
                ("FLEET_HOST_OK", "WARM_OK", "INSPECTOR_OK",
                 "BYTE_IDENTICAL_OK", "FLEET_AB ", "FLEET_INSPECT_OK")
            ):
                print(line)
    print("FLEET_SMOKE_OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
