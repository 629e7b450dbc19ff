#!/usr/bin/env python
"""CI serving-fleet chaos smoke (docs/SERVING.md "Fleet"). ONE child
process (scrubbed CPU-JAX, the chaos_smoke.py recipe) trains a real
checkpoint, brings up a 2-replica ``api.run_server_fleet`` deployment, and
drives the fleet's whole failure model through the router front door with
the deterministic replica drills of utils/faultinject.py:

1. BREAKER: replica 1's first three /predict calls are wedged
   (HYDRAGNN_FAULT_REPLICA_WEDGE="1:0,1,2:15") — every client call still
   succeeds (tail hedging + retry on the mate), the per-replica circuit
   breaker opens on the timeout failures, and after the cooldown a
   half-open probe against the now-healthy replica recloses it.
2. CACHE: the same graph predicted twice is served the second time from
   the content-addressed prediction cache, bit-identical, without
   touching the fleet.
3. KILL: replica 2 is SIGKILLed mid-load at a precise request index
   (HYDRAGNN_FAULT_REPLICA_KILL="2:400", reached by padding) while four
   concurrent clients stream requests — ZERO client-visible failures
   (the router retries on replica 1), and the supervisor restarts the
   dead worker back to ready. Replica 2 also runs the slow-replica drill
   (HYDRAGNN_FAULT_REPLICA_SLOW="2:0.01") for the whole run.
4. RELOAD: a new (scaled) checkpoint is published and
   ``manager.rolling_reload`` swaps the fleet one replica at a time
   UNDER concurrent load — ready capacity never dips below the floor,
   zero dropped requests, and predictions visibly move.
5. Teardown: the manager's aggregated ``fleet_serve`` metrics records and
   the typed replica_exit/replica_restart/breaker events are on disk for
   the run doctor, and the fleet drains cleanly.
6. QUANT: a second fleet comes up with ``Serving.weights_dtype: int8`` on
   a pre-quantized snapshot (both replicas report ``source=snapshot`` —
   no per-replica re-calibration) and agrees with the fp32 fleet's
   predictions; a clean rolling reload re-quantizes + canaries + swaps a
   new checkpoint fleet-wide; a drifted candidate (scales inflated by
   HYDRAGNN_FAULT_QUANT_DRIFT) is refused by the accuracy gate on every
   replica — ``installed == 0``, the fleet stays on the certified
   checkpoint, and the typed ``quant_drift`` event is on disk.

Exit 0 = fleet healthy; nonzero with a diagnostic otherwise.
"""

import os
import re
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "run-scripts"))

from smoke_env import child_env  # noqa: E402 — shared child-spawn recipe

_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import jax

import dataclasses
import itertools
import json
import threading
import urllib.request

import numpy as np

import hydragnn_tpu
from hydragnn_tpu.config import update_config, voi_from_config
from hydragnn_tpu.data import deterministic_graph_dataset, split_dataset
from hydragnn_tpu.data.pipeline import extract_variables
from hydragnn_tpu.serve import HTTPReplicaClient

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "serve_fleet",
        "format": "synthetic",
        "synthetic": {{"number_configurations": 80}},
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
            "num_epoch": 2, "batch_size": 4, "seed": 7,
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
    "Serving": {{
        "micro_batch_graphs": 4,
        "batch_window_s": 0.005,
        "step_timeout_s": 5.0,
        "hot_reload": True,
        "fleet_replicas": 2,
        "prediction_cache": True,
        "breaker_failures": 2,
        "breaker_cooldown_s": 1.0,
        "router_retries": 3,
        "router_backoff_s": 0.05,
        "router_hedge_min_s": 0.05,
        "router_timeout_s": 30.0,
        "fleet_restart_backoff_s": 1.0,
        "fleet_flap_window_s": 30.0,
        "fleet_flap_max_restarts": 5,
        "fleet_ready_floor": 0.5,
        "reload_probe_requests": 4,
        "reload_error_spike": 0.75,
    }},
}}

# ---- train 2 epochs: the fleet must come up on a REAL verified checkpoint
hydragnn_tpu.run_training(cfg)

# graphs matching the deployment's admission signature (serve_world recipe)
raw = deterministic_graph_dataset(60, seed=7, radius=2.0, max_neighbours=100)
tr, va, te = split_dataset(raw, 0.7, seed=0)
done = update_config(json.loads(json.dumps(cfg)), tr, va, te)
voi = voi_from_config(done)
ready_graphs = [extract_variables(g, voi) for g in raw]

_seq = itertools.count()

def ug():
    # unique graph per call: repeats would be served from the prediction
    # cache and never reach the fleet (the phases below need fleet traffic)
    i = next(_seq)
    g = ready_graphs[i % len(ready_graphs)]
    bump = np.float32(1e-6 * (i // len(ready_graphs) + 1))
    return dataclasses.replace(g, x=g.x + bump)

# ---- arm the replica chaos drills BEFORE spawn (children inherit environ):
# replica 1 wedges its first three /predict calls for 15 s (socket timeouts
# at the router -> breaker opens, then the unarmed 4th call recloses it);
# replica 2 runs 10 ms slower on every call and SIGKILLs itself at its
# 400th /predict — an index the KILL phase reaches deliberately by padding
import os
os.environ["HYDRAGNN_FAULT_REPLICA_WEDGE"] = "1:0,1,2:15"
os.environ["HYDRAGNN_FAULT_REPLICA_KILL"] = "2:400"
os.environ["HYDRAGNN_FAULT_REPLICA_SLOW"] = "2:0.01"

manager = hydragnn_tpu.run_server_fleet(cfg, wait_ready_s=600)
try:
    router = manager.router()
    assert sorted(router.replicas()) == ["replica1", "replica2"], (
        router.replicas())
    print("FLEET_READY replicas=%d" % len(router.replicas()), flush=True)

    def rstats(idx):
        port = manager.replica_state()[idx]["port"]
        req = urllib.request.Request(
            "http://127.0.0.1:%d/stats" % port, data=b"{{}}",
            headers={{"Content-Type": "application/json"}}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            return json.loads(resp.read().decode("utf-8"))

    # ---- 1. wedged replica: calls succeed, breaker opens then recloses -
    br = router.breaker("replica1")
    for _ in range(100):
        out = router.predict(ug(), timeout_s=2.5)
        assert isinstance(out, dict), out
        if br.opens >= 1:
            break
        time.sleep(0.1)
    assert br.opens >= 1, "breaker never opened: state=%s" % br.state
    for _ in range(100):
        if br.state == "closed" and br.closes >= 1:
            break
        out = router.predict(ug(), timeout_s=2.5)
        assert isinstance(out, dict), out
        time.sleep(0.1)
    assert br.state == "closed" and br.closes >= 1, (
        "breaker never reclosed: state=%s closes=%d" % (br.state, br.closes))
    assert router.stats()["hedges"] >= 1, router.stats()
    print("BREAKER_OK opens=%d closes=%d hedges=%d"
          % (br.opens, br.closes, router.stats()["hedges"]), flush=True)

    # ---- 2. prediction cache: second identical request is a bit-identical
    # hit served without touching the fleet --------------------------------
    g0 = ug()
    first = router.predict(g0, timeout_s=30.0)
    hits0 = router.stats()["cache_hits"]
    second = router.predict(g0, timeout_s=30.0)
    assert router.stats()["cache_hits"] == hits0 + 1, router.stats()
    assert sorted(first) == sorted(second), (first.keys(), second.keys())
    for k in first:
        a, b = np.asarray(first[k]), np.asarray(second[k])
        assert a.dtype == b.dtype and a.shape == b.shape, (k, a.dtype, b.dtype)
        assert a.tobytes() == b.tobytes(), "cache hit not bit-identical: %s" % k
    print("CACHE_OK hits=%d" % router.stats()["cache_hits"], flush=True)

    # ---- 3. SIGKILL mid-load: zero client-visible failures + restart ----
    s2 = rstats(2)["submitted"]
    assert s2 < 380, "kill index margin exhausted: replica2 served %d" % s2
    port2 = manager.replica_state()[2]["port"]
    pad = HTTPReplicaClient("http://127.0.0.1:%d" % port2, name="replica2")
    while rstats(2)["submitted"] < 400:
        pad.predict(ug(), timeout_s=30.0)  # next /predict is the kill
    errors, okays = [], []

    def pump(n):
        for _ in range(n):
            try:
                okays.append(router.predict(ug(), timeout_s=30.0))
            except Exception as e:  # noqa: BLE001 — any escape is the bug
                errors.append(e)

    workers = [threading.Thread(target=pump, args=(15,)) for _ in range(4)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    assert not errors, "client-visible failures under SIGKILL: %r" % errors[:3]
    assert len(okays) == 60, len(okays)
    deadline = time.time() + 60
    while time.time() < deadline:
        if manager.replica_state()[2]["restarts"] >= 1:
            break
        time.sleep(0.1)
    assert manager.replica_state()[2]["restarts"] >= 1, manager.replica_state()
    deadline = time.time() + 420
    while time.time() < deadline and manager.ready_count() < 2:
        time.sleep(0.5)
    assert manager.ready_count() == 2, manager.replica_state()
    print("KILL_OK served=%d errors=0 restarts=%d"
          % (len(okays), manager.replica_state()[2]["restarts"]), flush=True)

    # ---- 4. rolling reload under load: floor held, predictions move ----
    from flax import serialization
    from hydragnn_tpu.train.checkpoint import (
        latest_checkpoint_entry, save_model,
    )
    from hydragnn_tpu.train.optimizer import make_optimizer
    from hydragnn_tpu.train.state import TrainState

    run = manager.log_name
    entry = latest_checkpoint_entry(run)
    ep = int(re.search(r"_epoch(\\d+)\\.msgpack$", entry).group(1))
    with open(os.path.join("./logs", run, entry), "rb") as f:
        rawckpt = serialization.msgpack_restore(f.read())
    scaled = jax.tree_util.tree_map(
        lambda p: np.asarray(p) * 2.0, rawckpt["params"]
    )
    ts = TrainState.create(
        {{"params": scaled, "batch_stats": rawckpt.get("batch_stats", {{}})}},
        make_optimizer({{"type": "AdamW", "learning_rate": 0.01}}),
    )
    save_model(ts, run, epoch=ep + 1)

    port1 = manager.replica_state()[1]["port"]
    c1 = HTTPReplicaClient("http://127.0.0.1:%d" % port1, name="replica1")
    gq = ug()
    ref = c1.predict(gq, timeout_s=30.0)["s"]
    workers = [threading.Thread(target=pump, args=(20,)) for _ in range(2)]
    for w in workers:
        w.start()
    res = manager.rolling_reload(ready_graphs[:4], timeout_s=180.0)
    for w in workers:
        w.join()
    assert not errors, "dropped requests during rolling reload: %r" % errors[:3]
    assert res["status"] == "done", res
    assert res["installed"] == 2, res
    assert res["min_ready_seen"] >= res["floor"], res
    new = c1.predict(gq, timeout_s=30.0)["s"]
    assert not np.allclose(ref, new), "weights did not move after reload"
    want = "%s_epoch%d.msgpack" % (run, ep + 1)
    assert rstats(1)["current_checkpoint"] == want, rstats(1)
    print("RELOAD_OK installed=%d min_ready=%d floor=%d"
          % (res["installed"], res["min_ready_seen"], res["floor"]),
          flush=True)

    # ---- 5. fleet observability on disk for the run doctor --------------
    mpath = os.path.join("./logs", run, "metrics.jsonl")
    with open(mpath) as f:
        fleet_recs = [ln for ln in f if '"fleet_serve"' in ln]
    assert fleet_recs, "no aggregated fleet_serve metrics records"
    with open(os.path.join("./logs", run, "events.jsonl")) as f:
        evs = f.read()
    for needed in ("replica_exit", "replica_restart", "breaker_open",
                   "breaker_close"):
        assert needed in evs, "missing typed event %r" % needed
finally:
    manager.close()
print("FLEET_CLEAN_EXIT", flush=True)

# ---- 6. QUANT: int8 fleet from a pre-quantized snapshot, canary-gated
# rolling reload, and a fault-injected drifted candidate refused ---------
import glob

from hydragnn_tpu.data.graph import SpecLadder
from hydragnn_tpu.data.pipeline import spec_template_batches
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.serve import quantize as qz
from hydragnn_tpu.train.checkpoint import load_inference_entry
from hydragnn_tpu.train.state import InferenceState

# pre-quantize the latest entry beside the checkpoint (what a producing
# server would have published) so BOTH replicas take the snapshot fast
# path — serving int8 without re-quantizing or re-calibrating
entry_q = latest_checkpoint_entry(run)
model = create_model(done)
ladder = SpecLadder.for_dataset(ready_graphs, 4, num_buckets=2)
tmpl = spec_template_batches(ready_graphs, ladder)[0][1]
fpstate = load_inference_entry(
    InferenceState.create(init_model(model, tmpl, seed=0)), run, entry_q
)
qbatches = [b for _, b in spec_template_batches(ready_graphs, ladder)][:2]
qstate = qz.quantize_state(model, fpstate, qbatches, mode="weight_only")
qreport = qz.gate_or_raise(
    model, fpstate, qstate, qbatches, 0.05, run=run, entry=entry_q
)
qz.save_snapshot(
    qstate, dict(qreport, source="calibrated"), run, entry_q, "./logs"
)
print("QUANT_SNAPSHOT_OK entry=%s max_error=%.6f"
      % (entry_q, qreport["max_error"]), flush=True)

# disarm the replica chaos drills; arm the quantization-drift fault for
# the FUTURE epoch+3 entry only (children inherit environ at spawn, so
# this must be set before the int8 fleet comes up)
for k in ("HYDRAGNN_FAULT_REPLICA_WEDGE", "HYDRAGNN_FAULT_REPLICA_KILL",
          "HYDRAGNN_FAULT_REPLICA_SLOW"):
    os.environ.pop(k, None)
os.environ["HYDRAGNN_FAULT_QUANT_DRIFT"] = "epoch%d.:6.0" % (ep + 3)

cfg_q = json.loads(json.dumps(cfg))
cfg_q["Serving"]["weights_dtype"] = "int8"
cfg_q["Serving"]["quantization"] = {{
    "mode": "weight_only", "calibration_batches": 2, "max_error": 0.05,
}}
# replica-side event streams (events-h<i>.jsonl): the gate's quant_drift
# events fire inside the replica processes
cfg_q["Telemetry"] = {{"enabled": True}}

manager2 = hydragnn_tpu.run_server_fleet(cfg_q, wait_ready_s=600)
try:
    router2 = manager2.router()

    def rstats2(idx):
        port = manager2.replica_state()[idx]["port"]
        req = urllib.request.Request(
            "http://127.0.0.1:%d/stats" % port, data=b"{{}}",
            headers={{"Content-Type": "application/json"}}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            return json.loads(resp.read().decode("utf-8"))

    for i in (1, 2):
        st = rstats2(i)
        assert st.get("weights_dtype") == "int8", st
        q = st.get("quantization") or {{}}
        assert q.get("source") == "snapshot", (
            "replica %d did not load the pre-quantized snapshot: %r"
            % (i, q))
    # int8 predictions agree with the fp32 fleet's on the same graph
    out_q = np.asarray(router2.predict(gq, timeout_s=30.0)["s"])
    denom = float(np.max(np.abs(np.asarray(new)))) + 1e-8
    rel = float(np.max(np.abs(out_q - np.asarray(new)))) / denom
    assert rel <= 0.05, "int8 fleet drifted from fp32: rel=%.5f" % rel
    print("QUANT_FLEET_OK source=snapshot rel_err=%.5f" % rel, flush=True)

    # clean rolling reload: a NEW checkpoint is re-quantized, canaried,
    # and swapped fleet-wide (gate green)
    scaled3 = jax.tree_util.tree_map(
        lambda p: np.asarray(p) * 3.0, rawckpt["params"]
    )
    ts2 = TrainState.create(
        {{"params": scaled3, "batch_stats": rawckpt.get("batch_stats", {{}})}},
        make_optimizer({{"type": "AdamW", "learning_rate": 0.01}}),
    )
    save_model(ts2, run, epoch=ep + 2)
    res2 = manager2.rolling_reload(ready_graphs[:4], timeout_s=300.0)
    assert res2["status"] == "done" and res2["installed"] == 2, res2
    want2 = "%s_epoch%d.msgpack" % (run, ep + 2)
    st1 = rstats2(1)
    assert st1["current_checkpoint"] == want2, st1
    assert (st1.get("quantization") or {{}}).get("source") in (
        "calibrated", "snapshot"), st1
    moved = np.asarray(router2.predict(gq, timeout_s=30.0)["s"])
    assert not np.allclose(out_q, moved), "int8 reload did not move preds"
    print("QUANT_RELOAD_OK installed=%d source=%s"
          % (res2["installed"], st1["quantization"]["source"]), flush=True)

    # drifted candidate: the armed fault inflates the scales of the
    # epoch+3 entry after calibration — the gate must refuse it on every
    # replica and the fleet must stay on the prior checkpoint
    ts3 = TrainState.create(
        {{"params": scaled3, "batch_stats": rawckpt.get("batch_stats", {{}})}},
        make_optimizer({{"type": "AdamW", "learning_rate": 0.01}}),
    )
    save_model(ts3, run, epoch=ep + 3)
    res3 = manager2.rolling_reload(ready_graphs[:4], timeout_s=300.0)
    assert res3["status"] == "done" and res3["installed"] == 0, res3
    for i in (1, 2):
        st = rstats2(i)
        assert st["current_checkpoint"] == want2, (
            "replica %d left the certified checkpoint: %r" % (i, st))
    ev_text = ""
    for p in glob.glob(os.path.join("./logs", run, "events*.jsonl")):
        with open(p) as f:
            ev_text += f.read()
    assert "quant_drift" in ev_text, "no quant_drift event on disk"
    print("QUANT_GATE_OK refused installed=0", flush=True)
finally:
    manager2.close()
print("QUANT_CLEAN_EXIT", flush=True)
"""


_MARKERS = (
    "FLEET_READY",
    "BREAKER_OK",
    "CACHE_OK",
    "KILL_OK",
    "RELOAD_OK",
    "FLEET_CLEAN_EXIT",
    "QUANT_SNAPSHOT_OK",
    "QUANT_FLEET_OK",
    "QUANT_RELOAD_OK",
    "QUANT_GATE_OK",
    "QUANT_CLEAN_EXIT",
)


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="serve_fleet_")
    script = os.path.join(workdir, "serve_fleet_child.py")
    with open(script, "w") as f:
        f.write("import re, time\n" + _CHILD.format(repo=_REPO))
    proc = subprocess.Popen(
        [sys.executable, script], cwd=workdir,
        env=child_env({"HYDRAGNN_VALTEST": "0"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines = []
    deadline = time.time() + 1800
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break
        lines.append(line)
    else:
        proc.kill()
        print("serve_fleet FAIL: timed out\n" + "".join(lines)[-4000:])
        return 1
    out = "".join(lines)
    if proc.returncode != 0:
        print(f"serve_fleet FAIL: child rc={proc.returncode}:\n{out[-4000:]}")
        return 1
    missing = [m for m in _MARKERS if m not in out]
    if missing:
        print(f"serve_fleet FAIL: phases missing {missing}:\n{out[-4000:]}")
        return 1
    if not re.search(r"KILL_OK served=\d+ errors=0", out):
        print(f"serve_fleet FAIL: SIGKILL leaked client-visible failures:"
              f"\n{out[-4000:]}")
        return 1
    print(
        "serve_fleet OK: wedged replica absorbed (breaker opened + reclosed, "
        "hedges won), prediction cache hit bit-identical, SIGKILL mid-load "
        "retried to zero client-visible failures with supervisor restart, "
        "rolling reload under load held the ready floor and moved "
        "predictions, int8 fleet served from the pre-quantized snapshot and "
        "the accuracy gate refused the drifted candidate"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
