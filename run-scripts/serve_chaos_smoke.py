#!/usr/bin/env python
"""CI serving-plane chaos smoke (docs/SERVING.md "Failure model"). ONE
child process (scrubbed CPU-JAX, the chaos_smoke.py recipe) drives a real
``api.run_server`` deployment — train 2 epochs, come up on the verified
checkpoint with the ladder AOT-warmed and the retrace sentinel in error
mode — through every serve-plane failure injection in sequence:

1. LOAD: sustained load over every ladder level — all requests answered,
   ZERO retrace-sentinel violations (readiness == zero-retrace steady
   state).
2. ISOLATION: an injected corrupt request (HYDRAGNN_FAULT_SERVE_REQ_NAN)
   fails ALONE with a typed InvalidRequestError while the requests
   co-batched beside it succeed.
3. WEDGE: an injected wedged device step (HYDRAGNN_FAULT_SERVE_WEDGE)
   is bounded by the step watchdog — the batch fails typed
   (WedgedStepError), the runner recycles, and the NEXT request is served
   normally.
4. RELOAD: a new checkpoint published to the run dir hot-swaps in between
   batches with zero dropped in-flight requests and visibly different
   predictions; a CORRUPT candidate (flip_bit) is rejected and the current
   weights keep serving.
5. DRAIN: the parent sends a real SIGTERM; the child's server stops
   admitting (typed ServerDrainingError) while every already-admitted
   request still completes — zero dropped in-flight.

Exit 0 = serving plane healthy; nonzero with a diagnostic otherwise.
"""

import os
import re
import signal
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

import numpy as np

import hydragnn_tpu
from hydragnn_tpu.serve import (
    InvalidRequestError, ServerDrainingError, WedgedStepError,
)
from hydragnn_tpu.train.compile_plane import sentinel
from hydragnn_tpu.utils import faultinject

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "serve_chaos",
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
        "step_timeout_s": 1.0,
        "retrace_policy": "error",
        "hot_reload": True,
        "reload_poll_s": 0.1,
    }},
}}

# ---- train 2 epochs: the server must come up on a REAL verified checkpoint
hydragnn_tpu.run_training(cfg)

server = hydragnn_tpu.run_server(cfg, install_sigterm=True)
try:
    assert server.wait_ready(600), "warm-up failed: %r" % (server.failed,)
    assert server.current_checkpoint, "server did not restore a checkpoint"
    graphs = server._template_graphs  # known-valid graphs of this deployment

    # ---- 1. sustained load, error-mode sentinel: zero violations --------
    before = len(sentinel().violations())
    for _ in range(3):
        out = server.predict(graphs[:32], timeout=120)
        assert all(isinstance(o, dict) for o in out), out
    viol = len(sentinel().violations()) - before
    assert viol == 0, "retraces under sustained load: %d" % viol
    print("LOAD_OK n=%d violations=0" % (3 * 32), flush=True)

    # ---- 2. corrupt request fails alone; co-batched neighbors succeed ---
    base = server.stats()["submitted"]
    faultinject.configure(serve_req_nan=str(base + 1))
    out = server.predict(graphs[:3], timeout=120)
    faultinject.reset()
    assert isinstance(out[0], dict) and isinstance(out[2], dict), out
    assert isinstance(out[1], InvalidRequestError), out[1]
    assert out[1].reason == "nonfinite_features", out[1].reason
    print("ISOLATION_OK reason=%s" % out[1].reason, flush=True)

    # ---- 3. wedged step: bounded typed error + recycled runner ----------
    s = server.stats()
    nxt = s["batches"] + s["wedged_batches"] + s["failed_batches"]
    faultinject.configure(serve_wedge="%d:5" % nxt)
    err = server.submit(graphs[0]).error(60)
    faultinject.reset()
    assert isinstance(err, WedgedStepError), err
    after = server.predict([graphs[1]], timeout=120)[0]
    assert isinstance(after, dict), after
    print("WEDGE_OK recycled=1", flush=True)

    # ---- 4. hot reload: verified swap, then corrupt-candidate rejection -
    from hydragnn_tpu.train.checkpoint import save_model
    from hydragnn_tpu.train.optimizer import make_optimizer
    from hydragnn_tpu.train.state import TrainState

    ref = server.predict([graphs[0]], timeout=120)[0]["s"]
    run = server.log_name
    ep = int(re.search(r"_epoch(\\d+)\\.msgpack$",
                       server.current_checkpoint).group(1))
    tx = make_optimizer({{"type": "AdamW", "learning_rate": 0.01}})
    scaled = jax.tree_util.tree_map(lambda p: p * 2.0, server._state.params)
    ts = TrainState.create(
        {{"params": scaled, "batch_stats": server._state.batch_stats}}, tx
    )
    save_model(ts, run, epoch=ep + 1)
    # keep submitting while the watcher swaps: zero dropped requests
    deadline = time.time() + 30
    swapped = False
    while time.time() < deadline:
        got = server.predict(graphs[:4], timeout=120)
        assert all(isinstance(o, dict) for o in got), got
        if server.stats()["reloads"] >= 1:
            swapped = True
            break
        time.sleep(0.05)
    assert swapped, "hot reload never swapped: %r" % (server.stats(),)
    new = server.predict([graphs[0]], timeout=120)[0]["s"]
    assert not np.allclose(ref, new), "weights did not change after reload"
    want = "%s_epoch%d.msgpack" % (run, ep + 1)
    assert server.current_checkpoint == want, server.current_checkpoint
    print("RELOAD_OK checkpoint=%s" % server.current_checkpoint, flush=True)

    fname = save_model(ts, run, epoch=ep + 2)
    faultinject.flip_bit(fname)
    deadline = time.time() + 30
    while time.time() < deadline and server._watcher.rejected < 1:
        time.sleep(0.05)
    assert server._watcher.rejected >= 1, "corrupt candidate not rejected"
    assert server.current_checkpoint == want, (
        "corrupt candidate installed: %r" % server.current_checkpoint)
    still = server.predict([graphs[0]], timeout=120)[0]["s"]
    assert np.allclose(new, still), "serving weights moved on rejection"
    print("CORRUPT_REJECT_OK rejected=%d" % server._watcher.rejected,
          flush=True)

    # ---- 5. graceful SIGTERM drain: in-flight complete, no new admits ---
    handles = [server.submit(g) for g in graphs[:8]]
    print("READY_FOR_TERM inflight=%d" % len(handles), flush=True)
    deadline = time.time() + 60
    while time.time() < deadline and not server.draining:
        time.sleep(0.01)
    assert server.draining, "SIGTERM did not initiate the drain"
    assert server.drain(60), "drain did not finish"
    resolved = sum(1 for h in handles if isinstance(h.result(0), dict))
    assert resolved == len(handles), "dropped in-flight: %d/%d" % (
        resolved, len(handles))
    try:
        server.submit(graphs[0])
        raise AssertionError("draining server admitted a request")
    except ServerDrainingError:
        pass
    print("DRAIN_OK resolved=%d dropped=0" % resolved, flush=True)
finally:
    server.close(drain=False)
print("SERVE_CHAOS_CLEAN_EXIT", flush=True)
"""


_MARKERS = (
    "LOAD_OK",
    "ISOLATION_OK",
    "WEDGE_OK",
    "RELOAD_OK",
    "CORRUPT_REJECT_OK",
    "DRAIN_OK",
    "SERVE_CHAOS_CLEAN_EXIT",
)


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="serve_chaos_")
    script = os.path.join(workdir, "serve_chaos_child.py")
    with open(script, "w") as f:
        f.write("import re, time\n" + _CHILD.format(repo=_REPO))
    proc = subprocess.Popen(
        [sys.executable, script], cwd=workdir,
        env=child_env({"HYDRAGNN_VALTEST": "0"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines = []
    deadline = time.time() + 900
    termed = False
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break
        lines.append(line)
        if line.startswith("READY_FOR_TERM") and not termed:
            # the real signal, the real drain — not a drain() method call
            proc.send_signal(signal.SIGTERM)
            termed = True
    else:
        proc.kill()
        print("serve_chaos FAIL: timed out\n" + "".join(lines)[-3000:])
        return 1
    out = "".join(lines)
    if proc.returncode != 0:
        print(f"serve_chaos FAIL: child rc={proc.returncode}:\n{out[-3000:]}")
        return 1
    if not termed:
        print(f"serve_chaos FAIL: never saw READY_FOR_TERM:\n{out[-3000:]}")
        return 1
    missing = [m for m in _MARKERS if m not in out]
    if missing:
        print(f"serve_chaos FAIL: phases missing {missing}:\n{out[-3000:]}")
        return 1
    if not re.search(r"DRAIN_OK resolved=\d+ dropped=0", out):
        print(f"serve_chaos FAIL: drain dropped in-flight requests:"
              f"\n{out[-3000:]}")
        return 1
    print(
        "serve_chaos OK: zero-retrace sustained load, corrupt request "
        "isolated, wedged step bounded + recycled, hot reload swapped "
        "(corrupt candidate rejected), SIGTERM drained with zero dropped "
        "in-flight requests"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
