#!/usr/bin/env python
"""CI data-plane chaos smoke (docs/ROBUSTNESS.md "Data plane"). Four legs,
each in a fresh scrubbed CPU-JAX subprocess (the chaos_smoke.py recipe):

1. warn_skip: a training run over a dataset seeded with injected NaN
   samples (HYDRAGNN_FAULT_SAMPLE_NAN) completes, the per-reason skip tally
   matches the injection plan EXACTLY, and the loss decreases.
2. error: the same injection under ``Dataset.bad_sample_policy: error``
   fails fast with an actionable error naming the sample.
3. socket drop: a RemoteStoreClient fetch plane with injected connection
   drops (HYDRAGNN_FAULT_SOCKET_DROP) delivers every blob intact — bounded
   retries, zero sample loss.
4. kill-and-resume: SIGTERM BETWEEN STEPS checkpoints mid-epoch (state +
   loader cursor); ``Training.continue`` replays the remaining batches of
   the interrupted epoch in exactly the order an unkilled run produces
   (batch fingerprints compared against an unkilled reference leg).

Exit 0 = data plane healthy; nonzero with a diagnostic otherwise.
"""

import os
import re
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import sys
sys.path.insert(0, {repo!r})
import jax
"""

_TRAIN_CHILD = _PRELUDE + """
import hydragnn_tpu

# per-STEP batch fingerprints, printed in step order by wrapping
# train_epoch's step_fn: the resume-order assertion compares these across
# legs (build-time tracing would also catch prefetch lookahead and the
# model-init probe batch — step order is the ground truth)
import numpy as _np
import hydragnn_tpu.train.loop as _L
_orig_epoch = _L.train_epoch
def _traced_epoch(loader, step_fn, state, rng, start_batch=0, **kw):
    # forward the loop's keyword surface (telemetry, tracer, ...) untouched
    def stepped(s, b, r):
        print("BATCH %.4f" % float(_np.asarray(b.x).sum()), flush=True)
        return step_fn(s, b, r)
    return _orig_epoch(loader, stepped, state, rng, start_batch, **kw)
_L.train_epoch = _traced_epoch

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "data_chaos",
        "format": "synthetic",
        "synthetic": {{"number_configurations": 120}},
        "bad_sample_policy": {policy!r},
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
            "num_epoch": {num_epoch}, "batch_size": 4,
            "seed": 7,
            {extra}
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
}}
print("CHILD_READY", flush=True)
model, state, hist, *_ = hydragnn_tpu.run_training(cfg)
print("CLEAN_EXIT epochs=%d" % len(hist["train"]), flush=True)
"""

_SOCKET_CHILD = _PRELUDE + """
import socket
from hydragnn_tpu.data import DDStore, RemoteStoreClient
from hydragnn_tpu.utils import faultinject

with socket.socket() as s:
    s.bind(("127.0.0.1", 0)); port = s.getsockname()[1]
store = DDStore("/data_chaos_smoke", max_items=16, create=True, overwrite=True)
try:
    blobs = [bytes([i]) * (500 * (i + 1)) for i in range(8)]
    for i, b in enumerate(blobs):
        store.put(i, b)
    store.serve(port)
    client = RemoteStoreClient("127.0.0.1", port, retry_base=0.0, timeout_s=10)
    faultinject.configure(socket_drop="2,5,9")  # three mid-run drops
    got = [client.get(i) for i in range(8)]
    assert got == blobs, "sample loss through injected socket drops"
    client.close()
    print("SOCKET_OK drops_absorbed=3 samples=8", flush=True)
finally:
    store.close(unlink=True)
"""

_LOSS_RE = re.compile(r"epoch (\d+): train ([0-9.eE+-]+)")
_BATCH_RE = re.compile(r"^BATCH (\S+)$", re.M)
_MIDKILL_RE = re.compile(r"SIGTERM: checkpointed mid-epoch (\d+) at batch (\d+)")


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HYDRAGNN_VALTEST"] = "0"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    env.update(extra)
    return env


def _run(workdir, name, code, env, timeout=300):
    script = os.path.join(workdir, f"{name}.py")
    with open(script, "w") as f:
        f.write(code)
    return subprocess.run(
        [sys.executable, script], cwd=workdir, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="data_chaos_")
    train_code = lambda policy, num_epoch, extra="": _TRAIN_CHILD.format(
        repo=_REPO, policy=policy, num_epoch=num_epoch, extra=extra
    )

    # ---- leg 1: injected NaN samples under warn_skip -> exact tally + a
    # loss that still learns
    p = _run(workdir, "leg1", train_code("warn_skip", 3),
             _env(HYDRAGNN_FAULT_SAMPLE_NAN="3,7"))
    out = p.stdout + p.stderr
    if p.returncode != 0 or "CLEAN_EXIT" not in p.stdout:
        print(f"data_chaos FAIL leg1: run crashed (rc={p.returncode}):\n"
              f"{out[-2500:]}")
        return 1
    if "data-plane skips: 2 skipped [nonfinite_features=2]" not in out:
        print("data_chaos FAIL leg1: skip tally does not match the "
              f"injection plan (expected nonfinite_features=2):\n{out[-2500:]}")
        return 1
    losses = [float(m.group(2)) for m in _LOSS_RE.finditer(out)]
    if len(losses) < 3 or losses[-1] >= losses[0]:
        print(f"data_chaos FAIL leg1: loss did not decrease: {losses}")
        return 1

    # ---- leg 2: the same injection under `error` fails fast, actionably
    p = _run(workdir, "leg2", train_code("error", 3),
             _env(HYDRAGNN_FAULT_SAMPLE_NAN="3,7"))
    out = p.stdout + p.stderr
    if p.returncode == 0:
        print(f"data_chaos FAIL leg2: error policy did not fail:\n{out[-2000:]}")
        return 1
    if "rejected: nonfinite_features" not in out or "sample 3" not in out:
        print("data_chaos FAIL leg2: error is not actionable (no sample "
              f"index/reason):\n{out[-2000:]}")
        return 1

    # ---- leg 3: socket drops absorbed with zero sample loss
    p = _run(workdir, "leg3", _SOCKET_CHILD.format(repo=_REPO), _env())
    if p.returncode != 0 or "SOCKET_OK" not in p.stdout:
        print(f"data_chaos FAIL leg3: socket-drop leg failed "
              f"(rc={p.returncode}):\n{(p.stdout + p.stderr)[-2500:]}")
        return 1

    # ---- leg 4: kill-and-resume mid-epoch, same batch order as unkilled
    # 4a: unkilled reference epoch-0 fingerprints (same config/seed)
    p = _run(workdir, "leg4_ref", train_code("warn_skip", 1), _env())
    if p.returncode != 0:
        print(f"data_chaos FAIL leg4 ref: {(p.stdout + p.stderr)[-2000:]}")
        return 1
    ref = _BATCH_RE.findall(p.stdout)
    if len(ref) < 5:
        print(f"data_chaos FAIL leg4 ref: too few batches ({len(ref)})")
        return 1

    # 4b: SIGTERM between steps of epoch 0
    script = os.path.join(workdir, "leg4_kill.py")
    with open(script, "w") as f:
        f.write(train_code("warn_skip", 10000))
    proc = subprocess.Popen(
        [sys.executable, script], cwd=workdir, env=_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines, batches_seen, deadline = [], 0, time.time() + 300
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break
        lines.append(line)
        if line.startswith("BATCH "):
            batches_seen += 1
            if batches_seen == 2:  # mid-epoch 0, builds are ahead of steps
                proc.send_signal(signal.SIGTERM)
                break
    else:
        proc.kill()
        print("data_chaos FAIL leg4: never saw 2 batches:\n"
              + "".join(lines)[-2000:])
        return 1
    out, _ = proc.communicate(timeout=300)
    leg4 = "".join(lines) + out
    m = _MIDKILL_RE.search(leg4)
    if proc.returncode != 0 or m is None:
        print("data_chaos FAIL leg4: no mid-epoch checkpoint on SIGTERM "
              f"(rc={proc.returncode}):\n{leg4[-2500:]}")
        return 1
    cursor = int(m.group(2))

    # 4c: resume replays epoch 0 from the cursor, same order
    run_name = "GIN-r-2.0-ncl-2-hd-8-ne-10000-lr-0.01-bs-4"
    p = _run(
        workdir, "leg4_resume",
        train_code("warn_skip", 1,
                   extra=f'"continue": 1, "startfrom": {run_name!r},'),
        _env(),
    )
    out = p.stdout + p.stderr
    if p.returncode != 0 or "resuming mid-epoch" not in out:
        print(f"data_chaos FAIL leg4: resume leg did not arm mid-epoch "
              f"(rc={p.returncode}):\n{out[-2500:]}")
        return 1
    resumed = _BATCH_RE.findall(p.stdout)
    want = ref[cursor:]
    if resumed[: len(want)] != want:
        print("data_chaos FAIL leg4: resumed batch order diverges from the "
              f"unkilled run\n  cursor={cursor}\n  want={want}\n  "
              f"got={resumed[: len(want)]}")
        return 1

    print(
        "data_chaos OK: tally-exact warn_skip, actionable error policy, "
        f"{3} socket drops absorbed, mid-epoch resume replayed "
        f"{len(want)} batches in order from cursor {cursor}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
