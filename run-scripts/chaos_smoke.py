#!/usr/bin/env python
"""CI chaos smoke: SIGTERM a short CPU training run mid-epoch, resume it via
``Training.continue``, and assert the resumed loss CONTINUES the pre-kill
trend — the full preemption round-trip (checkpoint -> restore -> keep
learning), which the in-process preemption tests never exercised end-to-end.

Invoked from run-scripts/ci.sh. Self-contained: runs both legs in fresh
subprocess interpreters (CPU JAX, scrubbed env — same recipe as
tests/conftest.py) inside a temp dir, so no state leaks into the caller.

Exit 0 = round-trip healthy; nonzero with a diagnostic otherwise.
"""

import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import jax
import hydragnn_tpu

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "chaos_resume",
        "format": "synthetic",
        "synthetic": {{"number_configurations": 60}},
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
            "num_epoch": {num_epoch}, "batch_size": 8,
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

_EPOCH_RE = re.compile(r"epoch (\d+): train ([0-9.eE+-]+)")
_PLANE_RE = re.compile(
    r"compile plane: .*cache_hits=(\d+) cache_misses=(\d+) "
    r"time_to_first_step=([0-9.]+|n/a)s traces=\d+ violations=(\d+)"
)


def _env(workdir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    if workdir is not None:
        # ONE persistent compilation cache shared by both legs, private to
        # this smoke: the warm path of the round-trip is part of what it
        # asserts. min secs 0: CPU-sized compiles must be cached too.
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
        env["HYDRAGNN_COMPILE_CACHE_MIN_SECS"] = "0"
    return env


def _plane_stats(text):
    """(cache_hits, time_to_first_step, violations) from the compile-plane
    report line, or None."""
    m = None
    for m in _PLANE_RE.finditer(text):
        pass  # last line wins (a leg runs one training)
    if m is None:
        return None
    ttfs = None if m.group(3) == "n/a" else float(m.group(3))
    return int(m.group(1)), ttfs, int(m.group(4))


def _losses(text):
    return [float(m.group(2)) for m in _EPOCH_RE.finditer(text)]


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="chaos_smoke_")
    # ---- leg 1: train, SIGTERM after a few epochs, expect a clean
    # checkpointed stop (utils/preemption.py)
    script = os.path.join(workdir, "leg1.py")
    with open(script, "w") as f:
        f.write(_CHILD.format(repo=_REPO, num_epoch=10000, extra=""))
    proc = subprocess.Popen(
        [sys.executable, script], cwd=workdir, env=_env(workdir),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines, deadline = [], time.time() + 300
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break
        if line:
            lines.append(line)
        if "epoch 3:" in line:  # a few epochs of pre-kill trend banked
            break
    else:
        proc.kill()
        print("chaos_smoke FAIL: leg-1 training never reached epoch 3:\n"
              + "".join(lines)[-2000:])
        return 1
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=300)
    leg1 = "".join(lines) + out
    if proc.returncode != 0 or "SIGTERM: checkpointed" not in leg1:
        print("chaos_smoke FAIL: leg-1 did not stop cleanly on SIGTERM "
              f"(rc={proc.returncode}):\n{leg1[-2000:]}")
        return 1
    pre_kill = _losses(leg1)
    if len(pre_kill) < 3:
        print(f"chaos_smoke FAIL: too few pre-kill epochs parsed: {pre_kill}")
        return 1

    # ---- leg 2: resume via Training.continue from the preemption
    # checkpoint (same config -> same derived log name) and keep learning
    # the derived log name embeds num_epoch, so the resume leg names leg
    # 1's run dir explicitly (Training.startfrom — the documented way to
    # resume under a different recipe)
    leg1_name = "GIN-r-2.0-ncl-2-hd-8-ne-10000-lr-0.01-bs-8"
    if not os.path.isdir(os.path.join(workdir, "logs", leg1_name)):
        print(
            "chaos_smoke FAIL: expected leg-1 run dir "
            f"{leg1_name!r} not found in {workdir}/logs: "
            f"{os.listdir(os.path.join(workdir, 'logs'))}"
        )
        return 1
    script2 = os.path.join(workdir, "leg2.py")
    with open(script2, "w") as f:
        f.write(
            _CHILD.format(
                repo=_REPO,
                num_epoch=3,
                extra=f'"continue": 1, "startfrom": {leg1_name!r},',
            )
        )
    proc2 = subprocess.run(
        [sys.executable, script2], cwd=workdir, env=_env(workdir),
        capture_output=True, text=True, timeout=600,
    )
    if proc2.returncode != 0 or "CLEAN_EXIT" not in proc2.stdout:
        print("chaos_smoke FAIL: resume leg crashed "
              f"(rc={proc2.returncode}):\n{(proc2.stdout + proc2.stderr)[-2000:]}")
        return 1
    resumed = _losses(proc2.stdout)
    if not resumed:
        print(f"chaos_smoke FAIL: no resumed epochs parsed:\n{proc2.stdout[-2000:]}")
        return 1

    # the resumed run must CONTINUE the pre-kill trend, not restart: its
    # first epoch sits at (or below) the pre-kill floor, with bounded slack
    # for the one optimizer step of drift a mid-epoch kill can lose, and
    # far below the cold-start loss
    floor, cold = min(pre_kill), pre_kill[0]
    ok_continues = resumed[0] <= floor * 1.30
    ok_not_restart = resumed[0] < (cold + floor) / 2

    # compile-plane warm path (docs/PERFORMANCE.md "Compile plane"): the
    # resumed child shares the parent's persistent compilation cache, so it
    # must report cache hits > 0 and a time-to-first-step bounded by the
    # cold parent's (slack for CPU timing noise on tiny compiles)
    cold_plane = _plane_stats(leg1)
    warm_plane = _plane_stats(proc2.stdout + proc2.stderr)
    if cold_plane is None or warm_plane is None:
        print("chaos_smoke FAIL: compile-plane report line missing "
              f"(cold={cold_plane}, warm={warm_plane})")
        return 1
    warm_hits, warm_ttfs, warm_viol = warm_plane
    _, cold_ttfs, cold_viol = cold_plane
    ok_warm_hits = warm_hits > 0
    ok_ttfs = (
        warm_ttfs is not None
        and cold_ttfs is not None
        and warm_ttfs <= cold_ttfs * 1.25 + 1.0
    )
    ok_no_retrace = cold_viol == 0 and warm_viol == 0
    verdict = {
        "metric": "chaos resume smoke (SIGTERM -> Training.continue)",
        "pre_kill": [round(l, 6) for l in pre_kill],
        "resumed": [round(l, 6) for l in resumed],
        "resumed_first_vs_floor": round(resumed[0] / max(floor, 1e-12), 4),
        "compile_cache_hits_warm": warm_hits,
        "time_to_first_step_cold": cold_ttfs,
        "time_to_first_step_warm": warm_ttfs,
        "ok": bool(ok_continues and ok_not_restart and ok_warm_hits
                   and ok_ttfs and ok_no_retrace),
    }
    print(json.dumps(verdict))
    if not (ok_continues and ok_not_restart):
        print("chaos_smoke FAIL: resumed loss does not continue the "
              f"pre-kill trend (floor={floor}, cold={cold}, "
              f"resumed_first={resumed[0]})")
        return 1
    if not ok_warm_hits:
        print("chaos_smoke FAIL: resumed child reported zero compilation-"
              "cache hits — the warm restart path recompiled from scratch")
        return 1
    if not ok_ttfs:
        print("chaos_smoke FAIL: resumed child's time-to-first-step "
              f"{warm_ttfs}s not bounded by the cold parent's {cold_ttfs}s")
        return 1
    if not ok_no_retrace:
        print("chaos_smoke FAIL: retrace sentinel reported violations "
              f"(cold={cold_viol}, warm={warm_viol})")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
