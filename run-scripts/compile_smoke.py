#!/usr/bin/env python
"""CI compile-plane smoke (docs/PERFORMANCE.md "Compile plane").

Two subprocess legs over one shared persistent compilation cache:

1. **cold**: a short CPU training run with ``Training.precompile:
   background`` and the retrace sentinel in ``error`` mode — the run must
   finish cleanly (zero post-warm-up retraces, or the sentinel raises) and
   the report must show every ladder specialization precompiled.
2. **warm**: the identical run again — every XLA compile must now be served
   from the cache (``cache_hits > 0``) with a time-to-first-step bounded by
   the cold leg's.

Invoked from run-scripts/ci.sh. Self-contained: fresh interpreters, CPU
JAX, scrubbed env, temp workdir (same recipe as chaos_smoke.py).
Exit 0 = compile plane healthy; nonzero with a diagnostic otherwise.
"""

import json
import os
import re
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import jax
import hydragnn_tpu

cfg = {{
    "Verbosity": {{"level": 1}},
    "Dataset": {{
        "name": "compile_smoke",
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
            "num_epoch": 3, "batch_size": 8, "seed": 11,
            "num_pad_buckets": 3,
            "precompile": "background",
            "retrace_policy": "error",
            "Optimizer": {{"type": "AdamW", "learning_rate": 0.01}},
        }},
    }},
}}
model, state, hist, *_ = hydragnn_tpu.run_training(cfg)
print("CLEAN_EXIT epochs=%d" % len(hist["train"]), flush=True)
"""

_PLANE_RE = re.compile(
    # remat= (r11) and hbm_peak= (r12) are optional: the parsed fields keep
    # their group numbers across report-line growth
    r"compile plane: mode=(\S+) (?:remat=\S+ )?precompiled=(\d+)/(\d+) "
    r"compile_time_s=([0-9.]+) cache_hits=(\d+) cache_misses=(\d+) "
    r"time_to_first_step=([0-9.]+|n/a)s traces=(\d+) violations=(\d+)"
)


def _env(workdir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
    # CPU-sized compiles beat jax's default 1s cache-write floor
    env["HYDRAGNN_COMPILE_CACHE_MIN_SECS"] = "0"
    return env


def _run_leg(workdir, name):
    script = os.path.join(workdir, f"{name}.py")
    with open(script, "w") as f:
        f.write(_CHILD.format(repo=_REPO))
    proc = subprocess.run(
        [sys.executable, script], cwd=workdir, env=_env(workdir),
        capture_output=True, text=True, timeout=600,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0 or "CLEAN_EXIT" not in out:
        print(f"compile_smoke FAIL: {name} leg crashed "
              f"(rc={proc.returncode}) — a retrace-sentinel error here "
              f"means a silent recompile slipped in:\n{out[-3000:]}")
        return None
    m = None
    for m in _PLANE_RE.finditer(out):
        pass
    if m is None:
        print(f"compile_smoke FAIL: {name} leg printed no compile-plane "
              f"report:\n{out[-3000:]}")
        return None
    return {
        "mode": m.group(1),
        "precompiled": int(m.group(2)),
        "specializations": int(m.group(3)),
        "compile_time_s": float(m.group(4)),
        "cache_hits": int(m.group(5)),
        "cache_misses": int(m.group(6)),
        "time_to_first_step": (
            None if m.group(7) == "n/a" else float(m.group(7))
        ),
        "violations": int(m.group(9)),
    }


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="compile_smoke_")
    cold = _run_leg(workdir, "cold")
    if cold is None:
        return 1
    if cold["mode"] != "background":
        print(f"compile_smoke FAIL: cold leg mode {cold['mode']!r} — the "
              "default background precompile did not engage (no cache dir?)")
        return 1
    if cold["precompiled"] == 0 or (
        cold["precompiled"] != cold["specializations"]
    ):
        print("compile_smoke FAIL: background warm-up did not cover the "
              f"ladder: {cold['precompiled']}/{cold['specializations']}")
        return 1
    if cold["violations"] != 0:
        print("compile_smoke FAIL: retrace sentinel reported "
              f"{cold['violations']} violations on the cold leg")
        return 1

    warm = _run_leg(workdir, "warm")
    if warm is None:
        return 1
    ok_hits = warm["cache_hits"] > 0
    ok_viol = warm["violations"] == 0
    ok_ttfs = (
        warm["time_to_first_step"] is not None
        and cold["time_to_first_step"] is not None
        and warm["time_to_first_step"]
        <= cold["time_to_first_step"] * 1.25 + 1.0
    )
    verdict = {
        "metric": "compile-plane smoke (background precompile + error "
                  "sentinel; cold -> warm cache)",
        "cold": cold,
        "warm": warm,
        "ok": bool(ok_hits and ok_viol and ok_ttfs),
    }
    print(json.dumps(verdict))
    if not ok_hits:
        print("compile_smoke FAIL: warm leg reported zero cache hits — the "
              "persistent compilation cache did not survive the restart")
        return 1
    if not ok_viol:
        print("compile_smoke FAIL: retrace sentinel reported "
              f"{warm['violations']} violations on the warm leg")
        return 1
    if not ok_ttfs:
        print("compile_smoke FAIL: warm time-to-first-step "
              f"{warm['time_to_first_step']}s not bounded by cold "
              f"{cold['time_to_first_step']}s")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
