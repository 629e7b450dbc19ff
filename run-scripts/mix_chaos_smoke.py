#!/usr/bin/env python
"""CI mixture-plane chaos smoke (docs/GFM.md; wired into ci.sh). Three legs,
each a fresh scrubbed CPU-JAX subprocess (the data_chaos_smoke recipe):

A. **26-family churn**: a 26-branch synthetic GFM mixture trains end to end
   with blocking precompile and the retrace sentinel in ERROR mode (any
   unwarmed specialization aborts the leg), while one source is
   hot-REMOVED at the end of epoch 0 and another — poisoned with
   post-ingest NaNs — is quarantine-DEMOTED at draw time. The run must
   finish every epoch with no step failure, the demotion/removal must
   emit their typed events, and neither source may be drawn afterwards.

B. **SIGKILL -> bit-exact resume**: a 3-source mixture run is SIGKILLed
   mid-epoch-1 (after the epoch-0 checkpoint committed). The resumed run
   (``Training.continue``) restores the mixture sidecar and must replay
   the remaining draw sequence — every epoch-1/epoch-2 batch fingerprint
   (sample content + source draw order, HYDRAGNN_MIX_FINGERPRINT) equal
   to the unkilled reference run's.

C. **SIGTERM -> per-source-cursor resume**: SIGTERM between steps of
   epoch 0 checkpoints the mixture cursors inside the PR 4 loader-state
   sidecar; the resumed run must arm mid-epoch and replay epoch 0 from
   the cursor with fingerprints identical to the reference tail.

Exit 0 = mixture plane healthy; nonzero with a diagnostic otherwise.
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
sys.path.insert(0, __REPO__)
import jax
"""

_DATA = """
import dataclasses
import numpy as np
from hydragnn_tpu.data.synthetic import deterministic_graph_dataset
from hydragnn_tpu.data.pipeline import (
    MinMax, VariablesOfInterest, extract_variables, split_dataset,
)

def build(families, n_conf):
    raw = deterministic_graph_dataset(n_conf, seed=13)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["s"], ["graph"], [0], [1, 1, 1], [1])
    ready = [
        dataclasses.replace(extract_variables(g, voi), dataset_id=i % families)
        for i, g in enumerate(raw)
    ]
    return split_dataset(ready, 0.7, seed=0)

def config(families, num_epoch, extra=None):
    gh = {"num_sharedlayers": 1, "dim_sharedlayers": 8,
          "num_headlayers": 2, "dim_headlayers": [8, 8]}
    cfg = {
        "Verbosity": {"level": 1},
        "Dataset": {"name": "mix_chaos",
                    "node_features": {"dim": [1, 1, 1]},
                    "graph_features": {"dim": [1]}},
        "Mixture": {"temperature": 1.5, "demote_after": 2},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": [
                    {"type": "branch-%d" % b, "architecture": dict(gh)}
                    for b in range(families)
                ]},
            },
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["s"],
                "output_index": [0], "type": ["graph"],
                "denormalize_output": False,
            },
            "Training": {
                "num_epoch": num_epoch, "batch_size": 8, "seed": 7,
                "precompile": "blocking", "retrace_policy": "error",
                "Checkpoint": True, "checkpoint_warmup": 0,
                **(extra or {}),
                "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
    }
    return cfg
"""

# ---- leg A: 26-family churn (direct drive so the plane is reachable) -------
_CHURN_CHILD = _PRELUDE + _DATA + """
from hydragnn_tpu.api import prepare_data
from hydragnn_tpu.models.create import create_model, init_model
from hydragnn_tpu.obs.events import events as _events
from hydragnn_tpu.train import train_validate_test
from hydragnn_tpu.train.optimizer import make_optimizer
from hydragnn_tpu.train.state import TrainState

FAM = 26
tr, va, te = build(FAM, 180)
cfg, (tr_l, va_l, te_l), _ = prepare_data(config(FAM, 4), datasets=(tr, va, te))
assert type(tr_l).__name__ == "MixturePlane", type(tr_l)
assert len(tr_l.sources) == FAM, len(tr_l.sources)

# post-ingest rot: poison one source's samples AFTER the ingest gate (the
# draw-time validation + quarantine-demotion path)
rot_sid = tr_l._sid_of("ds3")
for g in tr_l.sources[rot_sid].graphs[:3]:
    np.asarray(g.x)[0, 0] = np.nan

# per-epoch draw census, captured BEFORE the hook resets it
draw_log = []
orig_hook = tr_l.mixture_epoch_hook
def hook(epoch, tasks, **kw):
    draw_log.append((epoch, dict(tr_l.epoch_draws)))
    orig_hook(epoch, tasks, **kw)
tr_l.mixture_epoch_hook = hook

removed = {}
def log_fn(epoch, scalars):
    if epoch == 0 and "ds7" not in removed:
        removed["ds7"] = tr_l._sid_of("ds7")
        tr_l.remove_source("ds7")
        print("REMOVED ds7 after epoch 0", flush=True)

model = create_model(cfg)
variables = init_model(model, next(iter(tr_l)), seed=7)
tx = make_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
state = TrainState.create(variables, tx)
state, hist = train_validate_test(
    model, state, tx, tr_l, va_l, te_l, cfg,
    log_name="mix_chaos_26", verbosity=1, seed=7, log_fn=log_fn,
)
assert len(hist["train"]) == 4, hist["train"]
assert all(np.isfinite(v) for v in hist["train"]), hist["train"]
assert rot_sid in tr_l.demoted, (tr_l.demoted, tr_l.fail_counts)
assert removed["ds7"] not in tr_l.sources
for epoch, draws in draw_log:
    if epoch >= 1:
        assert removed["ds7"] not in draws, (epoch, draws)
kinds = [e["kind"] for e in _events().snapshot()]
assert "mix_demote" in kinds and "mix_source_remove" in kinds, kinds
print("LEGA_OK families=%d demoted=%s epochs=%d" % (
    FAM, tr_l.demoted, len(hist["train"])), flush=True)
"""

# ---- legs B/C: run_training child (full api path incl. sidecars) -----------
# token substitution (.replace), NOT str.format: the shared _DATA block is
# full of literal dict braces
_TRAIN_CHILD = _PRELUDE + _DATA + """
import hydragnn_tpu

tr, va, te = build(3, 96)
cfg = config(3, __NUM_EPOCH__, extra=__EXTRA__)
print("CHILD_READY", flush=True)
model, state, hist, *_ = hydragnn_tpu.run_training(cfg, datasets=(tr, va, te))
print("CLEAN_EXIT epochs=%d" % len(hist["train"]), flush=True)
"""

_FP_RE = re.compile(r"^MIXBATCH e(\d+) b(\d+) ([0-9a-f]+)$", re.M)
_MIDKILL_RE = re.compile(r"SIGTERM: checkpointed mid-epoch (\d+) at batch (\d+)")


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from smoke_env import child_env  # noqa: E402


def _env(**extra):
    return child_env({
        "HYDRAGNN_VALTEST": "0",
        "HYDRAGNN_MIX_FINGERPRINT": "1",
        **extra,
    })


def _run(workdir, name, code, env, timeout=900):
    script = os.path.join(workdir, f"{name}.py")
    with open(script, "w") as f:
        f.write(code)
    return subprocess.run(
        [sys.executable, script], cwd=workdir, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _fingerprints(text):
    return {(int(m.group(1)), int(m.group(2))): m.group(3)
            for m in _FP_RE.finditer(text)}


def _kill_after(workdir, name, code, env, epoch, batches, sig):
    """Start a training child; deliver ``sig`` after seeing ``batches``
    MIXBATCH lines of ``epoch``. Returns (rc, full output)."""
    script = os.path.join(workdir, f"{name}.py")
    with open(script, "w") as f:
        f.write(code)
    proc = subprocess.Popen(
        [sys.executable, script], cwd=workdir, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines, seen, deadline = [], 0, time.time() + 900
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break
        lines.append(line)
        m = _FP_RE.match(line.strip())
        if m and int(m.group(1)) == epoch:
            seen += 1
            if seen >= batches:
                proc.send_signal(sig)
                break
    else:
        proc.kill()
        return None, "".join(lines)
    try:
        out, _ = proc.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    return proc.returncode, "".join(lines) + (out or "")


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="mix_chaos_")

    # ---- leg A: 26-family churn + demotion + zero retraces (error mode)
    p = _run(workdir, "legA",
             _CHURN_CHILD.replace("__REPO__", repr(_REPO)), _env())
    out = p.stdout + p.stderr
    if p.returncode != 0 or "LEGA_OK" not in out:
        print(f"mix_chaos FAIL legA (rc={p.returncode}):\n{out[-4000:]}")
        return 1

    # ---- leg B: SIGKILL mid-epoch-1 -> bit-exact epoch-1+ replay
    train_code = lambda num_epoch, extra="None": (
        _TRAIN_CHILD.replace("__REPO__", repr(_REPO))
        .replace("__NUM_EPOCH__", str(num_epoch))
        .replace("__EXTRA__", extra)
    )
    ref = _run(workdir, "legB_ref", train_code(3), _env())
    if ref.returncode != 0 or "CLEAN_EXIT" not in ref.stdout:
        print(f"mix_chaos FAIL legB ref (rc={ref.returncode}):\n"
              f"{(ref.stdout + ref.stderr)[-3000:]}")
        return 1
    ref_fp = _fingerprints(ref.stdout)
    if not any(e == 1 for e, _ in ref_fp):
        print(f"mix_chaos FAIL legB ref: no epoch-1 fingerprints ({ref_fp})")
        return 1

    rc, kill_out = _kill_after(
        workdir, "legB_kill", train_code(10000), _env(),
        epoch=1, batches=2, sig=signal.SIGKILL,
    )
    if rc is None or rc == 0:
        print(f"mix_chaos FAIL legB kill: child survived SIGKILL (rc={rc}):\n"
              f"{kill_out[-2000:]}")
        return 1
    kill_name = "GIN-r-2.0-ncl-2-hd-8-ne-10000-lr-0.01-bs-8"
    p = _run(
        workdir, "legB_resume",
        train_code(2, extra='{"continue": 1, "startfrom": "%s"}' % kill_name),
        _env(),
    )
    out = p.stdout + p.stderr
    if p.returncode != 0 or "CLEAN_EXIT" not in p.stdout:
        print(f"mix_chaos FAIL legB resume (rc={p.returncode}):\n{out[-4000:]}")
        return 1
    res_fp = _fingerprints(p.stdout)
    compared = 0
    for key, fp in sorted(res_fp.items()):
        if key not in ref_fp:
            continue  # ref ran 3 epochs; resume may print an extra one
        if ref_fp[key] != fp:
            print(f"mix_chaos FAIL legB: fingerprint diverged at epoch "
                  f"{key[0]} batch {key[1]}: ref={ref_fp[key]} resumed={fp}")
            return 1
        compared += 1
    want_e1 = sum(1 for e, _ in ref_fp if e == 1)
    if compared < want_e1:
        print(f"mix_chaos FAIL legB: only {compared} fingerprints compared "
              f"(need at least epoch 1's {want_e1}); resumed keys: "
              f"{sorted(res_fp)}")
        return 1
    missing = [k for k in ref_fp if k[0] == 1 and k not in res_fp]
    if missing:
        print(f"mix_chaos FAIL legB: resumed run missed epoch-1 batches "
              f"{missing}")
        return 1

    # ---- leg C: SIGTERM between steps -> per-source-cursor mid-epoch resume
    workdir_c = tempfile.mkdtemp(prefix="mix_chaos_c_")
    rc, term_out = _kill_after(
        workdir_c, "legC_kill", train_code(10000), _env(),
        epoch=0, batches=2, sig=signal.SIGTERM,
    )
    m = _MIDKILL_RE.search(term_out or "")
    if rc != 0 or m is None:
        print(f"mix_chaos FAIL legC: no mid-epoch checkpoint on SIGTERM "
              f"(rc={rc}):\n{(term_out or '')[-3000:]}")
        return 1
    cursor = int(m.group(2))
    p = _run(
        workdir_c, "legC_resume",
        train_code(1, extra='{"continue": 1, "startfrom": "%s"}' % kill_name),
        _env(),
    )
    out = p.stdout + p.stderr
    if p.returncode != 0 or "resuming mid-epoch" not in out:
        print(f"mix_chaos FAIL legC: resume did not arm mid-epoch "
              f"(rc={p.returncode}):\n{out[-4000:]}")
        return 1
    res_fp = _fingerprints(p.stdout)
    tail = {k: v for k, v in ref_fp.items() if k[0] == 0 and k[1] >= cursor}
    for key, fp in sorted(tail.items()):
        if res_fp.get(key) != fp:
            print(f"mix_chaos FAIL legC: cursor-resume tail diverged at "
                  f"batch {key[1]}: ref={fp} resumed={res_fp.get(key)}")
            return 1
    if not tail:
        print(f"mix_chaos FAIL legC: empty reference tail (cursor={cursor})")
        return 1

    print(
        "mix_chaos OK: 26-family churn leg (1 demoted, 1 hot-removed, "
        "error-mode sentinel clean), SIGKILL resume replayed "
        f"{compared} fingerprints bit-exactly, SIGTERM cursor resume "
        f"replayed {len(tail)} epoch-0 batches from cursor {cursor}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
