"""SIGTERM preemption: checkpoint at the epoch boundary and stop cleanly
(utils/preemption.py; the TPU-pod preemption analog of the reference's
SLURM-walltime stop, distributed.py:380-419)."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, __REPO__)
    import hydragnn_tpu

    cfg = {
        "Verbosity": {"level": 1},
        "Dataset": {
            "name": "preempt_ci",
            "format": "synthetic",
            "synthetic": {"number_configurations": 60},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["s"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": {"num_sharedlayers": 1,
                                            "dim_sharedlayers": 8,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [8, 8]}},
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["s"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 10000, "batch_size": 8,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
    }
    print("CHILD_READY", flush=True)
    model, state, hist, *_ = hydragnn_tpu.run_training(cfg)
    # reached only via the preemption break (10000 epochs would run forever)
    print(f"CLEAN_EXIT epochs={len(hist['train'])}", flush=True)
    """
)


@pytest.mark.slow  # full train-loop drive: exceeds the capped fast tier; runs in the ci.sh suite
def pytest_sigterm_checkpoints_and_stops(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD.replace("__REPO__", repr(_REPO)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        cwd=str(tmp_path),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )
    # wait until training is underway (first epoch line), then preempt
    deadline = time.time() + 240
    lines = []
    started = False
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:
            break  # child exited before training started
        if line:
            lines.append(line)
        if "epoch 1:" in line:
            started = True
            break
    if not started:
        proc.kill()
        tail = "".join(l for l in lines if l.strip())[-2000:]
        raise AssertionError(f"training never started:\n{tail}")
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=240)
    assert proc.returncode == 0, out[-2000:]
    assert "SIGTERM: checkpointed" in out, out[-2000:]
    assert "CLEAN_EXIT" in out, out[-2000:]
    # the preemption checkpoint exists and is loadable for resume
    run_dirs = list((tmp_path / "logs").iterdir())
    assert any((d / "latest").exists() for d in run_dirs if d.is_dir()), run_dirs


def pytest_handler_restored_and_flag_reset():
    """After training, SIGTERM disposition is restored and a stale flag
    cannot stop the next run (utils/preemption.py install/uninstall)."""
    from hydragnn_tpu.utils import preemption

    prev = signal.getsignal(signal.SIGTERM)
    preemption.install()
    preemption._flag.set()
    assert preemption.preempted()
    preemption.uninstall()
    assert signal.getsignal(signal.SIGTERM) == prev
    # a fresh install clears the stale flag
    preemption.install()
    assert not preemption.preempted()
    preemption.uninstall()


def pytest_final_save_gates_on_global_decision():
    """The end-of-run save must gate on the cross-host AGREED stop (recorded
    by the loop via note_global_stop), never the per-process SIGTERM flag:
    skewed signal delivery would otherwise hang non-preempted hosts in a
    collective orbax save (ADVICE r2, api.py final-save gate)."""
    from hydragnn_tpu.utils import preemption

    preemption.reset()
    # a SIGTERM that arrived but did NOT stop the loop (e.g. after the last
    # epoch): local flag set, no agreed stop -> final save must proceed
    preemption._flag.set()
    assert preemption.preempted()
    assert not preemption.global_stop_noted()
    # the loop's agreed stop records the collective decision
    preemption.note_global_stop()
    assert preemption.global_stop_noted()
    # install() for a fresh run clears both
    preemption.install()
    assert not preemption.global_stop_noted()
    assert not preemption.preempted()
    preemption.uninstall()
