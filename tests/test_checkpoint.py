"""Verified atomic checkpoint IO (train/checkpoint.py) under injected faults:
SIGKILL at every writer kill-point, bit-rot, flaky-FS IOErrors, retention
pruning, the msgpack<->orbax ``latest`` pointer, and the actionable-error
contract of ``load_existing_model``.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.checkpoint import (
    load_existing_model,
    save_model,
    save_model_orbax,
)
from hydragnn_tpu.utils import faultinject

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


def _tx():
    return make_optimizer({"type": "SGD", "learning_rate": 1e-2})


def _state(v, tx=None):
    return TrainState.create(
        {"params": {"w": np.full((4,), v, np.float32)}}, tx or _tx()
    )


def _w(state) -> float:
    return float(np.asarray(state.params["w"])[0])


# ---------------------------------------------------------------------------
# atomicity under SIGKILL: the ``latest`` pointer is the commit point


_KILL_CHILD = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, __REPO__)
    import numpy as np
    from hydragnn_tpu.train import TrainState, make_optimizer
    from hydragnn_tpu.train.checkpoint import save_model

    tmp, point = sys.argv[1], sys.argv[2]
    tx = make_optimizer({"type": "SGD", "learning_rate": 1e-2})
    def mk(v):
        return TrainState.create(
            {"params": {"w": np.full((4,), v, np.float32)}}, tx)
    save_model(mk(1.0), "run", path=tmp, epoch=0)
    os.environ["HYDRAGNN_FAULT_KILL_AT"] = point
    save_model(mk(2.0), "run", path=tmp, epoch=1)
    print("SURVIVED", flush=True)
    """
)


@pytest.mark.parametrize(
    "point,want",
    [
        # killed before the payload replace: epoch-1 file never exists
        ("ckpt_tmp_written", 1.0),
        # payload replaced but digest missing: pointer still commits epoch 0
        ("ckpt_msgpack_replaced", 1.0),
        # digest written but pointer not: restore follows the old pointer
        ("ckpt_digest_written", 1.0),
        # control: the un-killed save commits epoch 1
        ("none", 2.0),
    ],
)
def pytest_sigkill_mid_save_restores_last_verified(point, want, tmp_path):
    """Acceptance: SIGKILL anywhere inside a save, then restore, lands on
    the last VERIFIED checkpoint — digest checked, <= 1 epoch lost."""
    script = tmp_path / "child.py"
    script.write_text(_KILL_CHILD.replace("__REPO__", repr(_REPO)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run_dir = str(tmp_path / "ckpts")
    proc = subprocess.run(
        [sys.executable, str(script), run_dir, point],
        capture_output=True,
        text=True,
        env=env,
        timeout=240,
    )
    if point == "none":
        assert proc.returncode == 0 and "SURVIVED" in proc.stdout, (
            proc.returncode,
            proc.stdout[-1000:],
            proc.stderr[-1000:],
        )
    else:
        assert proc.returncode == -9, (point, proc.returncode, proc.stderr[-1000:])
    restored = load_existing_model(_state(0.0), "run", path=run_dir)
    assert _w(restored) == want, (point, _w(restored))


_SAMENAME_KILL_CHILD = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, __REPO__)
    import numpy as np
    from hydragnn_tpu.train import TrainState, make_optimizer
    from hydragnn_tpu.train.checkpoint import save_model

    tmp = sys.argv[1]
    tx = make_optimizer({"type": "SGD", "learning_rate": 1e-2})
    def mk(v):
        return TrainState.create(
            {"params": {"w": np.full((4,), v, np.float32)}}, tx)
    save_model(mk(1.0), "run", path=tmp)  # unsuffixed name, v1 + sidecar
    os.environ["HYDRAGNN_FAULT_KILL_AT"] = "ckpt_msgpack_replaced"
    save_model(mk(2.0), "run", path=tmp)  # v2 replaces v1 IN PLACE, killed
    """
)


def pytest_sigkill_same_name_resave_never_orphans_the_run(tmp_path):
    """Overwriting the SAME filename (unsuffixed/default name) killed
    between payload replace and sidecar write: the old sidecar must not
    survive to reject the fully-valid new payload — the save drops it
    first, so restore accepts the complete v2 payload (unverified, warned)
    instead of declaring the only checkpoint corrupt."""
    script = tmp_path / "child.py"
    script.write_text(_SAMENAME_KILL_CHILD.replace("__REPO__", repr(_REPO)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run_dir = str(tmp_path / "ckpts")
    proc = subprocess.run(
        [sys.executable, str(script), run_dir],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-1000:])
    with pytest.warns(UserWarning, match="no sha256 sidecar"):
        restored = load_existing_model(_state(0.0), "run", path=run_dir)
    assert _w(restored) == 2.0


# ---------------------------------------------------------------------------
# digest verification + fallback walk


def pytest_bitflip_falls_back_to_previous_epoch(tmp_path):
    """Acceptance: a bit-flipped checkpoint fails its sha256 check and
    restore falls back to the previous retained epoch."""
    save_model(_state(1.0), "run", path=str(tmp_path), epoch=0)
    fname = save_model(_state(2.0), "run", path=str(tmp_path), epoch=1)
    faultinject.flip_bit(fname)
    restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 1.0


def pytest_latest_pointing_to_missing_file_falls_back(tmp_path):
    save_model(_state(1.0), "run", path=str(tmp_path), epoch=0)
    fname = save_model(_state(2.0), "run", path=str(tmp_path), epoch=1)
    os.unlink(fname)
    restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 1.0


def pytest_sidecarless_checkpoint_restores_with_warning(tmp_path):
    """Pre-upgrade checkpoints (no sha256 sidecar) still restore — the
    atomic-replace protocol means a published file is complete — but the
    restore says it was unverified."""
    fname = save_model(_state(3.0), "run", path=str(tmp_path), epoch=0)
    os.unlink(fname + ".sha256")
    with pytest.warns(UserWarning, match="no sha256 sidecar"):
        restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 3.0


def pytest_transient_io_errors_retry(tmp_path, monkeypatch):
    """Acceptance: first-n-IOError saves succeed via the exponential-backoff
    retry (base pinned to 0 — no time-based sleeps in CI)."""
    monkeypatch.setenv("HYDRAGNN_CKPT_RETRY_BASE", "0")
    faultinject.configure(io_errors="2")
    save_model(_state(4.0), "run", path=str(tmp_path), epoch=0)
    faultinject.reset()
    restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 4.0
    # the digest sidecar exists and verifies (the save fully committed)
    assert os.path.exists(
        os.path.join(str(tmp_path), "run", "run_epoch0.msgpack.sha256")
    )


def pytest_io_errors_beyond_retries_propagate(tmp_path, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_CKPT_RETRY_BASE", "0")
    monkeypatch.setenv("HYDRAGNN_CKPT_RETRIES", "3")
    faultinject.configure(io_errors="50")
    with pytest.raises(OSError, match="injected transient IO error"):
        save_model(_state(5.0), "run", path=str(tmp_path), epoch=0)


def pytest_retention_prunes_epoch_chain(tmp_path):
    for e, v in enumerate([1.0, 2.0, 3.0, 4.0]):
        save_model(_state(v), "run", path=str(tmp_path), epoch=e, retention=2)
    files = sorted(os.listdir(tmp_path / "run"))
    assert not any("epoch0" in f or "epoch1" in f for f in files), files
    assert any("epoch2" in f for f in files) and any("epoch3" in f for f in files)
    restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 4.0


# ---------------------------------------------------------------------------
# actionable errors (satellite)


def pytest_missing_run_dir_error_is_actionable():
    with pytest.raises(FileNotFoundError, match="does not exist"):
        load_existing_model(_state(0.0), "no_such_run", path="/tmp/definitely_absent_root")


def pytest_empty_run_dir_error_lists_files_and_candidates(tmp_path):
    (tmp_path / "empty").mkdir()
    with pytest.raises(FileNotFoundError) as e:
        load_existing_model(_state(0.0), "empty", path=str(tmp_path))
    msg = str(e.value)
    assert "files present" in msg and "candidates tried" in msg


def pytest_all_copies_corrupt_error_names_each_rejection(tmp_path):
    f0 = save_model(_state(1.0), "run", path=str(tmp_path), epoch=0)
    f1 = save_model(_state(2.0), "run", path=str(tmp_path), epoch=1)
    faultinject.flip_bit(f0)
    faultinject.flip_bit(f1)
    with pytest.raises(FileNotFoundError) as e:
        load_existing_model(_state(0.0), "run", path=str(tmp_path))
    msg = str(e.value)
    assert "sha256 mismatch" in msg
    assert "run_epoch0.msgpack" in msg and "run_epoch1.msgpack" in msg


# ---------------------------------------------------------------------------
# HYDRAGNN_EPOCH hardening (satellite)


def pytest_malformed_epoch_env_warns_and_saves(tmp_path, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_EPOCH", "not-an-int")
    with pytest.warns(UserWarning, match="HYDRAGNN_EPOCH"):
        fname = save_model(_state(6.0), "run", path=str(tmp_path))
    assert fname.endswith("run.msgpack")  # fell back to the unsuffixed name
    restored = load_existing_model(_state(0.0), "run", path=str(tmp_path))
    assert _w(restored) == 6.0


def pytest_malformed_epoch_env_warns_and_saves_orbax(tmp_path, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_EPOCH", "3.5epochs")
    tx = _tx()
    with pytest.warns(UserWarning, match="HYDRAGNN_EPOCH"):
        save_model_orbax(_state(7.0, tx), "run", path=str(tmp_path))
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 7.0


# ---------------------------------------------------------------------------
# msgpack <-> orbax pointer round-trip (satellite)


def pytest_msgpack_then_orbax_latest_pointer_roundtrip(tmp_path):
    """One run dir, both backends in sequence: restore must follow the
    ``latest`` pointer to whichever backend wrote last; re-saving an
    existing orbax step must replace it (the mgr.delete path)."""
    tx = _tx()
    save_model(_state(1.0, tx), "run", path=str(tmp_path), epoch=0)
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 1.0
    # orbax save in the same run dir flips the pointer to orbax/1
    save_model_orbax(_state(2.0, tx), "run", path=str(tmp_path), epoch=1)
    with open(tmp_path / "run" / "latest") as f:
        assert f.read().strip() == "orbax/1"
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 2.0
    # re-save the SAME orbax step (best-val update of a resumed run):
    # CheckpointManager refuses existing steps, so the delete path must run
    save_model_orbax(_state(3.0, tx), "run", path=str(tmp_path), epoch=1)
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 3.0
    # and a later msgpack save flips the pointer back
    save_model(_state(4.0, tx), "run", path=str(tmp_path), epoch=2)
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 4.0


def pytest_orbax_retention_maps_to_max_to_keep(tmp_path):
    """Training.checkpoint_retention must bound the orbax step chain too
    (max_to_keep), not silently apply to the msgpack backend only."""
    import orbax.checkpoint as ocp

    tx = _tx()
    for e, v in enumerate([1.0, 2.0, 3.0, 4.0]):
        save_model_orbax(
            _state(v, tx), "run", path=str(tmp_path), epoch=e, retention=2
        )
    with ocp.CheckpointManager(
        str(tmp_path / "run" / "orbax")
    ) as mgr:
        assert sorted(mgr.all_steps()) == [2, 3], mgr.all_steps()
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 4.0


def pytest_corrupt_orbax_pointer_falls_back_to_msgpack(tmp_path):
    """A ``latest`` pointing at a missing orbax step walks back to the
    msgpack chain instead of crashing."""
    tx = _tx()
    save_model(_state(1.0, tx), "run", path=str(tmp_path), epoch=0)
    with open(tmp_path / "run" / "latest", "w") as f:
        f.write("orbax/99")
    restored = load_existing_model(_state(0.0, tx), "run", path=str(tmp_path))
    assert _w(restored) == 1.0
