"""The chip check's CPU side: ``chip_smoke.py`` must refuse a backend that is
not a TPU, and config completion must ask the backend that initialised — not
the environment — before switching the Pallas routes on."""

import inspect
import os
import subprocess
import sys

import hydragnn_tpu.config.config as config_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_chip_smoke_refuses_a_cpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "backend 'cpu'" in proc.stderr, proc.stderr[-500:]
    # no result line, and nothing was built before the refusal
    assert '"ok"' not in proc.stdout and proc.stdout.strip() == "", proc.stdout


def pytest_jit_target_follows_the_initialised_backend(monkeypatch):
    # the environment may say anything: JAX is on the CPU in this suite
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    is_tpu, source = config_mod._jit_target_inference()
    assert is_tpu is False and "'cpu'" in source, (is_tpu, source)
    # no platform-name branch and no libtpu probe: the function does not
    # read the environment at all
    src = inspect.getsource(config_mod._jit_target_inference)
    assert not any(t in src for t in ("os.environ", "getenv", "find_spec"))
