"""The chip check's CPU side: ``chip_smoke.py`` must refuse a backend that is
not a TPU, and config completion must ask the backend that initialised — not
the environment — before switching the Pallas routes on."""

import importlib.util
import inspect
import json
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


def pytest_chip_smoke_result_line_holds_the_contract_keys_only():
    # the driver rejects a last line with any key beside these (PR 21 was
    # refused once for carrying the legs and timings there)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    stamp = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = smoke.result_line({**stamp, "extra": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": stamp}
    src = inspect.getsource(smoke.main)
    assert src.rstrip().endswith(
        "print(result_line(device), flush=True)\n    return 0"), src[-200:]


def pytest_jit_target_follows_the_initialised_backend(monkeypatch):
    # the environment may say anything: JAX is on the CPU in this suite
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    is_tpu, source = config_mod._jit_target_inference()
    assert is_tpu is False and "'cpu'" in source, (is_tpu, source)
    # no platform-name branch and no libtpu probe: the function does not
    # read the environment at all
    src = inspect.getsource(config_mod._jit_target_inference)
    assert not any(t in src for t in ("os.environ", "getenv", "find_spec"))
