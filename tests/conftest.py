"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference CI strategy of exercising distributed code paths on CPU
(reference: .github/workflows/CI.yml:57-63 runs pytest under 2-rank Gloo);
here a single process exposes 8 XLA CPU devices so mesh/sharding code runs
for real without TPU hardware.

The environment is set here, in-process, before anything imports JAX: the
suite is a CPU suite wherever it runs (a machine with a chip included — the
chip is checked by ``chip_smoke.py``, not by pytest).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# the suite compiles thousands of tiny programs it never runs twice; a
# persistent cache would only add disk writes to a time-boxed tier. Tests
# that exercise the cache machinery arm tmp dirs via cp.set_cache_dir or
# monkeypatch this env themselves.
os.environ.setdefault("HYDRAGNN_COMPILE_CACHE", "0")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
