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

import pytest

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


@pytest.fixture
def flash_forward_once(monkeypatch):
    """The check the three decoder stacks' tests share (test_zaya.py,
    test_joyai.py, test_afmoe.py): with the flash route forced, a layer's remat
    keeps its launch's ``o`` and a row's ``lse``, so the training step's jaxpr
    holds ONE forward launch a block beside its two backward launches, and the
    step's two ``count:flash_blocks*`` entries say so."""
    import copy
    import re

    import jax

    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step
    from hydragnn_tpu.utils import tracer as tr

    def check(config, loader, model, variables, blocks: int):
        monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
        batch = next(iter(loader))
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        state = TrainState.create(copy.deepcopy(variables), tx)
        step = make_train_step(model, tx, False, False)
        names = re.findall(r"name=(hg_\w+)", str(jax.make_jaxpr(step)(state, batch, jax.random.PRNGKey(0))))
        forward = [n for n in names if n in (tr.HG_FLASH_ATTENTION, tr.HG_FLASH_WINDOW)]
        backward = [n for n in names if n in (tr.HG_FLASH_ATTENTION + tr.BWD, tr.HG_FLASH_WINDOW + tr.BWD)]
        assert (len(forward), len(backward)) == (blocks, 2 * blocks), names
        out, _ = model.apply(variables, batch, train=True, mutable=["batch_stats"])
        assert float(out[tr.CT_FLASH_BLOCKS]) == float(out[tr.CT_FLASH_BLOCKS_SAVED]) == blocks
        assert float(model.apply(variables, batch, train=False)[tr.CT_FLASH_BLOCKS_SAVED]) == 0.0

    return check
