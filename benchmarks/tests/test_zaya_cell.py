"""The ZAYA cell's own rehearsal, faults and control, run by hand like
`test_benchmark.py` (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_zaya_cell.py -q -p no:cacheprovider

`test_benchmark.py` shrinks a cell with `tiny.tiny_ctx`, which knows the
hidden width and the heads only; this file shrinks every ZAYA width, the
vocabulary, the documents and the packing budget (`tiny_zaya.tiny_ctx`).
"""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny_zaya  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
from test_benchmark import half_batch, unchanged_state  # noqa: E402


def _drive(seconds=0.5, trace=False, seed=2**31 + 99, mixed=False, **fault):
    import drive_train_tokens
    import jax

    ctx = tiny_zaya.tiny_ctx()
    ctx["config"]["program_config"]["NeuralNetwork"]["Training"]["mixed_precision"] = mixed
    return drive_train_tokens.drive(ctx, seed, seconds, trace, time.perf_counter(), jax.devices(),
                             common.cache_dirs(), scale=tiny_zaya.SCALE, **fault)


def pytest_rehearsal_result_line_and_counters():
    for trace in (False, True):
        r = _drive(trace=trace)
        assert list(r)[:4] == ["correct", "attempted", "failed", "metrics"] and list(r)[-1] == "compared"
        assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True, r["compared"]
        if trace:
            m = r["metrics"]
            assert m["compiles_in_window"]["value"] == 0
            assert 0 < m["tokens_routed_here_share.train"]["value"] <= 100
            assert m["expert_load_max_over_mean.train"]["value"] >= 1
            # no device trace on the CPU: the kernel readers find nothing and stay silent
            assert not any("roofline" in k or "time_share" in k for k in m)
        else:
            assert set(r["metrics"]) == {"train_graphs_per_s_per_chip", "setup_s"}
        json.dumps(r)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def pytest_fault_is_not_correct(fault):
    r = _drive(break_step=fault)
    assert r["correct"] is False, r["compared"]


def pytest_control_is_not_correct():
    """The reference in fp8, the nearest precision below the configuration's
    bfloat16, in the program's place."""
    import datagen
    import drive_train_tokens

    ctx = tiny_zaya.tiny_ctx()
    arch = dict(ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"], cca_time0=2, cca_time1=2)
    records = datagen.dataset(ctx["traffic"], common.cache_dirs()["data"], tiny_zaya.SCALE)
    steps = [[records[i * 8:(i + 1) * 8]] for i in range(3)]
    warmup = drive_train_tokens.warmup_of(ctx["traffic"])
    ref = drive_train_tokens.reference_readings("ZAYA", arch, 1, 5, steps, 1e-3, warmup_steps=warmup)
    control = drive_train_tokens.reference_readings("ZAYA", arch, 1, 5, steps, 1e-3, warmup_steps=warmup,
                                                    mode=compare.CONTROL_MODE[ctx["config"]["precision"]])
    ok, compared, _ = compare.compare(control, ref, ctx["traffic"]["limits"])
    assert not ok, compared
