"""Tests of the benchmark's own yardstick, run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

- the trace reduction against a small trace recorded on the chip;
- a CPU rehearsal of each cell's driver at a tiny size: the shape of the
  result line, `correct` true on a sound run; the four-chip traffic the
  same way on four virtual devices;
- the faults a training cell can have, planted under the timed path: a step
  that returns its state unchanged, half of the batch left out: `correct`
  comes out false;
- the control: the reference computed in the nearest precision below the
  configuration's, put in the program's place, comes out not correct.
"""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the rehearsal of the four-chip traffic
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

import tiny  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
import tracing  # noqa: E402

TRAIN_CELLS = [w["name"] for w in common.load_json(common.ROOT, "BENCHMARK.json")["workloads"]]


def _drive(workload, seconds=0.5, trace=False, seed=2**31 + 99, mixed=None, **fault):
    import importlib

    import jax

    ctx = tiny.tiny_ctx(workload)
    if mixed is not None:
        ctx["config"]["program_config"]["NeuralNetwork"]["Training"]["mixed_precision"] = mixed
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    return driver.drive(ctx, seed, seconds, trace, time.perf_counter(), jax.devices(),
                        common.cache_dirs(), scale=0.02, **fault)


# ---------------------------------------------------------------- faults

def unchanged_state(step):
    """A step that computes its loss and hands back the state it was given."""
    def broken(state, batch, rng):
        import jax
        import jax.numpy as jnp

        keep = jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), state)
        out = step(state, batch, rng)
        return (keep,) + tuple(out[1:])
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(state, batch, rng):
        import jax.numpy as jnp

        k = jnp.sum(batch.graph_mask.astype(jnp.int32)) // 2
        gm = batch.graph_mask & (jnp.arange(batch.graph_mask.shape[0]) < k)
        nm = batch.node_mask & gm[batch.node_graph]
        em = batch.edge_mask & nm[batch.receivers] & nm[batch.senders]
        return step(state, batch.replace(graph_mask=gm, node_mask=nm, edge_mask=em), rng)
    return broken


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def pytest_rehearsal_result_line(workload):
    for trace in (False, True):
        r = _drive(workload, trace=trace)
        assert list(r)[:4] == ["correct", "attempted", "failed", "metrics"]
        assert list(r)[-1] == "compared" and "device" in r
        assert r["attempted"] > 0 and r["failed"] == 0
        assert all(set(v) == {"value", "unit"} for v in r["metrics"].values())
        if trace:
            assert {"busy_s", "window_s"} <= set(r["device"])
            assert "compiles_in_window" in r["metrics"]
            assert r["metrics"]["compiles_in_window"]["value"] == 0
        else:
            assert set(r["metrics"]) == {"train_graphs_per_s_per_chip", "setup_s"}
        json.dumps(r)


def pytest_four_chip_traffic_rehearsal():
    """A cell that a later PR adds as data alone: the committed four-chip
    traffic through the program's mesh step, spelled config:traffic:chips."""
    import drive_train
    import jax

    ctx = tiny.tiny_ctx("egnn866_sc25:oc20_train_b160_dp4:4")
    ctx["config"]["program_config"]["NeuralNetwork"]["Training"]["mixed_precision"] = False
    r = drive_train.drive(ctx, 2**31 + 99, 0.5, False, time.perf_counter(), jax.devices(),
                          common.cache_dirs(), scale=0.08)
    assert r["correct"] is True, r["compared"]
    assert r["device"]["count"] == 4 and r["attempted"] > 0


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def pytest_sound_run_is_correct(workload):
    # float32 at the tiny size: a 32-wide bfloat16 step is noisier than the
    # cell's own 866-wide one, whose limits these are
    r = _drive(workload, mixed=False)
    assert r["correct"] is True, r["compared"]


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
@pytest.mark.parametrize("workload", TRAIN_CELLS)
def pytest_fault_is_not_correct(workload, fault):
    r = _drive(workload, break_step=fault, mixed=False)
    assert r["correct"] is False, r["compared"]
    failed = [k for k, v in r["compared"].items() if v["limit"] is not None and v["value"] > v["limit"]]
    assert failed, r["compared"]


# ---------------------------------------------------------------- control



@pytest.mark.parametrize("workload", TRAIN_CELLS)
def pytest_control_is_not_correct(workload):
    """The reference in the nearest lower precision, in the program's place."""
    import datagen

    ctx = tiny.tiny_ctx(workload, hidden=64, head=32)
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    traffic = ctx["traffic"]
    records = datagen.dataset(traffic, common.cache_dirs()["data"], 0.02)
    steps = [[records[i * 16:(i + 1) * 16]] for i in range(3)]
    input_dim = records[0]["x"].shape[1]
    lr = ctx["config"]["program_config"]["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]
    ref = compare.reference_readings(arch["mpnn_type"], arch, input_dim, 5, steps, lr)
    control = compare.reference_readings(arch["mpnn_type"], arch, input_dim, 5, steps, lr,
                                         mode=compare.CONTROL_MODE[ctx["config"]["precision"]])
    ok, compared, _ = compare.compare(control, ref, traffic["limits"])
    assert not ok, compared


# ---------------------------------------------------------------- trace reduction

def pytest_trace_reduction_on_recorded_trace():
    path = os.path.join(common.BENCH_DIR, "fixtures", "egnn866_oc20_train.trace_rows.json")
    fixture = common.load_json(path)
    rows = [tuple(r) for r in fixture["rows"]]
    got = tracing.reduce_events(rows, fixture["step_prefix"], 1)
    for key, want in fixture["expect"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert got["busy_s"] <= got["window_s"]
    assert got["mosaic_s"] <= got["busy_s"]
    assert [g[0] for g in got["idle_gaps"]] == fixture["expect_gap_labels"]


def pytest_union_and_gap_attribution_by_hand():
    d, h = "/device:TPU:0", "/host:CPU"
    rows = [
        (d, "XLA Ops", "fusion.1", 0, 10), (d, "XLA Ops", "edge_mosaic", 5, 10),
        (d, "XLA Ops", "fusion.2", 30, 10), (d, "XLA Modules", "jit_train_step(1)", 0, 15),
        (d, "XLA Modules", "jit_train_step(1)", 30, 10), (h, "main", "dataload", 14, 12),
    ]
    got = tracing.reduce_events(rows, "jit_train_step", 1)
    assert got["busy_s"] == pytest.approx(25e-9) and got["window_s"] == pytest.approx(40e-9)
    assert got["mosaic_s"] == pytest.approx(10e-9)
    assert got["idle_gaps"][0] == ["sum:dataload", pytest.approx(15e-9)]
    assert got["step_ms_p50"] == pytest.approx(25e-6)
