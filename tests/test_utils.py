"""Observability subsystem tests (SURVEY §5.1, §5.5): tracer regions, phase
timers, metric writer, walltime parsing, peak-memory stats, run logging."""

import json
import os
import time

import pytest

import numpy as np

from hydragnn_tpu.utils import (
    MetricsWriter,
    Profiler,
    Timer,
    parse_slurm_remaining,
    peak_memory_stats,
    print_timers,
    setup_log,
    tracer as tr,
)


def pytest_tracer_accumulates_regions():
    tr.reset()
    tr.enable()
    for _ in range(3):
        with tr.timer("region_a"):
            time.sleep(0.002)
    tr.start("region_b")
    tr.stop("region_b")
    regions = tr.get_regions()
    assert regions["region_a"]["count"] == 3
    assert regions["region_a"]["total"] >= 0.006
    assert regions["region_a"]["max"] >= regions["region_a"]["min"]
    assert regions["region_b"]["count"] == 1
    tr.disable()
    tr.start("after_disable")
    tr.stop("after_disable")
    assert "after_disable" not in tr.get_regions()
    tr.reset()


def pytest_tracer_reentrant_nesting():
    """start(name) on an already-open region nests (per-name stack) instead
    of overwriting the open timestamp — both stops record."""
    tr.reset()
    tr.enable()
    tr.start("outer")
    time.sleep(0.01)  # outer-only time >> inner, so the ratio check below
    tr.start("outer")  # re-entrant: nests      # is robust to sleep jitter
    time.sleep(0.002)
    tr.stop("outer")  # closes the INNER span (LIFO within the name)
    inner = tr.get_regions()["outer"]
    assert inner["count"] == 1
    assert 0.001 <= inner["total"] < 0.05, inner
    tr.stop("outer")  # closes the outer span, which contains the inner
    regions = tr.get_regions()["outer"]
    assert regions["count"] == 2
    # the outer span contains the inner sleep PLUS its own — if nesting
    # regressed to overwrite-on-start, both spans would measure ~equal
    assert regions["max"] >= 1.8 * regions["min"], regions
    # per-name stack fully unwound: another stop is a no-op
    tr.stop("outer")
    assert tr.get_regions()["outer"]["count"] == 2
    tr.reset()


def pytest_tracer_strict_annotation_lifo():
    """An out-of-nesting stop must unwind the xprof annotation stack in
    strict LIFO order — inner (still-open) annotations are closed early
    rather than exited out of order (scoped C++ objects)."""
    tr.reset()
    _ann_stack = tr._state.anns  # this thread's own stack
    tr.enable()
    tr.start("a")
    tr.start("b")
    tr.start("c")
    # annotations may be unavailable (no jax profiler) — the LIFO contract
    # is on the stack bookkeeping either way
    depth = len(_ann_stack)
    assert depth in (0, 3)
    tr.stop("a")  # out of nesting order: must pop c, b, then a
    assert len(_ann_stack) == 0
    # timing bookkeeping for the skipped names is still open and their
    # stops still record (annotations were sacrificed, not the spans)
    tr.stop("b")
    tr.stop("c")
    regions = tr.get_regions()
    assert {regions[k]["count"] for k in ("a", "b", "c")} == {1}
    # in-order close leaves one annotation popped per stop
    tr.start("x")
    tr.start("y")
    if depth:
        assert len(_ann_stack) == 2
    tr.stop("y")
    if depth:
        assert [n for n, _ in _ann_stack] == ["x"]
    tr.stop("x")
    assert len(_ann_stack) == 0
    tr.reset()


def pytest_tracer_profile_decorator_and_report(tmp_path, capsys):
    tr.reset()
    tr.enable()

    @tr.profile("decorated")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    tr.print_report()
    out = capsys.readouterr().out
    assert "decorated" in out
    path = str(tmp_path / "trace.json")
    tr.save_report(path)
    assert json.load(open(path))["decorated"]["count"] == 1
    tr.reset()


def pytest_timer_totals_and_print(capsys):
    Timer.reset()
    with Timer("phase_x"):
        time.sleep(0.002)
    t = Timer("phase_x").start()
    time.sleep(0.002)
    t.stop()
    assert Timer.totals()["phase_x"] >= 0.004
    print_timers(1)
    out = capsys.readouterr().out
    assert "phase_x" in out
    Timer.reset()


def pytest_metrics_writer_jsonl(tmp_path):
    w = MetricsWriter("run_x", path=str(tmp_path))
    w.add_scalar("loss/train", 1.5, 0)
    w.add_scalars({"loss/val": 2.5, "lr": 0.01}, 1)
    w.close()
    lines = [
        json.loads(l)
        for l in open(tmp_path / "run_x" / "scalars.jsonl")
    ]
    # schema: every record is exactly {tag: str, value: float, step: int} —
    # downstream consumers (HPO, plotting) parse on this shape
    for l in lines:
        assert set(l) == {"tag", "value", "step"}, l
        assert isinstance(l["tag"], str)
        assert isinstance(l["value"], float)
        assert isinstance(l["step"], int)
    tags = {(l["tag"], l["step"]): l["value"] for l in lines}
    assert tags[("loss/train", 0)] == 1.5
    assert tags[("loss/val", 1)] == 2.5


def pytest_metrics_writer_rank0_gating(tmp_path, monkeypatch):
    """Only process 0 writes: a non-zero rank's writer creates neither the
    run dir nor the stream, and its add_scalar is a silent no-op."""
    import jax

    monkeypatch.setattr(jax, "process_index", lambda: 1)
    w = MetricsWriter("run_r1", path=str(tmp_path))
    w.add_scalar("loss/train", 1.0, 0)
    w.add_scalars({"x": 2.0}, 1)
    w.close()
    assert not os.path.exists(tmp_path / "run_r1")
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    w0 = MetricsWriter("run_r0", path=str(tmp_path))
    w0.add_scalar("loss/train", 1.0, 0)
    w0.close()
    assert os.path.exists(tmp_path / "run_r0" / "scalars.jsonl")


def pytest_walltime_parser():
    assert parse_slurm_remaining("1-02:03:04") == 93784.0
    assert parse_slurm_remaining("02:03:04") == 7384.0
    assert parse_slurm_remaining("3:04") == 184.0
    assert parse_slurm_remaining("INVALID") is None
    assert parse_slurm_remaining("") is None
    assert parse_slurm_remaining("UNLIMITED") is None


def pytest_peak_memory_and_profiler(tmp_path):
    stats = peak_memory_stats()
    assert len(stats) >= 1
    p = Profiler({"enable": 1, "target_epoch": 0, "log_dir": str(tmp_path / "prof")})
    p.epoch_begin(0)
    import jax.numpy as jnp

    _ = (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
    p.epoch_end(0)
    # xprof trace directory created and non-empty
    found = [f for _, _, fs in os.walk(tmp_path / "prof") for f in fs]
    assert found, "no profiler trace written"


def pytest_setup_log_writes_file(tmp_path):
    logger = setup_log("logrun", path=str(tmp_path))
    logger.info("hello-world")
    text = open(tmp_path / "logrun" / "run.log").read()
    assert "hello-world" in text


def pytest_dump_testdata_env(tmp_path, monkeypatch):
    """HYDRAGNN_DUMP_TESTDATA pickles collected test predictions per rank
    (reference: train_validate_test.py:642-652)."""
    import pickle

    import numpy as np

    import hydragnn_tpu

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HYDRAGNN_DUMP_TESTDATA", "1")
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "dump_ci",
            "format": "synthetic",
            "synthetic": {"number_configurations": 40},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["sum_x_x2_x3"], "dim": [1]},
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
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 1, "batch_size": 8,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
    }
    model, state, *_ = hydragnn_tpu.run_training(cfg)
    hydragnn_tpu.run_prediction(cfg, model_state=state)
    path = tmp_path / "logs" / "testdata" / "testdata_rank0.pkl"
    assert path.is_file()
    with open(path, "rb") as f:
        blob = pickle.load(f)
    assert "sum_x_x2_x3" in blob["preds"]
    assert blob["preds"]["sum_x_x2_x3"].shape == blob["trues"]["sum_x_x2_x3"].shape


@pytest.mark.slow  # full train-loop drive: exceeds the capped fast tier; runs in the ci.sh suite
def pytest_orbax_checkpoint_roundtrip(tmp_path, monkeypatch):
    """Training.checkpoint_backend: orbax — save via CheckpointManager,
    resume ("continue") and predict restore through the same latest
    pointer (train/checkpoint.py save_model_orbax)."""
    import copy

    import numpy as np

    import hydragnn_tpu

    monkeypatch.chdir(tmp_path)
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "orbax_ci",
            "format": "synthetic",
            "synthetic": {"number_configurations": 40},
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
            "Training": {"num_epoch": 2, "batch_size": 8,
                          "checkpoint_backend": "orbax",
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
    }
    model, state, hist, cfg_out, *_ = hydragnn_tpu.run_training(cfg)
    ckpt_root = next((tmp_path / "logs").glob("*/orbax"))
    assert ckpt_root.is_dir()
    # resume restores through the orbax latest pointer
    cfg2 = copy.deepcopy(cfg)
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    _, state2, hist2, *_ = hydragnn_tpu.run_training(cfg2)
    assert len(hist2["train"]) == 2
    # prediction path (model_state=None) also restores from orbax
    tot, tasks, preds, trues = hydragnn_tpu.run_prediction(cfg_out)
    assert np.isfinite(tot)


def pytest_print_model_summary(capsys):
    """print_model dumps per-leaf shapes and the total parameter count
    (reference: print_model, model.py:289-297)."""
    import jax.numpy as jnp

    from hydragnn_tpu.utils import print_model

    variables = {
        "params": {
            "Dense_0": {"kernel": jnp.zeros((3, 4)), "bias": jnp.zeros((4,))},
            "Dense_1": {"kernel": jnp.zeros((4, 2))},
        }
    }
    total = print_model(variables, verbosity=2)
    assert total == 3 * 4 + 4 + 4 * 2
    out = capsys.readouterr().out
    assert "Total trainable parameters: 24" in out
    assert "Dense_0/kernel" in out
    # silent at low verbosity, still returns the count
    assert print_model(variables, verbosity=0) == 24
    assert "Total" not in capsys.readouterr().out


def pytest_device_prefetch_equivalence():
    """device_prefetch yields the same batches in the same order as plain
    iteration (as device arrays), surfaces producer errors, and releases its
    thread when abandoned mid-epoch."""
    import numpy as np

    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset
    from hydragnn_tpu.train.loop import device_prefetch

    graphs = deterministic_graph_dataset(24, seed=7)
    plain = list(GraphLoader(graphs, 6, seed=0))
    pre = list(device_prefetch(iter(GraphLoader(graphs, 6, seed=0)), depth=2))
    assert len(plain) == len(pre)
    for a, b in zip(plain, pre):
        np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
        np.testing.assert_array_equal(
            np.asarray(a.receivers), np.asarray(b.receivers)
        )

    def boom():
        yield plain[0]
        raise RuntimeError("producer boom")

    it = device_prefetch(boom(), depth=1)
    next(it)
    try:
        next(it)
    except RuntimeError as e:
        assert "producer boom" in str(e)
    else:
        raise AssertionError("expected producer error to surface")

    # abandoned mid-epoch: generator close must not hang
    it2 = device_prefetch(iter(GraphLoader(graphs, 6, seed=0)), depth=1)
    next(it2)
    it2.close()
