"""The readers of the program's regions and kernel names (``metrics/*.py``
added with the span recorder): each on a hand-built ``ctx``, on a ``ctx`` of
a program that has none of the regions or names (None, no raise), and on a
recorded trace of one traced run of the packed cell, host rows included.
CPU: `python -m pytest benchmarks/tests/test_span_metrics.py`."""

import importlib.util
import os

import pytest

import tiny  # noqa: F401  (puts benchmarks/ and the repo root on the path)
import common
import tracing

NEW = [
    "batch_build_ms_per_step.train", "h2d_stage_ms_per_step.train", "epoch_restart_share.train",
    "dispatch_ms_per_step.train", "host_loop_unaccounted_share.train", "fused_edge_time_share.train",
    "sorted_segment_time_share.train", "pallas_named_share.train",
]
FIXTURE = os.path.join(common.BENCH_DIR, "fixtures", "egnn866_oc20_train.named.trace_rows.json")


def read(name, ctx):
    path = os.path.join(common.BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def hand_ctx():
    """A window of 10 s, 40 steps, 2 epochs; a traced span of 2 s busy."""
    return {
        "chips": 1,
        "window": {"seconds": 10.0, "batches": 40, "epochs": 2},
        "counters": {"regions": {
            "dataload": 0.3, "rng_split": 4.0, "train_step": 0.2, "dispatch": 0.08, "epoch_drain": 5.4,
            "epoch_restart": 0.12, "batch_build": 1.0, "h2d_stage": 0.1}},
        "trace": {
            "busy_s": 2.0, "mosaic_s": 0.4,
            "mosaic_ops": [["%hg_fused_edge.1 f32[128,896] mosaic-custom-call", 0.25],
                           ["%hg_sorted_segment.3 f32[128,1024] mosaic-custom-call", 0.1],
                           ["%hg_sorted_segment.7 f32[128,1024] mosaic-custom-call", 0.03],
                           ["%edge_lin2.1 f32[128,896] mosaic-custom-call", 0.02]]},
    }


WANT = {
    "batch_build_ms_per_step.train": 25.0, "h2d_stage_ms_per_step.train": 2.5,
    "epoch_restart_share.train": 1.2, "dispatch_ms_per_step.train": 2.0,
    "host_loop_unaccounted_share.train": 1.0, "fused_edge_time_share.train": 12.5,
    "sorted_segment_time_share.train": 6.5, "pallas_named_share.train": 95.0,
}


@pytest.mark.parametrize("name", NEW)
def pytest_reader_on_hand_built_ctx(name):
    assert read(name, hand_ctx()) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def pytest_reader_finds_nothing_on_a_program_without_the_names(name):
    """The parent commit: `dataload` and `train_step` alone, kernels under
    their Flax module's name; and a run with no trace at all."""
    ctx = hand_ctx()
    ctx["counters"]["regions"] = {"dataload": 0.3, "train_step": 0.2}
    ctx["trace"]["mosaic_ops"] = [["%edge_lin2.1 f32[128,896] mosaic-custom-call", 0.4]]
    assert read(name, ctx) is None
    ctx["trace"] = None
    assert read(name, ctx) is None


def pytest_every_new_metric_is_declared_with_a_reader():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert declared[name]["moves"] == "train_graphs_per_s_per_chip"
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", f"{name}.py"))
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW  # appended, nothing moved


def fixture_ctx():
    """A ``ctx`` as ``drive_train`` builds it, from the recorded rows alone:
    the reduction of the device rows, and the regions summed from the host
    rows (the window is the recorded span)."""
    fixture = common.load_json(FIXTURE)
    rows = [tuple(r) for r in fixture["rows"]]
    trace = tracing.reduce_events(rows, fixture["step_prefix"], 1)
    regions, dispatched = {}, 0
    for plane, _, name, _, dur in rows:
        if not plane.startswith("/device:") and not name.startswith("PjitFunction"):
            regions[name] = regions.get(name, 0.0) + dur / 1e9
            dispatched += name == "dispatch"
    return fixture, {
        "chips": 1, "trace": trace, "counters": {"regions": regions},
        "window": {"seconds": trace["window_s"], "batches": dispatched, "epochs": 1},
    }


def pytest_readers_on_recorded_trace():
    fixture, ctx = fixture_ctx()
    got = {name: read(name, ctx) for name in NEW}
    for name, want in fixture["expect_metrics"].items():
        assert got[name] == pytest.approx(want, rel=1e-9), name
    assert set(fixture["expect_metrics"]) == set(NEW)
    # what the names are for: every Mosaic op is named, and the two kernels'
    # shares add up to the whole Mosaic share of busy time
    assert got["pallas_named_share.train"] == pytest.approx(100.0)
    whole = read("pallas_time_share.train", ctx)
    assert got["fused_edge_time_share.train"] + got["sorted_segment_time_share.train"] == pytest.approx(whole, abs=0.2)
    # host rows of all three threads are in the recording; it opens inside an
    # `epoch_drain` (an annotation open when the profiler starts is not
    # recorded), so the main thread's accounting has nothing to read there
    assert {"batch_build", "h2d_stage", "dispatch", "train_step", "dataload", "rng_split",
            "epoch_restart"} <= set(ctx["counters"]["regions"])
    assert "epoch_drain" not in ctx["counters"]["regions"]
    assert got["host_loop_unaccounted_share.train"] is None
