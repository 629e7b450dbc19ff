"""The six readers of the compile plane's counters (``metrics/start_*.py``,
``metrics/compile_seconds_in_window.py``): each on a hand-built ``ctx``, and
on the ``ctx`` of a program that counts only hits, misses and the two older
sums (the parent commit: None for what it lacks, no raise).
CPU: `python -m pytest benchmarks/tests/test_start_metrics.py`."""

import importlib.util
import os

import pytest

import tiny  # noqa: F401  (puts benchmarks/ and the repo root on the path)
import common

NEW = ["start_trace_s", "start_lower_s", "start_compile_or_fetch_s", "start_cache_fetch_s",
       "start_programs", "compile_seconds_in_window"]


def read(name, ctx):
    path = os.path.join(common.BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def hand_ctx():
    """A warm decoder start: 44 programs, and a window in which one small
    program was traced, lowered and fetched."""
    total = {"cache_hits": 41, "cache_misses": 0, "backend_compile_s": 9.5, "cache_retrieval_s": 4.75,
             "trace_s": 14.25, "lower_s": 3.5, "programs": 44}
    window = {"cache_hits": 1, "cache_misses": 0, "backend_compile_s": 0.5, "cache_retrieval_s": 0.25,
              "trace_s": 0.125, "lower_s": 0.0625, "programs": 1}
    return {"counters": {"compile_total": total, "compile": window, "time_to_first_step_s": 25.0}}


def parent_ctx():
    ctx = hand_ctx()
    for c in (ctx["counters"]["compile_total"], ctx["counters"]["compile"]):
        for key in ("trace_s", "lower_s", "programs"):
            del c[key]
    return ctx


WANT = {"start_trace_s": 14.25, "start_lower_s": 3.5, "start_compile_or_fetch_s": 9.5,
        "start_cache_fetch_s": 4.75, "start_programs": 44, "compile_seconds_in_window": 0.6875}
# the keys the parent counts are read there too; programs falls back on hits + misses
WANT_PARENT = {"start_trace_s": None, "start_lower_s": None, "start_compile_or_fetch_s": 9.5,
               "start_cache_fetch_s": 4.75, "start_programs": 41, "compile_seconds_in_window": None}


@pytest.mark.parametrize("name", NEW)
def pytest_reader_on_hand_built_ctx(name):
    assert read(name, hand_ctx()) == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def pytest_reader_on_a_program_without_the_new_counters(name):
    assert read(name, parent_ctx()) == WANT_PARENT[name]


@pytest.mark.parametrize("name", NEW)
def pytest_reader_finds_nothing_without_compile_counters(name):
    assert read(name, {"counters": {}}) is None
    assert read(name, {"counters": {"compile_total": {}, "compile": {}}}) is None


def pytest_a_quiet_window_reads_zero_beside_compiles_in_window():
    ctx = hand_ctx()
    ctx["counters"]["compile"] = {k: 0 for k in ctx["counters"]["compile"]}
    assert read("compile_seconds_in_window", ctx) == 0
    assert read("compiles_in_window", ctx) == 0


def pytest_every_new_metric_is_declared_with_a_reader():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert (m["layer"], m["moves"], m["better"]) == ("compile plane", "setup_s", "lower")
        assert "workloads" not in m  # every cell compiles
        assert os.path.exists(os.path.join(common.BENCH_DIR, "metrics", f"{name}.py"))


def pytest_the_result_line_leaves_out_what_the_program_lacks():
    """``common.read_per_layer`` on the parent's counters: the four metrics
    it can read, and not the other two."""
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    ctx = dict(parent_ctx(), bench={"per_layer": [m for m in bench["per_layer"] if m["name"] in NEW]},
               cell={"name": "egnn866_oc20_train"})
    got = common.read_per_layer(ctx)
    assert set(got) == {k for k, v in WANT_PARENT.items() if v is not None}
    assert got["start_programs"] == {"value": 41.0, "unit": "count"}
