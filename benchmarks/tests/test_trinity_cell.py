"""The Trinity cell's own rehearsal, faults and control, run by hand like
`test_joyai_cell.py` (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_trinity_cell.py -q -p no:cacheprovider

Every width, the window, the vocabulary, the documents and the packing budget
shrink (`tiny_trinity.tiny_ctx`); every mechanism stays.
"""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny_trinity  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
from test_benchmark import half_batch, unchanged_state  # noqa: E402

NEW_METRICS = ("window_flash_time_share.train", "window_flash_roofline_share.train", "full_flash_time_share.train",
               "full_flash_roofline_share.train", "window_pairs_share.train", "trinity_expert_time_share.train",
               "trinity_expert_roofline_share.train", "trinity_route_time_share.train",
               "trinity_expert_rows_per_token.train", "full_flash_step_fill.train", "window_flash_step_fill.train")


def _drive(seconds=0.5, trace=False, seed=2**31 + 99, **fault):
    import drive_train_tokens_lean
    import jax

    return drive_train_tokens_lean.drive(tiny_trinity.tiny_ctx(), seed, seconds, trace, time.perf_counter(),
                                         jax.devices(), common.cache_dirs(), scale=tiny_trinity.SCALE, **fault)


def pytest_rehearsal_result_line_and_counters():
    for trace in (False, True):
        r = _drive(trace=trace)
        assert list(r)[:4] == ["correct", "attempted", "failed", "metrics"] and list(r)[-1] == "compared"
        assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True, r["compared"]
        if trace:
            m = r["metrics"]
            assert m["compiles_in_window"]["value"] == 0
            # 4 of 16 held, 4 a token: one row a (token, layer) when balanced
            assert 0.5 < m["trinity_expert_rows_per_token.train"]["value"] < 2.0
            # window 16 under documents of 5-60 tokens
            assert 30 < m["window_pairs_share.train"]["value"] < 95
            assert 0 < m["step_mfu.train"]["value"] <= 100 if "step_mfu.train" in m else True
            # no device trace on the CPU: the kernel and scope readers find nothing and stay silent
            assert not any("roofline" in k or "time_share" in k for k in m)
        else:
            assert set(r["metrics"]) == {"train_graphs_per_s_per_chip", "setup_s"}
        json.dumps(r)


def _fixture_ctx():
    """A traced window as a chip run would hand it to the readers: counters of
    the tiny cell, a reduction with both kinds of flash launch and the grouped
    product as Mosaic ops and the scopes' seconds, the v5e's peaks."""
    ctx = tiny_trinity.tiny_ctx()
    arch = dict(ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"], input_dim=1)
    expert_layers, tokens, steps = 4, 170.0, 10.0
    regions = {"count:tokens": tokens * expert_layers * steps,
               "count:expert_rows_here": tokens * expert_layers * steps * 1.1,
               "count:causal_pairs": 3000.0 * steps, "count:window_pairs": 1900.0 * steps,
               "count:expert_rows_overrun": 0.0,
               "count:flash_tiles_visited": 12.0 * steps, "count:flash_steps_scheduled": 12.0 * steps,
               "count:flash_window_tiles_visited": 7.0 * steps, "count:flash_window_steps_scheduled": 8.0 * steps}
    trace = {"window_s": 1.0, "busy_s": 0.9, "mosaic_s": 0.4, "mosaic_ops": [
        ["%hg_flash_window.3 f32[4,192,128] mosaic-custom-call", 0.08],
        ["%hg_flash_window_bwd.5 f32[4,192,128] mosaic-custom-call", 0.12],
        ["%hg_flash_attention.3 f32[4,192,128] mosaic-custom-call", 0.03],
        ["%hg_flash_attention_bwd.5 f32[4,192,128] mosaic-custom-call", 0.06],
        ["%hg_grouped_expert.7 f32[2560,32] mosaic-custom-call", 0.06],
        ["%hg_grouped_expert_bwd.9 f32[2560,64] mosaic-custom-call", 0.04]],
        "scope_s": {"hg_router": 0.02, "hg_moe_dispatch": 0.01, "hg_moe_combine": 0.015}}
    return dict(ctx, arch=arch, chips=1, trace=trace, peaks=common.peaks_for("TPU v5 lite"),
                window={"seconds": 10.0, "batches": steps, "nodes": tokens * steps, "edges": 0.0, "graphs": 60.0},
                counters={"regions": regions})


def _read(name, ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location("m", os.path.join(common.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def pytest_every_new_reader_returns_a_number_from_a_fixture_trace():
    ctx = _fixture_ctx()
    listed = {m["name"]: m for m in common.load_json(common.ROOT, "BENCHMARK.json")["per_layer"]}
    got = {}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [tiny_trinity.CELL], name
        got[name] = _read(name, ctx)
        assert got[name] is not None and got[name] > 0, name
    assert abs(got["window_flash_time_share.train"] - 100 * 0.20 / 0.9) < 1e-9
    assert abs(got["full_flash_time_share.train"] - 100 * 0.09 / 0.9) < 1e-9
    assert abs(got["trinity_expert_time_share.train"] - 100 * 0.10 / 0.9) < 1e-9
    assert abs(got["trinity_route_time_share.train"] - 100 * 0.045 / 0.9) < 1e-9
    assert abs(got["window_pairs_share.train"] - 100 * 1900 / 3000) < 1e-9
    assert abs(got["trinity_expert_rows_per_token.train"] - 1.1) < 1e-9
    assert got["full_flash_step_fill.train"] == 100.0 and abs(got["window_flash_step_fill.train"] - 87.5) < 1e-9
    for name in NEW_METRICS:
        if "roofline" in name:
            assert got[name] <= 100, name
    assert 0 < _read("step_mfu.train", ctx) <= 100
    # a program without the scopes, counters or kernel names (the parent, and
    # the other decoder cells): the readers return nothing and do not raise
    bare = dict(ctx, counters={"regions": {}}, trace={**ctx["trace"], "mosaic_ops": []})
    bare["trace"].pop("scope_s")
    for name in NEW_METRICS:
        assert _read(name, bare) is None, name
    # the readers of names another stack shares (its full launches, its expert
    # product, its routing scopes) ask for this stack's keys besides
    other = dict(ctx, arch={k: v for k, v in ctx["arch"].items() if k != "layer_types"})
    for name in NEW_METRICS:
        if not name.startswith("window_"):
            assert _read(name, other) is None, name


def pytest_kernel_work_counts_the_issues_products():
    import kernel_work_afmoe as kw

    arch = common.load_json(common.BENCH_DIR, "configs", "trinity_mini_ep16.json")["program_config"][
        "NeuralNetwork"]["Architecture"]
    assert (kw.sliding_layers(arch), kw.full_layers(arch), kw.expert_layers(arch)) == (4, 1, 4)
    flops, nbytes = kw.flash_work(arch, 1.0, 1.0, 4)
    # 7 products of 2 x 128 FLOPs a pair and query head; q, o and their cotangents at 32 heads, k, v at 4
    assert flops == 7 * 2 * 128 * 32 * 4 and nbytes == 128 * 2 * ((2 * 32 + 2 * 4) + (4 * 32 + 4 * 4)) * 4
    flops, nbytes = kw.expert_work(arch, 1.0, 0.0)
    assert flops == 18 * 2048 * 1024 and nbytes == 9 * (2048 + 1024) * 2
    assert kw.expert_work(arch, 0.0, 1.0)[1] == 9 * 8 * 4 * 2048 * 1024 * 2


def pytest_the_cell_runs_through_the_accepted_lean_driver():
    import drive_train_tokens_lean as lean
    import importlib

    traffic = common.load_json(common.BENCH_DIR, "traffic", "docs_long_t16k_v25k.json")
    assert importlib.import_module("drive_" + traffic["kind"]) is lean
    # the scopes the one scope reader of the cell asks for are among the driver's
    assert {"hg_router", "hg_moe_dispatch", "hg_moe_combine"} <= set(lean.SCOPES)
    # every limit lies between a sound reading and a fault's, and none is a WORST leaf: one near tie of a
    # frequent id between two experts moves an expert's leaves by a third (PERF.md section 6, PR 35)
    assert set(traffic["limits"]) == {"grad_gap_median", "dparam_gap_median"}


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def pytest_fault_is_not_correct(fault):
    r = _drive(break_step=fault)
    assert r["correct"] is False, r["compared"]


def pytest_control_is_not_correct():
    """The reference in fp8, the nearest precision below the configuration's
    bfloat16, in the program's place."""
    import datagen
    import drive_train_tokens
    import drive_train_tokens_lean as lean

    ctx = tiny_trinity.tiny_ctx()
    cfg = ctx["config"]["program_config"]
    arch = dict(cfg["NeuralNetwork"]["Architecture"])
    records = datagen.dataset(ctx["traffic"], common.cache_dirs()["data"], tiny_trinity.SCALE)
    steps = [[records[i * 5:(i + 1) * 5]] for i in range(3)]
    warmup = drive_train_tokens.warmup_of(ctx["traffic"])
    ref = lean.reference_readings("AFMOE", arch, 1, 5, steps, 1e-3, warmup_steps=warmup)
    control = lean.reference_readings("AFMOE", arch, 1, 5, steps, 1e-3, warmup_steps=warmup,
                                      mode=compare.CONTROL_MODE[ctx["config"]["precision"]])
    ok, compared, _ = compare.compare(control, ref, ctx["traffic"]["limits"])
    assert not ok, compared
