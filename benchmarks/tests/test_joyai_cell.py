"""The JOYAI cell's own rehearsal, faults and control, run by hand like
`test_zaya_cell.py` (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_joyai_cell.py -q -p no:cacheprovider

Every width, the vocabulary, the documents and the packing budget shrink
(`tiny_joyai.tiny_ctx`); every mechanism stays.
"""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny_joyai  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
from test_benchmark import half_batch, unchanged_state  # noqa: E402

NEW_METRICS = ("mla_flash_time_share.train", "mla_flash_roofline_share.train", "topk_expert_time_share.train",
               "topk_expert_roofline_share.train", "moe_route_time_share.train", "mtp_time_share.train",
               "expert_rows_per_token.train", "topk_expert_load_max_over_mean.train")


def _drive(seconds=0.5, trace=False, seed=2**31 + 99, **fault):
    import drive_train_tokens_lean
    import jax

    return drive_train_tokens_lean.drive(tiny_joyai.tiny_ctx(), seed, seconds, trace, time.perf_counter(),
                                         jax.devices(), common.cache_dirs(), scale=tiny_joyai.SCALE, **fault)


def pytest_rehearsal_result_line_and_counters():
    for trace in (False, True):
        r = _drive(trace=trace)
        assert list(r)[:4] == ["correct", "attempted", "failed", "metrics"] and list(r)[-1] == "compared"
        assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True, r["compared"]
        if trace:
            m = r["metrics"]
            assert m["compiles_in_window"]["value"] == 0
            # 4 of 16 held, 4 a token: one row a (token, layer) when balanced
            assert 0.5 < m["expert_rows_per_token.train"]["value"] < 2.0
            assert m["topk_expert_load_max_over_mean.train"]["value"] >= 1
            # no device trace on the CPU: the kernel and scope readers find nothing and stay silent
            assert not any("roofline" in k or "time_share" in k for k in m)
        else:
            assert set(r["metrics"]) == {"train_graphs_per_s_per_chip", "setup_s"}
        json.dumps(r)


def _fixture_ctx():
    """A traced window as a chip run would hand it to the readers: counters of
    the tiny cell, a reduction with both kernels' Mosaic ops and the scopes'
    seconds, the v5e's peaks."""
    ctx = tiny_joyai.tiny_ctx()
    arch = dict(ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"], input_dim=1)
    layers, tokens, steps = 3, 150.0, 10.0
    regions = {"count:tokens": tokens * layers * steps, "count:expert_rows_here": tokens * layers * steps * 1.1,
               "count:expert_load_max": 60.0 * layers * steps, "count:expert_load_mean": 41.0 * layers * steps,
               "count:causal_pairs": 1400.0 * steps, "count:expert_rows_overrun": 0.0}
    trace = {"window_s": 1.0, "busy_s": 0.9, "mosaic_s": 0.3, "mosaic_ops": [
        ["%hg_flash_attention.3 f32[4,160,24] mosaic-custom-call", 0.1],
        ["%hg_flash_attention_bwd.5 f32[4,160,24] mosaic-custom-call", 0.1],
        ["%hg_grouped_expert.7 f32[2560,32] mosaic-custom-call", 0.06],
        ["%hg_grouped_expert_bwd.9 f32[2560,64] mosaic-custom-call", 0.04]],
        "scope_s": {"hg_router": 0.02, "hg_moe_dispatch": 0.01, "hg_moe_combine": 0.015, "hg_mtp": 0.2}}
    return dict(ctx, arch=arch, chips=1, trace=trace, peaks=common.peaks_for("TPU v5 lite"),
                window={"seconds": 10.0, "batches": steps, "nodes": tokens * steps, "edges": 0.0, "graphs": 100.0},
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
        assert listed[name]["workloads"] == [tiny_joyai.CELL], name
        got[name] = _read(name, ctx)
        assert got[name] is not None and got[name] > 0, name
    assert abs(got["moe_route_time_share.train"] - 100 * 0.045 / 0.9) < 1e-9
    assert abs(got["mtp_time_share.train"] - 100 * 0.2 / 0.9) < 1e-9
    assert abs(got["mla_flash_time_share.train"] - 100 * 0.2 / 0.9) < 1e-9
    assert abs(got["expert_rows_per_token.train"] - 1.1) < 1e-9
    assert got["mla_flash_roofline_share.train"] <= 100 and got["topk_expert_roofline_share.train"] <= 100
    assert 0 < _read("step_mfu.train", ctx) <= 100
    # a program without the scopes or counters (the parent): the readers return nothing and do not raise
    bare = dict(ctx, counters={"regions": {}}, trace={**ctx["trace"], "mosaic_ops": []})
    bare["trace"].pop("scope_s")
    for name in NEW_METRICS:
        assert _read(name, bare) is None, name


def pytest_kernel_work_counts_the_issues_products():
    import kernel_work_joyai as kw

    arch = common.load_json(common.BENCH_DIR, "configs", "joyai_flash_ep16.json")["program_config"][
        "NeuralNetwork"]["Architecture"]
    flops, nbytes = kw.mla_flash_work(arch, 1.0, 1.0)
    assert flops == (8 * 192 + 6 * 128) * 32 * 6 and nbytes == (6 * 192 + 6 * 128) * 2 * 32 * 6
    flops, nbytes = kw.topk_expert_work(arch, 1.0, 0.0)
    assert flops == 18 * 2048 * 768 and nbytes == 9 * (2048 + 768) * 2
    assert kw.topk_expert_work(arch, 0.0, 1.0)[1] == 9 * 16 * 5 * 2048 * 768 * 2


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

    ctx = tiny_joyai.tiny_ctx()
    cfg = ctx["config"]["program_config"]
    arch = dict(cfg["NeuralNetwork"]["Architecture"], rope_theta=32.0e6)
    records = datagen.dataset(ctx["traffic"], common.cache_dirs()["data"], tiny_joyai.SCALE)
    steps = [[records[i * 8:(i + 1) * 8]] for i in range(3)]
    warmup = drive_train_tokens.warmup_of(ctx["traffic"])
    ref = lean.reference_readings("JOYAI", arch, 1, 5, steps, 1e-3, warmup_steps=warmup)
    control = lean.reference_readings("JOYAI", arch, 1, 5, steps, 1e-3, warmup_steps=warmup,
                                      mode=compare.CONTROL_MODE[ctx["config"]["precision"]])
    ok, compared, _ = compare.compare(control, ref, ctx["traffic"]["limits"])
    assert not ok, compared
