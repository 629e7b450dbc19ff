"""Cells cut to a size the CPU holds, for the rehearsal and the fault tests:
widths and graph counts shrink, every code path stays."""

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

import common  # noqa: E402


def find_cell(workload: str, bench):
    """A cell of ``BENCHMARK.json`` by name, or one it does not list yet,
    spelled ``config:traffic:chips`` (rehearsals of cells a later PR adds)."""
    if ":" in workload:
        config, traffic, chips = workload.split(":")
        return {"name": f"{config}.{traffic}", "config": config, "traffic": traffic, "chips": int(chips)}
    return next(w for w in bench["workloads"] if w["name"] == workload)


def tiny_ctx(workload: str, hidden: int = 32, head: int = 24, batch: int = 16):
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = find_cell(workload, bench)
    ctx = copy.deepcopy(common.cell_from_files(cell, bench))
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    arch["hidden_dim"] = hidden
    for head_cfg in arch["output_heads"].values():
        head_cfg["dim_headlayers"] = [head] * len(head_cfg["dim_headlayers"])
        if "dim_sharedlayers" in head_cfg:
            head_cfg["dim_sharedlayers"] = 8
    ctx["traffic"]["training_overrides"]["batch_size"] = batch
    ctx["traffic"]["warmup_steps"] = 2
    return ctx
