"""Whole-step share of the chip's peak: FLOPs the steps of the window require
(forward and backward matrix products on REAL nodes, edges and graphs, no
recompute; benchmarks/flops.py) over the window's time, chips and peak."""

import flops


def read(ctx):
    if not ctx["peaks"]:
        return None
    w = ctx["window"]
    need = flops.train_step_flops(ctx["arch"], int(ctx["arch"]["input_dim"]), w["nodes"], w["edges"], w["graphs"])
    return 100.0 * need / w["seconds"] / (ctx["chips"] * ctx["peaks"]["flops_per_s_bf16"])
