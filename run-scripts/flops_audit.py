#!/usr/bin/env python
"""The ONE canonical FLOPs-counting recipe for every MFU claim in this repo.

Round-4's verdict flagged that three FLOPs/graph figures coexisted (51.6,
31.1, ~34.9) with no committed script behind any of them. This tool IS the
recipe now — docs/PERFORMANCE.md and PERF.md cite it, and any number not
produced by it is marked superseded.

Recipe (definitions):
- **Step** = the full jitted training step (forward + backward + optimizer
  update), exactly what bench.py times — lowered per padding specialization
  and compiled; FLOPs are XLA's own `cost_analysis()["flops"]` of each
  compiled executable (CPU backend lowering; counts are shape-derived, so
  CPU/TPU agree on the matmul terms that dominate).
- **Total per epoch** = sum over the epoch's batches of their
  specialization's FLOPs (bench.py uses the same sum).
- **Denominator** = REAL graphs (mask-counted), not padded slots — the
  number a user's dataset pays for. The padded-slot figure is also printed
  because padding waste is a real cost axis; it is NEVER the headline.
- **Workload** = bench.py's `_production_workload` (SC25 EGNN shape) with
  the bench's default envs unless overridden on the command line; the
  attribution mode also accepts --model MACE/DimeNet cells (VERDICT r4 #3).
- **Attribution** = `stablehlo.dot_general` ops parsed from the lowered
  module, 2*prod(out_shape)*prod(contract_dims) each, grouped by shape —
  the matmul share of the total. (Elementwise/gather/scatter make up the
  remainder; XLA's optimizer may fuse but does not add or remove dots.)

Usage:
  JAX_PLATFORMS=cpu python run-scripts/flops_audit.py            # EGNN SC25
  JAX_PLATFORMS=cpu python run-scripts/flops_audit.py --model MACE
  ... --batch-size 32 --num-configs 512
Prints one JSON line (machine) after a small table (human).
"""

import argparse
import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_DOT_RE = re.compile(
    r"stablehlo\.dot_general[^\n]*?"
    r"contracting_dims\s*=\s*\[([\d, ]*)\]\s*x\s*\[[\d, ]*\][^\n]*?"
    r":\s*\(tensor<([^>]+)>,\s*tensor<([^>]+)>\)\s*->\s*tensor<([^>]+)>"
)


def _dims(tensor_sig):
    """'128x1732xf32' -> [128, 1732]"""
    return [int(d) for d in tensor_sig.split("x")[:-1]]


def dot_flops_by_shape(stablehlo_text):
    """{(lhs, rhs, out) shape-sig: flops} for every dot_general in the text."""
    out = {}
    for m in _DOT_RE.finditer(stablehlo_text):
        cdims, lhs_sig, rhs_sig, out_sig = m.groups()
        lhs = _dims(lhs_sig)
        o = _dims(out_sig)
        contract = 1
        for i in (int(c) for c in cdims.split(",") if c.strip()):
            contract *= lhs[i]
        key = f"[{'x'.join(map(str, lhs))}]*[{'x'.join(map(str, _dims(rhs_sig)))}]"
        fl = 2.0 * contract
        for d in o:
            fl *= d
        out[key] = out.get(key, 0.0) + fl
    return out


def build_workload(model_name, batch_size, num_configs):
    os.environ["BENCH_BATCH_SIZE"] = str(batch_size)
    os.environ["BENCH_CELL_BATCH_SIZE"] = str(batch_size)
    os.environ["BENCH_NUM_CONFIGS"] = str(num_configs)
    import bench

    if model_name == "EGNN":
        return bench._production_workload(None, None)
    # MACE / DimeNet A/B-matrix cells (SC25-class shapes for their family:
    # these are the heaviest reference stacks — MACEStack.py:546,
    # DIMEStack.py:305 — and the riskiest TPU mappings in the repo)
    from bench import _model_cell_workload

    return _model_cell_workload(model_name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="EGNN",
                    choices=["EGNN", "MACE", "DimeNet"])
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--num-configs", type=int, default=None)
    ap.add_argument("--top", type=int, default=8,
                    help="attribution rows to print")
    args = ap.parse_args()
    # defaults = the canonical-table recipe per model (docs/PERFORMANCE.md):
    # a bare `--model MACE` run must reproduce the documented row
    if args.batch_size is None:
        args.batch_size = 32 if args.model == "EGNN" else 16
    if args.num_configs is None:
        args.num_configs = 512 if args.model == "EGNN" else 128

    import numpy as np

    import jax

    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    config, loader = build_workload(args.model, args.batch_size,
                                    args.num_configs)
    batches = list(loader)
    model = create_model(config)
    variables = init_model(model, batches[0], seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    mp = config["NeuralNetwork"]["Training"].get("mixed_precision", True)
    step = make_train_step(model, tx, mixed_precision=mp)
    rng = jax.random.PRNGKey(0)

    total_by_spec, dots_by_spec = {}, {}
    for b in batches:
        key = (b.num_nodes, b.num_edges)
        if key in total_by_spec:
            continue
        lowered = step.lower(state, b, rng)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        total_by_spec[key] = float(cost.get("flops", 0.0))
        dots_by_spec[key] = dot_flops_by_shape(lowered.as_text())

    real = sum(int(np.asarray(b.graph_mask).sum()) for b in batches)
    padded = sum(int(b.num_graphs) for b in batches)
    nodes_real = sum(int(np.asarray(b.node_mask).sum()) for b in batches)
    nodes_pad = sum(int(b.num_nodes) for b in batches)
    total = sum(total_by_spec[(b.num_nodes, b.num_edges)] for b in batches)
    dot_total = 0.0
    dot_by_shape = {}
    for b in batches:
        for k, v in dots_by_spec[(b.num_nodes, b.num_edges)].items():
            dot_by_shape[k] = dot_by_shape.get(k, 0.0) + v
            dot_total += v

    rows = sorted(dot_by_shape.items(), key=lambda kv: -kv[1])[: args.top]
    print(f"# {args.model} fwd+bwd+opt, batch {args.batch_size}, "
          f"{len(total_by_spec)} spec(s), {real} real graphs, "
          f"node occupancy {nodes_real / nodes_pad:.1%}")
    print(f"# total {total / real / 1e9:.2f} GFLOP/real-graph "
          f"({total / padded / 1e9:.2f}/padded slot); "
          f"dot_general share {dot_total / total:.1%}")
    for k, v in rows:
        print(f"#   {v / dot_total:6.1%}  {k}")
    print(json.dumps({
        "model": args.model,
        "batch_size": args.batch_size,
        "num_configs": args.num_configs,
        "mixed_precision": bool(mp),
        "specs": len(total_by_spec),
        "real_graphs": real,
        "node_occupancy": round(nodes_real / nodes_pad, 4),
        "gflops_per_real_graph": round(total / real / 1e9, 2),
        "gflops_per_padded_slot": round(total / padded / 1e9, 2),
        "dot_share": round(dot_total / total, 4),
        "top_dots": {k: round(v / dot_total, 4) for k, v in rows},
    }))


if __name__ == "__main__":
    main()
