"""By hand, on the CPU: the required-FLOPs functions of benchmarks/flops.py
against the dot count of run-scripts/flops_audit.py (``dot_flops_by_shape`` on
the lowered StableHLO of the program's own step) at the cells' shapes.

    JAX_PLATFORMS=cpu python benchmarks/tests/flops_check.py

The audit counts padded rows and the recompute that remat adds; the
benchmark's function counts what the algorithm requires. Both are printed on
PADDED rows, so that what is left is recompute and dots the function leaves
out (e.g. the gradient with respect to the first layer's input)."""

import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["HYDRAGNN_COMPILE_CACHE"] = "0"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny  # noqa: E402,F401  (puts benchmarks/ and the repo root on the path)
import common  # noqa: E402
import datagen  # noqa: E402
import flops  # noqa: E402
import drive_train  # noqa: E402


def audit_module():
    path = os.path.join(common.ROOT, "run-scripts", "flops_audit.py")
    spec = importlib.util.spec_from_file_location("flops_audit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(workload: str, audit) -> dict:
    import jax
    import numpy as np

    from hydragnn_tpu.api import prepare_data
    from hydragnn_tpu.data.graph import Graph
    from hydragnn_tpu.models import create_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step
    from reference import common as rc

    ctx = common.load_cell(workload)
    traffic = ctx["traffic"]
    records = datagen.dataset(traffic, common.cache_dirs()["data"], 0.25)
    parts = drive_train.split(len(records), 0.9, 0)
    graphs = drive_train.to_graphs(records, Graph)
    cfg = drive_train.program_config(ctx)
    # the kernel routes as on the chip, computed by their dense fallbacks here
    cfg["NeuralNetwork"]["Architecture"]["use_sorted_aggregation"] = True
    config, (loader, _, _), _ = prepare_data(cfg, tuple([graphs[i] for i in p] for p in parts))
    arch, training = config["NeuralNetwork"]["Architecture"], config["NeuralNetwork"]["Training"]
    import importlib

    ref = importlib.import_module(f"reference.{arch['mpnn_type'].lower()}")
    spec = ref.weight_spec(arch, int(arch["input_dim"]))
    variables = jax.eval_shape(lambda: rc.make_weights(spec, 0))
    model = create_model(config)
    tx = make_optimizer(training["Optimizer"])
    state = jax.eval_shape(lambda v: TrainState.create(v, tx), variables)
    step = make_train_step(model, tx, bool(training.get("compute_grad_energy")),
                           bool(training.get("mixed_precision")))
    batch = next(iter(loader))
    text = step.lower(state, batch, jax.random.PRNGKey(0)).as_text()
    by_shape = audit.dot_flops_by_shape(text)
    audit_total = float(sum(v[0] if isinstance(v, (tuple, list)) else v for v in by_shape.values()))
    n, e, g = (int(np.asarray(m).size) for m in (batch.node_mask, batch.edge_mask, batch.graph_mask))
    mine = flops.train_step_flops(arch, int(arch["input_dim"]), n, e, g)
    real = flops.train_step_flops(arch, int(arch["input_dim"]),
                                  int(np.asarray(batch.node_mask).sum()), int(np.asarray(batch.edge_mask).sum()),
                                  int(np.asarray(batch.graph_mask).sum()))
    return {"workload": workload, "padded_rows": [n, e, g], "audit_dot_flops": audit_total,
            "benchmark_flops_padded": mine, "benchmark_flops_real": real,
            "audit_over_benchmark": audit_total / mine,
            "real_graphs": int(np.asarray(batch.graph_mask).sum())}


if __name__ == "__main__":
    audit = audit_module()
    cells = [w["name"] for w in common.load_json(common.ROOT, "BENCHMARK.json")["workloads"]]
    for w in sys.argv[1:] or cells:
        print(json.dumps(check(w, audit)))
