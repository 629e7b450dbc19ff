"""By hand, on the chip: the user's entry, `hydragnn_tpu.run_training`, on a
cell's own completed configuration and dataset, beside the harness's window.

    python3 benchmarks/tests/entry_check.py <workload> <epochs> <out.json>

The harness drives the pieces `run_training` is made of; this shows whether
the two run the same program: the completed configuration's keys, the
loader's pad shapes, whether the entry's step is found in the compile cache
that a harness run of the same checkout filled, and the step time read from
a 3 s trace taken after the entry's first epoch (validation and test epochs
off, `HYDRAGNN_VALTEST=0`)."""

import json
import os
import sys
import threading
import time

os.environ["HYDRAGNN_VALTEST"] = "0"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402  (puts benchmarks/ and the repo root on the path)
import common  # noqa: E402
import tracing  # noqa: E402


def shapes(loader):
    specs = getattr(getattr(loader, "ladder", None), "specs", None) or [loader.spec]
    return [[s.n_nodes, s.n_edges, s.n_graphs] for s in specs]


def trace_after(first_steps: int, trace_dir: str, seconds: float = 3.0):
    """Starts a trace once the loop has run `first_steps` steps."""
    import jax

    from hydragnn_tpu.utils import tracer as tr

    def watch():
        while tr.get_regions().get("train_step", {}).get("count", 0) < first_steps:
            time.sleep(0.05)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level, options.host_tracer_level = 0, 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        time.sleep(seconds)
        jax.profiler.stop_trace()

    thread = threading.Thread(target=watch, daemon=True)
    thread.start()
    return thread


def main():
    import hydragnn_tpu
    from hydragnn_tpu.train.compile_plane import compile_metrics, install_metrics_listeners
    from hydragnn_tpu.utils import tracer as tr

    import drive_train

    workload, epochs, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    ctx = common.cell_from_files(tiny.find_cell(workload, bench), bench)
    dirs = common.cache_dirs()
    common.require_chips(1)
    harness = drive_train.setup(ctx, dirs)
    keys = lambda cfg: {sec: {k: v for k, v in cfg["NeuralNetwork"][sec].items() if not isinstance(v, (dict, list))}
                        for sec in ("Architecture", "Training")}
    report = {"workload": workload, "harness_config": keys(harness.config), "harness_shapes": shapes(harness.loader),
              "harness_steps_per_epoch": len(harness.loader)}
    steps_per_epoch = len(harness.loader)
    del harness
    _, _, datasets = drive_train.load_datasets(ctx["traffic"], dirs, 1.0)
    cfg = drive_train.program_config(ctx)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = epochs
    install_metrics_listeners()
    m0 = compile_metrics()
    trace_dir = os.path.join(dirs["trace"], "entry_check")
    watcher = trace_after(steps_per_epoch + 3, trace_dir)
    t = time.perf_counter()
    _, _, hist, config, loaders, _ = hydragnn_tpu.run_training(cfg, datasets=datasets)
    report["entry_wall_s"] = time.perf_counter() - t
    watcher.join(timeout=30)
    m1 = compile_metrics()
    regions = tr.get_regions()
    reduced = tracing.reduce_events(tracing.read_xplane(trace_dir), "jit_train_step", 1)
    report.update({
        "entry_config": keys(config), "entry_shapes": shapes(loaders[0]),
        "config_keys_that_differ": sorted(
            f"{sec}.{k}" for sec in ("Architecture", "Training")
            for k in set(report["harness_config"][sec]) | set(keys(config)[sec])
            if report["harness_config"][sec].get(k) != keys(config)[sec].get(k)),
        "entry_compile": {k: m1[k] - m0[k] for k in m1},
        "entry_regions": {k: {"count": v["count"], "total": v["total"]} for k, v in regions.items()
                          if k in ("train_step", "dataload")},
        "entry_train_loss": hist["train"],
        "entry_trace": reduced and {k: reduced[k] for k in
                                    ("window_s", "busy_s", "mosaic_s", "step_ms_p50", "steps", "device_ops")},
    })
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
