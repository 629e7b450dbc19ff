#!/usr/bin/env python
"""CI kernel-autotuning smoke (docs/TUNING.md).

Two subprocess invocations of the offline tuner CLI over one shared
tuned-table directory, interpret mode on CPU:

1. **sweep**: ``python -m hydragnn_tpu.tune`` on a tiny synthetic config
   that enables all four Pallas kernels (PNA multi-agg + sorted segment +
   fused edge + GPS flash attention) must sweep every (kernel, ladder
   level) slot and publish content-addressed entries.
2. **hit**: the identical invocation must be a 100% cache hit — zero
   fresh sweeps, every slot served from the table.

Then an in-process leg asserts the runtime consumes what the CLI wrote:
``setup_autotune`` + ``tile_plan`` must return the swept winner for a
sweep slot's exact key and emit the ``tile_plan`` choice event.

Invoked from run-scripts/ci.sh ahead of the tier-1 suite. Self-contained:
fresh interpreters, CPU JAX, scrubbed env, temp workdir (same recipe as
compile_smoke.py). Exit 0 = autotuning plane healthy.
"""

import json
import os
import re
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CONFIG = {
    "Verbosity": {"level": 1},
    "Dataset": {
        "name": "tune_smoke",
        "format": "synthetic",
        "synthetic": {"number_configurations": 48},
        "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
        "graph_features": {"name": ["s"], "dim": [1]},
    },
    "NeuralNetwork": {
        "Architecture": {
            "mpnn_type": "PNA", "radius": 2.0, "max_neighbours": 100,
            "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
            "global_attn_engine": "gps", "global_attn_heads": 2,
            "use_sorted_aggregation": True,
            "use_fused_edge_kernel": True,
            "use_flash_attention": True,
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
        "Training": {
            "num_epoch": 1, "batch_size": 8, "seed": 11,
            "num_pad_buckets": 2,
            "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
        },
    },
}

_SUMMARY_RE = re.compile(
    r"tune: (\d+) entr(?:y|ies) \((\d+) cache hit\(s\), (\d+) swept\)"
)

ALL_KERNELS = {"segment_sum", "fused_edge", "multi_agg", "flash_attention"}


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    return env


def _run_cli(workdir, cfg_path, table_dir, name):
    proc = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.tune", cfg_path,
         "--budget", "2", "--trials", "1", "--cache-dir", table_dir],
        cwd=workdir, env=_env(), capture_output=True, text=True, timeout=600,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        print(f"tune_smoke FAIL: {name} leg crashed "
              f"(rc={proc.returncode}):\n{out[-3000:]}")
        return None
    m = _SUMMARY_RE.search(out)
    if m is None:
        print(f"tune_smoke FAIL: {name} leg printed no summary line:"
              f"\n{out[-3000:]}")
        return None
    return {"entries": int(m.group(1)), "hits": int(m.group(2)),
            "swept": int(m.group(3)), "out": out}


def main():
    with tempfile.TemporaryDirectory(prefix="tune_smoke_") as workdir:
        cfg_path = os.path.join(workdir, "tune_smoke.json")
        with open(cfg_path, "w") as f:
            json.dump(_CONFIG, f)
        table_dir = os.path.join(workdir, "tuned_table")

        sweep = _run_cli(workdir, cfg_path, table_dir, "sweep")
        if sweep is None:
            return 1
        missing = {k for k in ALL_KERNELS if f"{k}:" not in sweep["out"]}
        if missing:
            print(f"tune_smoke FAIL: sweep leg never touched kernel(s) "
                  f"{sorted(missing)} — the smoke config must exercise all "
                  f"four Pallas kernels:\n{sweep['out'][-3000:]}")
            return 1
        if sweep["swept"] == 0:
            print("tune_smoke FAIL: sweep leg measured nothing "
                  f"(entries={sweep['entries']} hits={sweep['hits']}) — a "
                  "pre-populated table in a fresh tempdir is impossible")
            return 1
        n_files = len([f for f in os.listdir(table_dir)
                       if f.endswith(".json")])
        if n_files == 0:
            print("tune_smoke FAIL: sweep leg published no table entries")
            return 1

        hit = _run_cli(workdir, cfg_path, table_dir, "hit")
        if hit is None:
            return 1
        if hit["swept"] != 0 or hit["hits"] != hit["entries"]:
            print("tune_smoke FAIL: second invocation was not a 100% cache "
                  f"hit (entries={hit['entries']} hits={hit['hits']} "
                  f"swept={hit['swept']}) — the content-addressed keys "
                  "drifted between identical runs")
            return 1

        # in-process leg: the runtime consumes what the CLI wrote
        child = os.path.join(workdir, "consume.py")
        with open(child, "w") as f:
            f.write(_CONSUME.format(repo=_REPO, cfg=cfg_path,
                                    table=table_dir))
        proc = subprocess.run(
            [sys.executable, child], cwd=workdir, env=_env(),
            capture_output=True, text=True, timeout=600,
        )
        out = proc.stdout + proc.stderr
        if proc.returncode != 0 or "CONSUME_OK" not in out:
            print(f"tune_smoke FAIL: runtime-consume leg "
                  f"(rc={proc.returncode}):\n{out[-3000:]}")
            return 1

    print(f"tune_smoke OK: swept {sweep['swept']} slot(s) over 4 kernels, "
          f"second run {hit['hits']}/{hit['entries']} cache hits, runtime "
          "lookup served the swept winner")
    return 0


_CONSUME = """
import sys
sys.path.insert(0, {repo!r})
import json
from hydragnn_tpu.api import load_config, prepare_data
from hydragnn_tpu.tune import config_slots, runtime
from hydragnn_tpu.tune.table import TunedTable, device_kind
from hydragnn_tpu.tune import plans
from hydragnn_tpu.obs.events import events

config = load_config({cfg!r})
config, loaders, _ = prepare_data(config)
config["NeuralNetwork"]["Training"]["autotune"] = "cached"
config["NeuralNetwork"]["Training"]["autotune_cache_dir"] = {table!r}
out = runtime.setup_autotune(config, loaders[0], "tune_smoke")
assert out == {table!r}, out
table = runtime.active()
assert table is not None and table.size() > 0, "no table installed"
kernel, shapes, dtype = config_slots(config, loaders[0].ladder)[0]
spec = plans.KERNELS[kernel]
stored = table.lookup(kernel, spec.version, device_kind(), dtype,
                      runtime._shape_key(shapes))
assert stored is not None, "CLI entry invisible to the runtime lookup"
plan = runtime.tile_plan(kernel, shapes, dtype)
assert plan == plans.normalize(kernel, stored, shapes), (plan, stored)
evs = [e for e in events().snapshot() if e["kind"] == "tile_plan"]
assert evs and evs[-1]["source"] == "tuned", evs
print("CONSUME_OK kernel=%s plan=%s" % (kernel, json.dumps(plan)),
      flush=True)
"""


if __name__ == "__main__":
    sys.exit(main())
