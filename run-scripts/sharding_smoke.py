#!/usr/bin/env python
"""CI sharding-engine smoke (docs/PARALLELISM.md "Auditing a table").

One 8-virtual-device child process drives every shipped rule preset
end-to-end through the ONE mesh-step builder (parallel/engine.py):

- **dp / zero1 / zero2 / zero3** on the single-branch setup, **branch**
  on the 2-branch routed setup — each preset trains 2 real epochs and
  its losses must be finite and decreasing.
- **zero retraces after warm-up**: the retrace sentinel's trace counts
  (train/compile_plane.py) must not move after each preset's first
  executed batch.
- **comm-bytes-per-step**: the PR 13 accounting (``collective_census``
  over the compiled HLO) for the engine step on the 2D ``(data, model)``
  mesh, compared per preset against the retired builders' call path
  (the dp.py/branch.py shims on the legacy ``(branch, data)`` mesh) —
  the engine must spend no more collective bytes than the old-builder
  baseline.
- **per-leaf sharding tables**: the inspector's (obs/sharding.py)
  grep-able ``sharding[<preset>]`` table is printed for every preset,
  and the replicated-above-threshold audit must be CLEAN under zero-3
  (and must FIRE under dp at the same threshold, proving the audit can).

Invoked from run-scripts/ci.sh. Self-contained: fresh interpreter, CPU
JAX, scrubbed env, temp workdir (same recipe as compile_smoke.py).
Exit 0 = sharding engine healthy; nonzero with a diagnostic otherwise.
"""

import json
import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import json
import warnings

import jax
import numpy as np

from hydragnn_tpu.config import update_config
from hydragnn_tpu.data import (
    GraphLoader,
    MinMax,
    VariablesOfInterest,
    deterministic_graph_dataset,
    extract_variables,
    split_dataset,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.obs import sharding as obs_sharding
from hydragnn_tpu.parallel import (
    BranchRoutedLoader,
    Objective,
    make_mesh,
    make_mesh2d,
    make_mesh_train_step,
    place_state,
    preset,
    replicate_state,
    shard_optimizer_state,
    shard_params_zero3,
)
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.compile_plane import collective_census, sentinel

# hidden 64 makes the conv kernels 16 KB: big enough that a replicated
# copy trips the audit threshold below, and a zero-3 placement must not
AUDIT_THRESHOLD = 4096
MIN_SIZE = 8


def single_branch_setup(hidden=64, batch_size=16):
    raw = deterministic_graph_dataset(80, seed=7)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest(
        [0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1]
    )
    ready = [extract_variables(g, voi) for g in raw]
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    config = {{
        "NeuralNetwork": {{
            "Architecture": {{
                "mpnn_type": "GIN", "hidden_dim": hidden,
                "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {{"graph": {{
                    "num_sharedlayers": 2, "dim_sharedlayers": 4,
                    "num_headlayers": 2, "dim_headlayers": [10, 10],
                }}}},
            }},
            "Variables_of_interest": {{
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"],
            }},
            "Training": {{
                "batch_size": batch_size, "num_epoch": 2,
                "Optimizer": {{"type": "AdamW", "learning_rate": 0.02}},
            }},
        }},
        "Dataset": {{
            "node_features": {{"dim": [1, 1, 1]}},
            "graph_features": {{"dim": [1]}},
        }},
    }}
    config = update_config(config, tr, va, te)
    loader = GraphLoader(
        tr, batch_size, seed=0, num_shards=8, drop_last=True
    )
    return config, loader


def multibranch_setup(batch_size=16):
    import dataclasses

    raw = deterministic_graph_dataset(96, seed=11)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest(
        [0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1]
    )
    ready = [
        dataclasses.replace(extract_variables(g, voi), dataset_id=i % 2)
        for i, g in enumerate(raw)
    ]
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    gh = {{"num_sharedlayers": 1, "dim_sharedlayers": 8,
          "num_headlayers": 2, "dim_headlayers": [10, 10]}}
    config = {{
        "NeuralNetwork": {{
            "Architecture": {{
                "mpnn_type": "GIN", "hidden_dim": 8,
                "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {{"graph": [
                    {{"type": "branch-0", "architecture": dict(gh)}},
                    {{"type": "branch-1", "architecture": dict(gh)}},
                ]}},
            }},
            "Variables_of_interest": {{
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"],
            }},
            "Training": {{
                "batch_size": batch_size, "num_epoch": 2,
                "Optimizer": {{"type": "AdamW", "learning_rate": 0.02}},
            }},
        }},
        "Dataset": {{
            "node_features": {{"dim": [1, 1, 1]}},
            "graph_features": {{"dim": [1]}},
        }},
    }}
    config = update_config(config, tr, va, te)
    loader = BranchRoutedLoader(
        tr, batch_size=batch_size, branch_count=2, num_shards=8
    )
    return config, loader


def fresh(variables, tx):
    # donated steps delete their inputs; each leg gets its own buffers
    return TrainState.create(
        jax.tree_util.tree_map(np.array, variables), tx
    )


def census_bytes(jitted, *args):
    census = collective_census(jitted.lower(*args).compile().as_text())
    return census, int(sum(e["bytes"] for e in census.values()))


def legacy_step_and_state(name, model, tx, variables, loader):
    # the retired builders' exact call path: the dp.py/branch.py shims on
    # the legacy (branch, data) mesh — the recorded old-builder baseline
    # (bit-identity vs the engine is asserted in tests/test_sharding_rules.py)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        if name == "branch":
            from hydragnn_tpu.parallel.branch import (
                make_branch_parallel_train_step,
                place_branch_state,
            )

            mesh = make_mesh(branch_size=2)
            step = make_branch_parallel_train_step(model, tx, mesh)
            state = place_branch_state(fresh(variables, tx), tx, mesh)
            return step, state
        from hydragnn_tpu.parallel.dp import make_parallel_train_step

        mesh = make_mesh()
        step = make_parallel_train_step(
            model, tx, mesh,
            zero2=name in ("zero2", "zero3"), zero2_min_size=MIN_SIZE,
            zero3=name == "zero3",
        )
        state = replicate_state(fresh(variables, tx), mesh)
        if name in ("zero1", "zero2", "zero3"):
            state = state.replace(opt_state=shard_optimizer_state(
                state.opt_state, mesh, min_size=MIN_SIZE
            ))
        if name == "zero3":
            state = state.replace(params=shard_params_zero3(
                state.params, mesh, min_size=MIN_SIZE
            ))
        return step, state


def run_preset(name, config, loader):
    model = create_model(config)
    one = jax.tree_util.tree_map(
        lambda x: np.asarray(x)[0], next(iter(loader))
    )
    variables = init_model(model, one, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    batch = next(iter(loader))
    rng = jax.random.PRNGKey(0)

    # old-builder baseline comm bytes (shim call path, legacy mesh)
    legacy_step, s_legacy = legacy_step_and_state(
        name, model, tx, variables, loader
    )
    _, legacy_bytes = census_bytes(legacy_step, s_legacy, batch, rng)

    # the engine on the 2D (data, model) mesh
    routed = name == "branch"
    mesh = make_mesh2d(model_size=2 if routed else 1)
    table = (
        preset(name, num_branches=2) if routed
        else preset(name, min_size=MIN_SIZE)
    )
    step = make_mesh_train_step(Objective(model=model, tx=tx), table, mesh)
    state = place_state(fresh(variables, tx), table, mesh)
    census, engine_bytes = census_bytes(step, state, batch, rng)

    # end-to-end: first batch is warm-up, then the sentinel's trace
    # counts must not move — a retrace here is a silent recompile
    loader.set_epoch(0)
    it = iter(loader)
    rng, sub = jax.random.split(rng)
    state, first, _ = step(state, next(it), sub)
    counts0 = dict(sentinel().counts())
    losses = [float(first)]
    for batch2 in it:
        rng, sub = jax.random.split(rng)
        state, tot, _ = step(state, batch2, sub)
        losses.append(float(tot))
    loader.set_epoch(1)
    for batch2 in loader:
        rng, sub = jax.random.split(rng)
        state, tot, _ = step(state, batch2, sub)
        losses.append(float(tot))
    retraces = sum(dict(sentinel().counts()).values()) - sum(
        counts0.values()
    )

    # per-leaf sharding table + replicated-above-threshold audit
    report = obs_sharding.inspect_state(
        state, threshold_bytes=AUDIT_THRESHOLD, label=name, mesh=mesh
    )
    obs_sharding.record(report, emit_events=False)
    print(obs_sharding.format_report(report, leaves=True), flush=True)

    return {{
        "engine_bytes": engine_bytes,
        "legacy_bytes": legacy_bytes,
        "collectives": {{
            k: {{"count": int(v["count"]), "bytes": int(v["bytes"])}}
            for k, v in sorted(census.items())
        }},
        "losses_first": losses[0],
        "losses_last": losses[-1],
        "finite": bool(np.all(np.isfinite(losses))),
        "decreased": bool(losses[-1] < losses[0]),
        "retraces_after_warmup": int(retraces),
        "audit_warnings": len(report["audit"]),
        "sharded_leaves": report["summary"]["sharded_leaves"],
        "replicated_bytes": report["summary"]["replicated_bytes"],
        "per_device_bytes": report["summary"]["per_device_bytes"],
    }}


results = {{}}
config, loader = single_branch_setup()
for name in ("dp", "zero1", "zero2", "zero3"):
    results[name] = run_preset(name, config, loader)
config, loader = multibranch_setup()
results["branch"] = run_preset("branch", config, loader)
print("RESULT " + json.dumps(results), flush=True)
"""


def _env(workdir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [_REPO] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    return env


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="sharding_smoke_")
    script = os.path.join(workdir, "child.py")
    with open(script, "w") as f:
        f.write(_CHILD.format(repo=_REPO))
    proc = subprocess.run(
        [sys.executable, script], cwd=workdir, env=_env(workdir),
        capture_output=True, text=True, timeout=600,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        print(f"sharding_smoke FAIL: child crashed (rc={proc.returncode}):"
              f"\n{out[-4000:]}")
        return 1
    result_line = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            result_line = line[len("RESULT "):]
    if result_line is None:
        print(f"sharding_smoke FAIL: child printed no RESULT line:"
              f"\n{out[-4000:]}")
        return 1
    results = json.loads(result_line)

    ok = True

    def fail(msg):
        nonlocal ok
        ok = False
        print(f"sharding_smoke FAIL: {msg}")

    for name in ("dp", "zero1", "zero2", "zero3", "branch"):
        r = results.get(name)
        if r is None:
            fail(f"preset {name} produced no result")
            continue
        if not r["finite"]:
            fail(f"{name}: non-finite train loss")
        if not r["decreased"]:
            fail(f"{name}: loss did not decrease "
                 f"({r['losses_first']} -> {r['losses_last']})")
        if r["retraces_after_warmup"] != 0:
            fail(f"{name}: {r['retraces_after_warmup']} retraces after "
                 "warm-up — a silent recompile slipped into the engine step")
        if r["engine_bytes"] > r["legacy_bytes"]:
            fail(f"{name}: engine comm bytes {r['engine_bytes']} exceed "
                 f"the old-builder baseline {r['legacy_bytes']}")
    for name in ("zero2", "zero3", "branch"):
        if name in results and results[name]["sharded_leaves"] == 0:
            fail(f"{name}: no leaf ended up sharded")
    # the audit threshold is calibrated so dp's replicated kernels trip it
    # (the audit CAN fire) and zero-3's sharded placement must not
    if "dp" in results and results["dp"]["audit_warnings"] == 0:
        fail("dp: replicated-above-threshold audit found nothing — the "
             "audit threshold is no longer exercising the inspector")
    if "zero3" in results and results["zero3"]["audit_warnings"] != 0:
        fail(f"zero3: {results['zero3']['audit_warnings']} replicated-"
             "above-threshold audit findings — a leaf fell off the "
             "ZeRO-3 rule path")

    print(json.dumps({
        "metric": "sharding-engine smoke (per-preset comm bytes vs "
                  "old-builder baseline; zero retraces; zero-3 audit)",
        "presets": {
            name: {
                "comm_bytes": r["engine_bytes"],
                "baseline_bytes": r["legacy_bytes"],
                "collectives": r["collectives"],
                "sharded_leaves": r["sharded_leaves"],
                "replicated_bytes": r["replicated_bytes"],
                "audit_warnings": r["audit_warnings"],
            }
            for name, r in results.items()
        },
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
