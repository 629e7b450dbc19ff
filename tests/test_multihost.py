"""Two-process ``jax.distributed`` CPU test: setup_distributed rendezvous,
per-host GraphLoader sharding, and cross-host collectives — the analog of
the reference CI's 2-rank Gloo mpirun tier (reference:
.github/workflows/CI.yml:63, tests run under ``mpirun -n 2``)."""

import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, __REPO__)
    import numpy as np

    # rendezvous through the framework entry point (not jax directly):
    # HYDRAGNN_COORDINATOR + WORLD_SIZE/RANK, as a launcher would set them
    from hydragnn_tpu.parallel import local_host_info, setup_distributed

    setup_distributed()
    import jax

    assert jax.process_count() == 2, jax.process_count()
    host_count, host_index = local_host_info()
    assert host_count == 2
    assert host_index == jax.process_index()

    # per-host loader sharding: each host sees a disjoint half of the data
    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset

    graphs = deterministic_graph_dataset(40, seed=5)
    loader = GraphLoader(
        graphs, batch_size=8, shuffle=True, seed=0,
        host_count=host_count, host_index=host_index,
    )
    local_idx = loader._local_indices()
    assert len(local_idx) == 20

    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray(local_idx))
    all_idx = np.sort(np.asarray(gathered).ravel())
    assert np.array_equal(all_idx, np.arange(40)), "hosts overlap or drop samples"

    # epoch reshuffle stays consistent across hosts (same seed+epoch stream)
    loader.set_epoch(3)
    e3 = multihost_utils.process_allgather(np.asarray(loader._local_indices()))
    assert np.array_equal(np.sort(np.asarray(e3).ravel()), np.arange(40))

    # packed batching lockstep across REAL processes: both hosts derive the
    # same epoch length with NO communication (each simulates every host's
    # packing, data/pipeline.py _pack_state) and iterate exactly that many
    # batches
    from hydragnn_tpu.data.synthetic import oc20_shaped_dataset

    pgraphs = oc20_shaped_dataset(60)
    pl = GraphLoader(
        pgraphs, 8, pack=True, seed=0,
        host_count=host_count, host_index=host_index,
    )
    plens = np.asarray(
        multihost_utils.process_allgather(np.asarray([len(pl)]))
    ).ravel()
    assert plens[0] == plens[1] == len(list(pl)), plens

    # cross-host max reduction used by the edge-length normalization
    from hydragnn_tpu.data.transforms import global_max_edge_attr
    from hydragnn_tpu.data.graph import Graph

    g = Graph(
        x=np.zeros((2, 1), np.float32),
        pos=np.zeros((2, 3), np.float32),
        senders=np.array([0, 1], np.int32),
        receivers=np.array([1, 0], np.int32),
        edge_attr=np.full((2, 1), 1.0 + host_index, np.float32),
    )
    mx = global_max_edge_attr([g])
    assert mx == 2.0, mx  # the max lives on host 1; host 0 must still see it

    # a real cross-host psum over the global (2-host) device set
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    arr = multihost_utils.host_local_array_to_global_array(
        np.full((8,), float(host_index + 1), np.float32), mesh, P("data")
    )
    total = jax.jit(
        lambda x: jax.numpy.sum(x),
        out_shardings=NamedSharding(mesh, P()),
    )(arr)
    # replicated output: every host reads its addressable copy
    got = float(np.asarray(total.addressable_data(0)))
    assert got == 8 * 1.0 + 8 * 2.0, got

    # end-to-end: run_training over the global 16-device (2-host) mesh —
    # host-sharded loaders, shard_map DP step, psum'd grads, rank-0 save
    from hydragnn_tpu.api import run_training

    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "mh_ci",
            "format": "synthetic",
            "synthetic": {"number_configurations": 60},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1],
                              "column_index": [0, 6, 7]},
            "graph_features": {"name": ["sum_x_x2_x3"], "dim": [1],
                               "column_index": [0]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": {"num_sharedlayers": 1,
                                            "dim_sharedlayers": 8,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [8, 8]}},
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 3, "batch_size": 16,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.02}},
        },
    }
    model, state, hist, cfg_out, loaders, mm = run_training(cfg)
    assert len(hist["train"]) == 3
    assert all(np.isfinite(v) for v in hist["train"]), hist["train"]
    assert hist["train"][-1] < hist["train"][0], hist["train"]
    # both hosts computed identical psum'd losses (lockstep check)
    agreed = multihost_utils.process_allgather(
        np.asarray(hist["train"], np.float64)
    )
    np.testing.assert_allclose(agreed[0], agreed[1], rtol=1e-6)
    # rank-0-only checkpoint
    ckpt_exists = os.path.isdir(os.path.join(os.getcwd(), "logs"))
    assert ckpt_exists == (host_index == 0), (host_index, ckpt_exists)

    # prediction localizes the device-stacked loader (per-host plain eval)
    from hydragnn_tpu.api import run_prediction

    tot, tasks, preds, trues = run_prediction(cfg_out, model_state=state)
    assert np.isfinite(tot), tot
    assert preds["sum_x_x2_x3"].shape == trues["sum_x_x2_x3"].shape
    # the prediction gather hands every host the FULL test set (reference:
    # gather_tensor_ranks all-gather of test samples). 60 configs split
    # 42/9/9; the 9-sample test split trims to 8 for two equal host shards
    # of 4 — so the gathered set must be 8, not the local 4.
    sizes = multihost_utils.process_allgather(
        np.asarray([preds["sum_x_x2_x3"].shape[0]])
    )
    sizes = np.asarray(sizes).ravel()
    assert int(sizes[0]) == int(sizes[1]) == 8, sizes
    # and the globally reduced loss agrees across hosts
    tots = np.asarray(
        multihost_utils.process_allgather(np.asarray([tot]))
    ).ravel()
    np.testing.assert_allclose(tots[0], tots[1], rtol=1e-6)

    # ragged-count gather correctness
    from hydragnn_tpu.parallel import gather_across_hosts

    ragged = {"v": np.full((3 + host_index, 2), host_index, np.float32)}
    g = gather_across_hosts(ragged)
    assert g["v"].shape == (7, 2), g["v"].shape
    assert (g["v"][:3] == 0).all() and (g["v"][3:] == 1).all()

    # end-to-end branch-parallel decoders across the 2-host mesh: with
    # branch=2 each HOST serves one branch block (its 8 rows = one branch),
    # decoder banks shard P('branch') so each host's devices hold only its
    # branch's decoder params (the MultiTaskModelMP process-group analog)
    import dataclasses
    from hydragnn_tpu.data import MinMax, VariablesOfInterest, extract_variables
    from hydragnn_tpu.data.pipeline import split_dataset

    raw = deterministic_graph_dataset(96, seed=31)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1])
    ready = [
        dataclasses.replace(extract_variables(g, voi), dataset_id=i % 2)
        for i, g in enumerate(raw)
    ]
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    gh = {"num_sharedlayers": 1, "dim_sharedlayers": 8,
          "num_headlayers": 2, "dim_headlayers": [8, 8]}
    bp_cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "mh_branch",
                    "node_features": {"name": ["x"], "dim": [1]},
                    "graph_features": {"name": ["sum_x_x2_x3"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": [
                    {"type": "branch-0", "architecture": dict(gh)},
                    {"type": "branch-1", "architecture": dict(gh)},
                ]},
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 3, "batch_size": 16,
                          "branch_parallel": True,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.02}},
        },
    }
    model, state, hist, *_ = run_training(bp_cfg, datasets=(tr, va, te))
    assert all(np.isfinite(v) for v in hist["train"]), hist["train"]
    assert hist["train"][-1] < hist["train"][0], hist["train"]
    agreed = multihost_utils.process_allgather(
        np.asarray(hist["train"], np.float64)
    )
    np.testing.assert_allclose(agreed[0], agreed[1], rtol=1e-6)
    # run_training returns the LOCALIZED state (sharded decoder banks are
    # gathered collectively by materialize_replicated): every host must now
    # hold the FULL [2, ...] banks with per-branch weights that diverged
    # (each branch trained on its own dataset). Device-level sharding
    # assertions live in tests/test_parallel.py pytest_branch_parallel_*.
    dec_banks = 0
    for k, sub in state.params.items():
        if k.startswith(("graph_shared", "heads_NN")):
            for leaf in jax.tree_util.tree_leaves(sub):
                assert leaf.shape[0] == 2, (k, leaf.shape)
                assert not np.allclose(leaf[0], leaf[1]), (
                    f"{k}: branch slices identical — branch decode not trained")
                dec_banks += 1
    assert dec_banks, "no decoder banks found"
    # and both hosts hold the SAME gathered decoder banks
    bank0 = jax.tree_util.tree_leaves(state.params["heads_NN_0"])[0]
    gathered_banks = multihost_utils.process_allgather(np.asarray(bank0))
    np.testing.assert_allclose(gathered_banks[0], gathered_banks[1], rtol=1e-6)

    print("MULTIHOST_OK", host_index)
    """
)


def pytest_two_process_distributed(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "child.py"
    script.write_text(_CHILD.replace("__REPO__", repr(_REPO)))
    procs = []
    for rank in range(2):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "HYDRAGNN_COORDINATOR": f"127.0.0.1:{port}",
            "WORLD_SIZE": "2",
            "RANK": str(rank),
            # 8 virtual devices per process -> a 16-device global mesh
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
        rank_dir = tmp_path / f"rank{rank}"
        rank_dir.mkdir()
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
                cwd=str(rank_dir),
            )
        )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
        assert f"MULTIHOST_OK {rank}" in out


def pytest_native_launcher_fanout(tmp_path):
    """The C++ ``hydragnn-launch`` binary (native/launcher.cpp) fans out 2
    local ranks with a loopback coordinator and the env contract
    setup_distributed consumes — the native setup_ddp/torchrun analog
    (reference bootstrap: distributed.py:52-198). Both ranks must
    rendezvous into one 2-process jax.distributed runtime."""
    from hydragnn_tpu.native.build import build_executable

    binary = build_executable("launcher")
    child = tmp_path / "child.py"
    child.write_text(
        textwrap.dedent(
            """
            import os, sys
            sys.path.insert(0, __REPO__)
            # the launcher must have provided the whole contract
            assert os.environ["WORLD_SIZE"] == "2"
            assert os.environ["RANK"] in ("0", "1")
            assert os.environ["HYDRAGNN_COORDINATOR"].startswith("127.0.0.1:")
            from hydragnn_tpu.parallel import setup_distributed

            setup_distributed()
            import jax

            assert jax.process_count() == 2, jax.process_count()
            # ONE atomic write: the ranks share the pipe and buffered
            # prints interleave mid-token
            os.write(1, f"LAUNCH_OK {jax.process_index()}\\n".encode())
            """
        ).replace("__REPO__", repr(_REPO))
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [binary, "--nprocs", "2", "--", sys.executable, str(child)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "LAUNCH_OK 0" in out.stdout and "LAUNCH_OK 1" in out.stdout


def pytest_native_launcher_crash_takes_group_down(tmp_path):
    """A crashing NON-first rank must take the whole fan-out down even while
    rank 0 hangs: the launcher reaps in completion order (waitpid(-1)) and
    SIGTERMs the group on the first nonzero exit. A rank-ordered reap would
    block on rank 0 forever — the deadlock this test pins (launcher.cpp
    run_local_fanout)."""
    from hydragnn_tpu.native.build import build_executable

    binary = build_executable("launcher")
    child = tmp_path / "crashy.py"
    child.write_text(
        textwrap.dedent(
            """
            import os, sys, time
            if os.environ["RANK"] == "1":
                sys.exit(7)  # crash fast
            time.sleep(600)  # rank 0 "hangs in a collective"
            """
        )
    )
    t0 = time.monotonic()
    out = subprocess.run(
        [binary, "--nprocs", "2", "--", sys.executable, str(child)],
        capture_output=True, text=True, timeout=60,
    )
    elapsed = time.monotonic() - t0
    # rc propagates the first failing rank; the hung rank 0 was SIGTERMed
    # long before its 600 s sleep
    assert out.returncode == 7, (out.returncode, out.stderr[-2000:])
    assert elapsed < 30, f"launcher blocked {elapsed:.0f}s on the hung rank"
    assert "rank 1 exited rc=7" in out.stderr


def pytest_native_launcher_scheduler_mode(tmp_path):
    """Scheduler mode: one launcher per task, world from SLURM envs,
    coordinator derived from the SLURM nodelist (bracket-range expansion
    of the first host, the distributed.py:143-159 master discovery)."""
    from hydragnn_tpu.native.build import build_executable

    binary = build_executable("launcher")
    child = tmp_path / "env_probe.py"
    child.write_text(
        "import os\n"
        "print('COORD', os.environ.get('HYDRAGNN_COORDINATOR'))\n"
        "print('WS', os.environ.get('WORLD_SIZE'), "
        "os.environ.get('RANK'))\n"
    )
    env = {**os.environ}
    env.pop("HYDRAGNN_COORDINATOR", None)
    env.update(
        SLURM_NTASKS="4", SLURM_PROCID="3",
        SLURM_JOB_NODELIST="frontier[0007-0010],frontier0044",
        HYDRAGNN_MASTER_PORT="23456",
    )
    out = subprocess.run(
        [binary, "--", sys.executable, str(child)],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "COORD frontier0007:23456" in out.stdout
    assert "WS 4 3" in out.stdout
