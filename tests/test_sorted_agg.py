"""Sorted-segment aggregation wiring: config key -> loader edge sorting ->
model cfg -> ops dispatch (ops/segment.py segment_sum; the Pallas kernel
itself is covered by tests/test_pallas_segment.py in interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.config import update_config
from hydragnn_tpu.data import (
    GraphLoader,
    MinMax,
    VariablesOfInterest,
    deterministic_graph_dataset,
    extract_variables,
    oc20_shaped_dataset,
    split_dataset,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.ops.pallas_segment import sorted_segment_sum
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step


def _config(use_sorted):
    return {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "EGNN",
                "equivariance": True,
                "radius": 5.0,
                "max_neighbours": 10,
                "hidden_dim": 16,
                "num_conv_layers": 2,
                "use_sorted_aggregation": use_sorted,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 16,
                        "num_headlayers": 2,
                        "dim_headlayers": [16, 16],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["energy"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "batch_size": 8,
                "num_epoch": 1,
                "Optimizer": {"type": "AdamW", "learning_rate": 5e-3},
            },
        },
        "Dataset": {
            "node_features": {"dim": [1, 3]},
            "graph_features": {"dim": [1]},
        },
    }


def _graphs():
    import dataclasses

    graphs = oc20_shaped_dataset(24, mean_atoms=20, min_atoms=10, max_atoms=40,
                                 max_neighbours=10)
    out = []
    for g in graphs:
        out.append(dataclasses.replace(
            g,
            x=np.asarray(g.z, np.float32)[:, None],
            graph_y=None,
        ))
    return split_dataset(out, 0.8, seed=0)


def pytest_config_completion_measures_max_in_degree():
    tr, va, te = _graphs()
    config = update_config(_config(True), tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    top = max(
        int(np.bincount(g.receivers).max()) for g in (*tr, *va, *te)
    )
    assert arch["max_in_degree"] == top > 0


def pytest_sorted_training_converges_like_unsorted():
    tr, va, te = _graphs()
    losses = {}
    for use_sorted in (False, True):
        config = update_config(_config(use_sorted), tr, va, te)
        arch = config["NeuralNetwork"]["Architecture"]
        loader = GraphLoader(
            tr, 8, seed=0, drop_last=True,
            sort_edges=bool(arch["use_sorted_aggregation"]),
        )
        model = create_model(config)
        batch = next(iter(loader))
        if use_sorted:
            recv = np.asarray(batch.receivers)
            assert (np.diff(recv) >= 0).all(), "receivers not sorted"
        variables = init_model(model, batch, seed=0)
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        state = TrainState.create(variables, tx)
        step = make_train_step(model, tx)
        rng = jax.random.PRNGKey(0)
        seq = []
        for epoch in range(6):
            loader.set_epoch(epoch)
            for b in loader:
                rng, sub = jax.random.split(rng)
                state, tot, _ = step(state, b, sub)
            seq.append(float(tot))
        losses[use_sorted] = seq
    # both converge; edge order is semantically irrelevant so trajectories
    # agree to reduction-reorder tolerance at the first step
    for seq in losses.values():
        assert seq[-1] < seq[0]
    assert abs(losses[True][0] - losses[False][0]) < 0.05 * max(
        abs(losses[False][0]), 1e-3
    )


@pytest.mark.parametrize("mpnn_type", ["GIN", "SAGE", "SchNet", "PNA", "GAT",
                                        "CGCNN", "MFC", "PAINN", "PNAPlus",
                                        "PNAEq", "MACE"])
def pytest_sorted_agg_wired_across_models(mpnn_type):
    """Every wired conv type runs a training step with the flag on (the CPU
    backend falls back to XLA, so this pins the wiring, not the kernel)."""
    tr, va, te = _graphs()
    cfg = _config(True)
    cfg["NeuralNetwork"]["Architecture"]["mpnn_type"] = mpnn_type
    cfg["NeuralNetwork"]["Architecture"]["equivariance"] = False
    if mpnn_type == "SchNet":
        cfg["NeuralNetwork"]["Architecture"]["num_gaussians"] = 8
        cfg["NeuralNetwork"]["Architecture"]["num_filters"] = 8
    if mpnn_type == "MACE":
        cfg["NeuralNetwork"]["Architecture"].update(
            num_radial=6, max_ell=2, node_max_ell=1, correlation=2,
            hidden_dim=8,
        )
    config = update_config(cfg, tr, va, te)
    assert config["NeuralNetwork"]["Architecture"]["max_in_degree"] > 0
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True)
    model = create_model(config)
    batch = next(iter(loader))
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    step = make_train_step(model, tx)
    state, tot, _ = step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(tot))


def pytest_stale_max_in_degree_rejected():
    tr, va, te = _graphs()
    cfg = _config(True)
    cfg["NeuralNetwork"]["Architecture"]["max_in_degree"] = 1  # too small
    with pytest.raises(ValueError, match="max_in_degree"):
        update_config(cfg, tr, va, te)


def pytest_kernel_on_real_batch_layout():
    """The padded-batch edge layout (padding edges -> dummy node) satisfies
    the kernel's sortedness requirement end-to-end; real rows match XLA."""
    tr, va, te = _graphs()
    config = update_config(_config(True), tr, va, te)
    max_deg = config["NeuralNetwork"]["Architecture"]["max_in_degree"]
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True)
    batch = next(iter(loader))
    recv = jnp.asarray(batch.receivers)
    assert bool((jnp.diff(recv) >= 0).all())
    rng = np.random.default_rng(0)
    msg = jnp.asarray(rng.normal(size=(batch.num_edges, 24)).astype(np.float32))
    msg = jnp.where(batch.edge_mask[:, None], msg, 0.0)
    ref = jax.ops.segment_sum(msg, recv, num_segments=batch.num_nodes)
    out = sorted_segment_sum(
        msg, recv, batch.num_nodes, int(max_deg), interpret=True
    )
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("mpnn_type", ["PNA", "PNAPlus", "PNAEq"])
def pytest_pna_family_auto_enables_multi_agg(mpnn_type):
    """ONE knob: PNA-family configs with sorted_aggregation auto-enable the
    multi-agg route through the SAME use_fused_edge_kernel completion that
    EGNN's fused edge path follows — no extra config key, and the model
    factory threads the flag into the conv as its ``multi_agg`` switch
    (models/pna*.py; an explicit false opts out, same as EGNN)."""
    import copy

    from hydragnn_tpu.models import create_model
    from hydragnn_tpu.models.base import get_conv_ctor

    tr, va, te = _graphs()
    cfg = _config(True)
    cfg["NeuralNetwork"]["Architecture"]["mpnn_type"] = mpnn_type
    cfg["NeuralNetwork"]["Architecture"]["equivariance"] = (
        mpnn_type == "PNAEq"
    )
    config = update_config(copy.deepcopy(cfg), tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["use_fused_edge_kernel"] is True  # follows sorted-agg
    assert arch["max_in_degree"] > 0
    model = create_model(config)
    assert model.cfg.fused_edge_kernel is True
    _, ctor = get_conv_ctor(mpnn_type)
    conv = ctor(model.cfg, 16, 16, True)
    assert conv.multi_agg is True
    assert conv.sorted_agg is True and conv.max_in_degree > 0

    # explicit opt-out stays one flag too
    off = copy.deepcopy(cfg)
    off["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] = False
    done_off = update_config(off, tr, va, te)
    model_off = create_model(done_off)
    conv_off = ctor(model_off.cfg, 16, 16, True)
    assert conv_off.multi_agg is False


def pytest_sorted_agg_allowed_for_grad_energy(monkeypatch):
    """r6 inversion of the r5 guard: the sorted kernels now differentiate
    through a custom-JVP with plain-jnp tangents (ops/pallas_segment.py,
    ops/pallas_fused_edge.py), so grad-of-grad composes and energy-force
    configs get the sorted route. Config completion must (a) auto-enable
    sorted aggregation for grad-energy configs when jitting for TPU — the
    r5 completion kept them dense — and (b) accept the explicit
    combination it used to reject, with the fused flag following. The
    loss-level fused==dense proof for the energy+force objective lives in
    tests/test_fused_edge.py and the multichip dryrun."""
    tr, va, te = _graphs()
    cfg = _config(None)
    nn = cfg["NeuralNetwork"]
    nn["Training"]["compute_grad_energy"] = True
    nn["Variables_of_interest"]["output_dim"] = [1]
    nn["Variables_of_interest"]["type"] = ["node"]

    # (a) auto-default: when the initialised backend is a TPU, grad-energy
    # configs now flip sorted ON like everything else
    import hydragnn_tpu.config.config as config_mod

    monkeypatch.setattr(
        config_mod, "_jit_target_inference", lambda: (True, "test: tpu")
    )
    import copy

    nn["Architecture"].pop("use_sorted_aggregation", None)
    done = update_config(copy.deepcopy(cfg), tr, va, te)
    arch = done["NeuralNetwork"]["Architecture"]
    assert arch["use_sorted_aggregation"] is True
    assert arch["use_fused_edge_kernel"] is True
    assert arch["max_in_degree"] > 0

    # (b) the explicit combination the r5 guard rejected completes cleanly
    explicit = copy.deepcopy(cfg)
    explicit["NeuralNetwork"]["Architecture"]["use_sorted_aggregation"] = True
    done_ex = update_config(explicit, tr, va, te)
    assert done_ex["NeuralNetwork"]["Architecture"]["use_sorted_aggregation"] is True
