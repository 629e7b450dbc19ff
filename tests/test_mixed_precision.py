"""Mixed-precision (bf16 compute, f32 master weights) training path.

The TPU MXU is bfloat16-native; ``Training.mixed_precision`` casts params
and input channels to bf16 inside the differentiated step while the
optimizer state, gradients, and batch-norm running statistics stay f32
(train/loop.py make_train_step). These tests pin the contract: training
still converges, and every persistent array remains f32.
"""

import jax

import pytest
import jax.numpy as jnp
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
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train.loop import (
    cast_batch_bf16,
    cast_floats,
    make_eval_step,
    make_train_step,
)


def _setup(mpnn_type="PNA", hidden=16):
    raw = deterministic_graph_dataset(64, seed=97)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["t"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in raw]
    tr, va, te = split_dataset(ready, 0.8, seed=0)
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": mpnn_type,
                "hidden_dim": hidden,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": hidden,
                        "num_headlayers": 2,
                        "dim_headlayers": [hidden, hidden],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["t"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "batch_size": 16,
                "num_epoch": 1,
                "Optimizer": {"type": "AdamW", "learning_rate": 5e-3},
            },
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]}, "graph_features": {"dim": [1]}},
    }
    config = update_config(config, tr, va, te)
    loader = GraphLoader(tr, 16, seed=0, drop_last=True)
    model = create_model(config)
    batch = next(iter(loader))
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    return model, tx, state, loader


def pytest_mixed_precision_converges_and_keeps_f32_master():
    model, tx, state, loader = _setup()
    step = make_train_step(model, tx, mixed_precision=True)
    rng = jax.random.PRNGKey(0)
    losses = []
    for epoch in range(8):
        loader.set_epoch(epoch)
        for batch in loader:
            rng, sub = jax.random.split(rng)
            state, tot, _ = step(state, batch, sub)
        losses.append(float(tot))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses
    # persistent state stays f32: master params, optimizer state, BN stats
    for leaf in jax.tree_util.tree_leaves(state.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype
    for leaf in jax.tree_util.tree_leaves(state.batch_stats):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype


def pytest_mixed_precision_matches_f32_closely():
    """One step of bf16-compute training tracks the f32 step: same sign and
    magnitude of the loss, parameters within bf16 tolerance."""
    model, tx, state, loader = _setup()
    batch = next(iter(loader))
    rng = jax.random.PRNGKey(1)
    step32 = make_train_step(model, tx, mixed_precision=False)
    step16 = make_train_step(model, tx, mixed_precision=True)
    # donated buffers: run each step from a fresh copy of the state
    s32 = jax.tree_util.tree_map(jnp.copy, state)
    s16 = jax.tree_util.tree_map(jnp.copy, state)
    s32, tot32, _ = step32(s32, batch, rng)
    s16, tot16, _ = step16(s16, batch, rng)
    assert abs(float(tot32) - float(tot16)) < 0.05 * max(abs(float(tot32)), 1e-3)
    p32 = jax.tree_util.tree_leaves(s32.params)
    p16 = jax.tree_util.tree_leaves(s16.params)
    for a, b in zip(p32, p16):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=0.05, rtol=0.1
        )


def pytest_mixed_precision_eval_step():
    model, tx, state, loader = _setup()
    evalf = make_eval_step(model, mixed_precision=True)
    tot, tasks, outputs = evalf(state, next(iter(loader)))
    assert np.isfinite(float(tot))


def pytest_cast_helpers():
    batch = None
    tree = {"a": jnp.ones((2, 2), jnp.float32), "b": jnp.ones((2,), jnp.int32)}
    lo = cast_floats(tree, jnp.bfloat16)
    assert lo["a"].dtype == jnp.bfloat16 and lo["b"].dtype == jnp.int32
    hi = cast_floats(lo, jnp.float32)
    assert hi["a"].dtype == jnp.float32


@pytest.mark.slow  # full train-loop drive: exceeds the capped fast tier; runs in the ci.sh suite
def pytest_mixed_precision_checkpoint_resume(tmp_path, monkeypatch):
    """bf16-trained state checkpoints and resumes (Training.continue) with
    f32 master weights intact."""
    import os

    import hydragnn_tpu

    monkeypatch.chdir(tmp_path)
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "mp_resume",
            "format": "synthetic",
            "synthetic": {"number_configurations": 40},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["sum_x_x2_x3"], "dim": [1]},
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
            "Training": {"num_epoch": 2, "batch_size": 8,
                          "mixed_precision": True,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
    }
    model, state, hist, cfg_out, *_ = hydragnn_tpu.run_training(cfg)
    assert os.path.isdir("logs")
    # resume: same config + continue -> restores and keeps training
    import copy

    cfg2 = copy.deepcopy(cfg)
    cfg2["NeuralNetwork"]["Training"]["continue"] = 1
    model2, state2, hist2, *_ = hydragnn_tpu.run_training(cfg2)
    assert len(hist2["train"]) == 2
    for leaf in jax.tree_util.tree_leaves(state2.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32


def pytest_dimenet_bf16_jitted_grads_finite():
    """Regression: the r5 live-TPU A/B matrix trained the DimeNet cell to
    NaN under mixed precision (logs/ab_matrix.jsonl r5) while eager grads
    were finite. Padding edges carry eps-clamped ~1e-6 lengths; the upward
    spherical-Bessel recurrence amplifies rounding error to ~1e38 on those
    rows, padding triplets gather them (compute_triplets_np pads with the
    last edge slot), and XLA's fused backward turns the masked-inf pattern
    into 0*inf = NaN — only under jit. spherical_basis(edge_mask=...) now
    evaluates padding rows at a safe mid-range distance and zeroes them, so
    the garbage never exists. This test jits the exact failing construct on
    a triplet-padded batch and asserts every gradient leaf is finite."""
    raw = deterministic_graph_dataset(32, seed=97)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["t"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in raw]
    tr, va, te = split_dataset(ready, 0.8, seed=0)
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "DimeNet",
                "hidden_dim": 16,
                "num_conv_layers": 1,
                "num_radial": 6,
                "num_spherical": 7,
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
                "output_names": ["t"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "batch_size": 16,
                "num_epoch": 1,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]}, "graph_features": {"dim": [1]}},
    }
    config = update_config(config, tr, va, te)
    loader = GraphLoader(tr, 16, seed=0, drop_last=True, with_triplets=True)
    model = create_model(config)
    batch = next(iter(loader))
    # the trigger requires padding: both padding edges and padding triplets
    assert not bool(np.asarray(batch.edge_mask).all())
    assert not bool(np.asarray(batch.trip_mask).all())
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    step = make_train_step(model, tx, mixed_precision=True)
    rng = jax.random.PRNGKey(0)
    for i in range(3):
        state, tot, _ = step(state, batch, jax.random.fold_in(rng, i))
        assert np.isfinite(float(tot)), f"loss non-finite at step {i}"
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        assert bool(jnp.isfinite(leaf).all()), (
            f"non-finite params after bf16 steps: {jax.tree_util.keystr(path)}"
        )


# ---------------------------------------------------------------------------
# dtype discipline of the conv stack under mixed precision: features in the
# compute dtype, coordinates in float32 (models/layers.py
# pair_message_factored casts an edge term to the feature stream's dtype)
# ---------------------------------------------------------------------------


def _edge_stack(mpnn_type, equivariance, grad_energy, edge_lengths=False):
    """A 4-layer ``pair_message_factored`` stack on receiver-sorted
    OC20-shaped batches (tests/test_fused_edge.py's fixtures), kernels routed
    as config completion routes them. ``edge_lengths`` stores each edge's
    length as a one-column ``edge_attr`` (CGCNN's only edge term)."""
    import copy
    import dataclasses

    import test_fused_edge as tfe

    tr, va, te = tfe._shaped_graphs()
    config = copy.deepcopy(tfe._egnn_config(equivariance, grad_energy))
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(mpnn_type=mpnn_type, num_conv_layers=4)
    if edge_lengths:
        config["Dataset"]["edge_features"] = ["lengths"]
        tr, va, te = (
            [dataclasses.replace(g, edge_attr=np.linalg.norm(
                g.pos[g.senders] - g.pos[g.receivers], axis=1,
                keepdims=True).astype(np.float32)) for g in part]
            for part in (tr, va, te))
    config = update_config(config, tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True,
                         max_in_degree=arch.get("max_in_degree", 0))
    batch = next(iter(loader))
    model = create_model(config)
    return config, model, init_model(model, batch, seed=0), batch


@pytest.mark.parametrize(
    "mpnn_type,equivariance,grad_energy",
    [
        # the benchmark's model: layers 0-2 displace coordinates (f32 after
        # layer 0), layer 3 takes the fused-edge route
        ("EGNN", True, False),
        # every layer fused; positions arrive in bf16 and stay
        ("EGNN", False, False),
        # autograd forces: mp_cast keeps f32 positions, so the length is f32
        # from layer 0 on
        ("EGNN", False, True),
        # the other ``pair_message_factored`` stack that holds the rule: its
        # edge term is a stored ``edge_attr``, which mp_cast casts
        ("CGCNN", False, False),
    ],
)
def pytest_mp_conv_stack_features_bf16_coordinates_f32(
        mpnn_type, equivariance, grad_energy):
    from flax.traverse_util import flatten_dict

    from hydragnn_tpu.train.loop import mp_cast

    config, model, variables, batch = _edge_stack(
        mpnn_type, equivariance, grad_energy,
        edge_lengths=mpnn_type == "CGCNN")
    # the fused-edge route is on: a non-equivariant EGCL layer takes it
    assert config["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"]

    def forward(variables, batch):
        params, batch = mp_cast(variables["params"], batch, grad_energy)
        _, mutated = model.apply(
            {"params": params,
             "batch_stats": variables.get("batch_stats", {})},
            batch, train=True, capture_intermediates=True,
            mutable=["intermediates", "batch_stats"],
        )
        return mutated["intermediates"]

    captured = flatten_dict(jax.eval_shape(forward, variables, batch))
    n, e = batch.x.shape[0], batch.senders.shape[0]
    coords, features, sized = {}, {}, set()
    for path, outs in captured.items():
        if not path[0].startswith(("graph_convs_", "feature_layers_")):
            continue
        outs = jax.tree_util.tree_leaves(outs)
        if path[0].startswith("graph_convs_") and path[1:] == ("__call__",):
            # a conv returns (features, coordinates)
            coords[path[0]] = outs.pop(1).dtype
        for out in outs:
            features["/".join(path)] = out.dtype
            sized.add(out.shape[0])
    assert sized == {n, e}, sized  # node- and edge-sized arrays were seen
    promoted = {k: str(v) for k, v in features.items() if v != jnp.bfloat16}
    assert not promoted, promoted
    assert len(coords) == 4
    if equivariance or grad_energy:
        # displaced (or kept) coordinates are float32: a gate gain of 0.001
        # on a bf16 position would round away
        assert set(coords.values()) == {jnp.dtype(jnp.float32)}, coords


def pytest_f32_step_jaxpr_unchanged_by_the_edge_term_cast(monkeypatch):
    """With ``mixed_precision`` off the cast is the identity: the training
    step's jaxpr is the one the spelling without ``astype`` gives."""
    import re

    from flax import linen as nn

    import hydragnn_tpu.models.layers as layers

    def uncast(dim, inv, batch, name_recv, name_send, edge_terms=()):
        edge_in = nn.Dense(dim, use_bias=False, name=name_send)(inv)[
            batch.senders]
        # the order ``pair_message_factored`` sets for the row gathers
        inv, edge_in = jax.lax.optimization_barrier((inv, edge_in))
        node_recv = nn.Dense(dim, name=name_recv)(inv)
        for name, arr in edge_terms:
            edge_in = edge_in + nn.Dense(dim, use_bias=False, name=name)(arr)
        return node_recv, edge_in

    config, model, variables, batch = _edge_stack("EGNN", True, False)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    rng = jax.random.PRNGKey(0)

    def step_jaxpr():
        step = make_train_step(model, tx, mixed_precision=False)
        text = str(jax.make_jaxpr(step)(state, batch, rng))
        return re.sub(r" at 0x[0-9a-f]+", "", text)

    with_cast = step_jaxpr()
    monkeypatch.setattr(layers, "pair_message_factored", uncast)
    without = step_jaxpr()
    assert with_cast == without
    # and it is a real step: the edge products of all four layers are in it
    assert with_cast.count("dot_general") > 20
