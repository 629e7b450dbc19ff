"""Fused gather -> edge-dense -> sorted-segment-sum kernel (interpret mode on
CPU) vs the dense ``segment_sum`` + explicit-matmul reference: forward,
grad, and grad-of-grad (force-style loss), f32/bf16, ragged tails, empty
segments, degree spill, routing fallbacks, and model-level fused==unfused
(ops/pallas_fused_edge.py, ops/segment.py, models/layers.py, models/egnn.py).
"""

import copy
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.pallas_fused_edge import (
    fused_edge_message_sum,
    reference_edge_message_sum,
)
from test_pallas_segment import (
    _bits,
    _row_gather_case,
    _sorted_capped_receivers,
)


def _operands(rng, e, n, ci, co, dtype=np.float32):
    nr = jnp.asarray(rng.normal(size=(n, ci)).astype(dtype))
    ei = jnp.asarray(rng.normal(size=(e, ci)).astype(dtype))
    w = jnp.asarray(rng.normal(size=(ci, co)).astype(dtype) / np.sqrt(ci))
    b = jnp.asarray(rng.normal(size=(co,)).astype(dtype))
    return nr, ei, w, b


@pytest.mark.parametrize(
    "e,n,ci,co,max_degree",
    [
        (300, 50, 7, 13, 16),     # odd widths, small
        (1000, 128, 64, 64, 20),  # production-ish ratios
        (37, 400, 3, 5, 4),       # tiny ragged edge tail, many empty rows
        (512, 64, 130, 70, 16),   # >1 lane block in, odd out
    ],
)
def pytest_forward_matches_dense(e, n, ci, co, max_degree):
    rng = np.random.default_rng(e + n)
    recv = _sorted_capped_receivers(rng, e, n, max_degree)
    nr, ei, w, b = _operands(rng, e, n, ci, co)
    out = fused_edge_message_sum(
        nr, ei, w, b, jnp.asarray(recv), n, max_degree, interpret=True
    )
    ref = reference_edge_message_sum(nr, ei, w, b, jnp.asarray(recv), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def pytest_bf16_streams_with_f32_accumulation():
    rng = np.random.default_rng(11)
    recv = _sorted_capped_receivers(rng, 400, 64, 16)
    nr, ei, w, b = _operands(rng, 400, 64, 32, 32)
    cast = lambda x: x.astype(jnp.bfloat16)
    out = fused_edge_message_sum(
        cast(nr), cast(ei), cast(w), cast(b), jnp.asarray(recv), 64, 16,
        interpret=True,
    )
    assert out.dtype == jnp.bfloat16
    ref = reference_edge_message_sum(nr, ei, w, b, jnp.asarray(recv), 64)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=4e-2, atol=4e-2
    )


def pytest_empty_and_trailing_segments():
    """Segments with no edges (incl. a trailing run) come out zero — bias
    and the relu do not leak into edge-less rows."""
    rng = np.random.default_rng(2)
    recv = np.array([2, 2, 5], np.int32)
    nr, ei, w, b = _operands(rng, 3, 64, 4, 6)
    out = fused_edge_message_sum(
        nr, ei, w, b, jnp.asarray(recv), 64, 8, interpret=True
    )
    ref = reference_edge_message_sum(nr, ei, w, b, jnp.asarray(recv), 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    mask = np.ones(64, bool)
    mask[[2, 5]] = False
    assert np.abs(np.asarray(out)[mask]).max() == 0.0


def pytest_degree_spill_in_final_segment_is_contained():
    """Over-cap blast radius, pinned to the layout the framework actually
    produces: a segment holding more than max_degree edges has an
    UNSPECIFIED value and can also starve LATER rows inside its own
    row block (their edges fall past the K streamed windows) — which is
    exactly why data/graph.py routes every padding edge to the FINAL
    dummy node: with the over-cap segment last, every preceding segment
    stays exact. Assert that contract, with a spill far larger than one
    edge window so the test would catch a coverage regression."""
    rng = np.random.default_rng(3)
    n, max_degree = 40, 4
    # every node gets max_degree-1 edges; the LAST node (the dummy-node
    # position) additionally gets ~3 edge windows' worth of spill
    recv = np.concatenate([
        np.repeat(np.arange(n, dtype=np.int32), max_degree - 1),
        np.full(1500, n - 1, np.int32),
    ])
    recv = np.sort(recv).astype(np.int32)
    e = recv.shape[0]
    nr, ei, w, b = _operands(rng, e, n, 9, 11)
    out = np.asarray(fused_edge_message_sum(
        nr, ei, w, b, jnp.asarray(recv), n, max_degree, interpret=True
    ))
    ref = np.asarray(reference_edge_message_sum(
        nr, ei, w, b, jnp.asarray(recv), n
    ))
    np.testing.assert_allclose(out[: n - 1], ref[: n - 1],
                               rtol=2e-5, atol=2e-5)


def pytest_gradients_match_dense():
    """First-order grads w.r.t. every differentiable operand: the custom-JVP
    tangent rule transposes to the gather + two-matmul VJP."""
    rng = np.random.default_rng(5)
    n, e, ci, co, max_degree = 48, 220, 12, 10, 12
    recv = _sorted_capped_receivers(rng, e, n, max_degree)
    nr, ei, w, b = _operands(rng, e, n, ci, co)
    probe = jnp.asarray(rng.normal(size=(n, co)).astype(np.float32))

    def loss(nr, ei, w, b, agg):
        return jnp.sum(probe * jnp.tanh(agg(nr, ei, w, b)))

    fp = lambda *a: fused_edge_message_sum(
        *a, jnp.asarray(recv), n, max_degree, interpret=True
    )
    fd = lambda *a: reference_edge_message_sum(*a, jnp.asarray(recv), n)
    gp = jax.grad(loss, argnums=(0, 1, 2, 3))(nr, ei, w, b, fp)
    gd = jax.grad(loss, argnums=(0, 1, 2, 3))(nr, ei, w, b, fd)
    for a, c in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), (jnp.bfloat16, 5e-2)])
def pytest_grad_of_grad_force_style(dtype, tol):
    """Force-style second order: energy built through the fused op, forces
    = -dE/dpos via an inner jax.grad, outer training grad w.r.t. weights
    and positions — the exact composition the r5 custom_vjp kernel raised
    NotImplementedError on."""
    rng = np.random.default_rng(7)
    n, e, ci, max_degree = 32, 150, 8, 10
    recv = _sorted_capped_receivers(rng, e, n, max_degree)
    send = rng.integers(0, n, e).astype(np.int32)
    pos = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)).astype(dtype)
    proj = jnp.asarray(
        rng.normal(size=(3, ci)).astype(np.float32)
    ).astype(dtype)
    w = jnp.asarray(
        (rng.normal(size=(ci, ci)) / np.sqrt(ci)).astype(np.float32)
    ).astype(dtype)
    b = jnp.zeros((ci,), dtype)

    def energy(pos, w, agg):
        nr = pos @ proj
        ei = (pos[send] - pos[recv]) @ proj
        return jnp.sum(agg(nr, ei, w, b) ** 2)

    def force_loss(w, pos, agg):
        f = -jax.grad(energy, argnums=0)(pos, w, agg)
        return jnp.sum(f ** 2) + energy(pos, w, agg)

    fp = lambda *a: fused_edge_message_sum(
        *a, jnp.asarray(recv), n, max_degree, interpret=True
    )
    fd = lambda *a: reference_edge_message_sum(*a, jnp.asarray(recv), n)
    for argnums in (0, 1):  # d(force loss)/dW and /dpos — both second order
        gp = jax.grad(force_loss, argnums=argnums)(w, pos, fp)
        gd = jax.grad(force_loss, argnums=argnums)(w, pos, fd)
        scale = max(float(jnp.abs(gd.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(
            np.asarray(gp, np.float32) / scale,
            np.asarray(gd, np.float32) / scale, rtol=tol, atol=tol,
        )


def pytest_routing_fallback_and_force(monkeypatch):
    """ops/segment.py routing: =0 forces the dense reference (bit-identical),
    =1 forces the Pallas kernel in interpret mode off-TPU."""
    from hydragnn_tpu.ops.segment import fused_edge_message_sum as routed

    rng = np.random.default_rng(9)
    n, e, max_degree = 30, 90, 8
    recv = _sorted_capped_receivers(rng, e, n, max_degree)
    nr, ei, w, b = _operands(rng, e, n, 6, 6)
    ref = reference_edge_message_sum(nr, ei, w, b, jnp.asarray(recv), n)

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "0")
    out_dense = routed(nr, ei, w, b, jnp.asarray(recv), n, max_degree)
    np.testing.assert_array_equal(np.asarray(out_dense), np.asarray(ref))

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    out_kernel = routed(nr, ei, w, b, jnp.asarray(recv), n, max_degree)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# model level: the fused EGCL route is the same function and the same
# parameter tree as the unfused spelling
# ---------------------------------------------------------------------------


def _egnn_config(equivariance=False, grad_energy=False):
    arch = {
        "mpnn_type": "EGNN",
        "equivariance": equivariance,
        "radius": 5.0,
        "max_neighbours": 10,
        "hidden_dim": 16,
        "num_conv_layers": 2,
        "use_sorted_aggregation": True,
        "task_weights": [1.0],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": 16,
                "num_headlayers": 2,
                "dim_headlayers": [16, 16],
            }
        },
    }
    voi = {
        "input_node_features": [0],
        "output_names": ["energy"],
        "output_index": [0],
        "type": ["graph"],
    }
    training = {
        "batch_size": 8,
        "num_epoch": 1,
        "Optimizer": {"type": "AdamW", "learning_rate": 5e-3},
    }
    if grad_energy:
        arch["output_heads"] = {
            "node": {"num_headlayers": 2, "dim_headlayers": [16, 16],
                     "type": "mlp"},
        }
        voi.update(output_names=["graph_energy"], type=["node"],
                   output_dim=[1])
        training["compute_grad_energy"] = True
    return {
        "NeuralNetwork": {
            "Architecture": arch,
            "Variables_of_interest": voi,
            "Training": training,
        },
        "Dataset": {
            "node_features": {"dim": [1, 3]},
            "graph_features": {"dim": [1]},
        },
    }


def _shaped_graphs():
    from hydragnn_tpu.data import oc20_shaped_dataset, split_dataset

    graphs = oc20_shaped_dataset(24, mean_atoms=20, min_atoms=10,
                                 max_atoms=40, max_neighbours=10)
    out = []
    for g in graphs:
        out.append(dataclasses.replace(
            g, x=np.asarray(g.z, np.float32)[:, None], graph_y=None
        ))
    return split_dataset(out, 0.8, seed=0)


def pytest_fused_flag_completion():
    from hydragnn_tpu.config import update_config

    tr, va, te = _shaped_graphs()
    done = update_config(copy.deepcopy(_egnn_config()), tr, va, te)
    arch = done["NeuralNetwork"]["Architecture"]
    assert arch["use_fused_edge_kernel"] is True  # follows sorted-agg

    off = copy.deepcopy(_egnn_config())
    off["NeuralNetwork"]["Architecture"]["use_sorted_aggregation"] = False
    done_off = update_config(off, tr, va, te)
    assert done_off["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] is False

    explicit = copy.deepcopy(_egnn_config())
    explicit["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] = False
    done_ex = update_config(explicit, tr, va, te)
    assert done_ex["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] is False

    # explicit fused WITHOUT sorted can never engage — must fail loudly,
    # not silently A/B the unfused route against itself
    bad = copy.deepcopy(_egnn_config())
    bad["NeuralNetwork"]["Architecture"]["use_sorted_aggregation"] = False
    bad["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] = True
    with pytest.raises(ValueError, match="use_sorted_aggregation"):
        update_config(bad, tr, va, te)


@pytest.mark.parametrize("route_env", ["0", "1"])
def pytest_egcl_fused_equals_unfused(monkeypatch, route_env):
    """One training step on a real sorted batch: identical init param trees,
    loss agreement between the fused module and the unfused spelling, on
    BOTH the dense fallback (env 0) and the interpret kernel (env 1)."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", route_env)
    tr, va, te = _shaped_graphs()
    config = update_config(copy.deepcopy(_egnn_config()), tr, va, te)
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True)
    batch = next(iter(loader))
    losses, params0, sig0 = {}, None, None
    for fused in (True, False):
        c = copy.deepcopy(config)
        c["NeuralNetwork"]["Architecture"]["use_fused_edge_kernel"] = fused
        model = create_model(c)
        variables = init_model(model, batch, seed=0)
        sig = tuple(sorted(
            str(p) for p, _ in jax.tree_util.tree_leaves_with_path(variables)
        ))
        if sig0 is None:
            params0, sig0 = variables, sig
        else:
            assert sig == sig0, "fused/unfused parameter trees differ"
        tx = make_optimizer(c["NeuralNetwork"]["Training"]["Optimizer"])
        state = TrainState.create(
            jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), params0),
            tx,
        )
        step = make_train_step(model, tx)
        _, tot, _ = step(state, batch, jax.random.PRNGKey(0))
        losses[fused] = float(tot)
    assert np.isfinite(losses[True]) and np.isfinite(losses[False])
    assert abs(losses[True] - losses[False]) <= 1e-5 * max(
        1.0, abs(losses[False])
    ), losses


def pytest_energy_force_step_fused_equals_dense(monkeypatch):
    """The previously-guarded combination — use_sorted_aggregation (and the
    fused kernel) WITH Training.compute_grad_energy — runs and agrees with
    the dense route on the energy+force loss. This is the CPU tier-1 analog
    of the multichip dryrun's energy-force leg (__graft_entry__)."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader, lennard_jones_dataset
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    graphs = lennard_jones_dataset(24)
    tr, va, te = split_dataset(graphs, 0.75, seed=0)
    config = _egnn_config(grad_energy=True)
    config["NeuralNetwork"]["Architecture"].update(radius=2.5,
                                                   max_neighbours=32)
    config["Dataset"] = {"node_features": {"name": ["type"], "dim": [1]}}
    config = update_config(config, tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    # the r5 grad-energy guard is gone: sorted + grad-energy completes, and
    # the fused flag follows
    assert arch["use_sorted_aggregation"] is True
    assert arch["use_fused_edge_kernel"] is True
    model = create_model(config)
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True,
                         max_in_degree=arch["max_in_degree"])
    batch = next(iter(loader))
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    losses = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", flag)
        state = TrainState.create(
            jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                   variables), tx,
        )
        step = make_train_step(model, tx, compute_grad_energy=True)
        _, tot, _ = step(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(tot))
        losses[flag] = float(tot)
    assert abs(losses["1"] - losses["0"]) <= 1e-4 * max(
        1.0, abs(losses["0"])
    ), losses


# ---------------------------------------------------------------------------
# the receiver gather's transpose (ops/segment.py gather(sorted_ids=True)):
# on a packed, PADDED batch every gradient leaf equals the plain gather's.
# The hazard is the dummy node's row of d(node_recv): the kernel leaves it
# unspecified, no consumer masks it, and it enters the receiver projection's
# weight gradient through inv[dummy]. It is exact only while every padding
# edge's cotangent is zero, which each stack below has to hold.
# ---------------------------------------------------------------------------


def _plain_gather(monkeypatch):
    """Every call site's ``gather`` back to the bare ``values[index]``: the
    route the transposed one is compared with, all kernels left on."""
    import hydragnn_tpu.models.layers as layers
    import hydragnn_tpu.models.pna as pna
    import hydragnn_tpu.ops.pallas_fused_edge as fused

    for module in (layers, pna, fused):
        monkeypatch.setattr(
            module, "gather", lambda values, index, *a, **k: values[index])


def _padded_stack(mpnn_type, equivariance, num_layers=4):
    """A conv stack with its kernel routes on, and one packed batch whose
    node and edge slots both end in padding."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader
    from hydragnn_tpu.models import create_model, init_model

    tr, va, te = _shaped_graphs()
    config = copy.deepcopy(_egnn_config(equivariance))
    config["NeuralNetwork"]["Architecture"].update(
        mpnn_type=mpnn_type, num_conv_layers=num_layers)
    config = update_config(config, tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["use_sorted_aggregation"] and arch["max_in_degree"] > 0
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True,
                         pack=True, max_in_degree=arch["max_in_degree"])
    batch = next(iter(loader))
    n_pad = int((~np.asarray(batch.node_mask)).sum())
    e_pad = int((~np.asarray(batch.edge_mask)).sum())
    assert n_pad >= 1 and e_pad > arch["max_in_degree"], (n_pad, e_pad)
    # the layout the hazard is about: every padding edge on the LAST row
    assert (np.asarray(batch.receivers)[~np.asarray(batch.edge_mask)]
            == batch.x.shape[0] - 1).all()
    model = create_model(config)
    return model, init_model(model, batch, seed=0), batch


def _grad_leaves(model, variables, batch, grad_energy=False,
                 mixed_precision=False):
    """The training gradient of every parameter leaf, as the step takes it."""
    from hydragnn_tpu.train.loop import mp_cast, mp_restore_stats
    from hydragnn_tpu.train.loss import compute_loss

    def loss(params):
        b = batch
        if mixed_precision:
            params, b = mp_cast(params, b, grad_energy)
        tot, _, _, _ = compute_loss(
            model,
            {"params": params, "batch_stats": variables.get("batch_stats", {})},
            b, model.cfg, True, jax.random.PRNGKey(0), grad_energy)
        return tot.astype(jnp.float32)

    grads = jax.jit(jax.grad(loss))(variables["params"])
    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}


def _absmax(x):
    return np.abs(x).max()


def _assert_leaves_equal(got, ref, tol, norm=_absmax, floor_share=1e-3):
    """Leaf by leaf, by the leaf's own norm (its largest entry unless told
    otherwise); a leaf whose gradient is rounding alone (a bias in front of
    a batch norm: up to 1.8e-3 of the largest leaf's norm in bf16) is read
    against ``floor_share`` of the largest leaf's."""
    assert got.keys() == ref.keys()
    floor = floor_share * max(float(norm(r)) for r in ref.values())
    for name, r in ref.items():
        assert np.isfinite(got[name]).all(), name
        gap = float(norm(got[name] - r)) / max(float(norm(r)), floor)
        assert gap <= tol, (name, gap, tol)


@pytest.mark.parametrize(
    "mpnn_type,equivariance,mixed_precision,tol",
    [
        # the benchmark's model: layers 0-2 equivariant (the unfused gather),
        # layer 3 fused (the tangent rule's gather)
        ("EGNN", True, False, 1e-5),
        # as the cells run it: the forward is the same bit for bit, but XLA's
        # scatter-add accumulates in bf16 and the kernel in float32, and the
        # difference passes through up to three more bf16 layers. Read by L2
        # (measured 0 .. 7.4e-2 a leaf; either route lies 0.2 .. 0.6 from the
        # float32 gradient on most conv leaves at this width)
        ("EGNN", True, True, 0.15),
        # every layer through the fused call's tangent rule
        ("EGNN", False, False, 1e-5),
        # the other stacks that hand ``gather`` their flags
        ("CGCNN", False, False, 1e-5),
        ("PNAEq", False, False, 1e-5),
        ("PNA", False, False, 1e-5),
    ],
)
def pytest_transposed_gather_gradients_equal_plain_on_a_padded_batch(
        monkeypatch, mpnn_type, equivariance, mixed_precision, tol):
    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    model, variables, batch = _padded_stack(mpnn_type, equivariance)
    got = _grad_leaves(model, variables, batch,
                       mixed_precision=mixed_precision)
    recv = [k for k in got if "_recv" in k and "kernel" in k]
    assert recv, sorted(got)  # the leaves the dummy row would reach
    with monkeypatch.context() as plain:
        _plain_gather(plain)
        ref = _grad_leaves(model, variables, batch,
                           mixed_precision=mixed_precision)
    if mixed_precision:
        _assert_leaves_equal(got, ref, tol, np.linalg.norm, floor_share=1e-2)
    else:
        _assert_leaves_equal(got, ref, tol)
    assert any(np.abs(ref[k]).max() > 0 for k in recv)


def pytest_transposed_gather_is_in_the_egnn_step(monkeypatch):
    """The equivariant EGNN's gradient program: one linear call a gather
    (three unfused layers + the fused layer's tangent rule), each transposed
    into a sorted-segment kernel call; none with the route off."""
    model, variables, batch = _padded_stack("EGNN", True)

    def jaxpr():
        from hydragnn_tpu.train.loss import compute_loss

        loss = lambda p: compute_loss(
            model, {"params": p, "batch_stats": {}}, batch, model.cfg, True,
            jax.random.PRNGKey(0), False)[0]
        return str(jax.make_jaxpr(jax.grad(loss))(variables["params"]))

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    on = jaxpr()
    with monkeypatch.context() as plain:
        _plain_gather(plain)
        off = jaxpr()
    # the fused layer's rule closes on a linear call of its own, a sum of
    # tangents alone: the gradient holds its transpose, the ordered
    # ``dout[ids]`` gather
    assert off.count("= linear_call[") == 1, off.count("= linear_call[")
    # forward + transposed, four gathers more
    assert on.count("= linear_call[") == 9, on.count("= linear_call[")
    assert on.count("name=hg_sorted_segment") - off.count(
        "name=hg_sorted_segment") == 4
    assert off.count("scatter-add") - on.count("scatter-add") == 4


def pytest_energy_force_gradients_transposed_equal_plain(monkeypatch):
    """``compute_grad_energy``: the force is a gradient through the gather,
    the training gradient differentiates it again (a linear call's JVP is
    itself on the tangent, its transpose the call with the roles swapped).
    Every leaf's second-order gradient equals the plain gather's."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader, lennard_jones_dataset
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.models import create_model, init_model

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    tr, va, te = split_dataset(lennard_jones_dataset(24), 0.75, seed=0)
    config = _egnn_config(grad_energy=True)
    config["NeuralNetwork"]["Architecture"].update(radius=2.5,
                                                   max_neighbours=32)
    config["Dataset"] = {"node_features": {"name": ["type"], "dim": [1]}}
    config = update_config(config, tr, va, te)
    arch = config["NeuralNetwork"]["Architecture"]
    loader = GraphLoader(tr, 8, seed=0, drop_last=True, sort_edges=True,
                         pack=True, max_in_degree=arch["max_in_degree"])
    batch = next(iter(loader))
    assert not np.asarray(batch.edge_mask).all()
    model = create_model(config)
    variables = init_model(model, batch, seed=0)
    got = _grad_leaves(model, variables, batch, grad_energy=True)
    with monkeypatch.context() as plain:
        _plain_gather(plain)
        ref = _grad_leaves(model, variables, batch, grad_energy=True)
    # float32 second order: summation order alone (measured 2.6e-4)
    _assert_leaves_equal(got, ref, 2e-3)
    assert any(np.abs(v).max() > 0 for v in ref.values())


# ---------------------------------------------------------------------------
# ORDER for the row gathers (PERF.md section 6, PR 32): the barrier in
# ``pair_message_factored`` and the fused rule's closing linear call change
# the schedule and nothing else, so each is held to the unordered spelling
# bit for bit, under the transforms a step puts them through
# ---------------------------------------------------------------------------


def _order_case(dtype, c):
    """A padded batch's receiver-sorted ids (edge-less rows, a padding run to
    the dummy node), senders anywhere, ``[n, c]`` features."""
    recv, x, _, max_degree = _row_gather_case(dtype, c)
    send = np.random.default_rng(c + 1).integers(
        0, x.shape[0], recv.shape[0]).astype(np.int32)
    return recv, jnp.asarray(send), x, max_degree


def _assert_bit_equal(got, ref):
    got, ref = (jax.tree_util.tree_leaves(t) for t in (got, ref))
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(_bits(g), _bits(r))


@pytest.mark.parametrize("c", [3, 128, 866])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def pytest_pair_message_order_changes_no_bit(dtype, c):
    """``hoisted_pair_dense`` (sender gather, barrier, receiver projection,
    receiver gather) against ``(x W_r + b)[recv] + (x W_s)[send]`` spelled
    plainly: rows, parameter gradients and input gradient equal to the bit."""
    import types

    from flax import linen as nn
    from hydragnn_tpu.models.layers import hoisted_pair_dense

    recv, send, x, deg = _order_case(dtype, c)
    batch = types.SimpleNamespace(senders=send, receivers=recv)

    class Ordered(nn.Module):
        @nn.compact
        def __call__(self, v):
            return hoisted_pair_dense(c, v, batch, "recv", "send",
                                      sorted_ids=True, max_degree=deg)

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, v):
            return (nn.Dense(c, name="recv")(v)[recv]
                    + nn.Dense(c, use_bias=False, name="send")(v)[send])

    params = jax.tree_util.tree_map(
        lambda p: p.astype(dtype),
        Ordered().init(jax.random.PRNGKey(0), x))
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=(recv.shape[0], c)), dtype)

    def run(module):
        loss = lambda p, v: jnp.sum(
            (module.apply(p, v) * w).astype(jnp.float32))
        return module.apply(params, x), jax.jit(
            jax.grad(loss, argnums=(0, 1)))(params, x)

    _assert_bit_equal(run(Ordered()), run(Plain()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "transform", ["grad", "checkpoint", "grad_of_grad", "shard_map"])
def pytest_tangent_rule_closing_sum_composes(transform, dtype):
    """The tangent rule's statement (``max_degree`` given: the closing sum a
    linear call whose transpose bars ``dout`` behind the rows) against the
    oracle's (the plain ``segment_sum``): value and every gradient equal to
    the bit under ``jit(grad)``, ``jax.checkpoint``, grad-of-grad (energy-force
    training) and ``shard_map`` (the mesh step)."""
    from jax.sharding import Mesh, PartitionSpec as P

    recv, _, nr, deg = _order_case(dtype, 16)
    n, e = nr.shape[0], recv.shape[0]
    _, ei, w, b = _operands(np.random.default_rng(2), e, n, 16, 16)
    ops = tuple(o.astype(dtype) for o in (nr, ei, w, b))

    def apply(max_degree):
        fn = lambda *o: reference_edge_message_sum(
            *o, recv, n, max_degree)
        loss = lambda *o: jnp.sum(jnp.sin(fn(*o).astype(jnp.float32)))
        every = tuple(range(4))
        if transform == "grad":
            return fn(*ops), jax.jit(jax.grad(loss, every))(*ops)
        if transform == "checkpoint":
            return jax.jit(jax.grad(jax.checkpoint(loss), every))(*ops)
        if transform == "grad_of_grad":
            inner = lambda *o: jnp.sum(
                jax.grad(loss, 1)(*o).astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(inner, every))(*ops)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
        two = tuple(jnp.stack([o, 2 * o]) for o in ops)
        per_shard = lambda *o: jax.lax.psum(jax.tree_util.tree_map(
            lambda g: g[None], jax.grad(loss, every)(*(a[0] for a in o))), "d")
        return jax.jit(jax.shard_map(
            per_shard, mesh=mesh, in_specs=P("d"), out_specs=P()))(*two)

    _assert_bit_equal(apply(deg), apply(None))


def pytest_ordered_egnn_gradients_under_shard_map_equal_unsharded(monkeypatch):
    """The mesh step's composition, in the model: the equivariant EGNN's
    training gradient (three ordered layers, the fused layer's rule, four
    transposed gathers) taken per shard under ``shard_map`` and averaged
    equals the unsharded one."""
    from jax.sharding import Mesh, PartitionSpec as P

    from hydragnn_tpu.train.loss import compute_loss

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    model, variables, batch = _padded_stack("EGNN", True)
    ref = _grad_leaves(model, variables, batch)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
    twice = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), batch)

    def per_shard(params, shard):
        mine = jax.tree_util.tree_map(lambda x: x[0], shard)
        loss = lambda p: compute_loss(
            model, {"params": p, "batch_stats": {}}, mine, model.cfg, True,
            jax.random.PRNGKey(0), False)[0]
        return jax.lax.pmean(jax.grad(loss)(params), "d")

    grads = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P("d")), out_specs=P(),
        check_vma=False))(variables["params"], twice)
    got = {jax.tree_util.keystr(path): np.asarray(leaf, np.float32)
           for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}
    _assert_leaves_equal(got, ref, 1e-5)


def pytest_row_gather_order_is_in_the_egnn_step(monkeypatch):
    """The equivariant EGNN's gradient program holds the order at every
    edge-sized feature gather: one barrier a message layer forward and its
    transpose backward (``pair_message_factored``), and the fused layer's
    rule bars ``dout`` behind the recomputed rows (one more, backward only)."""
    from hydragnn_tpu.train.loss import compute_loss

    monkeypatch.setenv("HYDRAGNN_PALLAS_SEGMENT", "1")
    model, variables, batch = _padded_stack("EGNN", True)
    loss = lambda p: compute_loss(
        model, {"params": p, "batch_stats": {}}, batch, model.cfg, True,
        jax.random.PRNGKey(0), False)[0]
    forward = str(jax.make_jaxpr(loss)(variables["params"]))
    assert forward.count("optimization_barrier") == 4, forward.count(
        "optimization_barrier")
    grad = str(jax.make_jaxpr(jax.grad(loss))(variables["params"]))
    assert grad.count("optimization_barrier") == 9, grad.count(
        "optimization_barrier")


# ---------------------------------------------------------------------------
# the shape the bf16 training step runs (benchmarks' EGNN-866 cells), compiled
# for a described v5e at the real size (tests/test_chip_smoke.py rehearses
# the chip check's case of it in interpret mode)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a DESCRIBED v5e (the TPU compiler is installed; no chip
    is attached). Only this file's worker loads the TPU library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tangent", [False, True])
def pytest_bf16_kernel_compiles_for_v5e_at_the_cell_shape(v5e_chip, tangent):
    """Mosaic accepts the bf16 call the training step makes since the edge
    length joins the feature stream in bf16: ``[12160, 896]`` rows,
    196608 edges, in-degree bound 36, the kernel's own tiles."""
    from hydragnn_tpu.ops.pallas_fused_edge import normalize_tiles

    n, e, c, deg = 12136, 196608, 866, 36
    plan = normalize_tiles(c, c, jnp.bfloat16)
    assert plan[0] % 16 == 0 and plan[1] % 16 == 0, plan
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip)
    operands = (shaped((n, c)), shaped((e, c)), shaped((c, c)), shaped((c,)))
    ids = shaped((e,), jnp.int32)

    def kernel(ids, *ops):
        return fused_edge_message_sum(*ops, ids, n, deg, *plan, False)

    if tangent:
        fn = lambda ids, p, t: jax.jvp(lambda *o: kernel(ids, *o), p, t)
        compiled = jax.jit(fn).lower(ids, operands, operands).compile()
    else:
        compiled = jax.jit(kernel).lower(ids, *operands).compile()
    text = compiled.as_text()
    call = [line for line in text.splitlines()
            if "tpu_custom_call" in line and "hg_fused_edge" in line]
    assert len(call) == 1 and "f32[12160,896]" in call[0], call
    # its streams are the padded bf16 operands: rows, edges (+ 10 windows
    # of 512), weights
    for stream in ("bf16[12160,896]", "bf16[201728,896]", "bf16[896,896]"):
        assert stream in text, stream


@pytest.mark.parametrize("n,hq,hk,d_qk,d_v", [(16384, 32, 32, 192, 128), (32768, 8, 2, 128, 128)],
                         ids=["joyai_latent_attention_widths", "zaya_grouped_query_heads"])
def pytest_causal_flash_launches_compile_for_v5e_at_the_decoder_cells_shapes(v5e_chip, n, hq, hk, d_qk, d_v):
    """Mosaic accepts the causal flash kernel's three launches (forward,
    ``dq``, ``dk``/``dv``) at the decoder cells' shapes, bf16, with a head's
    streamed operands RESIDENT in VMEM (two copies of up to 16.8 MB under the
    raised scoped limit) and the window's loop inside the kernel. JOYAI:
    16384 tokens, 32 heads, queries and keys 192 wide beside values 128
    wide; the 192 streams as it is: no operand grows to 256 lanes (the
    heads-first copies are ``[32, 16384, 192]`` and ``[32, 16384, 128]``).
    ZAYA: 32768 tokens, 8 query heads on 2 key/value heads of 128."""
    import re

    from hydragnn_tpu.ops import pallas_flash_attention as pfa

    assert pfa._resident(n, d_qk + d_v, jnp.bfloat16)
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def loss(q, k, v, node_graph, node_mask):
        out = pfa.flash_causal_attention(q, k, v, node_graph, node_mask, 8192)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shaped((n, hq, d_qk)), shaped((n, hk, d_qk)), shaped((n, hk, d_v)),
        shaped((n,), jnp.int32), shaped((n,), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = re.findall(r"^\s*%(hg_flash_attention[a-z_]*)[.\d]* = .*custom-call\(", text, re.MULTILINE)
    assert sorted(calls) == ["hg_flash_attention", "hg_flash_attention_bwd", "hg_flash_attention_bwd"], calls
    assert f"bf16[{hq},{n},{d_qk}]" in text and f"bf16[{hk},{n},{d_v}]" in text
    assert f"bf16[{hq},{n},256]" not in text
    # dq, dk (qk wide) and dv in the operands' shapes (and a tuple's few bytes)
    assert 0 <= compiled.memory_analysis().output_size_in_bytes - 2 * n * (hq * d_qk + hk * (d_qk + d_v)) < 4096


def pytest_sliding_flash_launches_compile_for_v5e_at_the_trinity_cells_shape(v5e_chip):
    """Mosaic accepts the three launches WITH a sliding window at the Trinity
    cell's shape (16,384 tokens, 32 query heads on 4 key/value heads of 128,
    window 2,048 under documents of up to 16,383 tokens, bf16, the head
    resident), under names of their own, beside the full launches of the same
    operands in one program."""
    import re

    from hydragnn_tpu.ops import pallas_flash_attention as pfa

    n, hq, hk, d = 16384, 32, 4, 128
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def loss(q, k, v, node_graph, node_mask):
        out = pfa.flash_causal_attention(q, k, v, node_graph, node_mask, 16383, window=2048)
        out = out + pfa.flash_causal_attention(q, k, v, node_graph, node_mask, 16383)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shaped((n, hq, d)), shaped((n, hk, d)), shaped((n, hk, d)),
        shaped((n,), jnp.int32), shaped((n,), jnp.bool_)).compile()
    calls = re.findall(r"^\s*%(hg_flash_[a-z_]*)[.\d]* = .*custom-call\(", compiled.as_text(), re.MULTILINE)
    assert sorted(calls) == ["hg_flash_attention", "hg_flash_attention_bwd", "hg_flash_attention_bwd",
                             "hg_flash_window", "hg_flash_window_bwd", "hg_flash_window_bwd"], calls


def _cell_train_step_text(monkeypatch, v5e_chip):
    """The EGNN-866 cells' own train step (benchmarks/configs/
    egnn866_sc25.json, bf16), lowered at the packed cell's batch shape and
    compiled for the described v5e: its optimised program's text. The kernel
    routes ask ``jax.default_backend()`` at trace time, so the test answers
    for it while the step is traced."""
    import json

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader, oc20_shaped_dataset
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "egnn866_sc25.json")) as f:
        config = json.load(f)["program_config"]
    config["NeuralNetwork"]["Training"]["batch_size"] = 8
    config["NeuralNetwork"]["Architecture"].update(
        use_sorted_aggregation=True, use_fused_edge_kernel=True)
    datasets = split_dataset(oc20_shaped_dataset(
        24, mean_atoms=20, min_atoms=10, max_atoms=40, max_neighbours=10),
        0.8, seed=0)
    config = update_config(config, *datasets)
    config["NeuralNetwork"]["Architecture"]["max_in_degree"] = 36
    batch = next(iter(GraphLoader(
        datasets[0], 8, seed=0, drop_last=True, sort_edges=True, pack=True,
        max_in_degree=36)))
    model = create_model(config)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(init_model(model, batch, seed=0), tx)
    cell = {batch.x.shape[0]: 12136, batch.senders.shape[0]: 196608}
    assert len(cell) == 2 and batch.graph_mask.shape[0] not in cell

    def shaped(x, grow=False):
        aval = jax.api_util.shaped_abstractify(x)
        shape = tuple(cell.get(d, d) if grow and i == 0 else d
                      for i, d in enumerate(aval.shape))
        return jax.ShapeDtypeStruct(shape, aval.dtype, sharding=v5e_chip,
                                    weak_type=aval.weak_type)

    args = (jax.tree_util.tree_map(shaped, state),
            jax.tree_util.tree_map(lambda x: shaped(x, True), batch),
            shaped(jax.random.PRNGKey(0)))
    with monkeypatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        lowered = make_train_step(model, tx, mixed_precision=True).lower(*args)
    return lowered.compile().as_text()


@pytest.fixture(scope="module")
def cell_step_text(v5e_chip):
    """The packed cell's step as the cells run it, compiled once a module."""
    with pytest.MonkeyPatch.context() as patch:
        return _cell_train_step_text(patch, v5e_chip)


def pytest_cell_train_step_compiles_for_v5e_with_transposed_gathers(
        monkeypatch, v5e_chip, cell_step_text):
    """The whole bf16 train step of ``egnn866_oc20_train``'s shape
    (``[12136, 866]`` rows, 196608 edges, in-degree bound 36): Mosaic takes
    the four transposed calls, ten ``hg_sorted_segment`` calls for six, and
    four of the eight edge-sized ``bf16[12136,866]`` scatter-adds are gone
    (the four left transpose the SENDER gathers)."""
    import re

    def counts(text):
        # a Mosaic call is an instruction named after its ``pallas_call``
        mosaic = re.findall(r"^\s*%(hg_[a-z_]+)[.\d]* = .*custom-call\(", text,
                            re.MULTILINE)
        return (
            mosaic.count("hg_sorted_segment"),
            mosaic.count("hg_fused_edge"),
            len(re.findall(r"= bf16\[12136,866\]\S* scatter\(", text)),
        )

    transposed = counts(cell_step_text)
    with monkeypatch.context() as plain:
        _plain_gather(plain)
        before = counts(_cell_train_step_text(plain, v5e_chip))
    assert before == (6, 1, 8), before
    assert transposed == (10, 1, 4), transposed


def _row_gathers(text, shape):
    """The scheduled step's gather fusions that write ``shape``: (fusion,
    op_name, whether the gathered operand is assigned to VMEM, memory space
    ``S(1)`` in its layout), in schedule order."""
    import re

    gathering = set()
    name = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
        elif name and " gather(" in line:
            gathering.add(name)
    entry = re.search(r"^ENTRY .*?^\}", text, re.M | re.S).group(0)
    types = dict(re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ", entry, re.M))
    out = []
    for m in re.finditer(
            r"^\s*%([\w.\-]+) = " + re.escape(shape) + r"\S* fusion\(%([\w.\-]+), "
            r".*calls=%([\w.\-]+).*op_name=\"([^\"]*)\"", entry, re.M):
        fusion, operand, callee, op_name = m.groups()
        if callee in gathering and op_name.endswith("/gather"):
            out.append((fusion, op_name, "S(1)" in types[operand]))
    return out


def pytest_cell_train_step_reads_every_row_gather_from_vmem(cell_step_text):
    """Twelve ``bf16[196608,866]`` row gathers a step (seven forward, the
    fused rule's recomputed rows, four ``dout[receivers]``), every one
    under ``hg_row_gather`` and every one reading its ``[12136,866]``
    operand from VMEM: XLA leaves the operand there when its producer is
    scheduled directly before the gather, and such a gather writes at the
    speed of HBM, six times the one that fetches its rows from HBM (4.35 ms
    for 0.74 on the chip; PERF.md section 6, PR 32). The order that
    ``models/layers.py pair_message_factored`` and the fused rule's closing
    sum set is what this holds; without it six of the twelve read HBM."""
    from hydragnn_tpu.utils import tracer as tr

    rows = _row_gathers(cell_step_text, "bf16[196608,866]")
    assert len(rows) == 12, rows
    assert all(tr.HG_ROW_GATHER in op_name for _, op_name, _ in rows), rows
    from_hbm = [(fusion, op_name) for fusion, op_name, vmem in rows if not vmem]
    assert not from_hbm, from_hbm

