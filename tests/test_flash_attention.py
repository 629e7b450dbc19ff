"""Segment-masked Pallas flash attention (interpret mode on CPU) vs the two
dense GPS layouts: flash == flat-masked == per-graph gathered, forward and
grad, f32 + bf16, under jit; ragged batches, empty graph slots, the
Nmax-overflow poison, the ring block-summary reuse, and the bf16-under-jit
Performer leg (ops/pallas_flash_attention.py, models/gps.py,
parallel/ring_attention.py)."""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import Graph, PadSpec, batch_graphs
from hydragnn_tpu.models.gps import (
    MultiheadSelfAttention,
    PerformerSelfAttention,
)
from hydragnn_tpu.ops.pallas_flash_attention import (
    flash_block_summary,
    flash_self_attention,
    reference_block_summary,
    reference_gathered_attention,
    reference_masked_attention,
)


def _flat_batch(rng, sizes, n_pad_extra=6):
    """A hand-built flat layout: graphs contiguous, padding in the final
    slot — exactly what data/graph.py batching produces."""
    n_real = sum(sizes)
    g = len(sizes) + 1
    node_graph = np.concatenate(
        [np.full(s, i, np.int32) for i, s in enumerate(sizes)]
        + [np.full(n_pad_extra, g - 1, np.int32)]
    )
    node_mask = np.concatenate(
        [np.ones(n_real, bool), np.zeros(n_pad_extra, bool)]
    )
    return jnp.asarray(node_graph), jnp.asarray(node_mask), g


def _qkv(rng, n, h, d, dtype=np.float32):
    mk = lambda: jnp.asarray(rng.normal(size=(n, h, d)).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize(
    "sizes,h,d",
    [
        ([1, 1, 1], 1, 8),         # singleton graphs (diagonal blocks)
        ([17, 29, 5, 31, 2], 2, 16),  # ragged mix wider than one q block
    ],
)
def pytest_flash_matches_both_dense_layouts(sizes, h, d):
    rng = np.random.default_rng(sum(sizes))
    node_graph, node_mask, g = _flat_batch(rng, sizes)
    n = node_graph.shape[0]
    q, k, v = _qkv(rng, n, h, d)
    nmax = max(sizes)
    out = flash_self_attention(
        q, k, v, node_graph, node_mask, g, nmax, interpret=True
    )
    masked = reference_masked_attention(q, k, v, node_graph, node_mask)
    gathered = reference_gathered_attention(
        q, k, v, node_graph, node_mask, g, nmax
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(masked), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(gathered), rtol=2e-5, atol=2e-5
    )


def pytest_flash_under_jit_and_slack_bound():
    """Jitted call; an Nmax bound LARGER than the true max (the data-derived
    bound covers every split, not this batch) stays exact."""
    rng = np.random.default_rng(3)
    node_graph, node_mask, g = _flat_batch(rng, [9, 4, 14])
    n = node_graph.shape[0]
    q, k, v = _qkv(rng, n, 2, 8)
    ref = reference_masked_attention(q, k, v, node_graph, node_mask)
    for nmax in (14, 40):
        f = jax.jit(
            lambda q_, k_, v_, nm=nmax: flash_self_attention(
                q_, k_, v_, node_graph, node_mask, g, nm, 128, 128, True
            )
        )
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def pytest_flash_bf16_f32_accumulation():
    rng = np.random.default_rng(5)
    node_graph, node_mask, g = _flat_batch(rng, [9, 4, 14, 21])
    n = node_graph.shape[0]
    q, k, v = _qkv(rng, n, 4, 8)
    cast = lambda x: x.astype(jnp.bfloat16)
    out = jax.jit(
        lambda q_, k_, v_: flash_self_attention(
            q_, k_, v_, node_graph, node_mask, g, 21, 128, 128, True
        )
    )(cast(q), cast(k), cast(v))
    assert out.dtype == jnp.bfloat16
    ref = reference_masked_attention(q, k, v, node_graph, node_mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=4e-2, atol=4e-2
    )


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), (jnp.bfloat16, 5e-2)])
def pytest_flash_gradients_match_dense(dtype, tol):
    rng = np.random.default_rng(7)
    node_graph, node_mask, g = _flat_batch(rng, [6, 11, 3])
    n = node_graph.shape[0]
    q, k, v = _qkv(rng, n, 2, 8)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    probe = jnp.asarray(
        rng.normal(size=(n, 2, 8)).astype(np.float32)
    ).astype(dtype)

    def loss(q_, k_, v_, attend):
        return jnp.sum(probe * jnp.tanh(attend(q_, k_, v_)))

    fp = lambda *a: flash_self_attention(
        *a, node_graph, node_mask, g, 11, 128, 128, True
    )
    fd = lambda *a: reference_masked_attention(*a, node_graph, node_mask)
    gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, fp)
    gd = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, fd)
    for a, b in zip(gp, gd):
        scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale, rtol=tol, atol=tol,
        )


@pytest.mark.slow  # interpret-mode tracing of nested custom-JVP dominates
# (~8s regardless of shape); runs in the unfiltered CI suite
def pytest_flash_grad_of_grad_force_style():
    """Second order (the energy+force composition): energy through the flash
    op, inner jax.grad w.r.t. the q operand, outer training grad again —
    the custom-JVP's plain-jnp tangent must compose to any order."""
    rng = np.random.default_rng(9)
    node_graph, node_mask, g = _flat_batch(rng, [5, 4, 7])
    n = node_graph.shape[0]
    q, k, v = _qkv(rng, n, 1, 8)

    def energy(q_, attend):
        return jnp.sum(attend(q_, k, v) ** 2)

    def force_loss(q_, attend):
        f = -jax.grad(energy)(q_, attend)
        return jnp.sum(f ** 2) + energy(q_, attend)

    fp = lambda *a: flash_self_attention(
        *a, node_graph, node_mask, g, 7, 128, 128, True
    )
    fd = lambda *a: reference_masked_attention(*a, node_graph, node_mask)
    gp = jax.grad(force_loss)(q, fp)
    gd = jax.grad(force_loss)(q, fd)
    scale = max(float(jnp.abs(gd).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(gp) / scale, np.asarray(gd) / scale, rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# module level: routing, real batches, empty graph slots, overflow poison
# ---------------------------------------------------------------------------


def _random_graph(rng, n):
    s, r = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    keep = s != r
    return Graph(
        x=rng.normal(size=(n, 4)).astype(np.float32),
        pos=rng.normal(size=(n, 3)).astype(np.float32),
        senders=s[keep].astype(np.int32),
        receivers=r[keep].astype(np.int32),
    )


def pytest_module_flash_matches_dense_with_empty_graph_slots(monkeypatch):
    """MultiheadSelfAttention on a real padded batch with EXTRA empty graph
    slots: identical parameters, flash route (env-forced, interpret) equals
    both dense module layouts on real rows."""
    rng = np.random.default_rng(11)
    graphs = [_random_graph(rng, n) for n in (4, 6, 3)]
    spec = PadSpec.for_dataset(graphs, batch_size=6)  # 3 empty graph slots
    batch = batch_graphs(graphs, spec)
    C = 8
    x = jnp.asarray(rng.normal(size=(batch.num_nodes, C)).astype(np.float32))
    dense_g = MultiheadSelfAttention(channels=C, heads=2, max_nodes_per_graph=6)
    dense_m = MultiheadSelfAttention(channels=C, heads=2, max_nodes_per_graph=0)
    flash = MultiheadSelfAttention(
        channels=C, heads=2, max_nodes_per_graph=6, use_flash_attention=True
    )
    variables = dense_g.init(jax.random.PRNGKey(0), x, batch)
    out_g = dense_g.apply(variables, x, batch)
    out_m = dense_m.apply(variables, x, batch)
    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    out_f = jax.jit(lambda v, x_: flash.apply(v, x_, batch))(variables, x)
    mask = np.asarray(batch.node_mask)
    np.testing.assert_allclose(
        np.asarray(out_f)[mask], np.asarray(out_g)[mask], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_f)[mask], np.asarray(out_m)[mask], rtol=2e-5, atol=2e-5
    )
    # route OFF: the flag falls back to the gathered-dense oracle exactly
    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "0")
    out_off = flash.apply(variables, x, batch)
    np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out_g))


def pytest_module_flash_nmax_overflow_poisons(monkeypatch):
    """A real graph larger than the static bound must surface as NaN (the
    house silent-wrong-number contract), not as truncated attention."""
    rng = np.random.default_rng(13)
    graphs = [_random_graph(rng, n) for n in (4, 9)]
    spec = PadSpec.for_dataset(graphs, batch_size=2)
    batch = batch_graphs(graphs, spec)
    C = 4
    x = jnp.asarray(rng.normal(size=(batch.num_nodes, C)).astype(np.float32))
    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    flash = MultiheadSelfAttention(
        channels=C, heads=2, max_nodes_per_graph=6, use_flash_attention=True
    )
    variables = flash.init(jax.random.PRNGKey(0), x, batch)
    out = flash.apply(variables, x, batch)
    assert np.isnan(np.asarray(out)).all()


@pytest.mark.slow  # ~20s of jit; the multichip dryrun + BENCH_GPS smoke
# run the same model-level flash==dense contract in every CI tier
def pytest_gps_model_train_step_flash_equals_dense(monkeypatch):
    """Full GPS model (GIN + multihead attention around every conv): one
    train step from identical state through the flash route (interpret) and
    the dense oracle gives the same loss — the CPU analog of the multichip
    dryrun's flash leg (__graft_entry__._dryrun_gps_flash)."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import (
        GraphLoader,
        MinMax,
        VariablesOfInterest,
        deterministic_graph_dataset,
        extract_variables,
        split_dataset,
    )
    from hydragnn_tpu.data.lappe import add_dataset_pe
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    raw = deterministic_graph_dataset(16, seed=17)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1])
    ready = add_dataset_pe([extract_variables(g, voi) for g in raw], 1)
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "hidden_dim": 16, "num_conv_layers": 2,
                "global_attn_engine": "GPS", "global_attn_type": "multihead",
                "global_attn_heads": 4, "pe_dim": 1,
                "use_flash_attention": True,
                "output_heads": {"graph": {"num_sharedlayers": 1,
                                            "dim_sharedlayers": 8,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [8, 8]}},
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"], "output_index": [0],
                "type": ["graph"],
            },
            "Training": {"batch_size": 4, "num_epoch": 1,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 1e-3}},
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]},
                    "graph_features": {"dim": [1]}},
    }
    config = update_config(config, tr, va, te)
    model = create_model(config)
    loader = GraphLoader(tr, 4, seed=0, drop_last=True)
    batch = next(iter(loader))
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    losses = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", flag)
        state = TrainState.create(
            jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                   variables), tx,
        )
        step = make_train_step(model, tx)
        _, tot, _ = step(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(tot))
        losses[flag] = float(tot)
    assert abs(losses["1"] - losses["0"]) <= 1e-5 * max(
        1.0, abs(losses["0"])
    ), losses


def pytest_flash_config_completion(monkeypatch):
    """use_flash_attention completes like the other kernel flags: TPU jit
    target + GPS => on, no GPS => off, explicit value wins; the key lints
    as handled."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.config.lint import lint_config

    rng = np.random.default_rng(19)
    graphs = [_random_graph(rng, n) for n in (4, 6, 5)]
    import dataclasses

    ready = [
        dataclasses.replace(
            g,
            graph_targets={"y": np.zeros((1,), np.float32)},
        )
        for g in graphs
    ]

    def cfg(**arch_extra):
        arch = {
            "mpnn_type": "GIN", "hidden_dim": 8, "num_conv_layers": 1,
            "output_heads": {"graph": {"num_sharedlayers": 1,
                                        "dim_sharedlayers": 4,
                                        "num_headlayers": 1,
                                        "dim_headlayers": [4]}},
            "task_weights": [1.0],
        }
        arch.update(arch_extra)
        return {
            "NeuralNetwork": {
                "Architecture": arch,
                "Variables_of_interest": {
                    "input_node_features": [0], "output_names": ["y"],
                    "output_index": [0], "type": ["graph"],
                },
                "Training": {"batch_size": 2, "num_epoch": 1},
            },
            "Dataset": {"node_features": {"dim": [1]},
                        "graph_features": {"dim": [1]}},
        }

    import hydragnn_tpu.config.config as config_mod

    monkeypatch.setattr(
        config_mod, "_jit_target_inference", lambda: (True, "test: tpu")
    )
    done = update_config(
        cfg(global_attn_engine="GPS", global_attn_type="multihead",
            global_attn_heads=2, pe_dim=1),
        ready, ready, ready,
    )
    assert done["NeuralNetwork"]["Architecture"]["use_flash_attention"] is True
    done_off = update_config(cfg(), ready, ready, ready)
    assert done_off["NeuralNetwork"]["Architecture"]["use_flash_attention"] is False
    explicit = update_config(
        cfg(global_attn_engine="GPS", global_attn_type="multihead",
            global_attn_heads=2, pe_dim=1, use_flash_attention=False),
        ready, ready, ready,
    )
    assert explicit["NeuralNetwork"]["Architecture"]["use_flash_attention"] is False
    findings = {f.path: f.status for f in lint_config(done)}
    assert findings["NeuralNetwork.Architecture.use_flash_attention"] == "handled"


# ---------------------------------------------------------------------------
# ring reuse: the single-graph regime rides the same inner loop
# ---------------------------------------------------------------------------


def pytest_block_summary_matches_reference():
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.normal(size=(24, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(40, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(40, 2, 16)).astype(np.float32))
    km = jnp.asarray(rng.random(40) > 0.3)
    m, l, acc = flash_block_summary(q, k, v, km, 128, 128, True)
    mr, lr, accr = reference_block_summary(q, k, v, km)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(lr), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(accr),
                               rtol=2e-5, atol=2e-5)
    # fully-masked block: (NEG, 0, 0) — the merge-neutral element
    m0, l0, a0 = flash_block_summary(
        q, k, v, jnp.zeros((40,), bool), 128, 128, True
    )
    assert float(jnp.max(m0)) <= -1e29
    assert float(jnp.abs(l0).max()) == 0.0 and float(jnp.abs(a0).max()) == 0.0


def pytest_ring_flash_matches_dense_fwd_and_grad(monkeypatch):
    """Ring attention with the flash per-chip block (interpret) over the
    8-device mesh == the plain dense-einsum ring, forward and grad."""
    from jax.sharding import Mesh

    from hydragnn_tpu.parallel.ring_attention import sharded_global_attention

    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    rng = np.random.default_rng(23)
    n = 8 * 16
    q = jnp.asarray(rng.normal(size=(n, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(n, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(n, 2, 16)).astype(np.float32))
    mask = jnp.asarray(rng.random(n) > 0.2)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    out_f = sharded_global_attention(mesh, use_flash=True)(q, k, v, mask)
    out_d = sharded_global_attention(mesh, use_flash=False)(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_d), rtol=2e-5, atol=2e-5
    )
    lf = jax.jit(lambda q_: jnp.sum(
        sharded_global_attention(mesh, use_flash=True)(q_, k, v, mask) ** 2
    ))
    ld = jax.jit(lambda q_: jnp.sum(
        sharded_global_attention(mesh, use_flash=False)(q_, k, v, mask) ** 2
    ))
    gf, gd = jax.grad(lf)(q), jax.grad(ld)(q)
    np.testing.assert_allclose(
        np.asarray(gf), np.asarray(gd), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# Performer: the bf16-under-jit leg (the DimeNet-NaN bug class hides until
# a jitted bf16 forward fuses the padding garbage into the real rows)
# ---------------------------------------------------------------------------


def pytest_performer_bf16_under_jit_finite_and_close():
    rng = np.random.default_rng(25)
    graphs = [_random_graph(rng, n) for n in (4, 6, 3)]
    spec = PadSpec.for_dataset(graphs, batch_size=5)
    batch = batch_graphs(graphs, spec)
    C = 8
    x = jnp.asarray(rng.normal(size=(batch.num_nodes, C)).astype(np.float32))
    attn = PerformerSelfAttention(channels=C, heads=2)
    variables = attn.init(jax.random.PRNGKey(0), x, batch)
    out_f32 = attn.apply(variables, x, batch)
    cast = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, t
    )
    out_bf16 = jax.jit(
        lambda v, x_: attn.apply(v, x_, batch)
    )(cast(variables), x.astype(jnp.bfloat16))
    mask = np.asarray(batch.node_mask)
    assert np.isfinite(np.asarray(out_bf16, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(out_bf16, np.float32)[mask],
        np.asarray(out_f32)[mask],
        rtol=1e-1, atol=1e-1,
    )


# ---------------------------------------------------------------------------
# the causal launches' two schedules: the window's loop inside the kernel over
# a head's resident operands, or the grid's third axis over streamed tiles
# (ops/pallas_flash_attention.py CAUSAL_RESIDENT_BYTES). 128 x 128 tiles here.
# ---------------------------------------------------------------------------

from hydragnn_tpu.ops import pallas_flash_attention as pfa  # noqa: E402

# (document sizes, trailing padding, max_nodes_per_graph): the schedule's edges
CAUSAL_PACKS = {
    "boundary_inside_a_tile": ([40, 3, 150, 70, 1], 24, 150),
    "document_of_exactly_max_nodes": ([60, 300, 17], 7, 300),
    "document_spanning_four_tiles": ([50, 420, 30], 12, 512),
    "all_padding_last_query_block": ([100, 60], 200, 128),
    "padding_over_three_blocks": ([100, 60], 420, 128),
}
# (query heads, key/value heads, width of queries and keys, width of values)
CAUSAL_HEADS = {"group4_d16": (4, 1, 16, 16), "mla_192_128": (2, 2, 192, 128)}


def _causal_case(pack, heads, dtype, seed=0):
    sizes, pad, nmax = CAUSAL_PACKS[pack]
    hq, hk, d, dv = CAUSAL_HEADS[heads]
    n = sum(sizes) + pad
    node_graph = jnp.asarray(np.concatenate(
        [np.full(s, i) for i, s in enumerate(sizes)] + [np.full(pad, len(sizes))]).astype(np.int32))
    node_mask = jnp.asarray(np.arange(n) < sum(sizes))
    rng = np.random.default_rng(seed)
    mk = lambda h, w: jnp.asarray(rng.normal(size=(n, h, w)), jnp.float32).astype(dtype)
    q, k, v = mk(hq, d), mk(hk, d), mk(hk, dv)
    w = mk(hq, dv).astype(jnp.float32) * node_mask[:, None, None]
    return (q, k, v), w, node_graph, node_mask, nmax


def _causal_all(fn, ops, w):
    """Output and the three gradients of ``sum(fn(q, k, v) * w)``, float32."""
    f32 = lambda a: a.astype(jnp.float32)
    out = fn(*ops)
    grads = jax.grad(lambda *a: jnp.sum(f32(fn(*a)) * w), (0, 1, 2))(*ops)
    return [f32(out) * (w != 0).any(axis=2, keepdims=True)] + [f32(g) for g in grads]


def _first_grids(closed):
    import re

    return [tuple(int(g) for g in m.split(",") if g.strip())
            for m in re.findall(r"GridMapping\(grid=\(([\d, ]+)\)", str(closed))]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads", list(CAUSAL_HEADS))
@pytest.mark.parametrize("pack", list(CAUSAL_PACKS))
def pytest_causal_in_kernel_loop_matches_reference_and_grid(monkeypatch, pack, heads, dtype):
    """The in-kernel-loop launches (forward, ``dq``, ``dk``/``dv``) against the
    flat masked reference, and against the grid schedule on the same operands:
    the same tiles in the same order, so equal to float32 rounding."""
    ops, w, node_graph, node_mask, nmax = _causal_case(pack, heads, dtype)
    kernel = lambda *a: pfa.flash_causal_attention(*a, node_graph, node_mask, nmax, 128, 128, True)
    grids = _first_grids(jax.make_jaxpr(lambda *a: _causal_all(kernel, a, w))(*ops))
    assert grids and all(len(g) == 2 for g in grids), grids
    loop = _causal_all(kernel, ops, w)
    f32 = lambda a: a.astype(jnp.float32)
    want = _causal_all(lambda *a: pfa.reference_causal_attention(*a, node_graph, node_mask),
                       [f32(a) for a in ops], w)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for got, ref in zip(loop, want):
        assert np.isfinite(np.asarray(got)).all()
        assert float(jnp.abs(got - ref).max()) <= tol * max(float(jnp.abs(ref).max()), 1.0) * 4
    monkeypatch.setattr(pfa, "CAUSAL_RESIDENT_BYTES", 0)
    grids = _first_grids(jax.make_jaxpr(lambda *a: _causal_all(kernel, a, w))(*ops))
    assert all(len(g) == 3 for g in grids), grids
    for got, ref in zip(loop, _causal_all(kernel, ops, w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-6)


def pytest_causal_schedule_follows_the_resident_bytes(monkeypatch):
    """A head's streamed operands (rows x (qk width + value width) x bytes, one
    copy) at the budget: the loop; one byte past it: the grid. One result."""
    # at the constant as it stands: both decoder cells' heads fit, a 128k pack's does not
    fits = lambda n, width: pfa._resident(n, width, jnp.bfloat16)
    assert fits(32768, 256) and fits(16384, 320) and fits(65536, 256) and not fits(131072, 256)
    ops, w, node_graph, node_mask, nmax = _causal_case("document_spanning_four_tiles", "mla_192_128", jnp.bfloat16)
    n_pad = -(-ops[0].shape[0] // 128) * 128
    head_bytes = n_pad * (192 + 128) * 2
    kernel = lambda *a: pfa.flash_causal_attention(*a, node_graph, node_mask, nmax, 128, 128, True)
    results = {}
    for name, budget, axes in (("loop", head_bytes, 2), ("grid", head_bytes - 1, 3)):
        monkeypatch.setattr(pfa, "CAUSAL_RESIDENT_BYTES", budget)
        grids = _first_grids(jax.make_jaxpr(lambda *a: _causal_all(kernel, a, w))(*ops))
        # the forward alone, then the forward, ``dq`` and ``dk``/``dv`` of the gradient
        assert len(grids) == 4 and all(len(g) == axes for g in grids), (name, grids)
        results[name] = _causal_all(kernel, ops, w)
    for a, b in zip(results["loop"], results["grid"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("pack", list(CAUSAL_PACKS))
def pytest_causal_schedule_steps_count_the_windows(monkeypatch, pack):
    """``causal_schedule_steps`` against a replay of the windows in numpy: a
    query block's tiles run from the tile of its first row's document start to
    the tile of its own last real row; a block of padding alone keeps one."""
    _, _, node_graph, node_mask, nmax = _causal_case(pack, "group4_d16", jnp.float32)
    ng, real = np.asarray(node_graph), int(np.asarray(node_mask).sum())
    n, b = ng.shape[0], 128
    visited = 0
    for row0 in range(0, n, b):
        if row0 >= real:
            visited += 1
            continue
        row1 = min(row0 + b - 1, real - 1)
        visited += row1 // b - int(np.searchsorted(ng, ng[row0], side="left")) // b + 1
    got = pfa.causal_schedule_steps(node_graph, node_mask, nmax, 16, 16, jnp.float32, b, b)
    assert [float(x) for x in got] == [visited, visited]
    monkeypatch.setattr(pfa, "CAUSAL_RESIDENT_BYTES", 0)
    q_blocks = -(-n // b)
    k_windows = min((b + nmax - 1 + b - 1) // b + 1, q_blocks)
    got = pfa.causal_schedule_steps(node_graph, node_mask, nmax, 16, 16, jnp.float32, b, b)
    assert [float(x) for x in got] == [visited, q_blocks * k_windows]
    assert visited <= q_blocks * k_windows


# ---------------------------------------------------------------------------
# what a decoder layer's remat keeps of its causal launch (models/decoder.py
# remat_in_training; the tags are ops/pallas_flash_attention.py
# _causal_vjp_fwd's): ``o`` and one ``lse`` number a row, so the forward kernel
# runs once a step. Two layers of a projection, the launch and a residual add.
# ---------------------------------------------------------------------------

from flax import linen as nn  # noqa: E402

from hydragnn_tpu.models import decoder as dc  # noqa: E402

KEPT_WINDOWS = {"full": None, "window96": 96}


class _AttentionLayer(nn.Module):
    heads: tuple  # (query heads, key/value heads, width of queries and keys, width of values)
    window: object
    nmax: int

    @nn.compact
    def __call__(self, x, node_graph, node_mask):
        (hq, hk, d, dv), n = self.heads, x.shape[0]
        cut = (hq * d, (hq + hk) * d)
        y = x @ self.param("w_in", dc.INIT["lecun"], (x.shape[1], cut[1] + hk * dv))
        q, k, v = y[:, :cut[0]], y[:, cut[0]:cut[1]], y[:, cut[1]:]
        o = pfa.flash_causal_attention(q.reshape(n, hq, d), k.reshape(n, hk, d), v.reshape(n, hk, dv),
                                       node_graph, node_mask, self.nmax, 128, 128, True, window=self.window)
        return x + jnp.tanh(o.reshape(n, hq * dv)) @ self.param("w_out", dc.INIT["lecun"], (hq * dv, x.shape[1]))


class _AttentionStack(nn.Module):
    layer_cls: type
    heads: tuple
    window: object
    nmax: int

    @nn.compact
    def __call__(self, x, node_graph, node_mask):
        for i in range(2):
            x = self.layer_cls(self.heads, self.window, self.nmax, name=f"layers_{i}")(x, node_graph, node_mask)
        return jnp.sum(x * x * node_mask[:, None])


# how a layer is wrapped: the decoders' rule, a bare remat, none
KEPT_WRAPS = {"decoders": lambda cls: dc.remat_in_training(cls, True), "bare": nn.remat, "none": lambda cls: cls}


def _stack_grad(wrap, heads, window, seed=3):
    """-> (the gradient function of a two-layer stack over (input, parameters),
    its arguments)."""
    _, _, node_graph, node_mask, nmax = _causal_case("document_spanning_four_tiles", heads, jnp.float32)
    n = node_graph.shape[0]
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n, 32)), jnp.float32)
    stack = _AttentionStack(KEPT_WRAPS[wrap](_AttentionLayer), CAUSAL_HEADS[heads], window, nmax)
    params = _AttentionStack(_AttentionLayer, CAUSAL_HEADS[heads], window, nmax).init(
        jax.random.PRNGKey(seed), x, node_graph, node_mask)
    return jax.grad(lambda x_, p: stack.apply(p, x_, node_graph, node_mask), (0, 1)), (x, params)


def _launch_names(closed):
    """The names of every ``pallas_call`` of a jaxpr, nested jaxprs included."""
    import re

    return re.findall(r"name=(hg_\w+)", str(closed))


@pytest.mark.parametrize("heads", list(CAUSAL_HEADS))
@pytest.mark.parametrize("window", list(KEPT_WINDOWS))
def pytest_decoders_remat_runs_the_causal_forward_once_a_layer(window, heads):
    """The gradient's jaxpr of a two-layer stack: under the decoders' policy
    one forward launch a layer, under the bare remat two; ``dq`` and
    ``dk``/``dv`` one each either way."""
    name = pfa._causal_name(KEPT_WINDOWS[window])
    for wrap, forwards in (("decoders", 2), ("bare", 4), ("none", 2)):
        grad, args = _stack_grad(wrap, heads, KEPT_WINDOWS[window])
        names = _launch_names(jax.make_jaxpr(grad)(*args))
        assert (names.count(name), names.count(name + "_bwd")) == (forwards, 4), (wrap, names)


@pytest.mark.parametrize("heads", list(CAUSAL_HEADS))
@pytest.mark.parametrize("window", list(KEPT_WINDOWS))
def pytest_decoders_remat_gradients_equal_bare_and_unwrapped_to_the_bit(window, heads):
    got = {}
    for wrap in KEPT_WRAPS:
        grad, args = _stack_grad(wrap, heads, KEPT_WINDOWS[window])
        got[wrap] = jax.tree_util.tree_leaves(jax.jit(grad)(*args))
    assert all(float(jnp.abs(g).max()) > 0 for g in got["none"])
    for wrap in ("decoders", "bare"):
        for a, b in zip(got[wrap], got["none"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("schedule", ["loop", "grid"])
@pytest.mark.parametrize("heads", list(CAUSAL_HEADS))
@pytest.mark.parametrize("window", list(KEPT_WINDOWS))
def pytest_causal_backward_reads_the_row_form_lse_to_the_bit(monkeypatch, window, heads, schedule):
    """The forward writes each row's ``lse`` across 128 lanes; ``dq`` streamed
    that array and read lane 0, ``dk``/``dv`` read lane 0 as a row. What is
    kept now is lane 0 alone, and both launches read the row. Every lane holds
    lane 0's bits, so nothing a launch reads has changed, and ``dq``, ``dk``,
    ``dv`` from a row form cut by hand are the rule's own."""
    if schedule == "grid":
        monkeypatch.setattr(pfa, "CAUSAL_RESIDENT_BYTES", 0)
    w = KEPT_WINDOWS[window]
    ops, do, node_graph, node_mask, nmax = _causal_case("document_spanning_four_tiles", heads, jnp.bfloat16)
    o, lse = pfa._causal_fwd(*ops, node_graph, node_mask, nmax, 128, 128, True, w)
    row = lse[:, :, 0]
    assert lse.shape == row.shape + (128,) and row.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(lse), np.broadcast_to(np.asarray(row)[:, :, None], lse.shape))
    _, (q, k, v, o_kept, lse_kept, _, _) = pfa._causal_vjp_fwd(*ops, node_graph, node_mask, nmax, 128, 128, True, w)
    np.testing.assert_array_equal(np.asarray(lse_kept), np.asarray(row))
    np.testing.assert_array_equal(np.asarray(o_kept, np.float32), np.asarray(o, np.float32))
    do = do.astype(jnp.bfloat16)
    got = pfa._causal_vjp_bwd(nmax, 128, 128, True, w, (q, k, v, o, row, node_graph, node_mask), do)[:3]
    kernel = lambda *a: pfa.flash_causal_attention(*a, node_graph, node_mask, nmax, 128, 128, True, window=w)
    want = jax.vjp(kernel, *ops)[1](do)
    for a, b in zip(got, want):
        assert float(jnp.abs(b.astype(jnp.float32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
