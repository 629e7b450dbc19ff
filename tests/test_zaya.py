"""The decoder stack (``mpnn_type: "ZAYA"``, models/zaya.py) at a small size
on the CPU: hidden 64, 2 layers, 4 experts of which 2 are held, vocabulary 97,
documents of 3-40 tokens. The program against the benchmark's plain reference
(benchmarks/reference/zaya.py) on seeded weights; the expert shares add up;
the causal flash kernel and the grouped expert kernel (interpret mode) against
their jnp references; documents never see each other; no token is dropped."""

import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmarks")
for _p in (_REPO, _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from hydragnn_tpu.api import prepare_data  # noqa: E402
from hydragnn_tpu.data.synthetic import packed_documents_dataset  # noqa: E402
from hydragnn_tpu.models import create_model  # noqa: E402
from hydragnn_tpu.models import zaya as zm  # noqa: E402
from hydragnn_tpu.ops import pallas_grouped_matmul as gm  # noqa: E402
from hydragnn_tpu.ops.pallas_flash_attention import (  # noqa: E402
    flash_causal_attention, reference_causal_attention)
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train.loop import mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.train.loss import chunked_cross_entropy, compute_loss  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import common as rc  # noqa: E402
from reference import zaya as ref  # noqa: E402

VOCAB = 97


def small_config(held=(0, 1), mixed=False):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "docs", "node_features": {"name": ["token", "pos", "unused"], "dim": [1, 3, 3]},
                    "graph_features": {"name": ["unused"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "ZAYA", "hidden_dim": 64, "num_conv_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4, "experts_held": list(held),
                "moe_intermediate_size": 48, "router_hidden_size": 24, "vocab_size": VOCAB,
                "loss_chunk_rows": 64,
                "output_heads": {"node": {"type": "token", "num_headlayers": 0, "dim_headlayers": []}}},
            "Variables_of_interest": {"input_node_features": [0], "output_names": ["next_token"],
                                      "output_index": [0], "type": ["node"]},
            "Training": {"num_epoch": 1, "batch_size": 8, "pack_batches": True, "pack_node_slots": 160,
                         "pack_graph_slots": 12, "mixed_precision": mixed,
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}}}


@pytest.fixture(scope="module")
def docs():
    return packed_documents_dataset(40, 12.0, 0.8, 3, 40, VOCAB, seed=1)


def build(docs, held=(0, 1), mixed=False, seed=5):
    config, (loader, _, _), _ = prepare_data(small_config(held, mixed), (docs[:30], docs[30:35], docs[35:]))
    arch = config["NeuralNetwork"]["Architecture"]
    variables = rc.make_weights(ref.weight_spec(arch, 1), seed)
    # a balancing bias that moves some choices, the same in program and reference
    variables["batch_stats"] = jax.tree_util.tree_map(
        lambda b: b + 0.05 * jnp.arange(b.shape[0], dtype=jnp.float32), variables["batch_stats"])
    return config, arch, loader, create_model(config), variables


def ref_batch(batch):
    return {"x": jnp.asarray(batch.x, jnp.float32), "node_graph": jnp.asarray(batch.node_graph),
            "node_w": jnp.asarray(batch.node_mask, jnp.float32)}


def flat(tree):
    return {"/".join(str(k.key) for k in p): v for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def program_loss(model, variables, batch, mixed=False):
    def f(params):
        p, b = mp_cast(params, batch, False, mp_keep(model)) if mixed else (params, batch)
        out = compute_loss(model, {"params": p, "batch_stats": variables["batch_stats"]}, b, model.cfg,
                           True, jax.random.PRNGKey(0), False)
        return out[0].astype(jnp.float32)
    return f


# float32: the two differ in summation order only. bfloat16: every product's
# operands and the residual stream are rounded to 2^-8; the loss is a mean over
# ~150 tokens of a value near log(97), read to 1%; a leaf's gradient norm to 8%.
@pytest.mark.parametrize("mixed,loss_tol,grad_tol", [(False, 1e-5, 2e-4), (True, 1e-2, 8e-2)])
def pytest_program_matches_reference_loss_and_every_gradient_leaf(docs, mixed, loss_tol, grad_tol):
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    batch = next(iter(loader))
    loss, grads = jax.value_and_grad(program_loss(model, variables, batch, mixed))(variables["params"])
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss_fn(p, ref_batch(batch), arch, "f32", variables["batch_stats"]))(variables["params"])
    assert abs(float(loss) - float(ref_loss)) <= loss_tol * abs(float(ref_loss))
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    norms = {k: float(jnp.linalg.norm(want[k])) for k in want}
    median = float(np.median(list(norms.values())))
    for k in want:
        gap = float(jnp.linalg.norm(got[k].astype(jnp.float32) - want[k])) / max(norms[k], median)
        assert gap <= grad_tol, (k, gap)


# warmup: Optimizer.warmup_steps ramps the rate k/N over the first N optimizer
# steps; the reference's three steps run at 1/4, 2/4, 3/4 of it
@pytest.mark.parametrize("mixed,tol,warmup", [(False, 2e-4, 0), (True, 2e-2, 0), (False, 2e-4, 4)])
def pytest_three_adamw_steps_match_reference(docs, mixed, tol, warmup):
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    tx = make_optimizer({**config["NeuralNetwork"]["Training"]["Optimizer"], "warmup_steps": warmup})
    step = make_train_step(model, tx, False, mixed)
    state = TrainState.create(copy.deepcopy(variables), tx)
    batches = [b for _, b in zip(range(3), loader)]
    losses = []
    for i, b in enumerate(batches):
        state, tot, _ = step(state, b, jax.random.PRNGKey(i))
        losses.append(float(tot))
    p = variables["params"]
    opt = {"mu": jax.tree_util.tree_map(jnp.zeros_like, p), "nu": jax.tree_util.tree_map(jnp.zeros_like, p),
           "t": jnp.zeros((), jnp.float32)}
    ref_losses, buffers = [], variables["batch_stats"]
    for i, b in enumerate(batches):
        (loss, loads), g = jax.value_and_grad(
            lambda q: ref.loss_and_loads(q, ref_batch(b), arch, "f32", buffers), has_aux=True)(p)
        p, opt = rc.adamw_update(p, g, opt, 1e-3 * (min((i + 1) / warmup, 1.0) if warmup else 1.0))
        buffers = ref.balance(buffers, loads, arch)
        ref_losses.append(float(loss))
    # the balancing bias moved by its rule, three times, the same way in both
    for name, value in buffers.items():
        assert float(jnp.abs(value - variables["batch_stats"][name]).max()) > 0
        np.testing.assert_allclose(np.asarray(state.batch_stats[name]), np.asarray(value), atol=0.0021)
    np.testing.assert_allclose(losses, ref_losses, rtol=max(tol, 1e-5))
    got, want, start = flat(state.params), flat(p), flat(variables["params"])
    moved = {k: float(jnp.linalg.norm(want[k] - start[k])) for k in want}
    median = float(np.median(list(moved.values())))
    for k in want:
        gap = float(jnp.linalg.norm(got[k] - want[k])) / max(moved[k], median)
        assert gap <= (0.35 if mixed else 5e-3), (k, gap)


def _layer_inputs(docs, held):
    config, arch, loader, model, variables = build(docs, held=held)
    batch = next(iter(loader))
    z = model.cfg.zaya
    from hydragnn_tpu.models.base import _node_position_in_graph

    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(batch.x.shape[0], 64)), jnp.float32)
    aux = {"pos": _node_position_in_graph(batch), "node_graph": batch.node_graph, "node_mask": batch.node_mask}
    return z, variables, batch, u, aux


def pytest_expert_shares_add_up_to_the_uncut_layer(docs):
    """Share {0,1} + share {2,3} = all four experts held: what each chip of a
    pair computes, summed, is the uncut expert sublayer."""
    z_all, variables, batch, u, _ = _layer_inputs(docs, (0, 1, 2, 3))
    p_all = variables["params"]["layers_1"]
    beta = variables["batch_stats"]["router_bias_1"]
    s_prev = jnp.asarray(np.random.default_rng(4).normal(size=(u.shape[0], 24)), jnp.float32)
    whole, s_whole, counts, loads = zm.expert_sublayer(p_all, beta, u, s_prev, batch.node_mask, z_all, False)
    parts = []
    for held in ((0, 1), (2, 3)):
        z = zm.ZayaConfig.from_arch({**small_config(held)["NeuralNetwork"]["Architecture"],
                                     "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
                                     "rope_theta": 5e6, "rms_norm_eps": 1e-5})
        p = dict(p_all, **{k: p_all[k][jnp.asarray(held)] for k in ("experts_gate", "experts_up", "experts_down")})
        y, s, c, every = zm.expert_sublayer(p, beta, u, s_prev, batch.node_mask, z, False)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_whole))  # the router is whole on every chip
        np.testing.assert_array_equal(np.asarray(every), np.asarray(loads))
        parts.append((y, c))
    np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]), np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert int(parts[0][1].sum() + parts[1][1].sum()) == int(counts.sum()) == int(batch.node_mask.sum())
    assert float(jnp.abs(parts[0][0]).max()) > 0 and float(jnp.abs(parts[1][0]).max()) > 0


def pytest_no_token_dropped_when_every_token_picks_one_expert(docs):
    z, variables, batch, u, _ = _layer_inputs(docs, (0, 1))
    p = variables["params"]["layers_0"]
    beta = variables["batch_stats"]["router_bias_0"]
    everyone = jnp.ones((u.shape[0],), jnp.int32)  # expert 1, held in slot 1
    y, _, counts, _ = zm.expert_sublayer(p, beta, u, None, batch.node_mask, z, True, choice=everyone)
    real = np.asarray(batch.node_mask)
    assert counts.tolist() == [0, int(real.sum())]
    _, gate, _ = zm.route(p, beta, u, None, z, True)
    hid = jax.nn.silu(u @ p["experts_gate"][1]) * (u @ p["experts_up"][1])
    want = (hid @ p["experts_down"][1]) * gate[:, None]
    np.testing.assert_allclose(np.asarray(y)[real], np.asarray(want)[real], rtol=2e-5, atol=2e-6)
    assert not np.asarray(y)[~real].any()


# ---------------------------------------------------------------- kernels (interpret mode)

def _packed(sizes, pad):
    n = sum(sizes) + pad
    node_graph = np.concatenate([np.full(s, i) for i, s in enumerate(sizes)] + [np.full(pad, len(sizes))])
    return n, jnp.asarray(node_graph.astype(np.int32)), jnp.asarray(np.arange(n) < sum(sizes))


@pytest.mark.parametrize("block_q,block_k", [(32, 128), (64, 128), (128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def pytest_causal_flash_kernel_forward_and_backward(block_q, block_k, dtype):
    """Graph boundaries fall inside tiles (sizes are no multiple of a block);
    grouped-query heads; the tiled backward against the masked reference."""
    n, node_graph, node_mask = _packed([40, 3, 150, 70, 1], 24)
    rng = np.random.default_rng(0)
    mk = lambda h: jnp.asarray(rng.normal(size=(n, h, 16)), jnp.float32).astype(dtype)
    q, k, v, w = mk(4), mk(2), mk(2), mk(4).astype(jnp.float32) * node_mask[:, None, None]
    kernel = lambda q_, k_, v_: flash_causal_attention(q_, k_, v_, node_graph, node_mask, 150, block_q, block_k, True)
    oracle = lambda q_, k_, v_: reference_causal_attention(q_, k_, v_, node_graph, node_mask)
    f32 = lambda a: a.astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    real = np.asarray(node_mask)
    np.testing.assert_allclose(np.asarray(f32(kernel(q, k, v)))[real],
                               np.asarray(oracle(f32(q), f32(k), f32(v)))[real], rtol=tol, atol=tol)
    got = jax.grad(lambda *a: jnp.sum(f32(kernel(*a)) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * w), (0, 1, 2))(f32(q), f32(k), f32(v))
    for g, r in zip(got, want):
        assert float(jnp.abs(f32(g) - r).max()) <= tol * float(jnp.abs(r).max()) * 4


@pytest.mark.parametrize("routing", ["mixed", "one_expert", "none_held"])
def pytest_grouped_expert_kernel_forward_and_both_backward_products(routing):
    rng = np.random.default_rng(1)
    t, groups, k, n = 200, 3, 48, 40
    slot = {"mixed": rng.integers(0, groups + 1, size=t), "one_expert": np.full(t, 1),
            "none_held": np.full(t, groups)}[routing]
    bm, bn, bk = gm.normalize_tiles(t, k, n, 32, 128, 128, "float32")
    lay = gm.aligned_layout(jnp.asarray(slot), groups, bm)
    assert lay["counts"].tolist() == [int((slot == g).sum()) for g in range(groups)]
    x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(groups, k, n)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)

    def through(x_, w_):
        rows = gm.permute_rows(x_, lay["src"], lay["dest"])
        y = gm.grouped_matmul(rows, w_, lay["tile_group"], lay["n_tiles"], bm, bn, bk, True)
        return gm.permute_rows(y, lay["dest"], lay["src"])

    def direct(x_, w_):
        s = jnp.asarray(slot)
        return jnp.einsum("tk,tkn->tn", x_, w_[jnp.minimum(s, groups - 1)]) * (s < groups)[:, None]

    np.testing.assert_allclose(np.asarray(through(x, w)), np.asarray(direct(x, w)), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(through(*a) * cot), (0, 1))(x, w)
    want = jax.grad(lambda *a: jnp.sum(direct(*a) * cot), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


def pytest_grouped_tiles_fit_vmem_and_clamp_to_small_operands():
    assert gm.normalize_tiles(32768, 2048, 2048, dtype="bfloat16") == (512, 1024, 512)
    assert gm.normalize_tiles(32768, 2048, 2048, dtype="float32") == (512, 512, 512)
    assert gm.normalize_tiles(100, 64, 48) == (112, 128, 128)


# ---------------------------------------------------------------- isolation, loss, casts

def pytest_no_information_crosses_a_document_boundary(docs):
    """Perturb every token of one document: the hidden states (so the logits)
    of every other document are bit-identical, through the convolutions, the
    value shift, the attention and the router state."""
    config, arch, loader, model, variables = build(docs)
    batch = next(iter(loader))
    node_graph = np.asarray(batch.node_graph)
    target = 1
    new_z = np.asarray(batch.z).copy()
    new_z[node_graph == target] = (new_z[node_graph == target] + 7) % VOCAB
    run = jax.jit(lambda b: model.apply(variables, b, train=False)["next_token"])
    a, b = np.asarray(run(batch)), np.asarray(run(batch.replace(z=jnp.asarray(new_z))))
    others = np.asarray(batch.node_mask) & (node_graph != target)
    assert np.array_equal(a[others], b[others])
    assert not np.array_equal(a[node_graph == target], b[node_graph == target])


@pytest.mark.parametrize("chunk", [1, 7, 64, 1000])
def pytest_chunked_cross_entropy_equals_unchunked(chunk):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(16, VOCAB)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, VOCAB, size=50))
    w = jnp.asarray(rng.random(50) < 0.8, jnp.float32)

    def whole(h_, head_):
        logits = jnp.dot(h_, head_, precision="highest")
        return jnp.sum(w * (jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, tgt[:, None], -1)[:, 0]))

    got = jax.value_and_grad(lambda *a: chunked_cross_entropy(*a, tgt, w, chunk), (0, 1))(h, head)
    want = jax.value_and_grad(whole, (0, 1))(h, head)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-6)


def pytest_mp_cast_leaves_ids_int32_and_the_router_float32(docs):
    """The model says which leaves stay float32 (`float32_leaves`); the cast
    knows no model's names, and without a statement it casts every leaf."""
    config, arch, loader, model, variables = build(docs)
    batch = next(iter(loader))
    params, cast = mp_cast(variables["params"], batch, False, mp_keep(model))
    assert cast.z.dtype == jnp.int32 and np.array_equal(np.asarray(cast.z), np.asarray(batch.z))
    assert cast.x.dtype == jnp.bfloat16
    leaves = flat(params)
    assert all(v.dtype == (jnp.float32 if k.split("/")[-1].startswith("router_") else jnp.bfloat16)
               for k, v in leaves.items())
    assert mp_keep(object()) is None
    assert all(v.dtype == jnp.bfloat16 for v in flat(mp_cast(variables["params"], batch, False)[0]).values())


def pytest_positions_come_from_node_graph_not_pos(docs):
    """`pos` is bf16 under mp_cast (8191 is not representable): scrambling it
    changes nothing."""
    config, arch, loader, model, variables = build(docs)
    batch = next(iter(loader))
    run = jax.jit(lambda b: model.apply(variables, b, train=False)["next_token"])
    assert np.array_equal(np.asarray(run(batch)), np.asarray(run(batch.replace(pos=batch.pos * 0 + 3.0))))


# ---------------------------------------------------------------- config, counters, packing

@pytest.mark.parametrize("edit,message", [
    ({"head_dim": None}, "needs Architecture keys"),
    ({"experts_held": [1, 0]}, "ascending"),
    ({"experts_held": [0, 4]}, "below num_experts"),
    ({"num_key_value_heads": 3}, "multiple of num_key_value_heads"),
])
def pytest_config_completion_refuses_a_bad_zaya_key_at_once(docs, edit, message):
    cfg = small_config()
    cfg["NeuralNetwork"]["Architecture"].update(edit)
    with pytest.raises(ValueError, match=message):
        prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))


def pytest_pack_slots_state_the_budget_and_bound_the_bins(docs):
    config, (loader, _, _), _ = prepare_data(small_config(), (docs[:30], docs[30:35], docs[35:]))
    assert (loader.spec.n_nodes, loader.spec.n_graphs) == (160, 12)
    for b in loader:
        assert b.x.shape[0] == 160 and int(np.asarray(b.graph_mask).sum()) <= 11
        assert int(np.asarray(b.node_mask).sum()) <= 159
    cfg = small_config()
    cfg["NeuralNetwork"]["Training"]["pack_node_slots"] = 20  # the longest document has more
    with pytest.raises(ValueError, match="pack_node_slots"):
        prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))


def pytest_step_counters_reach_the_tracer_at_the_epoch_drain(docs):
    from hydragnn_tpu.train.loop import train_epoch

    config, arch, loader, model, variables = build(docs)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    step = make_train_step(model, tx, False, False)
    tr.reset()
    tr.enable()
    try:
        _, _, tasks, _, _ = train_epoch(loader, step, TrainState.create(variables, tx), jax.random.PRNGKey(0))
        regions = tr.get_regions()
    finally:
        tr.disable()
        tr.reset()
    real_tokens = sum(int(np.asarray(b.node_mask).sum()) for b in loader)
    assert regions[tr.CT_TOKENS]["total"] == 2 * real_tokens  # two expert layers
    assert 0 < regions[tr.CT_TOKENS_ROUTED_HERE]["total"] <= regions[tr.CT_TOKENS]["total"]
    assert regions[tr.CT_EXPERT_LOAD_MAX]["total"] >= regions[tr.CT_EXPERT_LOAD_MEAN]["total"] > 0
    pairs = sum(int(n) * (int(n) + 1) // 2 for b in loader
                for n in np.asarray(b.nodes_per_graph)[np.asarray(b.graph_mask)])
    assert regions[tr.CT_CAUSAL_PAIRS]["total"] == pairs
    # 160 node slots are one query block with one key tile in its window; a
    # head this small is resident, so the schedule runs the visited tiles only
    steps = sum(1 for _ in loader)
    assert regions[tr.CT_FLASH_TILES_VISITED]["total"] == steps
    assert regions[tr.CT_FLASH_STEPS_SCHEDULED]["total"] == steps
    assert tr.CT_TOKENS in tasks and "next_token" in tasks


def pytest_a_training_step_runs_each_blocks_flash_forward_once(docs, flash_forward_once):
    config, arch, loader, model, variables = build(docs)
    flash_forward_once(config, loader, model, variables, 2)  # two layers


def pytest_expert_rule_places_the_expert_axis_and_nothing_else():
    from hydragnn_tpu.parallel import rules

    rule = rules.expert_rule(2)
    leaf = np.zeros((2, 64, 48), np.float32)
    assert rule.compiled().search("layers_0/experts_gate") and rule.admits(leaf, {"model": 1})
    assert rule.admits(leaf, {"model": 2}) and not rule.admits(np.zeros((3, 4, 4)), {"model": 1})
    assert not rule.compiled().search("layers_0/router_out") and not rule.compiled().search("embedding")


def pytest_zaya_example_trains_through_run_training():
    import json

    import hydragnn_tpu
    from hydragnn_tpu.data.pipeline import split_dataset

    with open(os.path.join(_REPO, "examples", "zaya1", "zaya1.json")) as f:
        config = json.load(f)
    config["Verbosity"]["level"] = 0
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    ds = packed_documents_dataset(48, 24.0, 0.7, 4, 96, config["NeuralNetwork"]["Architecture"]["vocab_size"], seed=0)
    _, _, hist, done, _, _ = hydragnn_tpu.run_training(config, datasets=split_dataset(ds, 0.8, seed=0))
    assert hist["train"][1] < hist["train"][0]
    assert done["NeuralNetwork"]["Architecture"]["max_nodes_per_graph"] == max(g.num_nodes for g in ds)
