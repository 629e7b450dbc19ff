"""The second decoder stack (``mpnn_type: "JOYAI"``, models/joyai.py) at a
small size on the CPU that keeps every mechanism: hidden 64, a dense first
layer and 2 expert layers, 16 experts of which 4 are held, 4 a token, a shared
expert, head widths 24 (16 + 8) / 16, the multi-token-prediction module on,
vocabulary 97, documents of 3-40 tokens. The program against the benchmark's
plain reference (benchmarks/reference/joyai.py) on seeded weights; the causal
flash kernel (interpret mode) at unequal head widths; top-k dispatch and
combine against a dense one-hot spelling; the expert shares add up; the
module's mask at document boundaries; a row budget that is overrun poisons
the step."""

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
from hydragnn_tpu.models import decoder as dc  # noqa: E402
from hydragnn_tpu.models import joyai as jm  # noqa: E402
from hydragnn_tpu.ops import pallas_grouped_matmul as gm  # noqa: E402
from hydragnn_tpu.ops.pallas_flash_attention import (  # noqa: E402
    flash_causal_attention, reference_causal_attention)
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train.loop import mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.train.loss import _follows, compute_loss  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import common as rc  # noqa: E402
from reference import joyai as ref  # noqa: E402

VOCAB = 97
SHARES = ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15))


def small_config(held=SHARES[0], mixed=False, capacity=None):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "docs", "node_features": {"name": ["token", "pos", "unused"], "dim": [1, 3, 3]},
                    "graph_features": {"name": ["unused"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "JOYAI", "hidden_dim": 64, "num_conv_layers": 3, "num_attention_heads": 4,
                "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16, "rope_theta": 32.0e6, "intermediate_size": 128, "moe_intermediate_size": 32,
                "n_routed_experts": 16, "num_experts_per_tok": 4, "experts_held": list(held),
                "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1, "expert_row_capacity": capacity,
                "vocab_size": VOCAB, "loss_chunk_rows": 64,
                "output_heads": {"node": {"type": "token", "num_headlayers": 0, "dim_headlayers": []}}},
            "Variables_of_interest": {"input_node_features": [0], "output_names": ["next_token"],
                                      "output_index": [0], "type": ["node"]},
            "Training": {"num_epoch": 1, "batch_size": 8, "pack_batches": True, "pack_node_slots": 160,
                         "pack_graph_slots": 12, "mixed_precision": mixed,
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}}}


@pytest.fixture(scope="module")
def docs():
    return packed_documents_dataset(40, 12.0, 0.8, 3, 40, VOCAB, seed=1)


def build(docs, held=SHARES[0], mixed=False, seed=5, capacity=None):
    config, (loader, _, _), _ = prepare_data(small_config(held, mixed, capacity), (docs[:30], docs[30:35], docs[35:]))
    arch = config["NeuralNetwork"]["Architecture"]
    variables = rc.make_weights(ref.weight_spec(arch, 1), seed)
    # a balancing bias that moves some choices, the same in program and reference
    variables["batch_stats"] = jax.tree_util.tree_map(
        lambda b: b + 0.02 * jnp.arange(b.shape[0], dtype=jnp.float32), variables["batch_stats"])
    return config, arch, loader, create_model(config), variables


@pytest.fixture(scope="module")
def built(docs):
    return build(docs)


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


# float32: the two differ in summation order only (the program sums a token's
# rows by a scatter-add, the reference loops over the experts). bfloat16: every
# product's operands and the residual stream are rounded to 2^-8 and a top-4
# choice at a near tie may fall the other way; the loss is a mean over ~150
# tokens of a value near log(97), read to 1%; a leaf's gradient norm to 10%.
@pytest.mark.parametrize("mixed,loss_tol,grad_tol", [(False, 1e-5, 2e-4), (True, 1e-2, 1e-1)])
def pytest_program_matches_reference_loss_and_every_gradient_leaf(docs, mixed, loss_tol, grad_tol):
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    batch = next(iter(loader))
    loss, grads = jax.value_and_grad(program_loss(model, variables, batch, mixed))(variables["params"])
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss_fn(p, ref_batch(batch), arch, "f32", variables["batch_stats"]))(variables["params"])
    assert abs(float(loss) - float(ref_loss)) <= loss_tol * abs(float(ref_loss))
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    # every mechanism has leaves and every leaf a gradient
    for leaf in ("layers_0/mlp_gate", "layers_1/router", "layers_2/shared_up", "mtp_layer/experts_down",
                 "mtp_proj", "head", "embedding"):
        assert float(jnp.linalg.norm(want[leaf])) > 0, leaf
    norms = {k: float(jnp.linalg.norm(want[k])) for k in want}
    median = float(np.median(list(norms.values())))
    for k in want:
        gap = float(jnp.linalg.norm(got[k].astype(jnp.float32) - want[k])) / max(norms[k], median)
        assert gap <= grad_tol, (k, gap)


# through run_training's own step (make_train_step): three AdamW steps, the
# balancing bias moved by its rule each step; warmup ramps the rate k/N
@pytest.mark.parametrize("mixed,tol,warmup", [(False, 2e-4, 0), (False, 2e-4, 4)])
def pytest_three_adamw_steps_match_reference(docs, mixed, tol, warmup):
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    tx = make_optimizer({**config["NeuralNetwork"]["Training"]["Optimizer"], "warmup_steps": warmup})
    step = make_train_step(model, tx, False, mixed)
    state = TrainState.create(copy.deepcopy(variables), tx)
    batches = [b for _, b in zip(range(3), loader)]
    losses = []
    for i, b in enumerate(batches):
        state, tot, tasks = step(state, b, jax.random.PRNGKey(i))
        losses.append(float(tot))
    assert float(tasks["mtp"]) > 0 and abs(float(tasks["next_token"]) + 0.3 * float(tasks["mtp"]) - losses[-1]) < 1e-5
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
    assert sorted(buffers) == ["router_bias_1", "router_bias_2", "router_bias_mtp"]
    for name, value in buffers.items():
        assert float(jnp.abs(value - variables["batch_stats"][name]).max()) > 0
        np.testing.assert_allclose(np.asarray(state.batch_stats[name]), np.asarray(value), atol=0.0021)
    np.testing.assert_allclose(losses, ref_losses, rtol=max(tol, 1e-5))
    got, want, start = flat(state.params), flat(p), flat(variables["params"])
    moved = {k: float(jnp.linalg.norm(want[k] - start[k])) for k in want}
    median = float(np.median(list(moved.values())))
    for k in want:
        gap = float(jnp.linalg.norm(got[k] - want[k])) / max(moved[k], median)
        assert gap <= 5e-3, (k, gap)


# ---------------------------------------------------------------- the flash kernel at d_qk != d_v

def _packed(sizes, pad):
    graph = np.concatenate([np.full(n, g, np.int32) for g, n in enumerate(sizes)] + [np.full(pad, len(sizes), np.int32)])
    mask = np.concatenate([np.ones(sum(sizes), bool), np.zeros(pad, bool)])
    return jnp.asarray(graph), jnp.asarray(mask)


# float32 operands: the kernel and the oracle differ in summation order.
# (24, 16): widths below a lane tile are padded to it; (192, 128): latent
# attention's own widths, the 192 streamed as it is
@pytest.mark.parametrize("d_qk,d_v,heads", [(24, 16, 3), (192, 128, 2)])
def pytest_causal_flash_kernel_with_values_of_their_own_width(d_qk, d_v, heads):
    sizes, pad = (37, 5, 64, 21), 1
    node_graph, node_mask = _packed(sizes, pad)
    n = int(node_graph.shape[0])
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(n, heads, d_qk)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(n, heads, d_v)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, heads, d_v)), jnp.float32)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * w * node_mask[:, None, None])

    kernel = lambda q_, k_, v_: flash_causal_attention(q_, k_, v_, node_graph, node_mask, max(sizes), 32, 128, True)
    oracle = lambda q_, k_, v_: reference_causal_attention(q_, k_, v_, node_graph, node_mask)
    out = kernel(q, k, v)
    assert out.shape == (n, heads, d_v)
    real = np.asarray(node_mask)
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(oracle(q, k, v))[real], rtol=2e-5, atol=2e-5)
    # both backward launches: dq from one, dk and dv from the other
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for g, wnt, width in zip(got, want, (d_qk, d_qk, d_v)):
        assert g.shape == (n, heads, width)
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- top-k dispatch and combine

def _one_hot_layer(u, choice, gate, node_mask, w_gate, w_up, w_down, held):
    """sum over held e of (sum_j gate[t, j] [choice[t, j] == e]) expert_e(u): dense, no layout."""
    y = jnp.zeros_like(u)
    for slot, e in enumerate(held):
        weight = jnp.sum(jnp.where(choice == e, gate, 0.0), axis=-1) * node_mask
        y = y + weight[:, None] * ((jax.nn.silu(u @ w_gate[slot]) * (u @ w_up[slot])) @ w_down[slot])
    return y


@pytest.mark.parametrize("rows_budget", [0, 64])
def pytest_topk_dispatch_and_combine_equal_the_one_hot_spelling(rows_budget):
    t, d, f, experts, k, held = 24, 16, 8, 8, 3, (2, 3, 5)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    choice = np.stack([rng.permutation(experts)[:k] for _ in range(t)]).astype(np.int32)
    choice[0] = [0, 1, 4]   # a token with no row here
    choice[1] = [2, 3, 5]   # a token with all its rows here
    choice = jnp.asarray(choice)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    node_mask = jnp.asarray(np.arange(t) < t - 2)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(len(held), d, f)), jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(len(held), f, d)), jnp.float32)
    block_m = 16
    layout = dc.topk_layout(choice, node_mask, held, experts, block_m, rows_budget)
    rows_here = int(np.isin(np.asarray(choice), held)[np.asarray(node_mask)].sum())
    assert int(layout["counts"].sum()) == rows_here and int(layout["overrun"]) == 0
    assert int((np.asarray(layout["token"]) == 0).sum()) == 0 and int((np.asarray(layout["token"]) == 1).sum()) == 3
    if rows_budget:
        assert layout["src"].shape[0] == 64
    else:
        assert layout["src"].shape[0] == gm.aligned_rows(t * k, len(held), block_m)

    def program(u_, gate_, w_gate_, w_up_, w_down_):
        rows = dc.dispatch_rows(u_, layout["token"])
        out_rows = dc.expert_products(rows, w_gate_, w_up_, w_down_, layout, block_m, False)
        gate_row = jnp.concatenate([gate_.reshape(-1), jnp.zeros((1,))])[layout["src"]]
        return dc.combine_rows(out_rows, gate_row, layout["token"], t)

    dense = lambda u_, gate_, a, b, c: _one_hot_layer(u_, choice, gate_, node_mask.astype(jnp.float32), a, b, c, held)
    args = (u, gate, w_gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(program(*args)), np.asarray(dense(*args)), rtol=1e-5, atol=1e-5)
    assert not np.asarray(program(*args))[0].any()
    probe = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * probe), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * probe), argnums=(0, 1, 2, 3, 4))(*args)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), rtol=1e-4, atol=1e-4)


def pytest_a_row_budget_that_is_overrun_is_counted_and_never_cut_in_silence():
    slot = jnp.asarray([0] * 40 + [1] * 3 + [2] * 5, jnp.int32)   # 2 = not here
    whole = gm.aligned_layout(slot, 2, 16)
    assert int(whole["overrun"]) == 0 and whole["src"].shape[0] == gm.aligned_rows(48, 2, 16)
    cut = gm.aligned_layout(slot, 2, 16, rows=48)
    # group 0 takes three tiles of 16, so group 1's rows lie past the budget
    assert cut["src"].shape[0] == 48 and int(cut["overrun"]) == 3 and int(cut["n_tiles"]) == 3
    assert np.asarray(cut["dest"])[40:43].tolist() == [48, 48, 48]
    assert sorted(np.asarray(cut["src"])[:40].tolist()) == list(range(40))


def pytest_an_overrun_step_is_poisoned_and_skipped_by_the_guard(docs, monkeypatch):
    """A budget of ONE row tile for four held experts (at this size a tile an
    expert is all a budget is, so the test states a smaller one): the first
    batch overruns it, every output of the model is NaN, the counter says by
    how much, and the guarded step keeps its parameters and counts a skipped
    step."""
    config, arch, loader, model, variables = build(docs, capacity=0.25)
    monkeypatch.setattr(jm.JoyaiConfig, "row_budget", lambda self, tokens, block_m: block_m)
    batch = next(iter(loader))
    out, _ = model.apply(variables, batch, train=True, mutable=["batch_stats"])
    assert float(out[tr.CT_EXPERT_ROWS_OVERRUN]) > 0
    assert np.isnan(np.asarray(out["next_token"])).all() and np.isnan(np.asarray(out[jm.MTP_HIDDEN])).all()
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(copy.deepcopy(variables), tx)
    new, tot, _ = make_train_step(model, tx, False, False)(state, batch, jax.random.PRNGKey(0))
    assert not np.isfinite(float(tot)) and int(new.skipped_steps) == 1
    np.testing.assert_array_equal(np.asarray(new.params["head"]), np.asarray(variables["params"]["head"]))
    # the worst-case buffer (no capacity key) holds any routing: every token on all four held experts
    monkeypatch.undo()
    config, arch, loader, model, variables = build(docs)
    z = model.cfg.joyai
    u = jnp.asarray(np.random.default_rng(3).normal(size=(batch.x.shape[0], 64)), jnp.float32)
    everyone = jnp.tile(jnp.asarray([[1, 0, 2, 3]], jnp.int32), (u.shape[0], 1))
    y, counts, _, (overrun, here) = jm.expert_sublayer(
        variables["params"]["layers_1"], variables["batch_stats"]["router_bias_1"], u, batch.node_mask, z,
        choice=everyone)
    real = int(np.asarray(batch.node_mask).sum())
    assert counts.tolist() == [real] * 4 and int(overrun) == 0 and int(here) == real


# ---------------------------------------------------------------- the shares

def pytest_expert_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(docs):
    """What each chip of a group of four computes of one expert layer (experts
    0-3, 4-7, 8-11, 12-15; router and shared expert whole on each), summed with
    the shared expert counted once, is the reference's layer with all 16 held."""
    config, arch, loader, model, variables = build(docs, held=tuple(range(16)))
    batch = next(iter(loader))
    p_all, beta = variables["params"]["layers_1"], variables["batch_stats"]["router_bias_1"]
    u = jnp.asarray(np.random.default_rng(3).normal(size=(batch.x.shape[0], 64)), jnp.float32)
    m = ref._dims(arch)
    whole, ref_choice = ref.experts(p_all, beta, u, ref_batch(batch), m, "f32")
    shared = ref.gated(u, p_all["shared_gate"], p_all["shared_up"], p_all["shared_down"], "f32")
    total, rows = jnp.zeros_like(u), 0
    for held in SHARES:
        z = jm.JoyaiConfig.from_arch({**arch, "experts_held": list(held)})
        p = dict(p_all, **{k: p_all[k][jnp.asarray(held)] for k in ("experts_gate", "experts_up", "experts_down")})
        y, counts, every, (overrun, _) = jm.expert_sublayer(p, beta, u, batch.node_mask, z)
        part = y - shared   # each chip adds the whole shared expert: count it once
        assert float(jnp.abs(part).max()) > 0 and int(overrun) == 0
        total, rows = total + part, rows + int(counts.sum())
        choice, _ = jm.route(p, beta, u, z)
        np.testing.assert_array_equal(np.asarray(choice), np.asarray(ref_choice))   # the router is whole on every chip
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(np.asarray(total + shared)[real], np.asarray(whole)[real], rtol=1e-5, atol=1e-5)
    assert rows == 4 * int(real.sum())   # every assignment of every real token lands on exactly one share


# ---------------------------------------------------------------- the module's mask

def pytest_module_mask_at_document_boundaries(built):
    config, arch, loader, model, variables = built
    batch = next(iter(loader))
    sizes = np.asarray(batch.nodes_per_graph)[np.asarray(batch.graph_mask)]
    two = np.asarray(_follows(batch, 2))
    assert int(two.sum()) == int(np.maximum(sizes - 2, 0).sum())
    start = 0
    for n in sizes:   # the last two nodes of every document are out, the others in
        assert two[start:start + n].tolist() == [1.0] * max(n - 2, 0) + [0.0] * min(n, 2)
        start += n
    out, _ = model.apply(variables, batch, train=True, mutable=["batch_stats"])
    assert float(out[tr.CT_MTP_PAIRS]) == two.sum()
    # the module never sees the next document: changing every token of the LAST real document leaves the
    # module's state on all nodes before it as it was
    first_of_last = int(sizes[:-1].sum())
    other = batch.replace(z=batch.z.at[first_of_last:].set((batch.z[first_of_last:] + 7) % VOCAB))
    out2, _ = model.apply(variables, other, train=True, mutable=["batch_stats"])
    np.testing.assert_array_equal(np.asarray(out[jm.MTP_HIDDEN])[:first_of_last],
                                  np.asarray(out2[jm.MTP_HIDDEN])[:first_of_last])
    assert np.abs(np.asarray(out[jm.MTP_HIDDEN])[first_of_last:] - np.asarray(out2[jm.MTP_HIDDEN])[first_of_last:]).max() > 0


# ---------------------------------------------------------------- configuration, cast, counters

def pytest_mp_cast_leaves_ids_int32_and_the_router_float32(built):
    config, arch, loader, model, variables = built
    batch = next(iter(loader))
    p, b = mp_cast(variables["params"], batch, False, mp_keep(model))
    assert b.z.dtype == jnp.int32
    assert p["layers_1"]["router"].dtype == jnp.float32 and p["mtp_layer"]["router"].dtype == jnp.float32
    assert p["layers_1"]["experts_gate"].dtype == jnp.bfloat16 and p["head"].dtype == jnp.bfloat16


@pytest.mark.parametrize("edit,message", [
    ({"experts_held": [3, 1]}, "experts_held"),
    ({"experts_held": [16]}, "experts_held"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"kv_lora_rank": None}, "kv_lora_rank"),
    ({"expert_row_capacity": -1.0}, "expert_row_capacity"),
])
def pytest_config_completion_refuses_a_bad_joyai_key_at_once(docs, edit, message):
    cfg = small_config()
    cfg["NeuralNetwork"]["Architecture"].update(edit)
    with pytest.raises(ValueError, match=message):
        prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))


def pytest_row_budget_is_the_capacity_times_the_balanced_rows_plus_a_tile_an_expert():
    arch = {**small_config()["NeuralNetwork"]["Architecture"], "rope_interleave": True, "n_shared_experts": 1,
            "first_k_dense_replace": 1, "norm_topk_prob": True, "mtp_loss_weight": 0.3, "rms_norm_eps": 1e-6}
    z = jm.JoyaiConfig.from_arch({**arch, "n_routed_experts": 256, "num_experts_per_tok": 8,
                                  "experts_held": list(range(16)), "expert_row_capacity": 2.0})
    assert z.row_budget(16384, 512) == 16384 + 16 * 512   # the cell's: twice T / 2, a tile a held expert
    assert jm.JoyaiConfig.from_arch(arch).row_budget(16384, 512) == 0   # no key: the worst case


def pytest_step_counters_reach_the_tracer_at_the_epoch_drain(built):
    from hydragnn_tpu.train.loop import train_epoch

    config, arch, loader, model, variables = built
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    step = make_train_step(model, tx, False, False)
    tr.reset()
    tr.enable()
    try:
        _, _, tasks, _, _ = train_epoch(loader, step, TrainState.create(copy.deepcopy(variables), tx),
                                        jax.random.PRNGKey(0))
        regions = tr.get_regions()
    finally:
        tr.disable()
        tr.reset()
    real_tokens = sum(int(np.asarray(b.node_mask).sum()) for b in loader)
    assert regions[tr.CT_TOKENS]["total"] == 3 * real_tokens   # two expert layers and the module's
    assert 0 < regions[tr.CT_EXPERT_ROWS_HERE]["total"] <= 4 * regions[tr.CT_TOKENS]["total"]
    assert 0 < regions[tr.CT_TOKENS_ROUTED_HERE]["total"] <= regions[tr.CT_EXPERT_ROWS_HERE]["total"]
    assert regions[tr.CT_EXPERT_ROWS_OVERRUN]["total"] == 0
    assert regions[tr.CT_EXPERT_LOAD_MAX]["total"] >= regions[tr.CT_EXPERT_LOAD_MEAN]["total"] > 0
    mtp = sum(int(np.maximum(np.asarray(b.nodes_per_graph)[np.asarray(b.graph_mask)] - 2, 0).sum()) for b in loader)
    assert regions[tr.CT_MTP_PAIRS]["total"] == mtp
    # one query block, one key tile a step; the head is resident: no step more
    steps = sum(1 for _ in loader)
    assert regions[tr.CT_FLASH_TILES_VISITED]["total"] == steps
    assert regions[tr.CT_FLASH_STEPS_SCHEDULED]["total"] == steps
    assert tr.CT_CAUSAL_PAIRS in tasks and "next_token" in tasks and "mtp" in tasks


def pytest_a_training_step_runs_each_blocks_flash_forward_once(built, flash_forward_once):
    config, arch, loader, model, variables = built
    flash_forward_once(config, loader, model, variables, 4)  # three layers and the module's


def pytest_expert_rule_places_the_joyai_expert_banks():
    from hydragnn_tpu.parallel import rules

    rule = rules.expert_rule(4)
    assert rule.compiled().search("mtp_layer/experts_down") and rule.compiled().search("layers_1/experts_gate")
    assert not rule.compiled().search("layers_1/shared_gate") and not rule.compiled().search("layers_1/router")


# ---------------------------------------------------------------- the benchmark's own pieces

def pytest_forward_flops_agree_with_the_dot_count_of_the_reference(built):
    """`forward_flops` (what `step_mfu.train` reads) against the dot count of
    the reference's lowered forward pass (run-scripts/flops_audit.py
    `dot_flops_by_shape`, as benchmarks/tests/flops_check.py counts the
    program's step). A loop's body is in the text once: the reference's loop
    over the held experts counts as ONE expert on every row, so the count is
    asked for that many rows; its [T, T] attention products, which the count
    leaves out by design, are added by hand. Band: 1% (what is left are the
    router's and the norms' small products). As the benchmark calls it, on
    real tokens and balanced rows, it stays BELOW the reference's count: a
    share of the peak read from it cannot read high."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("flops_audit", os.path.join(_REPO, "run-scripts", "flops_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    config, arch, loader, model, variables = built
    b = ref_batch(next(iter(loader)))
    t = int(b["x"].shape[0])
    text = jax.jit(lambda p: ref.loss_and_loads(p, b, arch, "f32", variables["batch_stats"])[0]).lower(
        variables["params"]).as_text()
    dots = float(sum(audit.dot_flops_by_shape(text).values()))
    m = ref._dims(arch)
    blocks, expert_layers = m["layers"] + m["mtp"], m["layers"] + m["mtp"] - m["first"]
    attention = blocks * 2.0 * m["H"] * t * t * ((m["dn"] + m["dr"]) + m["dv"])
    one_expert_every_row = ref.forward_flops(arch, 1, t, 0, 0, rows_routed=t * expert_layers)
    assert abs(one_expert_every_row + attention - dots) <= 0.01 * dots, (one_expert_every_row, attention, dots)
    real = float(jnp.sum(b["node_w"]))
    assert ref.forward_flops(arch, 1, real, 0, 0) < dots - attention


def pytest_scope_seconds_are_read_from_the_compiled_steps_op_names(built):
    """`drive_train_tokens_lean.py`: every scope of the step is found in the
    compiled step's metadata, and a span's rows are summed by it."""
    import drive_train_tokens_lean as lean

    config, arch, loader, model, variables = built
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    log = lean._StepText(make_train_step(model, tx, False, False))
    batch = next(iter(loader))
    log(TrainState.create(copy.deepcopy(variables), tx), batch, jax.random.PRNGKey(0))
    log.read_names()
    for scope in lean.SCOPES:
        assert any(scope in v for v in log.names.values()), scope
    key, op_name = next((k, v) for k, v in log.names.items() if "hg_mtp" in v and "hg_router" in v)
    plane = "/device:TPU:0"
    rows = [(plane, "XLA Ops", f"{key.split(' ')[0]} = {key.split(' ')[1]}{{0}} fusion(...)", 0, 2_000_000_000),
            (plane, "XLA Ops", "%unknown.1 = f32[4]{0} fusion(...)", 0, 1_000_000_000),
            (plane, "XLA Modules", "jit_train_step", 0, 3_000_000_000)]
    got = lean.scope_seconds(rows, log.names, 1)
    assert got["hg_mtp"] == 2.0 and got["hg_router"] == 2.0 and got["hg_optimizer"] == 0.0


def pytest_joyai_example_trains_through_run_training():
    import json

    import hydragnn_tpu
    from hydragnn_tpu.data.pipeline import split_dataset

    with open(os.path.join(_REPO, "examples", "joyai_flash", "joyai_flash.json")) as f:
        config = json.load(f)
    config["Verbosity"]["level"] = 0
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    ds = packed_documents_dataset(48, 24.0, 0.7, 4, 96, config["NeuralNetwork"]["Architecture"]["vocab_size"], seed=0)
    _, _, hist, done, _, _ = hydragnn_tpu.run_training(config, datasets=split_dataset(ds, 0.8, seed=0))
    assert hist["train"][1] < hist["train"][0]
    assert done["NeuralNetwork"]["Architecture"]["experts_held"] == list(range(16))
