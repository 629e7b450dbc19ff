"""The third decoder stack (``mpnn_type: "AFMOE"``, models/afmoe.py: Trinity-Mini's
``model_type``) at a small size on the CPU that keeps every mechanism: hidden
64, one dense layer and one period of 3 sliding + 1 full expert layers, window
16 with documents of 5-60 tokens (most tokens lie past the window), 4 query /
2 key-value heads of 16, 16 experts of which 4 are held, 4 a token, a shared
expert, the gate, the head norms, four norms a layer, the embedding scale,
vocabulary 97. The program against the benchmark's plain reference
(benchmarks/reference/afmoe.py) on seeded weights; the causal flash kernel
(interpret mode) with a sliding window; a window that is ignored and a full
layer that is rotated are both caught; the window's pair counter; the expert
shares add up."""

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
from hydragnn_tpu.models import afmoe as am  # noqa: E402
from hydragnn_tpu.models import create_model  # noqa: E402
from hydragnn_tpu.models import decoder as dc  # noqa: E402
from hydragnn_tpu.ops import pallas_flash_attention as pfa  # noqa: E402
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train.loop import mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.train.loss import compute_loss  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import afmoe as ref  # noqa: E402
from reference import common as rc  # noqa: E402

VOCAB = 97
WINDOW = 16
SHARES = ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15))
KINDS = [am.SLIDING] * 4 + [am.FULL]


def small_config(held=SHARES[0], mixed=False, capacity=None):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "docs", "node_features": {"name": ["token", "pos", "unused"], "dim": [1, 3, 3]},
                    "graph_features": {"name": ["unused"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "AFMOE", "hidden_dim": 64, "num_conv_layers": 5, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 16, "layer_types": list(KINDS), "sliding_window": WINDOW,
                "rope_theta": 1.0e4, "intermediate_size": 128, "moe_intermediate_size": 32,
                "num_experts": 16, "num_experts_per_tok": 4, "experts_held": list(held), "num_dense_layers": 1,
                "route_scale": 2.826, "load_balance_coeff": 0.001, "expert_row_capacity": capacity,
                "vocab_size": VOCAB, "loss_chunk_rows": 64,
                "output_heads": {"node": {"type": "token", "num_headlayers": 0, "dim_headlayers": []}}},
            "Variables_of_interest": {"input_node_features": [0], "output_names": ["next_token"],
                                      "output_index": [0], "type": ["node"]},
            "Training": {"num_epoch": 1, "batch_size": 8, "pack_batches": True, "pack_node_slots": 192,
                         "pack_graph_slots": 12, "mixed_precision": mixed,
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}}}


@pytest.fixture(scope="module")
def docs():
    return packed_documents_dataset(40, 28.0, 0.6, 5, 60, VOCAB, seed=1)


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


def gaps(model, arch, variables, batch, mixed=False):
    """-> (relative loss gap, [worst, median] leaf's gradient gap) of the
    program against the reference, and the reference's gradients by leaf."""
    loss, grads = jax.value_and_grad(program_loss(model, variables, batch, mixed))(variables["params"])
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss_fn(p, ref_batch(batch), arch, "f32", variables["batch_stats"]))(variables["params"])
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    norms = {k: float(jnp.linalg.norm(want[k])) for k in want}
    median = float(np.median(list(norms.values())))
    leaf = [float(jnp.linalg.norm(got[k].astype(jnp.float32) - want[k])) / max(norms[k], median) for k in want]
    return abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)), [max(leaf), float(np.median(leaf))], want


# float32: the two differ in summation order only (the program sums a token's
# rows by a scatter-add, the reference loops over the experts): worst leaf
# 4e-7 on two seeds. bfloat16: every product's operands and the stream are
# rounded to 2^-8 and a top-4 choice at a near tie may fall the other way; the
# loss is a mean over ~170 tokens of a value near log(97), read to 1% (9e-5 /
# 3.4e-4 on two seeds). A held expert sees about 40 tokens here, so ONE
# flipped choice moves its leaves by 1/40 of their tokens: the worst leaf (an
# `experts_*`) read 0.011 / 0.127 over two seeds, the median leaf 0.0065 /
# 0.0073. Worst leaf 0.4, median 0.03.
@pytest.mark.parametrize("mixed,loss_tol,grad_tol", [(False, 1e-5, (5e-4, 5e-4)), (True, 1e-2, (0.4, 0.03))])
def pytest_program_matches_reference_loss_and_every_gradient_leaf(docs, mixed, loss_tol, grad_tol):
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    batch = next(iter(loader))
    # most tokens lie past the window: the sliding and the full layers differ
    n_g = np.asarray(batch.nodes_per_graph)[np.asarray(batch.graph_mask)]
    assert np.maximum(n_g - WINDOW, 0).sum() > 0.4 * n_g.sum()
    loss_gap, grad_gap, want = gaps(model, arch, variables, batch, mixed)
    assert loss_gap <= loss_tol and grad_gap[0] <= grad_tol[0] and grad_gap[1] <= grad_tol[1], (loss_gap, grad_gap)
    # every mechanism has leaves and every leaf a gradient
    for leaf in ("layers_0/mlp_gate", "layers_0/attn_gate", "layers_1/router", "layers_2/shared_up",
                 "layers_3/attn_q_norm", "layers_4/experts_down", "layers_4/attn_out_norm", "head", "embedding"):
        assert float(jnp.linalg.norm(want[leaf])) > 0, leaf


def pytest_a_program_that_ignores_the_window_is_caught(built, monkeypatch):
    """The comparison above FAILS on a program whose sliding layers attend
    the whole document: the worst leaf (a sliding layer's own, 0.37) by 100
    times its float32 band, and the MEDIAN leaf (0.089) too: the sublayers
    write into the stream at a tenth of its size (`afmoe.OUT_GAIN`), so a
    layer's fault reaches the leaves after it."""
    config, arch, loader, model, variables = built
    attend = dc.causal_attention
    monkeypatch.setattr(dc, "causal_attention", lambda q, k, v, aux, max_nodes, window=None: attend(
        q, k, v, aux, max_nodes, None))
    loss_gap, grad_gap, _ = gaps(model, arch, variables, next(iter(loader)))
    assert grad_gap[0] > 100 * 5e-4 and grad_gap[1] > 100 * 5e-4, (loss_gap, grad_gap)


def pytest_a_full_layer_that_is_rotated_is_caught(built, monkeypatch):
    """... and on a program that rotates its full layer's queries and keys,
    as both older stacks rotate every layer (worst leaf 0.45, median 0.037)."""
    config, arch, loader, model, variables = built
    sublayer = am.attention_sublayer
    monkeypatch.setattr(am, "attention_sublayer", lambda *a, rotate: sublayer(*a, rotate=True))
    loss_gap, grad_gap, _ = gaps(model, arch, variables, next(iter(loader)))
    assert grad_gap[0] > 100 * 5e-4 and grad_gap[1] > 50 * 5e-4, (loss_gap, grad_gap)


# through run_training's own step (make_train_step): three AdamW steps, the
# balancing bias moved by the sign rule each step; warmup ramps the rate k/N
@pytest.mark.parametrize("warmup", [0, 4])
def pytest_three_adamw_steps_match_reference(docs, warmup):
    config, arch, loader, model, variables = build(docs)
    tx = make_optimizer({**config["NeuralNetwork"]["Training"]["Optimizer"], "warmup_steps": warmup})
    step = make_train_step(model, tx, False, False)
    state = TrainState.create(copy.deepcopy(variables), tx)
    batches = [b for _, b in zip(range(3), loader)]
    losses = []
    for i, b in enumerate(batches):
        state, tot, tasks = step(state, b, jax.random.PRNGKey(i))
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
    assert sorted(buffers) == [f"router_bias_{i}" for i in (1, 2, 3, 4)]
    for name, value in buffers.items():
        # the sign rule: every expert's bias moves by the rate a step, three steps (a load at the mean: by none);
        # a near-tie in a choice may move one load across the mean between program and reference: two rates
        moved = np.abs(np.asarray(value - variables["batch_stats"][name]))
        assert moved.max() <= 0.0031 and moved.max() > 0.0009
        np.testing.assert_allclose(np.asarray(state.batch_stats[name]), np.asarray(value), atol=0.0021)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    got, want, start = flat(state.params), flat(p), flat(variables["params"])
    moved = {k: float(jnp.linalg.norm(want[k] - start[k])) for k in want}
    median = float(np.median(list(moved.values())))
    for k in want:
        gap = float(jnp.linalg.norm(got[k] - want[k])) / max(moved[k], median)
        assert gap <= 5e-3, (k, gap)


# ---------------------------------------------------------------- the flash kernel with a sliding window

# (document sizes, trailing padding): 128 x 128 tiles; a boundary inside a
# window, a document of several tiles, documents shorter than every window
WINDOW_PACKS = {
    "boundary_inside_a_window": ([40, 3, 150, 70, 1], 24),
    "document_spanning_four_tiles": ([50, 420, 30], 12),
    "padding_over_two_blocks": ([100, 60], 300),
}


def _window_case(pack, seed=0, hq=4, hk=2, d=16):
    sizes, pad = WINDOW_PACKS[pack]
    n = sum(sizes) + pad
    node_graph = jnp.asarray(np.concatenate(
        [np.full(s, i) for i, s in enumerate(sizes)] + [np.full(pad, len(sizes))]).astype(np.int32))
    node_mask = jnp.asarray(np.arange(n) < sum(sizes))
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
    q, k, v = mk(hq), mk(hk), mk(hk)
    w = mk(hq) * node_mask[:, None, None]
    return (q, k, v), w, node_graph, node_mask, max(sizes)


def _all(fn, ops, w):
    """Output and the three gradients of ``sum(fn(q, k, v) * w)``."""
    out = fn(*ops)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2))(*ops)
    return [out * (w != 0).any(axis=2, keepdims=True)] + list(grads)


# smaller than a tile (16, 100), a tile (128), larger than a tile (129, 200), larger than every document (1000)
@pytest.mark.parametrize("window", [16, 100, 128, 129, 200, 1000])
@pytest.mark.parametrize("pack", list(WINDOW_PACKS))
def pytest_windowed_flash_launches_match_the_reference(pack, window):
    """Forward, ``dq`` and ``dk``/``dv`` launches with a sliding window
    (interpret mode, the in-kernel loop) against the flat masked reference:
    float32, the two differ in the order of the online softmax's sums."""
    ops, w, node_graph, node_mask, nmax = _window_case(pack)
    got = _all(lambda *a: pfa.flash_causal_attention(*a, node_graph, node_mask, nmax, 128, 128, True, window=window),
               ops, w)
    want = _all(lambda *a: pfa.reference_causal_attention(*a, node_graph, node_mask, window=window), ops, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5, atol=2e-5)
    if window < nmax:  # the window binds: the full launch gives another answer
        full = pfa.reference_causal_attention(*ops, node_graph, node_mask)
        assert float(jnp.abs(full * (w != 0).any(axis=2, keepdims=True) - want[0]).max()) > 1e-2


@pytest.mark.parametrize("schedule", ["loop", "grid"])
def pytest_a_window_past_the_longest_document_is_bit_equal_to_no_window(monkeypatch, schedule):
    if schedule == "grid":
        monkeypatch.setattr(pfa, "CAUSAL_RESIDENT_BYTES", 0)
    ops, w, node_graph, node_mask, nmax = _window_case("document_spanning_four_tiles")
    kernel = lambda window: _all(lambda *a: pfa.flash_causal_attention(
        *a, node_graph, node_mask, nmax, 128, 128, True, window=window), ops, w)
    for a, b in zip(kernel(None), kernel(nmax)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if schedule == "grid":  # the grid schedule under a binding window, against the reference
        want = _all(lambda *a: pfa.reference_causal_attention(*a, node_graph, node_mask, window=100), ops, w)
        for g, r in zip(kernel(100), want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5, atol=2e-5)


def pytest_window_schedule_visits_no_tile_outside_the_window():
    """``causal_schedule_steps`` with a window against a replay in numpy: a
    query block's tiles run from the tile of ``max(document start, row0 - W +
    1)`` to its own."""
    _, _, node_graph, node_mask, nmax = _window_case("document_spanning_four_tiles")
    ng, real = np.asarray(node_graph), int(np.asarray(node_mask).sum())
    tiles = {}
    for window in (16, 129, 300):
        want = 0
        for q0 in range(0, len(ng), 128):
            if q0 >= real:
                want += 1
                continue
            first = max(int(np.searchsorted(ng, ng[q0], "left")), q0 - window + 1)
            want += min(q0 + 127, real - 1) // 128 - first // 128 + 1
        visited, scheduled = pfa.causal_schedule_steps(node_graph, node_mask, nmax, 16, 16, jnp.float32, 128, 128,
                                                       window=window)
        assert float(visited) == want == float(scheduled)
        tiles[window] = want
    none = pfa.causal_schedule_steps(node_graph, node_mask, nmax, 16, 16, jnp.float32, 128, 128)
    assert float(none[0]) >= tiles[300] >= tiles[129] >= tiles[16] and float(none[0]) > tiles[16]


def pytest_both_kinds_of_launch_carry_their_own_names(built, monkeypatch):
    """One step holds ``hg_flash_window`` launches (four sliding layers) and
    ``hg_flash_attention`` launches (the full layer), forward and backward."""
    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    config, arch, loader, model, variables = built
    batch = next(iter(loader))
    text = str(jax.make_jaxpr(jax.grad(program_loss(model, variables, batch)))(variables["params"]))
    assert tr.HG_FLASH_WINDOW in text and tr.HG_FLASH_WINDOW + tr.BWD in text
    assert tr.HG_FLASH_ATTENTION in text and tr.HG_FLASH_ATTENTION + tr.BWD in text


def pytest_window_pairs_counter_is_the_dense_masks_sum(built):
    config, arch, loader, model, variables = built
    for batch in loader:
        ng, mask = np.asarray(batch.node_graph), np.asarray(batch.node_mask)
        idx = np.arange(len(ng))
        causal = (ng[:, None] == ng[None, :]) & (mask[:, None] & mask[None, :]) & (idx[None, :] <= idx[:, None])
        for window in (1, WINDOW, 40, 1000):
            inside = causal & (idx[:, None] - idx[None, :] < window)
            assert float(dc.window_pairs(batch, window)) == inside.sum()
        assert float(dc.causal_pairs(batch)) == causal.sum() == float(dc.window_pairs(batch, 1000))


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
        e = am.AfmoeConfig.from_arch({**arch, "experts_held": list(held)}).experts
        p = dict(p_all, **{k: p_all[k][jnp.asarray(held)] for k in ("experts_gate", "experts_up", "experts_down")})
        y, counts, every, (overrun, _) = dc.expert_sublayer(p, beta, u, batch.node_mask, e)
        part = y - shared   # each chip adds the whole shared expert: count it once
        assert float(jnp.abs(part).max()) > 0 and int(overrun) == 0
        total, rows = total + part, rows + int(counts.sum())
        choice, _ = dc.route(p, beta, u, e)
        np.testing.assert_array_equal(np.asarray(choice), np.asarray(ref_choice))   # the router is whole on every chip
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(np.asarray(total + shared)[real], np.asarray(whole)[real], rtol=1e-5, atol=1e-5)
    assert rows == 4 * int(real.sum())   # every assignment of every real token lands on exactly one share


def pytest_sign_rule_moves_every_bias_by_the_rate_towards_the_mean_load():
    beta = jnp.asarray([0.1, -0.2, 0.0, 0.3])
    got = dc.sign_balanced_bias(beta, jnp.asarray([10.0, 2.0, 6.0, 6.0]), 0.001)
    np.testing.assert_allclose(np.asarray(got), [0.099, -0.199, 0.0, 0.3], atol=1e-7)


def pytest_an_overrun_step_is_poisoned_and_counted(docs, monkeypatch):
    config, arch, loader, model, variables = build(docs, capacity=0.25)
    monkeypatch.setattr(dc.ExpertSpec, "row_budget", lambda self, tokens, block_m: block_m)
    out, _ = model.apply(variables, next(iter(loader)), train=True, mutable=["batch_stats"])
    assert float(out[tr.CT_EXPERT_ROWS_OVERRUN]) > 0 and np.isnan(np.asarray(out["next_token"])).all()


# ---------------------------------------------------------------- keys, casts, counters

def pytest_mp_cast_leaves_ids_int32_and_the_router_float32(built):
    config, arch, loader, model, variables = built
    p, b = mp_cast(variables["params"], next(iter(loader)), False, mp_keep(model))
    assert b.z.dtype == jnp.int32 and p["layers_1"]["router"].dtype == jnp.float32
    assert p["layers_1"]["attn_gate"].dtype == jnp.bfloat16 and p["head"].dtype == jnp.bfloat16


@pytest.mark.parametrize("edit,message", [
    ({"layer_types": KINDS[:4]}, "layer_types"),
    ({"layer_types": KINDS[:4] + ["linear_attention"]}, "layer_types"),
    ({"sliding_window": None}, "sliding_window"),
    ({"experts_held": [3, 1]}, "experts_held"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"num_attention_heads": 3}, "num_key_value_heads"),
    ({"head_dim": 15}, "head_dim"),
    ({"intermediate_size": None}, "intermediate_size"),
])
def pytest_config_completion_refuses_a_bad_afmoe_key_at_once(docs, edit, message):
    cfg = small_config()
    cfg["NeuralNetwork"]["Architecture"].update(edit)
    with pytest.raises(ValueError, match=message):
        prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))


def pytest_lint_knows_the_afmoe_keys_and_their_two_rules():
    from hydragnn_tpu.config.lint import lint_config

    cfg = small_config()
    assert all(f.status == "handled" for f in lint_config(cfg)), [f for f in lint_config(cfg) if f.status != "handled"]
    cfg["NeuralNetwork"]["Architecture"].update({"layer_types": KINDS[:3], "sliding_window": None})
    bad = {f.path.rsplit(".", 1)[1] for f in lint_config(cfg) if f.status == "invalid"}
    assert bad == {"layer_types", "sliding_window"}


def pytest_full_layers_alone_need_no_window(docs):
    cfg = small_config()
    cfg["NeuralNetwork"]["Architecture"].update({"layer_types": [am.FULL] * 5, "sliding_window": None})
    config, (loader, _, _), _ = prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))
    out = create_model(config).apply(
        rc.make_weights(ref.weight_spec(config["NeuralNetwork"]["Architecture"], 1), 3), next(iter(loader)))
    assert tr.CT_WINDOW_PAIRS not in out and tr.CT_FLASH_TILES_VISITED in out


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
    assert regions[tr.CT_TOKENS]["total"] == 4 * real_tokens   # four expert layers
    assert 0 < regions[tr.CT_EXPERT_ROWS_HERE]["total"] <= 4 * regions[tr.CT_TOKENS]["total"]
    assert regions[tr.CT_EXPERT_ROWS_OVERRUN]["total"] == 0
    assert regions[tr.CT_WINDOW_PAIRS]["total"] == sum(float(dc.window_pairs(b, WINDOW)) for b in loader)
    assert regions[tr.CT_CAUSAL_PAIRS]["total"] == sum(float(dc.causal_pairs(b)) for b in loader)
    assert 0.3 < regions[tr.CT_WINDOW_PAIRS]["total"] / regions[tr.CT_CAUSAL_PAIRS]["total"] < 0.9
    # one query block, one key tile a step, for either kind; the head is resident: no step more
    steps = sum(1 for _ in loader)
    for name in (tr.CT_FLASH_TILES_VISITED, tr.CT_FLASH_STEPS_SCHEDULED, tr.CT_FLASH_WINDOW_TILES_VISITED,
                 tr.CT_FLASH_WINDOW_STEPS_SCHEDULED):
        assert regions[name]["total"] == steps, name
    assert "next_token" in tasks


def pytest_a_training_step_runs_each_blocks_flash_forward_once(built, flash_forward_once):
    config, arch, loader, model, variables = built
    flash_forward_once(config, loader, model, variables, 5)  # four sliding layers and a full one


def pytest_expert_rule_places_the_afmoe_expert_banks():
    from hydragnn_tpu.parallel import rules

    rule = rules.expert_rule(4)
    assert rule.compiled().search("layers_1/experts_down") and rule.compiled().search("layers_4/experts_gate")
    assert not rule.compiled().search("layers_1/shared_gate") and not rule.compiled().search("layers_1/attn_gate")


# ---------------------------------------------------------------- the benchmark's own pieces

def pytest_forward_flops_agree_with_the_dot_count_of_the_reference(built):
    """`forward_flops` (what `step_mfu.train` reads) against the dot count of
    the reference's lowered forward pass (run-scripts/flops_audit.py
    `dot_flops_by_shape`). A loop's body is in the text once: the reference's
    loop over the held experts counts as ONE expert on every row, so the count
    is asked for that many rows; its [T, T] attention products, which the
    count leaves out by design, are added by hand (each layer attends over the
    whole [T, T] whatever its window: the mask decides). Band: 1% (what is
    left are the norms' small products). As the benchmark calls it, on real
    tokens and balanced rows, it stays BELOW the reference's count: a share of
    the peak read from it cannot read high."""
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
    expert_layers = m["layers"] - m["first"]
    # lax.map's body is in the text once: one block of queries against every key
    attention = m["layers"] * 2.0 * m["H"] * min(ref.QUERY_BLOCK, t) * t * 2 * m["d"]
    one_expert_every_row = ref.forward_flops(arch, 1, t, 0, 0, rows_routed=t * expert_layers)
    assert abs(one_expert_every_row + attention - dots) <= 0.01 * dots, (one_expert_every_row, attention, dots)
    real = float(jnp.sum(b["node_w"]))
    assert ref.forward_flops(arch, 1, real, 0, 0) < dots - attention


def pytest_trinity_example_trains_through_run_training():
    import json

    import hydragnn_tpu
    from hydragnn_tpu.data.pipeline import split_dataset

    with open(os.path.join(_REPO, "examples", "trinity_mini", "trinity_mini.json")) as f:
        config = json.load(f)
    config["Verbosity"]["level"] = 0
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    ds = packed_documents_dataset(48, 24.0, 0.7, 4, 96, config["NeuralNetwork"]["Architecture"]["vocab_size"], seed=0)
    _, _, hist, done, _, _ = hydragnn_tpu.run_training(config, datasets=split_dataset(ds, 0.8, seed=0))
    assert hist["train"][1] < hist["train"][0]
    assert done["NeuralNetwork"]["Architecture"]["experts_held"] == list(range(16))
