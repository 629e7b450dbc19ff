"""The fourth decoder stack (``mpnn_type: "KEYEVL2"``, models/keyevl2.py: the
language model of Keye-VL-2.0) at a small size on the CPU that keeps every
mechanism: hidden 64, four layers, 4 query / 2 key-value heads of 16, an
indexer of 4 heads of 16 that selects 8 keys a query under documents of 5-60
tokens (most queries attend a chosen part of their prefix), 16 experts of
which 4 are held, 4 a token under softmax scores, the auxiliary loss, the
indexer's loss, vocabulary 97. The program against the benchmark's plain
reference (benchmarks/reference/keyevl2.py) on seeded weights, through both
routes (plain jnp, and the Pallas kernels interpreted); the indexer kernel,
the masked flash launches and the indexer loss's launch against their plain
references; ``select=None`` leaves the causal launches as they were; a
program that attends every key is caught; the expert shares add up."""

import copy
import hashlib
import os
import re
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
from hydragnn_tpu.config.lint import lint_config  # noqa: E402
from hydragnn_tpu.data.synthetic import packed_documents_dataset  # noqa: E402
from hydragnn_tpu.models import create_model  # noqa: E402
from hydragnn_tpu.models import decoder as dc  # noqa: E402
from hydragnn_tpu.models import keyevl2 as km  # noqa: E402
from hydragnn_tpu.ops import pallas_dsa_indexer as dsa  # noqa: E402
from hydragnn_tpu.ops import pallas_flash_attention as pfa  # noqa: E402
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train.loop import mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.train.loss import compute_loss  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import common as rc  # noqa: E402
from reference import keyevl2 as ref  # noqa: E402

VOCAB = 97
TOPK = 8
SHARES = ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15))


def small_config(held=SHARES[0], mixed=False, capacity=None):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "docs", "node_features": {"name": ["token", "pos", "unused"], "dim": [1, 3, 3]},
                    "graph_features": {"name": ["unused"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "KEYEVL2", "hidden_dim": 64, "num_conv_layers": 4, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1.0e4, "moe_intermediate_size": 32,
                "num_experts": 16, "num_experts_per_tok": 4, "experts_held": list(held),
                "expert_row_capacity": capacity, "indexer_num_heads": 4, "indexer_head_dim": 16,
                "indexer_topk": TOPK, "vocab_size": VOCAB, "loss_chunk_rows": 64,
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
    return config, arch, loader, create_model(config), rc.make_weights(ref.weight_spec(arch, 1), seed)


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
        return out[0].astype(jnp.float32), out[1]
    return f


def gaps(model, arch, variables, batch, mixed=False):
    """-> (relative loss gap, [worst, median] leaf's gradient gap) of the
    program against the reference, the program's tasks, the reference's
    gradients by leaf."""
    (loss, tasks), grads = jax.value_and_grad(program_loss(model, variables, batch, mixed), has_aux=True)(
        variables["params"])
    ref_loss, ref_grads = jax.value_and_grad(lambda p: ref.loss_fn(p, ref_batch(batch), arch, "f32"))(
        variables["params"])
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    norms = {k: float(jnp.linalg.norm(want[k])) for k in want}
    median = float(np.median(list(norms.values())))
    leaf = [float(jnp.linalg.norm(got[k].astype(jnp.float32) - want[k])) / max(norms[k], median) for k in want]
    return abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)), [max(leaf), float(np.median(leaf))], tasks, want


def _selection_binds(batch):
    n_g = np.asarray(batch.nodes_per_graph)[np.asarray(batch.graph_mask)]
    return np.maximum(n_g - TOPK, 0).sum() > 0.5 * n_g.sum()


# float32: the two differ in summation order only: worst leaf 5e-7 through
# either route. bfloat16 (jnp route): the operands and the stream are rounded
# to 2^-8 and a top-4 expert choice or a top-8 key choice at a near tie may
# fall the other way; worst leaf 0.4, median 0.03 as the AFMOE stack's band.
@pytest.mark.parametrize("route,mixed,loss_tol,grad_tol", [
    ("jnp", False, 1e-5, (5e-5, 5e-5)), ("kernels", False, 1e-5, (5e-5, 5e-5)), ("jnp", True, 1e-2, (0.4, 0.03))])
def pytest_program_matches_reference_loss_logits_and_every_gradient_leaf(docs, monkeypatch, route, mixed,
                                                                          loss_tol, grad_tol):
    if route == "kernels":
        monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    batch = next(iter(loader))
    assert _selection_binds(batch)  # most queries attend a chosen part of their prefix
    loss_gap, grad_gap, tasks, want = gaps(model, arch, variables, batch, mixed)
    assert loss_gap <= loss_tol and grad_gap[0] <= grad_tol[0] and grad_gap[1] <= grad_tol[1], (loss_gap, grad_gap)
    assert float(tasks["index"]) > 0 and float(tasks["balance"]) > 0
    # every mechanism has leaves and every leaf a gradient: the indexer's from its own loss
    for leaf in ("layers_0/index_q", "layers_1/index_k", "layers_2/index_w", "layers_3/index_k_bias",
                 "layers_0/router", "layers_2/attn_q_norm", "layers_3/experts_down", "head", "embedding"):
        assert float(jnp.linalg.norm(want[leaf])) > 0, leaf
    if not mixed:  # the logits of the final hidden state
        out = model.apply(variables, batch, train=False)
        h_ref = ref.forward(variables["params"], ref_batch(batch), arch)[0]
        real = np.asarray(batch.node_mask)
        head = variables["params"]["head"]
        np.testing.assert_allclose(np.asarray(out["next_token"] @ head)[real], np.asarray(h_ref @ head)[real],
                                   rtol=1e-4, atol=1e-4)


def pytest_indexer_loss_trains_the_indexer_alone(built):
    """With the token loss and the auxiliary loss switched off, only the
    indexer's leaves have a gradient: ``p`` and the indexer's input are
    detached."""
    config, arch, loader, model, variables = built
    batch = next(iter(loader))

    def index_only(params):
        out, _ = model.apply({"params": params, "batch_stats": variables["batch_stats"]}, batch, train=True,
                             mutable=["batch_stats"])
        return out[km.INDEX_LOSS]

    grads = flat(jax.grad(index_only)(variables["params"]))
    for name, g in grads.items():
        moved = float(jnp.linalg.norm(g)) > 0
        assert moved == ("/index_" in name), name


def pytest_a_program_that_attends_every_key_is_caught(built, monkeypatch):
    """The comparison FAILS on a program whose layers attend every earlier key
    of the document (the selection off, all else equal): worst and median
    leaf far past the float32 band."""
    config, arch, loader, model, variables = built
    sparse = dc.sparse_attention

    def dense(q, k, v, qi, ki, w, aux, max_nodes, topk):
        return sparse(q, k, v, qi, ki, w, aux, max_nodes, 10 ** 6)

    monkeypatch.setattr(dc, "sparse_attention", dense)
    loss_gap, grad_gap, _, _ = gaps(model, arch, variables, next(iter(loader)))
    assert grad_gap[0] > 100 * 5e-5 and grad_gap[1] > 100 * 5e-5, (loss_gap, grad_gap)


# through run_training's own step (make_train_step): three AdamW steps; the one
# buffer holds each layer's loads of the latest step
def pytest_three_adamw_steps_match_reference(docs):
    config, arch, loader, model, variables = build(docs)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    step = make_train_step(model, tx, False, False)
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
    for b in batches:
        (loss, loads), g = jax.value_and_grad(
            lambda q: ref.loss_and_loads(q, ref_batch(b), arch, "f32"), has_aux=True)(p)
        p, opt = rc.adamw_update(p, g, opt, 1e-3)
        buffers = ref.balance(buffers, loads, arch)
        ref_losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(state.batch_stats["expert_loads"]), np.asarray(buffers["expert_loads"]))
    got, want, start = flat(state.params), flat(p), flat(variables["params"])
    moved = {k: float(jnp.linalg.norm(want[k] - start[k])) for k in want}
    median = float(np.median(list(moved.values())))
    for k in want:
        assert float(jnp.linalg.norm(got[k] - want[k])) / max(moved[k], median) <= 5e-3, k


def pytest_a_training_step_runs_each_sparse_launch_once(built, flash_forward_once):
    """The layer's remat keeps the flash launch's ``o`` and ``lse``, the
    selection and the indexer loss's gradient: one ``hg_dsa_indexer``, one
    ``hg_dsa_indexer_bwd`` and one forward sparse launch a layer in the
    step's jaxpr, beside the two backward launches."""
    config, arch, loader, model, variables = built
    os.environ["HYDRAGNN_PALLAS_FLASH"] = "1"
    try:
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        step = make_train_step(model, tx, False, False)
        state = TrainState.create(copy.deepcopy(variables), tx)
        names = re.findall(r"name=(hg_\w+)", str(jax.make_jaxpr(step)(state, next(iter(loader)),
                                                                        jax.random.PRNGKey(0))))
    finally:
        del os.environ["HYDRAGNN_PALLAS_FLASH"]
    count = lambda n: sum(1 for x in names if x == n)
    assert count(tr.HG_DSA_INDEXER) == count(tr.HG_DSA_INDEXER + tr.BWD) == count(tr.HG_FLASH_SPARSE) == 4, names
    assert count(tr.HG_FLASH_SPARSE + tr.BWD) == 8 and count(tr.HG_FLASH_ATTENTION) == 0, names


def pytest_expert_shares_add_up_to_the_uncut_layer(docs):
    """At a small size: the four shares of 16 experts (4 held each) give
    expert outputs whose sum is the uncut layer's (all 16 held), at the same
    softmax routing; attention and the indexer are whole on every chip."""
    cfg_all = build(docs, held=tuple(range(16)))
    arch_all, model_all = cfg_all[1], cfg_all[3]
    batch = next(iter(cfg_all[2]))
    full = rc.make_weights(ref.weight_spec(arch_all, 1), 7)["params"]
    u = jnp.asarray(np.random.default_rng(0).standard_normal((batch.node_mask.shape[0], 64)), jnp.float32)
    mask = jnp.asarray(batch.node_mask)
    layer = full["layers_1"]
    whole, _, _, _ = dc.expert_sublayer(layer, jnp.zeros((16,)), u, mask, model_all.cfg.keyevl2.experts)
    parts = jnp.zeros_like(whole)
    for held in SHARES:
        z = build(docs, held=held)[3].cfg.keyevl2
        idx = np.asarray(held)
        p = dict(layer, **{k: layer[k][idx] for k in ("experts_gate", "experts_up", "experts_down")})
        y, _, _, _ = dc.expert_sublayer(p, jnp.zeros((16,)), u, mask, z.experts)
        parts = parts + y
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=1e-5, atol=1e-6)
    ref_y = ref.experts(layer, u, ref_batch(batch), ref._dims(arch_all), "f32")[0]
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_y), rtol=1e-4, atol=1e-6)


def pytest_softmax_top_k_with_renormalised_gates_and_the_balancing_loss(built):
    """``route`` under ``score`` softmax: the choice is the top-k of the
    softmax over ALL experts and the gates the chosen probabilities over
    their sum; ``balance_loss`` is ``coef * E * sum_e f_e P_e``, ``f`` summed
    over the slots: both against a plain numpy spelling and the reference."""
    config, arch, loader, model, variables = built
    z = model.cfg.keyevl2
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((64, 16)) / 8.0, jnp.float32)}
    choice, gate = dc.route(p, jnp.zeros((16,)), u, z.experts)
    logits = np.asarray(u, np.float64) @ np.asarray(p["router"], np.float64)
    s = np.exp(logits - logits.max(1, keepdims=True))
    s /= s.sum(1, keepdims=True)
    top = np.argsort(-s, axis=1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(choice), 1), np.sort(top, 1))
    chosen = np.take_along_axis(s, np.asarray(choice), 1)
    np.testing.assert_allclose(np.asarray(gate), chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gate).sum(1), 1.0, rtol=1e-6)
    # the balancing loss: 2 layers of these 50 tokens
    every = np.zeros(16)
    np.add.at(every, np.asarray(choice).reshape(-1), 1.0)
    got = km.balance_loss(jnp.asarray(2 * every, jnp.float32), jnp.asarray(2 * s.sum(0), jnp.float32), 2,
                          jnp.float32(50), 0.001)
    f, prob = every / 50, s.sum(0) / 50
    np.testing.assert_allclose(float(got), 0.001 * 16 * np.sum(f * prob), rtol=1e-5)
    assert abs(0.001 * 16 * np.sum(f * prob) - 0.001 * 4) < 0.002  # near k * coef when balanced


def pytest_a_layer_scores_its_router_once(built, monkeypatch):
    """The routing and the balancing term's probabilities read one
    computation of the router's scores a layer."""
    config, arch, loader, model, variables = built
    calls, scores = [], dc.router_scores
    monkeypatch.setattr(dc, "router_scores", lambda *a: calls.append(1) or scores(*a))
    batch = next(iter(loader))
    jax.make_jaxpr(lambda v: model.apply(v, batch, train=False))(variables)
    assert len(calls) == arch["num_conv_layers"]


def pytest_the_references_balancing_term_takes_the_steps_loads(built):
    """Given the step's loads, the reference's auxiliary loss takes ``f``
    from them: a batch's own loads give its loss back, other loads another."""
    config, arch, loader, model, variables = built
    b, p = ref_batch(next(iter(loader))), variables["params"]
    loss, loads = ref.loss_and_loads(p, b, arch, "f32")
    given = lambda step: float(ref.loss_fn(p, b, arch, "f32", dict(variables["batch_stats"], step_loads=step)))
    np.testing.assert_allclose(given(loads), float(loss), rtol=1e-6)
    assert abs(given(jnp.roll(loads, 1, axis=1)) - float(loss)) > 1e-6 * abs(float(loss))


def pytest_selected_pairs_counter_is_a_brute_count(built):
    config, arch, loader, model, variables = built
    for batch in [b for _, b in zip(range(3), loader)]:
        ng, nm = np.asarray(batch.node_graph), np.asarray(batch.node_mask)
        pos = np.zeros(len(ng), int)
        for i in range(1, len(ng)):
            pos[i] = pos[i - 1] + 1 if ng[i] == ng[i - 1] else 0
        brute = int(np.sum(np.where(nm, np.minimum(pos + 1, TOPK), 0)))
        out = model.apply(variables, batch, train=False)
        assert float(out[tr.CT_DSA_SELECTED_PAIRS]) == brute == float(dc.window_pairs(batch, TOPK))
        sel, _ = dsa.reference_select(*_indexer_inputs(len(ng), 0), jnp.asarray(ng), jnp.asarray(nm), TOPK)
        assert int(np.sum(np.asarray(sel))) == brute


# ---------------------------------------------------------------- the kernels, interpreted

# (document sizes) on 1,024 slots: two tiles of 512 keys, so both bit planes of
# a word; a document across the tile boundary, one shorter than the budget
DOCS = [300, 5, 600, 100]
T = 1024


def _layout(docs=DOCS, n=T):
    ng = np.concatenate([np.full(k, i) for i, k in enumerate(docs)] + [np.full(n - sum(docs), len(docs))])
    pos = np.concatenate([np.arange(k) for k in docs] + [np.zeros(n - sum(docs), int)])
    return jnp.asarray(ng, jnp.int32), jnp.asarray(np.arange(n) < sum(docs)), jnp.asarray(pos, jnp.int32)


def _indexer_inputs(n, seed, heads=4, d=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, heads, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((n, heads)), jnp.float32))


def _kernel_select(qi, ki, w, ng, nm, pos, topk):
    words, tau, tie, lse = dsa.dsa_select(qi, ki, w, ng, nm, pos, topk, T - 1, interpret=True)
    return np.asarray(dsa.unpack_select(words, T))[:T], tau, tie, lse


@pytest.mark.parametrize("topk", [64, 2048])
def pytest_indexer_kernel_selects_as_the_reference_on_tie_free_scores(topk):
    """The ``hg_dsa_indexer`` launch, interpreted: the same selection as
    ``lax.top_k`` over the plain scores, a row's count ``min(n_t, topk)``, its
    threshold the ``topk``-th largest score and its log-sum-exp the plain
    one. At ``topk`` 2048 every document is shorter: every earlier key."""
    ng, nm, pos = _layout()
    qi, ki, w = _indexer_inputs(T, 0)
    got, tau, _, lse = _kernel_select(qi, ki, w, ng, nm, pos, topk)
    want, want_lse = dsa.reference_select(qi, ki, w, ng, nm, topk)
    want = np.asarray(want)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got.sum(1), np.where(np.asarray(nm), np.minimum(np.asarray(pos) + 1, topk), 0))
    if topk >= max(DOCS):
        np.testing.assert_array_equal(got, np.asarray(dsa.allowed_pairs(ng, nm)))
    scores = np.asarray(dsa.index_scores(qi, ki, w))
    real = np.asarray(nm)
    kth = np.array([scores[t][want[t]].min() if real[t] else 0.0 for t in range(T)])
    np.testing.assert_allclose(np.where(real, np.asarray(dsa._from_key(tau)), 0.0), kth, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse)[real], np.asarray(want_lse)[real], rtol=1e-5, atol=1e-5)


def pytest_indexer_kernel_breaks_ties_to_the_lower_position():
    """Forced ties: every key's score is one of three values (keys repeat
    three rows), so most rows' thresholds fall inside a run of equal scores;
    the kernel takes the lowest positions of the run, as ``lax.top_k``."""
    ng, nm, pos = _layout()
    qi, ki, w = _indexer_inputs(T, 1)
    ki = jnp.tile(ki[:3], (T // 3 + 1, 1))[:T]
    got, _, _, _ = _kernel_select(qi, ki, w, ng, nm, pos, 64)
    want, _ = dsa.reference_select(qi, ki, w, ng, nm, 64)
    np.testing.assert_array_equal(got, np.asarray(want))
    row = int(np.argmax(np.asarray(pos) == 500))  # 501 keys in 3 runs of equal score: 64 of them
    chosen = np.flatnonzero(got[row])
    assert len(chosen) == 64 and len(np.unique(np.asarray(dsa.index_scores(qi, ki, w))[row, chosen])) <= 2


def pytest_masked_flash_launches_match_masked_reference_attention():
    """Forward, ``dq`` and ``dk``/``dv`` launches with a selection bitmask,
    interpreted, against the flat reference under the same boolean mask;
    named ``hg_flash_sparse*``; the log-sum-exp they return is the masked
    softmax's."""
    ng, nm, _ = _layout()
    rng = np.random.default_rng(2)
    sel = (rng.random((T, T)) < 0.3) & np.asarray(dsa.allowed_pairs(ng, nm))
    words = dsa.pack_select(jnp.asarray(sel), T)
    q, k, v, ct = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in
                   ((T, 4, 32), (T, 2, 32), (T, 2, 32), (T, 4, 32)))

    def kernel(q, k, v):
        o, lse = pfa.flash_causal_attention(q, k, v, ng, nm, T - 1, interpret=True, select=words)
        return jnp.sum(o * ct), (o, lse)

    def plain(q, k, v):
        o = pfa.reference_causal_attention(q, k, v, ng, nm, select=jnp.asarray(sel))
        return jnp.sum(o * ct), o

    (_, (o, lse)), g = jax.value_and_grad(kernel, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, o_ref), g_ref = jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, 2, axis=1)) / np.sqrt(32.0)
    want = jax.nn.logsumexp(jnp.where(jnp.asarray(sel)[None], s, -jnp.inf), axis=-1)
    rows = sel.any(1)
    np.testing.assert_allclose(np.asarray(lse)[:, :T][:, rows], np.asarray(want)[:, rows], rtol=1e-5, atol=1e-5)
    names = re.findall(r"name=(hg_\w+)", str(jax.make_jaxpr(jax.grad(lambda *a: kernel(*a)[0], argnums=(0, 1, 2)))(
        q, k, v)))
    assert sorted(names) == [tr.HG_FLASH_SPARSE, tr.HG_FLASH_SPARSE + tr.BWD, tr.HG_FLASH_SPARSE + tr.BWD], names


def pytest_indexer_loss_launch_and_its_gradient_match_the_plain_formula():
    """The ``hg_dsa_indexer_bwd`` launch, interpreted: the KL sum and its
    gradient in ``qI``, ``kI``, ``w`` against ``jax.grad`` of the plain
    formula (``p`` from the masked flash launch's log-sum-exp)."""
    ng, nm, pos = _layout()
    qi, ki, w = _indexer_inputs(T, 4)
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in ((T, 4, 32), (T, 2, 32)))
    words, _, _, lse_i = dsa.dsa_select(qi, ki, w, ng, nm, pos, 64, T - 1, interpret=True)
    _, lse = pfa.flash_causal_attention(q, k, k, ng, nm, T - 1, interpret=True, select=words)
    sel = dsa.unpack_select(words, T)[:T]
    val, grads = jax.value_and_grad(lambda *a: dsa.dsa_index_loss(*a, q, k, lse, words, lse_i, ng, nm, T - 1,
                                                                   interpret=True), argnums=(0, 1, 2))(qi, ki, w)
    want, want_grads = jax.value_and_grad(lambda *a: dsa.reference_index_loss(*a, q, k, sel), argnums=(0, 1, 2))(
        qi, ki, w)
    np.testing.assert_allclose(float(val), float(want), rtol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))))


def pytest_bitmask_packs_and_transposes_bit_for_bit():
    rng = np.random.default_rng(6)
    for n in (700, 1024):
        sel = rng.random((n, n)) < 0.4
        words = dsa.pack_select(jnp.asarray(sel), n)
        assert words.shape == (dsa.select_words(n) // dsa.SELECT_TILE, n, dsa.SELECT_TILE)
        np.testing.assert_array_equal(np.asarray(dsa.unpack_select(words, n)), sel)
        np.testing.assert_array_equal(np.asarray(dsa.unpack_select(dsa.transpose_select(words, n), n)), sel.T)


# the jaxpr text of a causal flash block's three launches (value and gradient)
# at the three decoder cells' shapes, bf16, sha256: the parent tree's, before
# the selection operand existed (select=None traces them unchanged)
CAUSAL_JAXPR_SHA256 = {
    (32768, 8, 2, 128, 128, None): "938da4a9bb9e836aff96f1e95bef540324c2bff541e149c48b9deab77b8c1229",
    (16384, 32, 32, 192, 128, None): "f5701c5aa9a6d5e673b265729fbe62ba0e18344c41fef6c066e7a6ef33c2b660",
    (16384, 32, 4, 128, 128, None): "0d89b61f5102e9e7412ee53a92198e5fc229fea27f527510f37968f5f02fa893",
    (16384, 32, 4, 128, 128, 2048): "21bf4ce814f6824e0bf40feb5dcb6ca68085bc0a8ec7a250f067cd47decabfcd",
}


@pytest.mark.parametrize("shape", list(CAUSAL_JAXPR_SHA256), ids=["zaya", "joyai", "trinity_full", "trinity_window"])
def pytest_no_selection_traces_the_causal_launches_as_they_were(shape):
    n, hq, hk, d, dv, window = shape
    sh = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)

    def f(q, k, v, g, m):
        return jnp.sum(pfa.flash_causal_attention(q, k, v, g, m, n - 1, window=window).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(
        sh((n, hq, d)), sh((n, hk, d)), sh((n, hk, dv)), sh((n,), jnp.int32), sh((n,), jnp.bool_)))
    assert hashlib.sha256(text.encode()).hexdigest() == CAUSAL_JAXPR_SHA256[shape]


# ---------------------------------------------------------------- config

@pytest.mark.parametrize("edit,message", [
    ({"indexer_num_kv_heads": 2}, "ONE key head"),
    ({"indexer_topk": None}, "indexer_topk"),
    ({"experts_held": [3, 1]}, "experts_held"),
    ({"num_key_value_heads": 3}, "multiple"),
])
def pytest_config_completion_refuses_a_bad_keyevl2_key_at_once(docs, edit, message):
    cfg = small_config()
    cfg["NeuralNetwork"]["Architecture"].update(edit)
    with pytest.raises(ValueError, match=message):
        prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))


def pytest_lint_knows_the_keyevl2_keys():
    findings = lint_config(small_config())
    unknown = [f.path for f in findings if f.status == "unknown"]
    assert not unknown, unknown


def pytest_an_overrun_step_is_poisoned_and_counted(docs, monkeypatch):
    """A row budget too small for the step's routing poisons the hidden state
    and counts the rows left out."""
    config, arch, loader, model, variables = build(docs, capacity=0.25)
    monkeypatch.setattr(dc.ExpertSpec, "row_budget", lambda self, tokens, block_m: block_m)
    out = model.apply(variables, next(iter(loader)), train=False)
    assert float(out[tr.CT_EXPERT_ROWS_OVERRUN]) > 0 and bool(jnp.all(jnp.isnan(out["next_token"])))


# ---------------------------------------------------------------- compiled for a described v5e

@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a DESCRIBED v5e (the TPU compiler is installed; no chip
    is attached)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def pytest_sparse_launches_compile_for_v5e_at_the_cells_shape(v5e_chip):
    """Mosaic accepts the indexer's two launches and the three masked causal
    launches at the Keye-VL-2.0 cell's shape: 32,768 slots, 16 indexer heads
    of 64 on one key head, the top 2,048, 32 query heads on 4 key/value heads
    of 128, bf16; each launch under its own name."""
    n, rows = 32768, 32768
    groups = dsa.select_words(rows) // dsa.SELECT_TILE
    sh = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    ints, flags = sh((n,), jnp.int32), sh((n,), jnp.bool_)

    def step(qi, ki, w, q, k, v, g, m, pos):
        words, _, _, lse_i = dsa.dsa_select(qi, ki, w, g, m, pos, 2048, n - 1)
        o, lse = pfa.flash_causal_attention(q, k, v, g, m, n - 1, select=words)
        index = dsa.dsa_index_loss(qi, ki, w, q, k, lse, words, lse_i, g, m, n - 1)
        return jnp.sum(o.astype(jnp.float32) ** 2) + index

    compiled = jax.jit(jax.grad(step, argnums=(0, 1, 2, 3, 4, 5))).lower(
        sh((n, 16, 64)), sh((n, 64)), sh((n, 16)), sh((n, 32, 128)), sh((n, 4, 128)), sh((n, 4, 128)),
        ints, flags, ints).compile()
    calls = re.findall(r"^\s*%(hg_[a-z_]*)[.\d]* = .*custom-call\(", compiled.as_text(), re.MULTILINE)
    assert sorted(calls) == sorted([tr.HG_DSA_INDEXER, tr.HG_DSA_INDEXER + tr.BWD, tr.HG_FLASH_SPARSE,
                                    tr.HG_FLASH_SPARSE + tr.BWD, tr.HG_FLASH_SPARSE + tr.BWD]), calls
    assert groups == 2
