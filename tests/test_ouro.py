"""The fifth decoder stack (``mpnn_type: "OURO"``, models/ouro.py: a looped
language model whose held layers run ``total_ut_steps`` times a step under one
set of weights, an exit after every pass weighted by a learned exit gate) at
a small size on the CPU that keeps every mechanism: hidden 64, two held
layers, four passes, 4 heads of 16 on 4 key/value heads, an MLP of 96,
vocabulary 97, documents of 5-60 tokens. The program against the
benchmark's plain reference (benchmarks/reference/ouro.py) on seeded
weights, through both routes (plain jnp, and the flash kernels interpreted):
the loss, each exit's logits, the exit distribution and every leaf's
gradient, the gate's among them; one pass is a plain stack; the training
step holds the pass body once; the configuration file's parameter count;
the step's counters. The head's one scan over every exit is
tests/test_token_head.py's."""

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
from hydragnn_tpu.models import ouro as om  # noqa: E402
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train import loss as ls  # noqa: E402
from hydragnn_tpu.train.loop import mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import common as rc  # noqa: E402
from reference import ouro as ref  # noqa: E402

VOCAB = 97


def small_config(passes=4, layers=2, mixed=False):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "docs", "node_features": {"name": ["token", "pos", "unused"], "dim": [1, 3, 3]},
                    "graph_features": {"name": ["unused"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "OURO", "hidden_dim": 64, "num_conv_layers": layers, "num_attention_heads": 4,
                "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96, "rope_theta": 1.0e4,
                "total_ut_steps": passes, "vocab_size": VOCAB, "loss_chunk_rows": 64,
                "output_heads": {"node": {"type": "token", "num_headlayers": 0, "dim_headlayers": []}}},
            "Variables_of_interest": {"input_node_features": [0], "output_names": ["next_token"],
                                      "output_index": [0], "type": ["node"]},
            "Training": {"num_epoch": 1, "batch_size": 8, "pack_batches": True, "pack_node_slots": 192,
                         "pack_graph_slots": 12, "mixed_precision": mixed,
                         "Optimizer": {"type": "AdamW", "learning_rate": 1e-3}}}}


@pytest.fixture(scope="module")
def docs():
    return packed_documents_dataset(40, 28.0, 0.6, 5, 60, VOCAB, seed=1)


def build(docs, passes=4, layers=2, mixed=False, seed=5):
    config, (loader, _, _), _ = prepare_data(small_config(passes, layers, mixed), (docs[:30], docs[30:35], docs[35:]))
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
        out = ls.compute_loss(model, {"params": p, "batch_stats": variables["batch_stats"]}, b, model.cfg,
                              True, jax.random.PRNGKey(0), False)
        return out[0].astype(jnp.float32), out[1]
    return f


def gaps(model, arch, variables, batch, mixed=False):
    """-> (relative loss gap, [worst, median] leaf's gradient gap) of the
    program against the reference at ``highest``, the program's tasks, the
    reference's gradients by leaf."""
    (loss, tasks), grads = jax.value_and_grad(program_loss(model, variables, batch, mixed), has_aux=True)(
        variables["params"])
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(lambda p: ref.loss_fn(p, ref_batch(batch), arch, "f32"))(
            variables["params"])
    got, want = flat(grads), flat(ref_grads)
    assert set(got) == set(want)
    norms = {k: float(jnp.linalg.norm(want[k])) for k in want}
    median = float(np.median(list(norms.values())))
    leaf = {k: float(jnp.linalg.norm(got[k].astype(jnp.float32) - want[k])) / max(norms[k], median) for k in want}
    return (abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
            [max(leaf.values()), float(np.median(list(leaf.values())))], tasks, want, leaf)


# float32: the program and the reference differ in summation order only (the
# chunked head, the flash kernel's blocked softmax, the log-domain exit
# distribution): worst leaf 8.5e-7, median 2.7e-7 through either route, the
# gate's two leaves 2.5e-7 and 2e-8; the band is the other stacks' float32
# band. bfloat16 (jnp route): operands and the stream rounded to 2^-8 through
# eight layer applications: loss 3.8e-4, worst leaf 0.024, median 0.008, the
# gate 4.5e-3, read here; the band is the other stacks' bfloat16 band. A
# program of three passes reads loss 3.8e-3, worst 0.28, median 0.15.
@pytest.mark.parametrize("route,mixed,loss_tol,grad_tol", [
    ("jnp", False, 1e-5, (5e-5, 5e-5)), ("kernels", False, 1e-5, (5e-5, 5e-5)), ("jnp", True, 1e-2, (0.4, 0.03))])
def pytest_program_matches_reference_loss_exits_and_every_gradient_leaf(docs, monkeypatch, route, mixed,
                                                                         loss_tol, grad_tol):
    if route == "kernels":
        monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    config, arch, loader, model, variables = build(docs, mixed=mixed)
    batch = next(iter(loader))
    loss_gap, grad_gap, tasks, want, leaf = gaps(model, arch, variables, batch, mixed)
    assert loss_gap <= loss_tol and grad_gap[0] <= grad_tol[0] and grad_gap[1] <= grad_tol[1], (loss_gap, grad_gap)
    # the gate's leaves are compared like every other, and do train
    for name in ("exit_gate", "exit_gate_bias"):
        assert float(jnp.linalg.norm(want[name])) > 0 and leaf[name] <= grad_tol[0], (name, leaf[name])
    assert float(tasks["exit_entropy"]) > 0
    if mixed:
        return
    # each exit's logits, and the exit distribution, on the real tokens:
    # float32 against float32, summation order apart
    out = model.apply(variables, batch, train=False)
    with jax.default_matmul_precision("highest"):
        exits, logits = ref.forward(variables["params"], ref_batch(batch), arch)
    real, head = np.asarray(batch.node_mask), variables["params"]["head"]
    assert out["next_token"].shape == (4,) + batch.node_mask.shape + (64,)
    for t in range(4):
        np.testing.assert_allclose(np.asarray(out["next_token"][t] @ head)[real], np.asarray(exits[t] @ head)[real],
                                   rtol=1e-4, atol=1e-4)
    p, _ = om.exit_distribution(out[dc.EXIT_GATE])
    np.testing.assert_allclose(np.asarray(p)[:, real], np.asarray(ref.exit_distribution(logits))[:, real],
                               rtol=1e-5, atol=1e-6)


def pytest_a_program_with_three_passes_is_caught(docs):
    """The comparison FAILS on a program that runs three passes where the
    reference runs four: loss and gradients far past the float32 band."""
    config, arch, loader, model, variables = build(docs, passes=3)
    arch4 = dict(arch, total_ut_steps=4)
    loss_gap, grad_gap, _, _, _ = gaps(model, arch4, rc.make_weights(ref.weight_spec(arch4, 1), 5), next(iter(loader)))
    assert loss_gap > 100 * 1e-5 and grad_gap[0] > 100 * 5e-5, (loss_gap, grad_gap)


def pytest_exit_distribution_sums_to_one_and_matches_the_products():
    """``p`` sums to one over the exits, equals the equations' products, the
    last pass's logit takes no part, and the log domain holds at logits of
    +-60 where a product of sigmoids underflows."""
    logits = jnp.asarray(np.random.default_rng(0).normal(scale=3.0, size=(4, 50)), jnp.float32)
    p, logp = om.exit_distribution(logits)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0, rtol=1e-6)
    # the log domain and the products round apart by a few float32 ulps of 1
    np.testing.assert_allclose(np.asarray(p), np.asarray(ref.exit_distribution(logits)), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(om.exit_distribution(logits.at[-1].set(9.0))[0]), np.asarray(p))
    _, logp = om.exit_distribution(jnp.full((4, 3), 60.0))
    assert np.all(np.isfinite(np.asarray(logp))) and float(logp[3, 0]) < -150
    one, _ = om.exit_distribution(logits[:1])
    np.testing.assert_array_equal(np.asarray(one), 1.0)


def pytest_one_pass_is_a_plain_stack(docs):
    """At ``total_ut_steps`` 1 the exit distribution is 1 on every token, the
    entropy term 0, and the stack is the held layers applied once in order,
    the final norm and the token loss of the other stacks."""
    config, arch, loader, model, variables = build(docs, passes=1)
    batch = next(iter(loader))
    out = model.apply(variables, batch, train=False)
    params, z = variables["params"], model.cfg.ouro
    x, _ = dc.embed_tokens(params["embedding"], batch.z, z.vocab_size)
    aux = dc.batch_aux(batch)
    for i in range(2):
        x = om.layer(params[f"layers_{i}"], x, aux, z, model.cfg.max_nodes_per_graph)
    h = dc.rms_norm(x, params["final_norm"], z.rms_norm_eps)
    np.testing.assert_allclose(np.asarray(out["next_token"][0]), np.asarray(h), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(om.exit_distribution(out[dc.EXIT_GATE])[0]), 1.0)
    loss, tasks, _, _ = ls.compute_loss(model, variables, batch, model.cfg, False, None, False)
    assert float(tasks["exit_entropy"]) == 0.0
    want = ls.token_loss(h, params["head"], batch, 64)
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))


def _launches(jaxpr, scans=()):
    """(kernel name, lengths of the scans around it) of every Pallas launch
    of a jaxpr, nested ones included: each equation counted where it stands,
    however the printer would share it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], scans
        inner = scans + (eqn.params["length"],) if eqn.primitive.name == "scan" else scans
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, (jax.extend.core.ClosedJaxpr, jax.extend.core.Jaxpr)):
                    yield from _launches(getattr(x, "jaxpr", x), inner)


def pytest_training_step_holds_the_pass_body_once(built, monkeypatch):
    """With the flash route forced, the training step's jaxpr holds the
    pass body once: one forward flash launch a HELD layer inside the forward
    scan over the four passes (the layer's remat keeps ``o`` and ``lse``
    there), two backward launches a held layer inside the backward scan: 2
    and 4 launches, not passes x layers; the step counts all 8 applications
    as blocks whose forward was kept."""
    config, arch, loader, model, variables = built
    monkeypatch.setenv("HYDRAGNN_PALLAS_FLASH", "1")
    batch = next(iter(loader))
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(copy.deepcopy(variables), tx)
    step = make_train_step(model, tx, False, False)
    launches = list(_launches(jax.make_jaxpr(step)(state, batch, jax.random.PRNGKey(0)).jaxpr))
    names = [name for name, _ in launches]
    assert names.count(tr.HG_FLASH_ATTENTION) == 2 and names.count(tr.HG_FLASH_ATTENTION + tr.BWD) == 4, launches
    assert len(launches) == 6 and all(scans == (4,) for _, scans in launches), launches
    out, _ = model.apply(variables, batch, train=True, mutable=["batch_stats"])
    assert float(out[tr.CT_FLASH_BLOCKS]) == float(out[tr.CT_FLASH_BLOCKS_SAVED]) == 8.0
    assert float(model.apply(variables, batch, train=False)[tr.CT_FLASH_BLOCKS_SAVED]) == 0.0


def pytest_counters_read_the_held_layers_and_their_applications(docs):
    """At the configuration's depth (six held layers, four passes): 6 layers
    held and 24 applications a step, 24 flash blocks; ``count:tokens`` the
    real tokens times the applications."""
    config, arch, loader, model, variables = build(docs, layers=6)
    batch = next(iter(loader))
    out, _ = model.apply(variables, batch, train=True, mutable=["batch_stats"])
    assert float(out[tr.CT_LOOP_LAYERS_HELD]) == 6.0 and float(out[tr.CT_LOOP_LAYER_APPLICATIONS]) == 24.0
    assert float(out[tr.CT_FLASH_BLOCKS]) == 24.0
    assert float(out[tr.CT_TOKENS]) == 24.0 * float(np.sum(np.asarray(batch.node_mask)))


def pytest_configuration_file_holds_509661185_parameters():
    """``weight_spec`` of benchmarks/configs/ouro_2p6b_pp8.json: six layers
    of 51,388,416, embedding and untied head of 2 x 49,152 x 2,048, the final
    norm and the gate."""
    from common import load_json

    arch = load_json(_BENCH, "configs", "ouro_2p6b_pp8.json")["program_config"]["NeuralNetwork"]["Architecture"]
    spec = ref.weight_spec(arch, 1)
    params = sum(int(np.prod(shape)) for path, shape, _ in spec if path[0] == "params")
    per_layer = sum(int(np.prod(shape)) for path, shape, _ in spec if path[:2] == ("params", "layers_0"))
    assert (params, per_layer) == (509_661_185, 51_388_416)


def pytest_three_adamw_steps_match_reference(docs):
    """Through the program's own step (make_train_step): three AdamW steps
    against the reference's, the losses, every leaf and the one buffer (each
    exit's weight of the latest step)."""
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
        with jax.default_matmul_precision("highest"):
            (loss, loads), g = jax.value_and_grad(
                lambda q: ref.loss_and_loads(q, ref_batch(b), arch, "f32"), has_aux=True)(p)
        p, opt = rc.adamw_update(p, g, opt, 1e-3)
        buffers = ref.balance(buffers, loads, arch)
        ref_losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state.batch_stats["exit_weight"]), np.asarray(buffers["exit_weight"]),
                               rtol=1e-4)
    got, want, start = flat(state.params), flat(p), flat(variables["params"])
    moved = {k: float(jnp.linalg.norm(want[k] - start[k])) for k in want}
    median = float(np.median(list(moved.values())))
    for k in want:
        assert float(jnp.linalg.norm(got[k] - want[k])) / max(moved[k], median) <= 5e-3, k
