"""The token head's chunked cross-entropy (train/loss.py
``chunked_cross_entropy``): differentiated, its forward scan forms each
chunk's ``softmax - onehot`` and both gradient products and the backward only
scales them. Held against the rule it replaced, each chunk checkpointed and
its logits computed again in the backward scan (``checkpointed_scan`` below,
kept here as the oracle): directly, through ``token_loss`` and through
``compute_loss`` of the five tiny decoder stacks of the benchmark
(benchmarks/tests/tiny_*.py). The rows' weights take a gradient, each row's
cross-entropy over the divisor, as a plain ``jnp`` cross-entropy's autodiff
gives it (the looped stack's exit gate trains through it). The gradient jaxpr
of a training step holds one scan of three products a chunk for each head
pass and no head product elsewhere (the looped stack: one scan over all its
exits); evaluation runs one product a chunk."""

import copy
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmarks")
for _p in (_REPO, _BENCH, os.path.join(_BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from hydragnn_tpu.api import prepare_data  # noqa: E402
from hydragnn_tpu.data.synthetic import packed_documents_dataset  # noqa: E402
from hydragnn_tpu.models import create_model  # noqa: E402
from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step  # noqa: E402
from hydragnn_tpu.train import loss as ls  # noqa: E402
from hydragnn_tpu.train.loop import make_eval_step, mp_cast, mp_keep  # noqa: E402
from hydragnn_tpu.utils import tracer as tr  # noqa: E402
from reference import common as rc  # noqa: E402

VOCAB = 97
STACKS = ("zaya", "joyai", "trinity", "keyevl2", "ouro")


def checkpointed_scan(hidden, head, targets, weights, chunk_rows, den=1.0):
    """The earlier rule: a scan of checkpointed chunks, so the backward scan
    computes each chunk's logits again before its two gradient products."""
    t = hidden.shape[0]
    chunk = max(1, min(int(chunk_rows), t))
    pad = (-t) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n_chunks = (t + pad) // chunk

    @jax.checkpoint
    def one(h, tgt, w):
        logits = jnp.dot(h, head.astype(h.dtype), preferred_element_type=jnp.float32,
                         precision="highest" if h.dtype == jnp.float32 else None)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (lse - picked))

    def body(total, xs):
        return total + one(*xs), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (hidden.reshape(n_chunks, chunk, -1), targets.reshape(n_chunks, chunk),
         weights.reshape(n_chunks, chunk)))
    return total / den


def _f32(x):
    return np.asarray(x, np.float32)


def _rel(got, want) -> float:
    """max|got - want| over max|want|."""
    return float(np.max(np.abs(_f32(got) - _f32(want))) / max(np.max(np.abs(_f32(want))), 1e-30))


def _ulps(got, want, scale=None):
    """|got - want| in bfloat16 ulps of ``scale`` (elementwise: of the larger
    of the two)."""
    m = np.maximum(np.abs(_f32(got)), np.abs(_f32(want))) if scale is None else scale
    return np.abs(_f32(got) - _f32(want)) / 2.0 ** (np.floor(np.log2(np.maximum(m, 1e-30))) - 7)


def _operands(rows, dtype, seed=0):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(rows, 16)), jnp.float32).astype(dtype)
    head = jnp.asarray(rng.normal(size=(16, VOCAB)), jnp.float32).astype(dtype)
    targets = jnp.asarray(rng.integers(0, VOCAB, rows), jnp.int32)
    # a fifth of the rows weigh nothing, as the last node of a document does
    weights = jnp.asarray(rng.random(rows) < 0.8, jnp.float32)
    return hidden, head, targets, weights


@pytest.mark.parametrize("scale", [1.0, 0.3])
@pytest.mark.parametrize("rows", [64, 50])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def pytest_rule_matches_the_checkpointed_scan(dtype, rows, scale):
    """Loss and both gradients under ``jax.jit``, 16-row chunks (50 rows pad
    the last chunk), with the divisor folded into the forward: in float32
    within 1e-7 (loss) and 1e-6 (gradients) of the oracle; in bfloat16 equal
    to it under a unit outer cotangent. Under 0.3 (the MTP pass's weight) the
    stored gradient is scaled once where the oracle scaled every chunk:
    ``dh`` within one ulp of each element; the head's gradient, which both
    sum over chunks in the head's bfloat16, within one ulp of its largest
    element for each chunk summed."""
    hidden, head, targets, weights = _operands(rows, jnp.dtype(dtype))
    den = jnp.maximum(jnp.sum(weights), 1.0)

    def run(rule):
        f = lambda h, hd: scale * rule(h, hd, targets, weights, 16, den)
        return jax.jit(jax.value_and_grad(f, (0, 1)))(hidden, head)

    (loss, grads), (want_loss, want_grads) = run(ls.chunked_cross_entropy), run(checkpointed_scan)
    assert abs(float(loss) - float(want_loss)) <= 1e-7 * abs(float(want_loss))
    assert [g.dtype for g in grads] == [hidden.dtype, head.dtype]
    if dtype == "float32":
        for g, w in zip(grads, want_grads):
            assert _rel(g, w) <= 1e-6
    elif scale == 1.0:
        for g, w in zip(grads, want_grads):
            assert np.array_equal(_f32(g), _f32(w))
    else:
        assert np.max(_ulps(grads[0], want_grads[0])) <= 1.0
        chunks = ls.head_chunks(rows, 16)
        assert np.max(_ulps(grads[1], want_grads[1], np.max(np.abs(_f32(want_grads[1]))))) <= chunks


@pytest.mark.parametrize("rows", [64, 50])
def pytest_every_operand_takes_the_plain_cross_entropys_gradient(rows):
    """Against ``jax.grad`` of a plain ``jnp`` cross-entropy, float32 under
    ``jax.jit``, 16-row chunks (50 rows pad the last chunk): the gradients
    of ``hidden``, ``head`` AND ``weights`` (each row's cross-entropy over
    ``den``) within 1e-6, the weights drawn in [0, 1) as an exit
    distribution's are."""
    hidden, head, targets, _ = _operands(rows, jnp.float32, seed=5)
    weights = jnp.asarray(np.random.default_rng(6).random(rows), jnp.float32)

    def plain(h, hd, w):
        logits = jnp.dot(h, hd, precision="highest")
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.sum(w * ce) / 7.0

    rule = lambda h, hd, w: ls.chunked_cross_entropy(h, hd, targets, w, 16, 7.0)
    got, want = (jax.jit(jax.value_and_grad(f, (0, 1, 2)))(hidden, head, weights) for f in (rule, plain))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert _rel(g, w) <= 1e-6


def pytest_primal_is_the_sum_over_rows():
    """Undifferentiated, the rule is the plain weighted sum over rows divided
    by ``den``, padded rows included at weight 0."""
    hidden, head, targets, weights = _operands(50, jnp.float32, seed=3)
    logits = jnp.dot(hidden, head, precision="highest")
    per_row = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    want = float(jnp.sum(weights * per_row)) / 7.0
    got = float(jax.jit(lambda *a: ls.chunked_cross_entropy(*a, 16, 7.0))(hidden, head, targets, weights))
    assert abs(got - want) <= 1e-6 * abs(want)


# ---- the four tiny stacks of the benchmark ----------------------------------------------------------------------


def _tiny(stack: str, mixed: bool = False):
    """(config, first batch, model, variables) of a benchmark cell shrunk
    as its rehearsal shrinks it."""
    import drive_train

    ctx = importlib.import_module(f"tiny_{stack}").tiny_ctx()
    cfg = drive_train.program_config(ctx)
    cfg["NeuralNetwork"]["Training"]["mixed_precision"] = mixed
    g = ctx["traffic"]["generator_params"]
    docs = packed_documents_dataset(40, g["median_tokens"], g["sigma"], g["min_tokens"], g["max_tokens"],
                                    g["vocab_size"], seed=1)
    config, (loader, _, _), _ = prepare_data(cfg, (docs[:30], docs[30:35], docs[35:]))
    arch = config["NeuralNetwork"]["Architecture"]
    ref = importlib.import_module(f"reference.{arch['mpnn_type'].lower()}")
    variables = rc.make_weights(ref.weight_spec(arch, int(arch["input_dim"])), 5)
    return config, next(iter(loader)), create_model(config), variables


@pytest.fixture(scope="module")
def tiny():
    built = {}

    def get(stack, mixed=False):
        if (stack, mixed) not in built:
            built[stack, mixed] = _tiny(stack, mixed)
        return built[stack, mixed]

    return get


def _value_and_grad(model, variables, batch, mixed):
    def f(params):
        p, b = mp_cast(params, batch, False, mp_keep(model)) if mixed else (params, batch)
        out = ls.compute_loss(model, {"params": p, "batch_stats": variables["batch_stats"]}, b, model.cfg,
                              True, jax.random.PRNGKey(0), False)
        return out[0].astype(jnp.float32), out[1]

    return jax.jit(jax.value_and_grad(f, has_aux=True))(variables["params"])


def _against_oracle(monkeypatch, model, variables, batch, mixed=False):
    (loss, tasks), grads = _value_and_grad(model, variables, batch, mixed)
    with monkeypatch.context() as m:
        m.setattr(ls, "chunked_cross_entropy", checkpointed_scan)
        (want_loss, want_tasks), want_grads = _value_and_grad(model, variables, batch, mixed)
    flat = lambda tree: dict(jax.tree_util.tree_flatten_with_path(tree)[0])
    return loss, tasks, flat(grads), want_loss, want_tasks, flat(want_grads)


@pytest.mark.parametrize("stack", STACKS)
def pytest_compute_loss_of_a_tiny_stack_matches_the_oracle(monkeypatch, tiny, stack):
    """The whole float32 loss within 1e-7 and every parameter's gradient
    within 1e-6 of the oracle's, through ``compute_loss``: the tied head
    (ZAYA, KEYEVL2's and Trinity's own untied ones) and the JOYAI stack's
    untied head under two passes, the second at the MTP's weight 0.3."""
    config, batch, model, variables = tiny(stack)
    loss, tasks, grads, want_loss, want_tasks, want_grads = _against_oracle(monkeypatch, model, variables, batch)
    assert abs(float(loss) - float(want_loss)) <= 1e-7 * abs(float(want_loss))
    assert ("mtp" in tasks) == (stack == "joyai")
    assert set(grads) == set(want_grads)
    for key, g in grads.items():
        assert _rel(g, want_grads[key]) <= 1e-6, jax.tree_util.keystr(key)


def pytest_bf16_step_gradient_equals_the_oracles_under_a_unit_cotangent(monkeypatch, tiny):
    """The ZAYA stack's bfloat16 step (head tied to the embedding, one pass,
    the head's outer cotangent exactly 1.0): loss and every gradient leaf
    equal to the oracle's, bit for bit."""
    config, batch, model, variables = tiny("zaya", mixed=True)
    loss, _, grads, want_loss, _, want_grads = _against_oracle(monkeypatch, model, variables, batch, mixed=True)
    assert float(loss) == float(want_loss)
    for key, g in grads.items():
        assert np.array_equal(_f32(g), _f32(want_grads[key])), jax.tree_util.keystr(key)


@pytest.mark.parametrize("ahead", [1, 2])
def pytest_token_loss_matches_the_oracle(monkeypatch, tiny, ahead):
    """``token_loss`` at ``ahead`` 1 and 2 (the MTP's targets, more rows of
    weight 0) on the ZAYA stack's batch, its hidden drawn: loss and both
    gradients against the oracle's under ``jax.jit``."""
    config, batch, model, variables = tiny("zaya")
    head = variables["params"]["embedding"]
    hidden = jnp.asarray(np.random.default_rng(ahead).normal(size=(batch.z.shape[0], head.shape[0])), jnp.float32)
    f = lambda h, hd: ls.token_loss(h, hd, batch, 64, ahead)
    got = jax.jit(jax.value_and_grad(f, (0, 1)))(hidden, head)
    with monkeypatch.context() as m:
        m.setattr(ls, "chunked_cross_entropy", checkpointed_scan)
        want = jax.jit(jax.value_and_grad(f, (0, 1)))(hidden, head)
    assert abs(float(got[0]) - float(want[0])) <= 1e-7 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert _rel(g, w) <= 1e-6


# ---- structure: one scan of three products a chunk ------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _dots(sub)


def _touches_vocab(eqn) -> bool:
    return any(VOCAB in v.aval.shape for v in list(eqn.invars) + list(eqn.outvars) if hasattr(v, "aval"))


def _head_scans(jaxpr, outside):
    """The scans whose body holds a product over the vocabulary, as
    ``(length, products in the body)``; products over the vocabulary outside
    every such scan are appended to ``outside``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and _touches_vocab(eqn):
            outside.append(eqn)
        subs = list(_sub_jaxprs(eqn))
        if eqn.primitive.name == "scan" and any(_touches_vocab(d) for s in subs for d in _dots(s)):
            found.append((eqn.params["length"], sum(1 for s in subs for _ in _dots(s))))
            continue
        for sub in subs:
            found += _head_scans(sub, outside)
    return found


@pytest.mark.parametrize("stack,passes", [("zaya", 1), ("joyai", 2)])
def pytest_training_step_has_one_head_scan_of_three_products(tiny, stack, passes):
    """The training step's gradient jaxpr: for each head pass ONE scan over
    the chunks whose body holds three products (logits, ``dh``, the head's
    gradient) and no product over the vocabulary outside it: the backward
    runs none. The evaluation step: one scan of ONE product a chunk a pass.
    The two counters say the same: every chunk's gradient formed in the
    forward in training, none in evaluation."""
    config, batch, model, variables = tiny(stack)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(copy.deepcopy(variables), tx)
    chunks = ls.head_chunks(batch.z.shape[0], model.cfg.decoder.loss_chunk_rows)
    assert chunks > 1

    train_step = make_train_step(model, tx, False, False)
    outside = []
    scans = _head_scans(jax.make_jaxpr(train_step)(state, batch, jax.random.PRNGKey(0)).jaxpr, outside)
    assert scans == [(chunks, 3)] * passes and not outside, (scans, len(outside))
    eval_step = make_eval_step(model, False, False)
    outside = []
    assert _head_scans(jax.make_jaxpr(eval_step)(state, batch).jaxpr, outside) == [(chunks, 1)] * passes
    assert not outside

    _, _, tasks = train_step(state, batch, jax.random.PRNGKey(0))
    assert float(tasks[tr.CT_HEAD_CHUNKS]) == float(tasks[tr.CT_HEAD_CHUNKS_GRAD_IN_FORWARD]) == passes * chunks
    _, tasks, _ = eval_step(TrainState.create(copy.deepcopy(variables), tx), batch)
    assert float(tasks[tr.CT_HEAD_CHUNKS]) == passes * chunks
    assert float(tasks[tr.CT_HEAD_CHUNKS_GRAD_IN_FORWARD]) == 0.0


def pytest_looped_stack_has_one_head_scan_over_every_exit(tiny):
    """The looped stack's four exits go through ONE chunked cross-entropy:
    the training step's gradient jaxpr has one scan over the chunks of ``4 T``
    rows whose body holds three products, and no product over the vocabulary
    outside it; the evaluation step one scan of one product a chunk; the
    counters count the chunks of every exit."""
    config, batch, model, variables = tiny("ouro")
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(copy.deepcopy(variables), tx)
    passes = model.cfg.ouro.total_ut_steps
    chunks = ls.head_chunks(passes * batch.z.shape[0], model.cfg.decoder.loss_chunk_rows)
    assert passes == 4 and chunks == 4 * ls.head_chunks(batch.z.shape[0], model.cfg.decoder.loss_chunk_rows)

    train_step = make_train_step(model, tx, False, False)
    outside = []
    assert _head_scans(jax.make_jaxpr(train_step)(state, batch, jax.random.PRNGKey(0)).jaxpr, outside) == [(chunks, 3)]
    assert not outside
    eval_step = make_eval_step(model, False, False)
    assert _head_scans(jax.make_jaxpr(eval_step)(state, batch).jaxpr, outside) == [(chunks, 1)] and not outside
    _, _, tasks = train_step(state, batch, jax.random.PRNGKey(0))
    assert float(tasks[tr.CT_HEAD_CHUNKS]) == float(tasks[tr.CT_HEAD_CHUNKS_GRAD_IN_FORWARD]) == chunks
