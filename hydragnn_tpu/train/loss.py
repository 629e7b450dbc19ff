"""Masked multi-task losses.

Equivalent of the reference's ``Base.loss``/``loss_hpweighted``
(hydragnn/models/Base.py:572-580, 659-686) adapted to padded batches: every
reduction is over *real* rows only (graph_mask / node_mask), which reproduces
the reference's per-batch mean over ragged tensors.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..data.graph import GraphBatch
from ..models.base import ModelConfig
from ..models.decoder import EXIT_GATE, LOSS_TERM_PREFIX, MTP_HIDDEN, follows
from ..models.ouro import ENTROPY_WEIGHT, exit_distribution
from ..utils import tracer as tr


def _elementwise(loss_type: str, err: jnp.ndarray) -> jnp.ndarray:
    lt = loss_type.lower()
    if lt == "mse":
        return err**2
    if lt in ("mae", "l1"):
        return jnp.abs(err)
    if lt == "rmse":  # reduced later; rmse applied at head level
        return err**2
    raise ValueError(
        f"unknown loss_function_type {loss_type!r} (GaussianNLLLoss is handled "
        "by multitask_loss via the variance heads)"
    )


def masked_mean(
    values: jnp.ndarray, mask: jnp.ndarray, row_weights=None
) -> jnp.ndarray:
    """Mean over real rows. ``row_weights`` (optional, per-row) turns it
    into the weighted mean Σ w·m·v / Σ w·m·C — the per-branch loss
    balancing hook (docs/GFM.md): with all weights 1 (or None) the
    computation is byte-identical to the unweighted path."""
    m = mask.reshape(mask.shape + (1,) * (values.ndim - mask.ndim)).astype(values.dtype)
    if row_weights is not None:
        w = row_weights.reshape(
            row_weights.shape + (1,) * (values.ndim - row_weights.ndim)
        ).astype(values.dtype)
        m = m * w
    denom = jnp.maximum(jnp.sum(m) * values.shape[-1], 1.0)
    return jnp.sum(values * m) / denom


def head_loss(
    pred: jnp.ndarray,
    target: jnp.ndarray,
    mask: jnp.ndarray,
    loss_type: str,
    row_weights=None,
) -> jnp.ndarray:
    per_elem = _elementwise(loss_type, pred - target)
    loss = masked_mean(per_elem, mask, row_weights)
    if loss_type.lower() == "rmse":
        loss = jnp.sqrt(loss)
    return loss


def gaussian_nll(
    pred: jnp.ndarray,
    var: jnp.ndarray,
    target: jnp.ndarray,
    mask: jnp.ndarray,
    eps: float = 1e-6,
    row_weights=None,
) -> jnp.ndarray:
    """Gaussian negative log likelihood with predicted variance
    (torch GaussianNLLLoss semantics, full=False; reference wires the variance
    head via var_output, Base.py:92-96 and the `headvar = out**2` split)."""
    v = jnp.maximum(var, eps)
    per_elem = 0.5 * (jnp.log(v) + (pred - target) ** 2 / v)
    return masked_mean(per_elem, mask, row_weights)


def _per_branch_head_loss(
    per_elem: jnp.ndarray,
    mask: jnp.ndarray,
    branch_of_row: jnp.ndarray,
    num_branches: int,
    loss_type: str,
) -> jnp.ndarray:
    """[num_branches] masked mean of one head's per-element loss, reduced
    per branch — the in-graph per-branch loss census the mixture drift
    monitor consumes (mix/balance.py). Costs two segment-sums per head."""
    m = mask.reshape(
        mask.shape + (1,) * (per_elem.ndim - mask.ndim)
    ).astype(per_elem.dtype)
    row_num = jnp.sum(per_elem * m, axis=tuple(range(1, per_elem.ndim)))
    row_den = jnp.sum(m, axis=tuple(range(1, m.ndim))) * per_elem.shape[-1]
    seg = jnp.clip(branch_of_row.astype(jnp.int32), 0, num_branches - 1)
    num = jax.ops.segment_sum(row_num, seg, num_segments=num_branches)
    den = jax.ops.segment_sum(row_den, seg, num_segments=num_branches)
    out = num / jnp.maximum(den, 1.0)
    if loss_type.lower() == "rmse":
        out = jnp.sqrt(out)
    return out


def _chunk_loss(h, head, tgt, w):
    """One chunk's ``sum(w * ce)`` and its rows' cross-entropy ``ce =
    logsumexp(logits) - logit[tgt]`` ``[chunk]``, the logits ``[chunk, V]`` in
    float32."""
    logits = jnp.dot(h, head.astype(h.dtype), preferred_element_type=jnp.float32,
                     precision="highest" if h.dtype == jnp.float32 else None)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ce = lse - jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
    return jnp.sum(w * ce), ce


def _chunk_rows(t: int, chunk_rows: int) -> int:
    return max(1, min(int(chunk_rows), t))


def head_chunks(t: int, chunk_rows: int) -> int:
    """The row chunks ``chunked_cross_entropy`` splits ``t`` rows into."""
    return -(-t // _chunk_rows(t, chunk_rows))


def _stacked(hidden, targets, weights, chunk_rows):
    """The rows padded with zero-weight rows to whole chunks, ``[n, chunk, ...]``."""
    t = hidden.shape[0]
    chunk = _chunk_rows(t, chunk_rows)
    pad = (-t) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n = (t + pad) // chunk
    return hidden.reshape(n, chunk, -1), targets.reshape(n, chunk), weights.reshape(n, chunk)


def _in_order(values):
    """``values [n]`` summed one after the other from the first, as a scan's
    carry adds them."""
    return jax.lax.scan(lambda total, v: (total + v, None), jnp.zeros((), jnp.float32), values)[0]


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _cross_entropy(hidden, head, targets, weights, den, chunk_rows):
    def body(total, xs):
        h, tgt, w = xs
        return total + _chunk_loss(h, head, tgt, w)[0], None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), _stacked(hidden, targets, weights, chunk_rows))
    return total / den


def _cross_entropy_fwd(hidden, head, targets, weights, den, chunk_rows):
    # each chunk's cotangent, as autodiff of ``total / den`` under a unit
    # cotangent forms it: the gradient kept is the one autodiff would give
    ct = jnp.ones((), jnp.float32) / den

    def body(dhead, xs):
        h, tgt, w = xs
        value, pull, ce = jax.vjp(lambda h_, head_: _chunk_loss(h_, head_, tgt, w), h, head, has_aux=True)
        dh, dhead_chunk = pull(ct)
        return dhead + dhead_chunk, (value, dh, ce)

    # last chunk first, as the transposed scan adds the head's gradient
    dhead, (values, dh, ce) = jax.lax.scan(body, jnp.zeros_like(head),
                                           _stacked(hidden, targets, weights, chunk_rows), reverse=True)
    t = hidden.shape[0]
    dh = dh.reshape(-1, hidden.shape[1])[:t]
    return _in_order(values) / den, (dh, dhead, ce.reshape(-1)[:t], den)


def _cross_entropy_bwd(chunk_rows, residuals, g):
    # the weights' cotangent is each row's cross-entropy over ``den``: a
    # weight that is a function of parameters (a looped stack's exit
    # distribution, ``loop_exit_loss``) trains through it; a constant mask's
    # is never used and compiles away
    dh, dhead, ce, den = residuals
    return (g * dh).astype(dh.dtype), (g * dhead).astype(dhead.dtype), None, g * ce / den, None


_cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def chunked_cross_entropy(hidden, head, targets, weights, chunk_rows: int, den=1.0):
    """Sum over rows of ``weights * (logsumexp(hidden @ head) - logit[target])``,
    divided by ``den``, with the logits in float32 and only ``chunk_rows`` rows
    of them alive at a time: ``[32768, 32784]`` float32 logits whole would be
    4.3 GB. ``hidden [T, D]``, ``head [D, V]``, ``targets [T]`` int,
    ``weights [T]`` float32, ``den`` a float32 scalar.

    Undifferentiated (evaluation, serving) it is one scan of one product a
    chunk. Differentiated, the loss is a scalar whose cotangent only scales
    the gradient, so the forward is ONE scan that forms each chunk's logits,
    ``w * (softmax - onehot) / den`` and both gradient products (``dh`` and
    the head's, summed over chunks), and the backward multiplies those by the
    cotangent: three products a chunk, none in the backward. The weights take
    a gradient too, ``ce / den`` a row (each row's cross-entropy, kept from
    the forward scan)."""
    return _cross_entropy(hidden, head, targets, weights, jnp.asarray(den, jnp.float32), int(chunk_rows))


def _follows(batch: GraphBatch, ahead: int):
    """Weight 1.0 on the real nodes whose node ``ahead`` places on is real and
    in their graph."""
    return follows(batch.node_graph, batch.node_mask, ahead).astype(jnp.float32)


def token_loss(hidden, head, batch: GraphBatch, chunk_rows: int, ahead: int = 1):
    """The token head: cross-entropy of the id of the node ``ahead`` places on
    (the NEXT node; 2 for a multi-token-prediction module), summed over the
    real nodes for which that node is in the same graph and divided by the
    count of (node, next node) pairs: the mean next-node loss for ``ahead`` 1,
    and a second loss on the same count beside it (DeepSeek-V3 divides both by
    the sequence length). Through ``head [D, V]`` (the embedding where it is
    tied). Ids ride in ``batch.z``; float32 throughout."""
    with tr.scope(tr.HG_TOKEN_LOSS):
        ids = jnp.clip(batch.z.astype(jnp.int32), 0, head.shape[1] - 1)
        den = jnp.maximum(jnp.sum(_follows(batch, 1)), 1.0)
        return chunked_cross_entropy(hidden, head, jnp.roll(ids, -ahead), _follows(batch, ahead), chunk_rows, den)


def loop_exit_loss(exits, gate_logits, head, batch: GraphBatch, chunk_rows: int):
    """A looped stack's head (models/ouro.py): ``exits [P, T, D]``, each
    pass's normalised output, and the exit gate's logits ``gate_logits [P,
    T]``. The exit distribution ``p`` (``models/ouro.py exit_distribution``)
    weighs each exit's next-token cross-entropy ``ce_t``, less ``beta =
    models/ouro.py ENTROPY_WEIGHT`` times its entropy: ``L = (1/den) sum_i
    follows_i [sum_t p_t(i) ce_t(i) - beta H(p(i))]``, ``den`` the count of
    (node, next node) pairs as ``token_loss`` counts it. The P exits are ONE
    ``chunked_cross_entropy`` over ``[P T, D]`` rows, the targets repeated and
    the weights ``p_t(i) follows_i``: one scan and one head-gradient
    accumulator, and the weights' cotangent carries ``ce / den`` to the gate.
    -> (L, the expected cross-entropy, the mean entropy)."""
    with tr.scope(tr.HG_TOKEN_LOSS):
        passes, t, d = exits.shape
        ids = jnp.clip(batch.z.astype(jnp.int32), 0, head.shape[1] - 1)
        w1 = _follows(batch, 1)
        den = jnp.maximum(jnp.sum(w1), 1.0)
        p, logp = exit_distribution(gate_logits.astype(jnp.float32))
        ce = chunked_cross_entropy(exits.reshape(passes * t, d), head, jnp.tile(jnp.roll(ids, -1), passes),
                                   (p * w1).reshape(-1), chunk_rows, den)
        entropy = -jnp.sum(w1 * jnp.sum(p * logp, axis=0)) / den
        return ce - ENTROPY_WEIGHT * entropy, ce, entropy


def head_counts(chunks: int, train: bool) -> Dict:
    """The head's row chunks a step and those whose gradient the forward scan
    formed (``chunked_cross_entropy``: all of them in training), as the
    step's two ``count:head_chunks*`` entries."""
    return {tr.CT_HEAD_CHUNKS: jnp.float32(chunks),
            tr.CT_HEAD_CHUNKS_GRAD_IN_FORWARD: jnp.float32(chunks if train else 0)}


def _apply(model, variables, batch, train, rng):
    """-> (outputs, mutated collections): batch statistics are mutable in
    training only."""
    if train:
        return model.apply(variables, batch, train=True, mutable=["batch_stats"],
                           rngs={"dropout": rng})
    return model.apply(variables, batch, train=False), {}


def _token_head_loss(model, variables, batch, cfg, train, rng):
    outputs, mutated = _apply(model, variables, batch, train, rng)
    name, params = cfg.output_names[0], variables["params"]
    # an untied head where the stack has one (models/joyai.py), else the embedding
    head, chunk = params.get("head", params["embedding"]), cfg.decoder.loss_chunk_rows
    if EXIT_GATE in outputs:
        # a looped stack's exits, one a pass, weighted by its exit gate
        # (models/ouro.py): ONE head scan over all of them
        exits = outputs[name]
        loss, ce, entropy = loop_exit_loss(exits, outputs[EXIT_GATE], head, batch, chunk)
        tasks = {name: ce, "exit_entropy": entropy}
        tasks.update(head_counts(head_chunks(exits.shape[0] * exits.shape[1], chunk), train))
    else:
        loss = token_loss(outputs[name], head, batch, chunk)
        tasks, passes = {name: loss}, 1
        if MTP_HIDDEN in outputs:
            # the multi-token-prediction module's loss, through the same head
            tasks["mtp"] = token_loss(outputs[MTP_HIDDEN], head, batch, chunk, ahead=2)
            loss = loss + cfg.joyai.mtp_loss_weight * tasks["mtp"]
            passes = 2
        tasks.update(head_counts(passes * head_chunks(outputs[name].shape[0], chunk), train))
    for key, term in outputs.items():
        if key.startswith(LOSS_TERM_PREFIX):
            # a stack's own term of the loss (models/keyevl2.py), weighted there
            tasks[key[len(LOSS_TERM_PREFIX):]] = term
            loss = loss + term
    tasks.update({k: v for k, v in outputs.items() if k.startswith(tr.COUNTER_PREFIX)})
    return loss, tasks, mutated, {name: outputs[name]}


def compute_loss(
    model,
    variables: Dict,
    batch: GraphBatch,
    cfg: ModelConfig,
    train: bool,
    rng,
    compute_grad_energy: bool,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Dict, Dict[str, jnp.ndarray]]:
    """Single entry point for both objectives, shared by the single-device and
    mesh-parallel step builders: returns (total, per-task losses, mutated
    collections, outputs)."""
    if cfg.decoder is not None:
        return _token_head_loss(model, variables, batch, cfg, train, rng)
    if compute_grad_energy:
        def apply_outputs(b):
            if train:
                return model.apply(
                    variables,
                    b,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": rng},
                )
            return model.apply(variables, b, train=False), None

        tot, tasks, aux, preds = energy_force_loss(apply_outputs, batch, cfg)
        return tot, tasks, aux or {}, preds
    outputs, mutated = _apply(model, variables, batch, train, rng)
    tot, tasks = multitask_loss(outputs, batch, cfg)
    return tot, tasks, mutated, outputs


def energy_force_loss(
    apply_outputs: "callable",
    batch: GraphBatch,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], object, Dict[str, jnp.ndarray]]:
    """Energy + autograd-force loss (reference: Base.energy_force_loss,
    hydragnn/models/Base.py:582-636). Returns
    ``(total, per_task_losses, aux, predictions)`` where ``predictions`` holds
    the graph energies [G] and forces [N,3] already computed for the loss.

    The model's single node head predicts per-node energy; graph energy is the
    masked segment-sum over nodes and forces are ``-dE/dpos`` — in JAX a plain
    ``jax.grad`` through the forward (vs the reference's
    ``torch.autograd.grad(..., create_graph=True)`` dance), so the force loss
    backward is just second-order AD handled by XLA.

    ``apply_outputs(batch) -> (outputs, aux)`` must close over params so that
    this function can differentiate w.r.t. positions only; ``aux`` (e.g.
    mutated batch stats) is threaded through ``has_aux`` and returned.

    Targets: ``batch.graph_targets['energy']`` [G,1] and
    ``batch.node_targets['forces']`` [N,3].
    """
    assert cfg.num_heads == 1 and cfg.output_type[0] == "node", (
        "energy-force training needs exactly one node head predicting nodal "
        "energy (reference assert, Base.py:590-593)"
    )
    name = cfg.output_names[0]
    node_mask_f = batch.node_mask.astype(batch.pos.dtype)
    graph_mask_f = batch.graph_mask.astype(batch.pos.dtype)

    def graph_energy_sum(pos):
        outputs, aux = apply_outputs(batch.replace(pos=pos))
        node_e = outputs[name][:, 0] * node_mask_f
        graph_e = jnp.zeros((batch.num_graphs,), node_e.dtype)
        graph_e = graph_e.at[batch.node_graph].add(node_e)
        return jnp.sum(graph_e * graph_mask_f), (graph_e, aux)

    (_, (graph_e_pred, aux)), de_dpos = jax.value_and_grad(
        graph_energy_sum, has_aux=True
    )(batch.pos)
    forces_pred = -de_dpos

    e_true = batch.graph_targets["energy"].reshape(-1)
    f_true = batch.node_targets["forces"]

    energy_loss = head_loss(
        graph_e_pred[:, None], e_true[:, None], batch.graph_mask, cfg.loss_function_type
    )
    force_loss = head_loss(
        forces_pred, f_true, batch.node_mask, cfg.loss_function_type
    )
    # auto-balanced force weight: energy and force terms contribute equally
    # in the units of the data (Base.py:626-631)
    e_w = cfg.normalized_task_weights[0]
    mean_abs_e = masked_mean(jnp.abs(e_true)[:, None], batch.graph_mask)
    mean_abs_f = masked_mean(jnp.abs(f_true), batch.node_mask)
    f_w = e_w * mean_abs_e / (mean_abs_f + 1e-8)
    tot = e_w * energy_loss + f_w * force_loss
    tasks = {name: energy_loss, "forces": force_loss}
    preds = {
        name: graph_e_pred[:, None],
        "forces": forces_pred * node_mask_f[:, None],
    }
    return tot, tasks, aux, preds


def predict_energy_forces(
    apply_outputs: "callable", batch: GraphBatch, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inference-side energies [G] and forces [N,3] (masked)."""
    name = cfg.output_names[0]
    node_mask_f = batch.node_mask.astype(batch.pos.dtype)

    def graph_energy_sum(pos):
        outputs, _ = apply_outputs(batch.replace(pos=pos))
        node_e = outputs[name][:, 0] * node_mask_f
        graph_e = jnp.zeros((batch.num_graphs,), node_e.dtype)
        graph_e = graph_e.at[batch.node_graph].add(node_e)
        return jnp.sum(graph_e * batch.graph_mask.astype(node_e.dtype)), graph_e

    (_, graph_e), de_dpos = jax.value_and_grad(graph_energy_sum, has_aux=True)(
        batch.pos
    )
    forces = -de_dpos * node_mask_f[:, None]
    return graph_e, forces


def multitask_loss(
    outputs: Dict[str, jnp.ndarray],
    batch: GraphBatch,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Total weighted loss + per-task unweighted losses
    (reference: loss_hpweighted, Base.py:659-686).

    Multibranch models with ``cfg.branch_loss_weights`` set (planted by
    the Mixture config section, mix/balance.py) weight every graph's loss
    contribution by its branch's static weight — the in-graph half of
    per-branch loss balancing; ``cfg.branch_loss_metrics`` additionally
    emits per-branch total-loss scalars as ``branch<i>`` task entries, so
    the drift monitor gets its census through the loop's existing
    device-side bookkeeping (no extra host syncs)."""
    weights = cfg.normalized_task_weights
    B = int(cfg.num_branches)
    blw = cfg.branch_loss_weights if B > 1 else None
    graph_branch = batch.dataset_id.astype(jnp.int32)
    gw = None
    if blw:
        w_arr = jnp.asarray(blw, jnp.float32)
        gw = w_arr[jnp.clip(graph_branch, 0, B - 1)]
    want_branch = B > 1 and cfg.branch_loss_metrics
    tot = 0.0
    tasks: Dict[str, jnp.ndarray] = {}
    branch_tot = jnp.zeros((B,), jnp.float32) if want_branch else None
    for name, t, w in zip(cfg.output_names, cfg.output_type, weights):
        pred = outputs[name]
        if t == "graph":
            target = batch.graph_targets[name]
            mask = batch.graph_mask
            branch_of_row = graph_branch
        else:
            target = batch.node_targets[name]
            mask = batch.node_mask
            branch_of_row = graph_branch[batch.node_graph]
        target = target.reshape(pred.shape)
        row_w = None if gw is None else (
            gw if t == "graph" else gw[batch.node_graph]
        )
        if cfg.var_output:
            task = gaussian_nll(
                pred, outputs[f"{name}__var"], target, mask, row_weights=row_w
            )
        else:
            task = head_loss(
                pred, target, mask, cfg.loss_function_type, row_weights=row_w
            )
        tasks[name] = task
        tot = tot + w * task
        if want_branch:
            if cfg.var_output:
                # gaussian-NLL census: same per-element formula the head
                # loss reduces, never the rmse sqrt
                v = jnp.maximum(outputs[f"{name}__var"], 1e-6)
                per_elem = 0.5 * (jnp.log(v) + (pred - target) ** 2 / v)
                per_branch_type = "mse"
            else:
                per_elem = _elementwise(cfg.loss_function_type, pred - target)
                per_branch_type = cfg.loss_function_type
            branch_tot = branch_tot + w * _per_branch_head_loss(
                per_elem, mask, branch_of_row, B, per_branch_type
            )
    if want_branch:
        for b in range(B):
            tasks[f"branch{b}"] = branch_tot[b]
    return tot, tasks
