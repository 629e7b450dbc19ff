"""What the decoder stacks share (models/zaya.py, models/joyai.py,
models/afmoe.py, models/keyevl2.py, models/ouro.py).

Token = node, document = graph, packed sequence = packed batch: the batcher
lays graphs out contiguously along the flat node axis, so ``node_graph`` is a
packed sequence's segment ids and a node's index within its graph is its
position. Here, once: RMSNorm, RoPE from that index (rotate-half and
interleaved), the token embedding, the route to the causal flash kernel, the
SiLU-gated expert products on group-aligned rows, dispatch and combine around
them for a token of several assignments (top-k), the top-k router (sigmoid or
softmax scores) and the expert sublayer of the stacks that route so
(``ExpertSpec``, ``route``, ``expert_sublayer``), the balancing rules of a
router's bias buffer, the route to the learned sparse attention
(``sparse_attention``), the initial scales, the per-layer rematerialisation (a
layer keeps its incoming residual stream and, of its causal flash launch, ``o``
and one ``lse`` number a row: the forward kernel runs once a step; of its
indexer, the selection and the indexer loss's gradient) and the poison of a
step that cannot stand.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.remat import CAUSAL_FLASH_RESIDUAL_NAMES, DSA_RESIDUAL_NAMES
from ..utils import tracer as tr

# the key of a multi-token-prediction module's hidden state among a model's
# outputs (models/joyai.py returns it, train/loss.py reads it)
MTP_HIDDEN = "mtp_hidden"
# the key of a looped stack's exit gate logits ``[passes, T]`` among its
# outputs (models/ouro.py returns them beside its passes' exits, train/loss.py
# ``loop_exit_loss`` reads both)
EXIT_GATE = "exit_gate_logits"
# a model output under this prefix is a term of the training loss, added to
# the token loss as it is (already weighted) and reported under its name
# (train/loss.py reads it)
LOSS_TERM_PREFIX = "loss:"

# the gain of the router bias's balancing rule (``balanced_bias``); a constant
# of the stacks, not a key
ROUTER_BIAS_GAIN = 0.01


def rms_norm(x, gain, eps: float):
    """RMSNorm in float32, returned in the input's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, rot: int, theta: float, interleaved: bool = False):
    """RoPE on the first ``rot`` channels of each head of ``x [T, H, d]``; the
    angle from the in-graph index, in float32. Rotate-half pairs channel ``i``
    with ``i + rot / 2``; ``interleaved`` pairs ``2 i`` with ``2 i + 1``."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf[..., :rot].reshape(xf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(xf.shape[:-1] + (rot,))
        out = jnp.concatenate([turned, xf[..., rot:]], axis=-1)
    else:
        x1, x2, rest = xf[..., :half], xf[..., half:rot], xf[..., rot:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


def dense(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def embed_tokens(emb, ids_raw, vocab_size: int):
    """Rows of the embedding for the node ids of ``batch.z``. ``emb`` is
    stored as a head reads it, ``[hidden, vocabulary]``: the lookup takes rows
    of the transpose. -> (x [T, hidden], ids [T] clipped)."""
    ids = jnp.clip(ids_raw.astype(jnp.int32), 0, vocab_size - 1)
    return emb.T[ids], ids


def batch_aux(batch) -> Dict:
    """What a layer reads of the batch: each node's index in its graph, the
    segment ids and the mask."""
    from .base import _node_position_in_graph

    return {"pos": _node_position_in_graph(batch), "node_graph": batch.node_graph,
            "node_mask": batch.node_mask}


def follows(node_graph, node_mask, ahead: int):
    """Real nodes whose node ``ahead`` places on is real and in their graph
    (bool ``[T]``): the terms of a token loss ``ahead`` places ahead."""
    same = (jnp.roll(node_graph, -ahead) == node_graph) & jnp.roll(node_mask, -ahead) & node_mask
    return same.at[-ahead:].set(False)


def causal_attention(q, k, v, aux, max_nodes: int, window: Optional[int] = None):
    """Route: the Pallas flash kernel on the TPU (or where
    ``HYDRAGNN_PALLAS_FLASH`` forces it, interpreted), else the flat masked
    reference. ``v`` may be narrower than ``q`` and ``k``; ``window`` is a
    sliding layer's bound (a query sees its ``window`` latest keys)."""
    from ..ops.pallas_flash_attention import (
        _flash_route_enabled, flash_causal_attention, reference_causal_attention)

    node_graph, node_mask = aux["node_graph"], aux["node_mask"]
    if not _flash_route_enabled():
        return reference_causal_attention(q, k, v, node_graph, node_mask, window)
    return flash_causal_attention(
        q, k, v, node_graph, node_mask, max_nodes,
        interpret=jax.default_backend() != "tpu", window=window,
    )


def sparse_attention(q, k, v, qi, ki, w, aux, max_nodes: int, topk: int):
    """Causal attention over the keys a learned indexer selects
    (ops/pallas_dsa_indexer.py): ``qi [T, H, d]``, ``ki [T, d]``, ``w [T,
    H]`` score every earlier key of a query's document; the query attends the
    ``min(n_t, topk)`` of largest score. -> (o ``[T, Hq, dv]``, the indexer
    loss ``sum over real t of KL(p_t || softmax over S_t of I)``, which
    trains ``qi``, ``ki``, ``w`` alone). The Pallas launches where the flash
    route is on (``hg_dsa_indexer``, the ``hg_flash_sparse`` launches,
    ``hg_dsa_indexer_bwd``), else the plain ``jnp`` references."""
    from ..ops import pallas_dsa_indexer as dsa
    from ..ops.pallas_flash_attention import (
        _flash_route_enabled, flash_causal_attention, reference_causal_attention)

    node_graph, node_mask = aux["node_graph"], aux["node_mask"]
    if not _flash_route_enabled():
        sel, _ = dsa.reference_select(qi, ki, w, node_graph, node_mask, topk)
        o = reference_causal_attention(q, k, v, node_graph, node_mask, select=sel)
        return o, dsa.reference_index_loss(qi, ki, w, q, k, sel)
    interpret = jax.default_backend() != "tpu"
    words, _, _, lse_index = dsa.dsa_select(qi, ki, w, node_graph, node_mask, aux["pos"], topk, max_nodes,
                                            interpret)
    o, lse = flash_causal_attention(q, k, v, node_graph, node_mask, max_nodes, interpret=interpret,
                                    select=words)
    loss = dsa.dsa_index_loss(qi, ki, w, q, k, lse, words, lse_index, node_graph, node_mask, max_nodes,
                              interpret)
    return o, loss


def expert_products(x_rows, w_gate, w_up, w_down, layout, block_m: int, kernel: bool):
    """SiLU-gated expert MLP on the group-aligned rows (``block_m`` the
    layout's row tile)."""
    from ..ops.pallas_grouped_matmul import grouped_matmul, reference_grouped_matmul

    tg, nt = layout["tile_group"], layout["n_tiles"]

    def gmm(a, w):
        if not kernel:
            return reference_grouped_matmul(a, w.astype(a.dtype), tg, block_m)
        return grouped_matmul(a, w.astype(a.dtype), tg, nt, block_m,
                              interpret=jax.default_backend() != "tpu")

    h = jax.nn.silu(gmm(x_rows, w_gate)) * gmm(x_rows, w_up)
    return gmm(h, w_down)


def gated_mlp(u, w_gate, w_up, w_down):
    """``W_down (silu(W_gate u) * W_up u)`` on every row."""
    return dense(jax.nn.silu(dense(u, w_gate)) * dense(u, w_up), w_down)


def held_slot(choice, experts_held):
    """Each chosen expert's place among the experts held, or
    ``len(experts_held)`` for one held elsewhere: a compare with each held id
    inside one reduction, with no gather (a table lookup costs a scalar read
    an assignment on the chip)."""
    held = len(experts_held)
    hit = choice[..., None] == jnp.asarray(experts_held, choice.dtype)
    return held + jnp.sum(jnp.where(hit, jnp.arange(held, dtype=choice.dtype) - held, 0), axis=-1)


def topk_layout(choice, node_mask, experts_held, num_experts: int, block_m: int, rows: int = 0):
    """The group-aligned layout of a top-k choice ``[T, k]``: a token is ``k``
    assignments (assignment ``t k + j``), each a row of its own where its
    expert is held, so a token has 0 to ``k`` rows here. ``rows`` is the row
    budget (0: the worst case, every assignment of every token). Adds to
    ``aligned_layout``'s keys ``token [R]``, the token of each row (``T`` for
    a row that holds none), and ``tokens_here []``, the tokens with a row."""
    from ..ops.pallas_grouped_matmul import aligned_layout

    t, k = choice.shape
    slot = jnp.where(node_mask[:, None], held_slot(choice, experts_held), len(experts_held))
    layout = aligned_layout(slot.reshape(-1), len(experts_held), block_m, rows)
    layout["token"] = jnp.where(layout["src"] < t * k, layout["src"] // k, t)
    layout["tokens_here"] = jnp.sum(jnp.any(slot < len(experts_held), axis=1).astype(jnp.int32))
    return layout


@jax.custom_vjp
def dispatch_rows(u, token):
    """``concat(u, 0)[token]``: a gather WITH REPEATS of the tokens' rows into
    the aligned buffer (a token with several experts here is read several
    times, one with none not at all). Its cotangent is the sum over a token's
    rows, taken in float32."""
    zero = jnp.zeros((1,) + u.shape[1:], u.dtype)
    return jnp.concatenate([u, zero], axis=0)[token]


def _dispatch_fwd(u, token):
    # the residual carries the token count and the dtype as an empty array
    return dispatch_rows(u, token), (token, jnp.zeros((u.shape[0], 0), u.dtype))


def _dispatch_bwd(res, g):
    token, like = res
    t = like.shape[0]
    du = jnp.zeros((t + 1,) + g.shape[1:], jnp.float32).at[token].add(g.astype(jnp.float32))
    return du[:t].astype(like.dtype), None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


def combine_rows(out_rows, gate_row, token, tokens: int):
    """``y[t] = sum over t's rows r of gate_row[r] * out_rows[r]`` in float32
    ``[tokens, D]``: zero for a token with no row here."""
    w = out_rows.astype(jnp.float32) * gate_row.astype(jnp.float32)[:, None]
    return jnp.zeros((tokens + 1, out_rows.shape[1]), jnp.float32).at[token].add(w)[:tokens]


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """What the top-k router and the expert sublayer read of a stack's keys
    (``JoyaiConfig.experts``, ``AfmoeConfig.experts``,
    ``KeyeConfig.experts``)."""

    num_experts: int  # the router's width: all the experts of a layer
    top_k: int
    experts_held: Tuple[int, ...]
    width: int  # an expert's intermediate size
    shared: int  # shared experts (one MLP of ``width x shared``)
    scale: float  # the gates' factor
    norm_gates: bool = True
    row_capacity: float = 0.0
    score: str = "sigmoid"  # the router's scores: "sigmoid" or "softmax" over all experts

    def row_budget(self, tokens: int, block_m: int) -> int:
        """Static rows of the aligned buffer for ``tokens`` token slots:
        ``row_capacity`` times the rows a balanced router sends here plus one
        row tile a held expert; 0 is the worst case."""
        if self.row_capacity <= 0:
            return 0
        expected = tokens * self.top_k * len(self.experts_held) / self.num_experts
        rows = math.ceil(self.row_capacity * expected)
        return -(-rows // block_m) * block_m + len(self.experts_held) * block_m


def _chosen(choice, experts: int):
    """``[T, k, experts]``: whether expert ``e`` is the token's ``j``-th
    choice. Only ever computed inside the reduction that reads it."""
    return choice[..., None] == jnp.arange(experts, dtype=choice.dtype)


@jax.custom_vjp
def pick(s, choice):
    """``take_along_axis(s, choice, -1)`` for ``s [T, E]`` and ``choice [T,
    k]``, spelt as a sum over the one-hot of each choice: a token's choices
    are distinct, so every value is the chosen one plus exact zeros, and the
    cotangent is the same kind of sum, with no scatter."""
    return jnp.sum(jnp.where(_chosen(choice, s.shape[-1]), s[:, None, :], 0.0), axis=-1)


def _pick_fwd(s, choice):
    # the residual carries the width and the dtype as an empty array
    return pick(s, choice), (choice, jnp.zeros((0, s.shape[-1]), s.dtype))


def _pick_bwd(res, g):
    choice, like = res
    ds = jnp.sum(jnp.where(_chosen(choice, like.shape[-1]), g[..., None].astype(like.dtype), 0.0), axis=1)
    return ds, None


pick.defvjp(_pick_fwd, _pick_bwd)


def expert_loads(choice, weight, experts: int):
    """Each expert's load over a layer's choices ``[T, k]``: the sum of
    ``weight [T]`` over the tokens that chose it, in float32 (whole numbers
    for a 0/1 weight, so exact), as a one-hot sum with no scatter."""
    return jnp.sum(jnp.where(_chosen(choice, experts), weight.astype(jnp.float32)[:, None, None], 0.0),
                   axis=(0, 1))


def router_scores(p: Dict, u, e: ExpertSpec):
    """``s [T, num_experts]``, float32: ``sigmoid(W_r u)``, or under
    ``e.score`` "softmax" the softmax of ``W_r u`` over all experts."""
    logits = jnp.dot(u.astype(jnp.float32), p["router"].astype(jnp.float32), precision="highest")
    return jax.nn.softmax(logits, axis=-1) if e.score == "softmax" else jax.nn.sigmoid(logits)


def route(p: Dict, beta, u, e: ExpertSpec, scores=None):
    """The router, in float32: ``s = router_scores`` over ALL experts (or
    ``scores``, where the stack has them already), the choice the ``top_k``
    largest of ``s + beta``, the gates the chosen ``s`` (normalised to sum 1
    under ``norm_gates``) times ``scale``. -> (choice [T, k], gate [T, k])."""
    s = router_scores(p, u, e) if scores is None else scores
    # the balancing bias is a buffer: it moves the choice, takes no gradient
    _, choice = jax.lax.top_k(s + jax.lax.stop_gradient(beta.astype(jnp.float32)), e.top_k)
    gate = pick(s, choice)
    if e.norm_gates:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return choice, gate * e.scale


def expert_sublayer(p: Dict, beta, u, node_mask, e: ExpertSpec, choice=None,
                    row_budget: Optional[Callable[[int, int], int]] = None, scores=None):
    """The expert sublayer on the normalised stream ``u [T, D]``: route over
    all experts, compute the rows whose expert is in ``e.experts_held``
    (``p["experts_*"]`` hold those, in that order), nothing for the others,
    and the shared expert on every token. -> (y [T, D] before the residual
    add, the held experts' loads [held], every expert's load [num_experts],
    [rows past the budget, tokens with a row here]). ``choice`` overrides the
    router's (tests); ``row_budget`` the spec's own rule; ``scores`` the
    router's scores where the stack has computed them (``route``)."""
    from ..ops.pallas_grouped_matmul import normalize_tiles, permute_rows

    t, d_model = u.shape
    k = e.top_k
    with tr.scope(tr.HG_ROUTER):
        routed, gate = route(p, beta, u, e, scores)
        choice = routed if choice is None else choice
        kernel = jax.default_backend() == "tpu"
        # each expert's rows start at a multiple of the kernel's row tile
        block_m = normalize_tiles(t * k, d_model, e.width, dtype=u.dtype)[0]
        layout = topk_layout(choice, node_mask, e.experts_held, e.num_experts, block_m,
                             (row_budget or e.row_budget)(t, block_m))
    with tr.scope(tr.HG_MOE_DISPATCH):
        rows = dispatch_rows(u, layout["token"])
    out_rows = expert_products(rows, p["experts_gate"], p["experts_up"], p["experts_down"],
                               layout, block_m, kernel)
    with tr.scope(tr.HG_MOE_COMBINE):
        # a row's gate; its cotangent goes back through the inverse map
        gate_row = permute_rows(gate.reshape(-1, 1), layout["src"], layout["dest"])[:, 0]
        y = combine_rows(out_rows, gate_row, layout["token"], t)
    if e.shared:
        with tr.scope(tr.HG_SHARED_EXPERT):
            y = y + gated_mlp(u, p["shared_gate"], p["shared_up"], p["shared_down"]).astype(jnp.float32)
    every = expert_loads(choice, node_mask, e.num_experts)
    return y.astype(u.dtype), layout["counts"], every, jnp.stack([layout["overrun"], layout["tokens_here"]])


def expert_layer_stats(counts, overrun, here):
    """A layer's five counters from ``expert_sublayer``'s: [rows computed
    here, largest held load, mean held load, rows past the budget, tokens
    with a row here], float32."""
    counts, overrun = counts.astype(jnp.float32), overrun.astype(jnp.float32)
    return jnp.stack([jnp.sum(counts) - overrun, jnp.max(counts), jnp.mean(counts), overrun,
                      here.astype(jnp.float32)])


def expert_param_shapes(d: int, e: ExpertSpec) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of an expert sublayer's leaves: the router
    over all experts, the held experts' banks, the shared expert."""
    f, held = e.width, len(e.experts_held)
    shapes = {
        "router": ((d, e.num_experts), "lecun"),
        "experts_gate": ((held, d, f), "lecun"), "experts_up": ((held, d, f), "lecun"),
        "experts_down": ((held, f, d), "small"),
    }
    if e.shared:
        fs = f * e.shared
        shapes.update({"shared_gate": ((d, fs), "lecun"), "shared_up": ((d, fs), "lecun"),
                       "shared_down": ((fs, d), "small")})
    return shapes


def sign_balanced_bias(beta, loads, rate: float):
    """Loss-free balancing as published (arXiv:2408.15664): once a training
    step, outside the gradient, each expert's bias moves by ``rate`` towards
    the mean load, ``b_e += rate * sign(mean load - load_e)``."""
    loads = jax.lax.stop_gradient(loads)
    return beta + rate * jnp.sign(jnp.mean(loads) - loads)


def balanced_bias(beta, loads):
    """The balancing rule of the router's bias buffer (loss-free balancing,
    arXiv:2408.15664, its proportional variant), applied once a training step
    outside the gradient: each expert's bias moves by ``ROUTER_BIAS_GAIN``
    times its load's shortfall against the mean load, as a share of the mean.
    A top-1 router trained without it sends every token of a batch to one
    expert a layer within tens of steps."""
    loads = jax.lax.stop_gradient(loads)
    mean = jnp.mean(loads)
    return beta + ROUTER_BIAS_GAIN * (mean - loads) / jnp.maximum(mean, 1.0)


def _lecun(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) / (shape[-2] ** 0.5)


def _small(key, shape, dtype=jnp.float32):  # variance_scaling(0.001, fan_avg, uniform)
    lim = (3.0 * 0.001 / ((shape[-2] + shape[-1]) / 2.0)) ** 0.5
    return jax.random.uniform(key, shape, dtype, -lim, lim)


# the init kinds of a layer's ``layer_param_shapes``: ``lecun`` (normal, fan-in
# the second-to-last axis), ``small`` (the projections that write into the
# residual stream start near zero, so that the stream, and with it the router,
# sees the token and not the mean of its prefix: with every matrix at LeCun
# scale the attention's average drowns the embedding and every token of a batch
# picks one expert, read on the chip in PR 29), ``ones`` and ``zeros``
INIT = {"lecun": _lecun, "small": _small, "ones": nn.initializers.ones, "zeros": nn.initializers.zeros}


def layer_params(module: nn.Module, shapes: Dict) -> Dict:
    """The leaves of ``shapes`` (name -> (shape, init kind)) as ``module``'s
    parameters."""
    return {name: module.param(name, INIT[kind], shape) for name, (shape, kind) in shapes.items()}


def remat_in_training(layer, train: bool):
    """Every layer is rematerialised in training. Kept across the remat: the
    layer's incoming residual stream and what the causal flash launch's
    backward reads of its forward (``o [T, Hq, dv]`` and one float32 a (head,
    row) of ``lse``, tagged in ops/pallas_flash_attention.py
    ``_causal_vjp_fwd``), so that the forward kernel runs once a step; of a
    learned sparse attention, the selection and the indexer loss's gradient
    (tagged in ops/pallas_dsa_indexer.py), so that its two launches run once
    a step; everything else of a layer is computed again in the backward
    pass. ``layer`` is a flax module class, or a function of arrays applied
    inside a ``scan`` (models/ouro.py: the scan then keeps the same residuals
    of every application)."""
    if not train:
        return layer
    policy = jax.checkpoint_policies.save_only_these_names(*CAUSAL_FLASH_RESIDUAL_NAMES, *DSA_RESIDUAL_NAMES)
    if isinstance(layer, type) and issubclass(layer, nn.Module):
        return nn.remat(layer, policy=policy)
    return jax.checkpoint(layer, policy=policy)


def flash_blocks(blocks: int, train: bool) -> Dict:
    """A stack's attention blocks and those whose flash residuals its layers'
    remat keeps (``remat_in_training``: all of them in training), as the
    step's two ``count:flash_blocks*`` entries."""
    return {tr.CT_FLASH_BLOCKS: jnp.float32(blocks), tr.CT_FLASH_BLOCKS_SAVED: jnp.float32(blocks if train else 0)}


def poison(x, bad):
    """A step that cannot stand (a graph past the static bound under-covers
    its key window in the flash kernel; a routing past the row budget leaves
    rows out) surfaces as NaN, never as wrong numbers: the guard
    (``hg_guard``) skips and counts it."""
    return jnp.where(bad, jnp.nan, x)


def graphs_overflow(batch, max_nodes: int):
    return jnp.any((batch.nodes_per_graph > max_nodes) & batch.graph_mask)


def causal_pairs(batch):
    """(query, key) pairs within graphs, one layer's."""
    n_g = batch.nodes_per_graph.astype(jnp.float32) * batch.graph_mask.astype(jnp.float32)
    return jnp.sum(n_g * (n_g + 1.0) * 0.5)


def window_pairs(batch, window: int):
    """(query, key) pairs within graphs and within a sliding ``window``
    (``0 <= i - j < window``), one layer's: a graph of n nodes has ``n (n +
    1) / 2`` up to the window and ``W (W + 1) / 2 + (n - W) W`` past it."""
    n_g = batch.nodes_per_graph.astype(jnp.float32) * batch.graph_mask.astype(jnp.float32)
    w = jnp.float32(window)
    return jnp.sum(jnp.where(n_g <= w, n_g * (n_g + 1.0) * 0.5, w * (w + 1.0) * 0.5 + (n_g - w) * w))


def flash_steps(batch, max_nodes: int, d: int, dv: int, dtype, window: Optional[int] = None) -> Dict:
    """The causal flash launch's schedule on this batch, one block's forward
    launch, one head (queries and keys ``d`` wide, values ``dv``, streamed as
    ``dtype``): the tiles its windows hold and the steps the schedule runs for
    them, as the step's two ``count:flash_*`` entries; a sliding layer's
    (``window``) under names of their own."""
    from ..ops.pallas_flash_attention import causal_schedule_steps

    visited, scheduled = causal_schedule_steps(batch.node_graph, batch.node_mask, max_nodes, d, dv, dtype,
                                               window=window)
    if window is not None:
        return {tr.CT_FLASH_WINDOW_TILES_VISITED: visited, tr.CT_FLASH_WINDOW_STEPS_SCHEDULED: scheduled}
    return {tr.CT_FLASH_TILES_VISITED: visited, tr.CT_FLASH_STEPS_SCHEDULED: scheduled}
