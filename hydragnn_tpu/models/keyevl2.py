"""KEYEVL2 stack: the language model of Keye-VL-2.0 (named after the
``model_type`` of its published ``config.json``), a decoder whose every layer
attends only to the keys a learned indexer selects, on the graph plumbing
(token = node, document = graph; what it shares with the other decoder stacks
is models/decoder.py).

A layer: ``x <- x + Attn(N_1(x))``; ``x <- x + MoE(N_2(x))``, RMSNorms.

- Attention, ``u = N_1(x)``: ``q = W_q u`` -> ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k u``, ``v = W_v u`` -> ``num_key_value_heads`` heads
  (query head ``h`` reads key/value head ``h // group``); ``q`` and ``k``
  RMS-normalised per head (gains ``[head_dim]``), then RoPE over the whole
  head (rotate-half, ``rope_theta``) from the index in the document (text:
  the three sections of ``mrope_section`` see one position, so the rotation
  is plain RoPE); ``y = W_o o``. No bias, no gate.
- The indexer (``sa_config``, DeepSeek-V3.2's): on ``u`` with no gradient into
  it, ``qI = W_qI u`` -> ``indexer_num_heads`` heads of ``indexer_head_dim``,
  ONE key head ``kI = LayerNorm(W_kI u)``, weights ``w = W_w u`` (one a query
  head), RoPE on ``qI`` and ``kI``; scores ``I[t, s] = H^-1/2 d^-1/2 sum_j
  w[t, j] ReLU(qI[t, j] . kI[s])`` over the earlier keys of the document; the
  query attends its ``min(n_t, indexer_topk)`` keys of largest score (a tie
  to the lower position): models/decoder.py ``sparse_attention``, the
  kernels of ops/pallas_dsa_indexer.py and the causal flash launches under
  the selection. The indexer trains on its own loss, ``sum_t KL(p_t ||
  softmax over S_t of I[t, .])`` with ``p_t`` the attention's distribution
  over ``S_t`` averaged over its heads (detached), at weight
  ``INDEX_LOSS_WEIGHT``, divided by the token loss's count of pairs and
  summed over layers.
- Experts: ``softmax(W_r u)`` over ALL ``num_experts`` in float32, the
  ``num_experts_per_tok`` largest, gates renormalised to sum 1 under
  ``norm_topk_prob``; no shared expert; the rows of the experts held here
  (``experts_held``) and the row budget of ``expert_row_capacity``
  (models/decoder.py ``expert_sublayer``, ``ExpertSpec.score`` "softmax").
- Balancing: the auxiliary loss of the public ``load_balancing_loss_func``,
  ``AUX_LOSS_COEF * E * sum_e f_e P_e`` over the step's real tokens
  and all layers, ``f_e`` the share of (token, slot) assignments that chose
  ``e`` (summed over the slots), ``P_e`` the mean router probability. No bias
  buffer: the one buffer (``batch_stats`` ``expert_loads [layers,
  num_experts]``) holds each layer's loads of the latest training step for
  whoever watches the routing, and nothing reads it.

Initial scales: ``decoder.INIT``'s (``W_o`` and the experts' down
projections, which write into the stream, start near zero). The plain
reference of these equations is benchmarks/reference/keyevl2.py.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..utils import tracer as tr
from . import decoder as dc

ARCH_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta", "moe_intermediate_size",
    "num_experts", "num_experts_per_tok", "norm_topk_prob", "experts_held", "expert_row_capacity",
    "indexer_num_heads", "indexer_head_dim", "indexer_num_kv_heads", "indexer_topk", "vocab_size",
    "rms_norm_eps", "loss_chunk_rows",
)
# the indexer key's LayerNorm (DeepSeek-V3.2's public Indexer)
INDEX_NORM_EPS = 1.0e-6
# the weights of the two loss terms: the indexer's KL at 1 (DeepSeek-V3.2's
# sparse stage), the balancing loss at Qwen3-MoE's 0.001; the published
# configuration names neither
INDEX_LOSS_WEIGHT = 1.0
AUX_LOSS_COEF = 0.001
INDEX_LOSS = dc.LOSS_TERM_PREFIX + "index"
BALANCE_LOSS = dc.LOSS_TERM_PREFIX + "balance"


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The ``KEYEVL2`` keys of ``Architecture`` (docs/CONFIG.md), named as the
    published ``config.json`` names them (``sa_config``'s with the prefix
    ``indexer_``)."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, ...]
    vocab_size: int
    indexer_num_heads: int
    indexer_head_dim: int
    indexer_topk: int
    indexer_num_kv_heads: int = 1
    norm_topk_prob: bool = True
    expert_row_capacity: float = 0.0
    rope_theta: float = 1.0e7
    rms_norm_eps: float = 1.0e-6
    loss_chunk_rows: int = 4096

    @staticmethod
    def from_arch(arch: Dict) -> "KeyeConfig":
        optional = ("expert_row_capacity",)
        missing = [k for k in ARCH_KEYS if k not in arch or (arch[k] is None and k not in optional)]
        if missing:
            raise ValueError(f"mpnn_type KEYEVL2 needs Architecture keys {missing}")
        z = KeyeConfig(
            experts_held=tuple(int(e) for e in arch["experts_held"]),
            norm_topk_prob=bool(arch["norm_topk_prob"]),
            expert_row_capacity=float(arch["expert_row_capacity"] or 0.0),
            **{k: float(arch[k]) for k in ("rope_theta", "rms_norm_eps")},
            **{k: int(arch[k]) for k in (
                "num_attention_heads", "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "vocab_size", "indexer_num_heads", "indexer_head_dim",
                "indexer_topk", "indexer_num_kv_heads", "loss_chunk_rows")},
        )
        if z.num_attention_heads % z.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if z.head_dim % 2 or z.indexer_head_dim % 2:
            raise ValueError("head_dim and indexer_head_dim must be even (RoPE pairs their channels)")
        if z.indexer_num_kv_heads != 1:
            raise ValueError("the indexer scores against ONE key head: indexer_num_kv_heads must be 1")
        if z.indexer_topk < 1 or z.indexer_num_heads < 1:
            raise ValueError("indexer_topk and indexer_num_heads must be at least 1")
        held = z.experts_held
        if not held or sorted(set(held)) != list(held) or held[0] < 0 or held[-1] >= z.num_experts:
            raise ValueError(
                f"experts_held {list(held)} must be ascending, distinct ids below num_experts {z.num_experts}")
        if not 1 <= z.num_experts_per_tok <= z.num_experts:
            raise ValueError("num_experts_per_tok must lie in 1 .. num_experts")
        if z.expert_row_capacity < 0:
            raise ValueError("expert_row_capacity must not be negative")
        return z

    @property
    def experts(self) -> dc.ExpertSpec:
        """The router's and the expert sublayer's numbers, as models/decoder.py reads them."""
        return dc.ExpertSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok, experts_held=self.experts_held,
            width=self.moe_intermediate_size, shared=0, scale=1.0, norm_gates=self.norm_topk_prob,
            row_capacity=self.expert_row_capacity, score="softmax")


def layer_norm(x, gain, bias, eps: float):
    """LayerNorm over the last axis in float32, returned in the input's dtype."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def indexer(p: Dict, u, pos, z: KeyeConfig):
    """-> (qI [T, H, d], kI [T, d], w [T, H]) on ``u`` with no gradient into
    it: the indexer trains on its own loss and the stream never sees it."""
    t, h, d = u.shape[0], z.indexer_num_heads, z.indexer_head_dim
    u = jax.lax.stop_gradient(u)
    with tr.scope(tr.HG_DSA_PROJ):
        qi = dc.rope(dc.dense(u, p["index_q"]).reshape(t, h, d), pos, d, z.rope_theta)
        ki = layer_norm(dc.dense(u, p["index_k"]), p["index_k_norm"], p["index_k_bias"], INDEX_NORM_EPS)
        ki = dc.rope(ki[:, None, :], pos, d, z.rope_theta)[:, 0]
        w = dc.dense(u, p["index_w"])
    return qi, ki, w


def attention_sublayer(p: Dict, u, aux, z: KeyeConfig, max_nodes: int):
    """QK-normed grouped-query attention over the indexer's selection on the
    normalised stream ``u [T, D]`` -> (``[T, D]``, this layer's indexer loss,
    a sum over real tokens)."""
    t = u.shape[0]
    h, hk, d = z.num_attention_heads, z.num_key_value_heads, z.head_dim
    with tr.scope(tr.HG_ATTN_PROJ):
        q = dc.rms_norm(dc.dense(u, p["attn_q"]).reshape(t, h, d), p["attn_q_norm"], z.rms_norm_eps)
        k = dc.rms_norm(dc.dense(u, p["attn_k"]).reshape(t, hk, d), p["attn_k_norm"], z.rms_norm_eps)
        v = dc.dense(u, p["attn_v"]).reshape(t, hk, d)
        q, k = (dc.rope(a, aux["pos"], d, z.rope_theta) for a in (q, k))
    qi, ki, w = indexer(p, u, aux["pos"], z)
    o, index_loss = dc.sparse_attention(q, k, v, qi, ki, w, aux, max_nodes, z.indexer_topk)
    return dc.dense(o.reshape(t, h * d), p["attn_o"]), index_loss


def layer_param_shapes(hidden: int, z: KeyeConfig) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of one layer's parameter leaves (the kinds:
    ``models/decoder.py INIT``)."""
    d, hd, hi, di = hidden, z.head_dim, z.indexer_num_heads, z.indexer_head_dim
    wide, narrow = z.num_attention_heads * hd, z.num_key_value_heads * hd
    shapes = {
        "attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
        "attn_q": ((d, wide), "lecun"), "attn_k": ((d, narrow), "lecun"), "attn_v": ((d, narrow), "lecun"),
        "attn_o": ((wide, d), "small"), "attn_q_norm": ((hd,), "ones"), "attn_k_norm": ((hd,), "ones"),
        "index_q": ((d, hi * di), "lecun"), "index_k": ((d, di), "lecun"), "index_w": ((d, hi), "lecun"),
        "index_k_norm": ((di,), "ones"), "index_k_bias": ((di,), "zeros"),
    }
    shapes.update(dc.expert_param_shapes(d, z.experts))
    return shapes


class KeyeLayer(nn.Module):
    """One layer: sparse attention, then the expert sublayer, each behind its
    norm. -> (x, ``decoder.expert_layer_stats``, every expert's load, every
    expert's summed router probability over real tokens, the layer's indexer
    loss)."""

    hidden: int
    z: KeyeConfig
    max_nodes: int

    @nn.compact
    def __call__(self, x, aux):
        z = self.z
        p = dc.layer_params(self, layer_param_shapes(self.hidden, z))
        y, index_loss = attention_sublayer(p, dc.rms_norm(x, p["attn_norm"], z.rms_norm_eps), aux, z,
                                           self.max_nodes)
        x = x + y
        u = dc.rms_norm(x, p["mlp_norm"], z.rms_norm_eps)
        beta = jnp.zeros((z.num_experts,), jnp.float32)  # no bias buffer
        with tr.scope(tr.HG_ROUTER):
            scores = dc.router_scores(p, u, z.experts)
            probs = jnp.sum(scores * aux["node_mask"].astype(jnp.float32)[:, None], axis=0)
        y, counts, every, (overrun, here) = dc.expert_sublayer(p, beta, u, aux["node_mask"], z.experts,
                                                               scores=scores)
        return x + y, dc.expert_layer_stats(counts, overrun, here), every, probs, index_loss


def balance_loss(every, probs, layers: int, tokens, coef: float):
    """``coef * E * sum_e f_e P_e`` over all layers: ``every`` and ``probs``
    summed over layers ``[E]``, ``f_e`` the assignments per (token, layer),
    ``P_e`` the mean probability per (token, layer)."""
    n = jnp.maximum(tokens * layers, 1.0)
    f, prob = jax.lax.stop_gradient(every) / n, probs / n
    return coef * every.shape[0] * jnp.sum(f * prob)


class KeyeModel(nn.Module):
    """Embedding, the layers, the final norm. ``__call__`` returns the final
    normalised hidden state ``[N, hidden]`` under the head's name, the two
    loss terms under ``decoder.LOSS_TERM_PREFIX`` names and the step's
    counters under ``tr.COUNTER_PREFIX`` names."""

    cfg: "ModelConfig"  # noqa: F821 - models/base.py

    @staticmethod
    def float32_leaves(name: str) -> bool:
        """The router's matrix: ``train/loop.py mp_keep`` asks, and the
        mixed-precision cast leaves it float32."""
        return name == "router"

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg, z = self.cfg, self.cfg.keyevl2
        d_model, layers = cfg.hidden_dim, cfg.num_conv_layers
        if batch.z is None:
            raise ValueError("mpnn_type KEYEVL2 reads node ids from batch.z (int32)")
        emb = self.param("embedding", dc.INIT["lecun"], (d_model, z.vocab_size))
        # the untied head: train/loss.py reads it
        self.param("head", dc.INIT["lecun"], (d_model, z.vocab_size))
        x, _ = dc.embed_tokens(emb, batch.z, z.vocab_size)
        aux = dc.batch_aux(batch)
        layer_cls = dc.remat_in_training(KeyeLayer, train)
        loads = self.variable("batch_stats", "expert_loads", lambda: jnp.zeros((layers, z.num_experts), jnp.float32))
        stats = jnp.zeros((5,), jnp.float32)
        probs = jnp.zeros((z.num_experts,), jnp.float32)
        index_loss = jnp.zeros((), jnp.float32)
        every = []
        for i in range(layers):
            x, c, e, pr, li = layer_cls(d_model, z, cfg.max_nodes_per_graph, name=f"layers_{i}")(x, aux)
            stats, probs, index_loss = stats + c, probs + pr, index_loss + li
            every.append(e)
        every = jnp.stack(every)
        if train and not self.is_initializing():
            loads.value = jax.lax.stop_gradient(every)
        x = dc.rms_norm(x, self.param("final_norm", nn.initializers.ones, (d_model,)), z.rms_norm_eps)
        bad = dc.graphs_overflow(batch, cfg.max_nodes_per_graph) | (stats[3] > 0)
        tokens = jnp.sum(batch.node_mask.astype(jnp.float32))
        pairs = jnp.maximum(jnp.sum(dc.follows(batch.node_graph, batch.node_mask, 1).astype(jnp.float32)), 1.0)
        return {
            cfg.output_names[0]: dc.poison(x, bad),
            INDEX_LOSS: INDEX_LOSS_WEIGHT * index_loss / pairs,
            BALANCE_LOSS: balance_loss(jnp.sum(every, axis=0), probs, layers, tokens, AUX_LOSS_COEF),
            tr.CT_TOKENS: tokens * layers,
            tr.CT_TOKENS_ROUTED_HERE: stats[4],
            tr.CT_EXPERT_ROWS_HERE: stats[0],
            tr.CT_EXPERT_LOAD_MAX: stats[1],
            tr.CT_EXPERT_LOAD_MEAN: stats[2],
            tr.CT_EXPERT_ROWS_OVERRUN: stats[3],
            tr.CT_CAUSAL_PAIRS: dc.causal_pairs(batch),
            tr.CT_DSA_SELECTED_PAIRS: dc.window_pairs(batch, z.indexer_topk),
            **dc.flash_blocks(layers, train),
        }
