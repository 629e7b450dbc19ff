"""JOYAI stack: a decoder language model with latent attention and many small
experts, on the graph plumbing (token = node, document = graph; what it
shares with models/zaya.py is models/decoder.py).

A layer is pre-norm, two norms a layer, plain residual adds:

- MLA (multi-head latent attention, DeepSeek-V2, arXiv:2405.04434), in its
  expanded training form: ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` ->
  heads of ``[q_nope ; q_rope]``; ``[c_kv ; k_rope] = W_kva u``, ``c_kv <-
  RMSNorm(c_kv)``; ``[k_nope ; v]`` a head ``= W_kvb c_kv``; interleaved RoPE
  from the index in the document on ``q_rope`` and on the ONE ``k_rope`` every
  head shares; keys ``[k_nope ; k_rope]`` and values materialised a head;
  causal attention within the document through ops/pallas_flash_attention.py
  ``flash_causal_attention`` at scale ``1/sqrt(qk width)`` with values of their
  own width; ``y = W_o concat(heads)``. No bias anywhere. The absorbed form is
  a serving matter and is not here.
- then a dense SiLU-gated MLP (the first ``first_k_dense_replace`` layers) or
  an expert sublayer: ``s = sigmoid(W_r u)`` in float32 over ALL
  ``n_routed_experts``; choice = the ``num_experts_per_tok`` largest of ``s +
  b`` (``b`` a buffer in ``batch_stats``: no gradient, moved once a training
  step by ``decoder.balanced_bias``); gate ``routed_scaling_factor * s_e / (sum
  of the chosen s + 1e-20)``; ``y = shared(u) + sum over chosen e of g_e
  expert_e(u)``. The sublayer is told which experts it holds
  (``Architecture.experts_held``): it routes over all, computes the rows whose
  expert lives here (a token is 0 to k rows: dispatch is a gather with
  repeats, combine a gate-weighted sum over a token's rows; the grouped
  product of ops/pallas_grouped_matmul.py between them) and adds nothing for
  the others; the shared expert runs on every token. On one chip the layer
  runs without its exchange.

The row buffer: ``Architecture.expert_row_capacity`` c > 0 budgets ``c`` times
the rows a balanced router sends here (``T k held / n_routed_experts``), plus
one row tile a held expert for alignment; 0 or null takes the worst case
(every assignment of every token). A step whose rows overrun a budget is
poisoned (NaN -> ``hg_guard`` skips and counts it) and counted in
``count:expert_rows_overrun``: never cut in silence.

Multi-token prediction (depth 1, DeepSeek-V3, arXiv:2412.19437 section 2.2):
with ``h`` the main stack's last stream before the final norm, ``h' = W_eh
[RMSNorm_e(Emb(t_(i+1))) ; RMSNorm_h(h_i)]``, one expert layer of its own, a
norm; the model returns it beside the main hidden state and train/loss.py
reads both through the SHARED, untied head (``params["head"]``).

The plain reference of these equations is benchmarks/reference/joyai.py.
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
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_interleave",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "routed_scaling_factor", "norm_topk_prob", "num_nextn_predict_layers",
    "mtp_loss_weight", "experts_held", "expert_row_capacity", "vocab_size",
    "rms_norm_eps", "loss_chunk_rows",
)

MTP_HIDDEN = dc.MTP_HIDDEN


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    """The ``JOYAI`` keys of ``Architecture`` (docs/CONFIG.md), named as the
    published ``config.json`` names them."""

    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, ...]
    vocab_size: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    expert_row_capacity: float = 0.0
    rope_theta: float = 1.0e4
    rope_interleave: bool = True
    rms_norm_eps: float = 1.0e-6
    loss_chunk_rows: int = 4096

    @staticmethod
    def from_arch(arch: Dict) -> "JoyaiConfig":
        missing = [k for k in ARCH_KEYS if k not in arch or (arch[k] is None and k != "expert_row_capacity")]
        if missing:
            raise ValueError(f"mpnn_type JOYAI needs Architecture keys {missing}")
        z = JoyaiConfig(
            experts_held=tuple(int(e) for e in arch["experts_held"]),
            routed_scaling_factor=float(arch["routed_scaling_factor"]),
            norm_topk_prob=bool(arch["norm_topk_prob"]),
            mtp_loss_weight=float(arch["mtp_loss_weight"]),
            expert_row_capacity=float(arch["expert_row_capacity"] or 0.0),
            rope_theta=float(arch["rope_theta"]),
            rope_interleave=bool(arch["rope_interleave"]),
            rms_norm_eps=float(arch["rms_norm_eps"]),
            **{k: int(arch[k]) for k in (
                "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "vocab_size", "n_shared_experts",
                "first_k_dense_replace", "num_nextn_predict_layers", "loss_chunk_rows")},
        )
        if z.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        held = z.experts_held
        if not held or sorted(set(held)) != list(held) or held[0] < 0 or held[-1] >= z.n_routed_experts:
            raise ValueError(
                f"experts_held {list(held)} must be ascending, distinct ids below n_routed_experts "
                f"{z.n_routed_experts}")
        if not 1 <= z.num_experts_per_tok <= z.n_routed_experts:
            raise ValueError("num_experts_per_tok must lie in 1 .. n_routed_experts")
        if z.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers must be 0 or 1 (one multi-token-prediction module)")
        if z.n_shared_experts < 0 or z.first_k_dense_replace < 0 or z.expert_row_capacity < 0:
            raise ValueError("n_shared_experts, first_k_dense_replace and expert_row_capacity must not be negative")
        return z

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def experts(self) -> dc.ExpertSpec:
        """The router's and the expert sublayer's numbers, as models/decoder.py reads them."""
        return dc.ExpertSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok, experts_held=self.experts_held,
            width=self.moe_intermediate_size, shared=self.n_shared_experts, scale=self.routed_scaling_factor,
            norm_gates=self.norm_topk_prob, row_capacity=self.expert_row_capacity)

    def row_budget(self, tokens: int, block_m: int) -> int:
        """Static rows of the aligned buffer for ``tokens`` token slots; 0 is
        the worst case."""
        return self.experts.row_budget(tokens, block_m)


def mla_sublayer(p: Dict, u, aux, z: JoyaiConfig, max_nodes: int):
    """Latent attention on the normalised stream ``u [T, D]`` -> ``[T, D]``
    (before the residual add). ``p`` holds the layer's ``mla_*`` leaves."""
    pos, t = aux["pos"], u.shape[0]
    h, dn, dr, dv = z.num_attention_heads, z.qk_nope_head_dim, z.qk_rope_head_dim, z.v_head_dim
    turn = lambda a: dc.rope(a, pos, dr, z.rope_theta, z.rope_interleave)
    with tr.scope(tr.HG_MLA_PROJ):
        c_q = dc.rms_norm(dc.dense(u, p["mla_q_a"]), p["mla_q_norm"], z.rms_norm_eps)
        q = dc.dense(c_q, p["mla_q_b"]).reshape(t, h, dn + dr)
        kv = dc.dense(u, p["mla_kv_a"])
        c_kv = dc.rms_norm(kv[:, :z.kv_lora_rank], p["mla_kv_norm"], z.rms_norm_eps)
        kv_b = dc.dense(c_kv, p["mla_kv_b"]).reshape(t, h, dn + dv)
        # one rotated key part, shared by every head
        k_rope = jnp.broadcast_to(turn(kv[:, z.kv_lora_rank:].reshape(t, 1, dr)), (t, h, dr))
        q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
        k = jnp.concatenate([kv_b[..., :dn], k_rope], axis=-1)
        v = kv_b[..., dn:]
    o = dc.causal_attention(q, k, v, aux, max_nodes).reshape(t, h * dv)
    return dc.dense(o, p["mla_o"])


def route(p: Dict, beta, u, z: JoyaiConfig):
    """The router (``decoder.route``) at this stack's numbers: -> (choice
    [T, k] over ALL experts, gate [T, k])."""
    return dc.route(p, beta, u, z.experts)


def expert_sublayer(p: Dict, beta, u, node_mask, z: JoyaiConfig, choice=None):
    """The expert sublayer (``decoder.expert_sublayer``) at this stack's
    numbers, its row budget ``z.row_budget``."""
    return dc.expert_sublayer(p, beta, u, node_mask, z.experts, choice, z.row_budget)


def layer_param_shapes(hidden: int, z: JoyaiConfig, dense_mlp: bool) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of one layer's parameter leaves (the kinds:
    ``models/decoder.py INIT``; ``small`` for the projections that write
    into the residual stream)."""
    d, h = hidden, z.num_attention_heads
    shapes = {
        "attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
        "mla_q_a": ((d, z.q_lora_rank), "lecun"), "mla_q_norm": ((z.q_lora_rank,), "ones"),
        "mla_q_b": ((z.q_lora_rank, h * z.qk_head_dim), "lecun"),
        "mla_kv_a": ((d, z.kv_lora_rank + z.qk_rope_head_dim), "lecun"),
        "mla_kv_norm": ((z.kv_lora_rank,), "ones"),
        "mla_kv_b": ((z.kv_lora_rank, h * (z.qk_nope_head_dim + z.v_head_dim)), "lecun"),
        "mla_o": ((h * z.v_head_dim, d), "small"),
    }
    if dense_mlp:
        f = z.intermediate_size
        shapes.update({"mlp_gate": ((d, f), "lecun"), "mlp_up": ((d, f), "lecun"), "mlp_down": ((f, d), "small")})
        return shapes
    shapes.update(dc.expert_param_shapes(d, z.experts))
    return shapes


class JoyaiLayer(nn.Module):
    """One layer: MLA, then the dense MLP (``dense_mlp``) or the expert
    sublayer. -> (x, [rows computed here, largest held load, mean held load,
    rows past the budget, tokens with a row here], every expert's load)."""

    hidden: int
    z: JoyaiConfig
    dense_mlp: bool
    max_nodes: int

    @nn.compact
    def __call__(self, x, aux, beta):
        z = self.z
        p = dc.layer_params(self, layer_param_shapes(self.hidden, z, self.dense_mlp))
        u = dc.rms_norm(x, p["attn_norm"], z.rms_norm_eps)
        x = x + mla_sublayer(p, u, aux, z, self.max_nodes)
        u = dc.rms_norm(x, p["mlp_norm"], z.rms_norm_eps)
        if self.dense_mlp:
            y = dc.gated_mlp(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            return x + y, jnp.zeros((5,), jnp.float32), jnp.zeros((z.n_routed_experts,), jnp.float32)
        y, counts, every, (overrun, here) = expert_sublayer(p, beta, u, aux["node_mask"], z)
        return x + y, dc.expert_layer_stats(counts, overrun, here), every


class JoyaiModel(nn.Module):
    """Embedding, the layers, the final norm, the multi-token-prediction
    module. ``__call__`` returns the final normalised hidden state ``[N,
    hidden]`` under the head's name, the module's under ``MTP_HIDDEN``, and
    the step's counters under ``tr.COUNTER_PREFIX`` names."""

    cfg: "ModelConfig"  # noqa: F821 - models/base.py

    @staticmethod
    def float32_leaves(name: str) -> bool:
        """The router's matrix: ``train/loop.py mp_keep`` asks, and the
        mixed-precision cast leaves it float32."""
        return name == "router"

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg, z = self.cfg, self.cfg.joyai
        d_model = cfg.hidden_dim
        if batch.z is None:
            raise ValueError("mpnn_type JOYAI reads node ids from batch.z (int32)")
        emb = self.param("embedding", dc.INIT["lecun"], (d_model, z.vocab_size))
        # the untied head: train/loss.py reads it for both losses
        self.param("head", dc.INIT["lecun"], (d_model, z.vocab_size))
        x0, _ = dc.embed_tokens(emb, batch.z, z.vocab_size)
        aux = dc.batch_aux(batch)
        layer_cls = dc.remat_in_training(JoyaiLayer, train)
        stats = jnp.zeros((5,), jnp.float32)
        expert_layers = 0

        def run(x, name, bias_name, dense_mlp):
            # the balancing bias: a buffer (no gradient, no optimizer state),
            # moved once a training step by the loads it produced
            beta = None if dense_mlp else self.variable(
                "batch_stats", bias_name, lambda: jnp.zeros((z.n_routed_experts,), jnp.float32))
            x, c, loads = layer_cls(d_model, z, dense_mlp, cfg.max_nodes_per_graph, name=name)(
                x, aux, jnp.zeros((z.n_routed_experts,), jnp.float32) if dense_mlp else beta.value)
            if beta is not None and train and not self.is_initializing():
                beta.value = dc.balanced_bias(beta.value, loads)
            return x, c

        x = x0
        for i in range(cfg.num_conv_layers):
            dense_mlp = i < z.first_k_dense_replace
            x, c = run(x, f"layers_{i}", f"router_bias_{i}", dense_mlp)
            stats, expert_layers = stats + c, expert_layers + (not dense_mlp)
        out = {}
        mask = batch.node_mask
        if z.num_nextn_predict_layers:
            with tr.scope(tr.HG_MTP):
                # the next token's embedding; nothing where the document ends
                e_next = jnp.where(dc.follows(batch.node_graph, mask, 1)[:, None], jnp.roll(x0, -1, axis=0),
                                   jnp.zeros((), x0.dtype))
                norm = lambda name, a: dc.rms_norm(
                    a, self.param(name, nn.initializers.ones, (d_model,)), z.rms_norm_eps)
                joined = jnp.concatenate([norm("mtp_enorm", e_next), norm("mtp_hnorm", x)], axis=-1)
                h = dc.dense(joined, self.param("mtp_proj", dc.INIT["lecun"], (2 * d_model, d_model)))
                h, c = run(h, "mtp_layer", "router_bias_mtp", False)
                stats, expert_layers = stats + c, expert_layers + 1
                out[MTP_HIDDEN] = norm("mtp_final_norm", h)
        x = dc.rms_norm(x, self.param("final_norm", nn.initializers.ones, (d_model,)), z.rms_norm_eps)
        bad = dc.graphs_overflow(batch, cfg.max_nodes_per_graph) | (stats[3] > 0)
        out = {k: dc.poison(v, bad) for k, v in out.items()}
        real = jnp.sum(mask.astype(jnp.float32))
        out.update({
            cfg.output_names[0]: dc.poison(x, bad),
            tr.CT_TOKENS: real * expert_layers,
            tr.CT_TOKENS_ROUTED_HERE: stats[4],
            tr.CT_EXPERT_ROWS_HERE: stats[0],
            tr.CT_EXPERT_LOAD_MAX: stats[1],
            tr.CT_EXPERT_LOAD_MEAN: stats[2],
            tr.CT_EXPERT_ROWS_OVERRUN: stats[3],
            tr.CT_CAUSAL_PAIRS: dc.causal_pairs(batch),
            **dc.flash_steps(batch, cfg.max_nodes_per_graph, z.qk_head_dim, z.v_head_dim, x.dtype),
            **dc.flash_blocks(cfg.num_conv_layers + z.num_nextn_predict_layers, train),
            tr.CT_MTP_PAIRS: jnp.sum(dc.follows(batch.node_graph, mask, 2).astype(jnp.float32))
            * z.num_nextn_predict_layers,
        })
        return out
