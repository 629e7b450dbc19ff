"""AFMOE stack: a decoder language model whose attention layers are of two
kinds, sliding-window and full, on the graph plumbing (token = node, document
= graph; what it shares with models/zaya.py and models/joyai.py is
models/decoder.py). Named after the ``model_type`` of Trinity-Mini's published
``config.json``.

A layer normalises each sublayer going in AND coming out (four gains a
layer): ``x <- x + N_2(Attn(N_1(x)))``; ``x <- x + N_4(MLP(N_3(x)))``. The
embedding is scaled by ``sqrt(hidden)`` (``mup_enabled``).

- Attention, ``u = N_1(x)``: ``q = W_q u`` -> ``num_attention_heads`` heads of
  ``head_dim``; ``k = W_k u``, ``v = W_v u`` -> ``num_key_value_heads`` heads
  (query head ``h`` reads key/value head ``h // group``); ``q`` and ``k``
  normalised per head (RMSNorm over the head's channels, gains
  ``[head_dim]``); on a SLIDING layer only, RoPE over the whole head
  (rotate-half) from the index in the document: a FULL layer carries no
  position signal; causal attention within the document through
  ops/pallas_flash_attention.py ``flash_causal_attention``, on a sliding layer
  with ``window=sliding_window`` (query ``i`` sees key ``j`` iff ``0 <= i - j <
  window``); ``y = W_o (o * sigmoid(W_g u))``, the gate elementwise on the
  concatenated heads. No bias anywhere.
- then a dense SiLU-gated MLP (the first ``num_dense_layers`` layers) or the
  expert sublayer of models/decoder.py (``expert_sublayer``: sigmoid scores
  over ALL ``num_experts``, the ``num_experts_per_tok`` largest of ``s + b``,
  gates ``route_scale * s_e / sum of the chosen s`` under ``route_norm``, a
  shared expert on every token, the rows of the experts held here
  (``Architecture.experts_held``) and nothing for the others, the row budget
  of ``expert_row_capacity`` and the poison of a step that overruns it).
- Balancing: the bias buffer ``b`` (``batch_stats``: no gradient) moves once a
  training step by ``decoder.sign_balanced_bias`` at ``load_balance_coeff``.

Initial scales: what writes into the residual stream starts near zero
(``decoder.INIT``'s reason). In this stack that is the OUTGOING norm of each
sublayer, which rescales whatever the projection before it gives, so the
gains of ``attn_out_norm`` and ``mlp_out_norm`` start at ``OUT_GAIN`` and
every other gain at 1. An outgoing gain is stored zero-centred (the gain is
``OUT_GAIN + w``, the leaf ``w [hidden]`` starts at zero, as Gemma stores ``1
+ w``): weight decay then pulls it to its start and not to nothing. With
gains of 1 the attention's output, a mean over the prefix that hardly differs
between tokens, reaches the router at the size of the token's own embedding
and the top-k choice collapses onto few experts (read on the chip, PERF.md
section 6, PR 35). ``OUT_GAIN`` is the largest start that keeps the row budget
in the probes: at a hundredth of it the stream IS the token's embedding (a
bfloat16 stream rounds the sublayers' outputs away), every occurrence of an
id routes alike, and one near tie of a frequent id between two experts moves
a whole expert's rows between float32 and bfloat16.

Which kind a layer is comes from ``layer_types`` (one entry a layer,
``"sliding_attention"`` or ``"full_attention"``) and ``num_dense_layers``. The
plain reference of these equations is benchmarks/reference/afmoe.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..utils import tracer as tr
from . import decoder as dc

ARCH_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "layer_types", "sliding_window",
    "rope_theta", "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "num_dense_layers", "route_scale", "route_norm", "load_balance_coeff",
    "mup_enabled", "experts_held", "expert_row_capacity", "vocab_size", "rms_norm_eps", "loss_chunk_rows",
)
SLIDING, FULL = "sliding_attention", "full_attention"
# where an outgoing norm's gain starts: the scale at which a sublayer first
# writes into a stream of unit size (PERF.md section 6, PR 35: 1 collapses the
# router on the chip, 0.3 and 0.2 overrun the row budget in the probe, 0.1 is
# the largest start that does not)
OUT_GAIN = 0.1


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The ``AFMOE`` keys of ``Architecture`` (docs/CONFIG.md), named as the
    published ``config.json`` names them."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, ...]
    vocab_size: int
    num_shared_experts: int = 1
    num_dense_layers: int = 1
    route_scale: float = 1.0
    route_norm: bool = True
    load_balance_coeff: float = 0.001
    mup_enabled: bool = True
    expert_row_capacity: float = 0.0
    rope_theta: float = 1.0e4
    rms_norm_eps: float = 1.0e-5
    loss_chunk_rows: int = 4096

    @staticmethod
    def from_arch(arch: Dict) -> "AfmoeConfig":
        optional = ("expert_row_capacity", "sliding_window")
        missing = [k for k in ARCH_KEYS if k not in arch or (arch[k] is None and k not in optional)]
        if missing:
            raise ValueError(f"mpnn_type AFMOE needs Architecture keys {missing}")
        z = AfmoeConfig(
            layer_types=tuple(str(t) for t in arch["layer_types"]),
            sliding_window=int(arch["sliding_window"] or 0),
            experts_held=tuple(int(e) for e in arch["experts_held"]),
            route_scale=float(arch["route_scale"]),
            route_norm=bool(arch["route_norm"]),
            load_balance_coeff=float(arch["load_balance_coeff"]),
            mup_enabled=bool(arch["mup_enabled"]),
            expert_row_capacity=float(arch["expert_row_capacity"] or 0.0),
            rope_theta=float(arch["rope_theta"]),
            rms_norm_eps=float(arch["rms_norm_eps"]),
            **{k: int(arch[k]) for k in (
                "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts", "num_experts_per_tok", "vocab_size",
                "num_shared_experts", "num_dense_layers", "loss_chunk_rows")},
        )
        layers = arch.get("num_conv_layers")
        if layers is not None and len(z.layer_types) != int(layers):
            raise ValueError(
                f"layer_types has {len(z.layer_types)} entries for num_conv_layers {int(layers)}: one a layer")
        unknown = sorted(set(z.layer_types) - {SLIDING, FULL})
        if unknown:
            raise ValueError(f"layer_types entries must be {SLIDING!r} or {FULL!r}, got {unknown}")
        if SLIDING in z.layer_types and z.sliding_window < 1:
            raise ValueError("a stack with a sliding_attention layer needs sliding_window >= 1")
        if z.num_attention_heads % z.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if z.head_dim % 2:
            raise ValueError("head_dim must be even (RoPE pairs its channels)")
        held = z.experts_held
        if not held or sorted(set(held)) != list(held) or held[0] < 0 or held[-1] >= z.num_experts:
            raise ValueError(
                f"experts_held {list(held)} must be ascending, distinct ids below num_experts {z.num_experts}")
        if not 1 <= z.num_experts_per_tok <= z.num_experts:
            raise ValueError("num_experts_per_tok must lie in 1 .. num_experts")
        if z.num_shared_experts < 0 or z.num_dense_layers < 0 or z.expert_row_capacity < 0:
            raise ValueError("num_shared_experts, num_dense_layers and expert_row_capacity must not be negative")
        return z

    @property
    def experts(self) -> dc.ExpertSpec:
        """The router's and the expert sublayer's numbers, as models/decoder.py reads them."""
        return dc.ExpertSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok, experts_held=self.experts_held,
            width=self.moe_intermediate_size, shared=self.num_shared_experts, scale=self.route_scale,
            norm_gates=self.route_norm, row_capacity=self.expert_row_capacity)

    def window_of(self, layer: int):
        """A layer's sliding bound, or None on a full layer."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else None


def attention_sublayer(p: Dict, u, aux, z: AfmoeConfig, max_nodes: int, window, rotate: bool):
    """Gated grouped-query attention on the normalised stream ``u [T, D]`` ->
    ``[T, D]`` (before the outgoing norm). ``window`` is the layer's sliding
    bound or None; ``rotate`` whether its queries and keys carry RoPE (a
    sliding layer's do, a full layer's do not)."""
    t = u.shape[0]
    h, hk, d = z.num_attention_heads, z.num_key_value_heads, z.head_dim
    with tr.scope(tr.HG_ATTN_PROJ):
        q = dc.rms_norm(dc.dense(u, p["attn_q"]).reshape(t, h, d), p["attn_q_norm"], z.rms_norm_eps)
        k = dc.rms_norm(dc.dense(u, p["attn_k"]).reshape(t, hk, d), p["attn_k_norm"], z.rms_norm_eps)
        v = dc.dense(u, p["attn_v"]).reshape(t, hk, d)
        if rotate:
            q, k = (dc.rope(a, aux["pos"], d, z.rope_theta) for a in (q, k))
    o = dc.causal_attention(q, k, v, aux, max_nodes, window).reshape(t, h * d)
    with tr.scope(tr.HG_ATTN_GATE):
        o = o * jax.nn.sigmoid(dc.dense(u, p["attn_gate"]))
    return dc.dense(o, p["attn_o"])


def layer_param_shapes(hidden: int, z: AfmoeConfig, dense_mlp: bool) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of one layer's parameter leaves (the kinds:
    ``models/decoder.py INIT``; ``small`` for the projections that write
    into the residual stream)."""
    d, hd = hidden, z.head_dim
    wide, narrow = z.num_attention_heads * hd, z.num_key_value_heads * hd
    shapes = {
        "attn_in_norm": ((d,), "ones"), "mlp_in_norm": ((d,), "ones"),
        # the OUTGOING norms are what writes into the stream here: OUT_GAIN + w
        "attn_out_norm": ((d,), "zeros"), "mlp_out_norm": ((d,), "zeros"),
        "attn_q": ((d, wide), "lecun"), "attn_k": ((d, narrow), "lecun"), "attn_v": ((d, narrow), "lecun"),
        "attn_gate": ((d, wide), "lecun"), "attn_o": ((wide, d), "small"),
        "attn_q_norm": ((hd,), "ones"), "attn_k_norm": ((hd,), "ones"),
    }
    if dense_mlp:
        f = z.intermediate_size
        shapes.update({"mlp_gate": ((d, f), "lecun"), "mlp_up": ((d, f), "lecun"), "mlp_down": ((f, d), "small")})
        return shapes
    shapes.update(dc.expert_param_shapes(d, z.experts))
    return shapes


class AfmoeLayer(nn.Module):
    """One layer: gated attention (sliding under ``window``, else full), then
    the dense MLP (``dense_mlp``) or the expert sublayer, each between its two
    norms. -> (x, ``decoder.expert_layer_stats``, every expert's load)."""

    hidden: int
    z: AfmoeConfig
    dense_mlp: bool
    window: "int | None"
    max_nodes: int

    @nn.compact
    def __call__(self, x, aux, beta):
        z = self.z
        p = dc.layer_params(self, layer_param_shapes(self.hidden, z, self.dense_mlp))
        norm = lambda a, name: dc.rms_norm(a, p[name], z.rms_norm_eps)
        norm_out = lambda a, name: dc.rms_norm(a, OUT_GAIN + p[name].astype(jnp.float32), z.rms_norm_eps)
        y = attention_sublayer(p, norm(x, "attn_in_norm"), aux, z, self.max_nodes, self.window,
                               rotate=self.window is not None)
        x = x + norm_out(y, "attn_out_norm")
        u = norm(x, "mlp_in_norm")
        if self.dense_mlp:
            y = dc.gated_mlp(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            return (x + norm_out(y, "mlp_out_norm"), jnp.zeros((5,), jnp.float32),
                    jnp.zeros((z.num_experts,), jnp.float32))
        y, counts, every, (overrun, here) = dc.expert_sublayer(p, beta, u, aux["node_mask"], z.experts)
        return x + norm_out(y, "mlp_out_norm"), dc.expert_layer_stats(counts, overrun, here), every


class AfmoeModel(nn.Module):
    """Embedding (scaled), the layers, the final norm. ``__call__`` returns
    the final normalised hidden state ``[N, hidden]`` under the head's name
    and the step's counters under ``tr.COUNTER_PREFIX`` names."""

    cfg: "ModelConfig"  # noqa: F821 - models/base.py

    @staticmethod
    def float32_leaves(name: str) -> bool:
        """The router's matrix: ``train/loop.py mp_keep`` asks, and the
        mixed-precision cast leaves it float32."""
        return name == "router"

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg, z = self.cfg, self.cfg.afmoe
        d_model = cfg.hidden_dim
        if batch.z is None:
            raise ValueError("mpnn_type AFMOE reads node ids from batch.z (int32)")
        emb = self.param("embedding", dc.INIT["lecun"], (d_model, z.vocab_size))
        # the untied head: train/loss.py reads it
        self.param("head", dc.INIT["lecun"], (d_model, z.vocab_size))
        x, _ = dc.embed_tokens(emb, batch.z, z.vocab_size)
        if z.mup_enabled:
            x = (x.astype(jnp.float32) * math.sqrt(d_model)).astype(x.dtype)
        aux = dc.batch_aux(batch)
        layer_cls = dc.remat_in_training(AfmoeLayer, train)
        stats = jnp.zeros((5,), jnp.float32)
        expert_layers = 0
        for i in range(cfg.num_conv_layers):
            dense_mlp = i < z.num_dense_layers
            # the balancing bias: a buffer (no gradient, no optimizer state),
            # moved once a training step by the loads it produced
            beta = None if dense_mlp else self.variable(
                "batch_stats", f"router_bias_{i}", lambda: jnp.zeros((z.num_experts,), jnp.float32))
            x, c, loads = layer_cls(d_model, z, dense_mlp, z.window_of(i), cfg.max_nodes_per_graph,
                                    name=f"layers_{i}")(
                x, aux, jnp.zeros((z.num_experts,), jnp.float32) if dense_mlp else beta.value)
            if beta is not None and train and not self.is_initializing():
                beta.value = dc.sign_balanced_bias(beta.value, loads, z.load_balance_coeff)
            stats, expert_layers = stats + c, expert_layers + (not dense_mlp)
        x = dc.rms_norm(x, self.param("final_norm", nn.initializers.ones, (d_model,)), z.rms_norm_eps)
        bad = dc.graphs_overflow(batch, cfg.max_nodes_per_graph) | (stats[3] > 0)
        out = {
            cfg.output_names[0]: dc.poison(x, bad),
            tr.CT_TOKENS: jnp.sum(batch.node_mask.astype(jnp.float32)) * expert_layers,
            tr.CT_TOKENS_ROUTED_HERE: stats[4],
            tr.CT_EXPERT_ROWS_HERE: stats[0],
            tr.CT_EXPERT_LOAD_MAX: stats[1],
            tr.CT_EXPERT_LOAD_MEAN: stats[2],
            tr.CT_EXPERT_ROWS_OVERRUN: stats[3],
            tr.CT_CAUSAL_PAIRS: dc.causal_pairs(batch),
            **dc.flash_blocks(cfg.num_conv_layers, train),
        }
        steps = lambda window: dc.flash_steps(batch, cfg.max_nodes_per_graph, z.head_dim, z.head_dim, x.dtype, window)
        if FULL in z.layer_types:
            out.update(steps(None))
        if SLIDING in z.layer_types:
            out[tr.CT_WINDOW_PAIRS] = dc.window_pairs(batch, z.sliding_window)
            out.update(steps(z.sliding_window))
        return out
