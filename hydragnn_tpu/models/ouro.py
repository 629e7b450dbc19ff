"""OURO stack: a looped language model (LoopLM, arXiv:2510.25741; named after
the ``model_type`` of Ouro-2.6B's published ``config.json``) on the graph
plumbing (token = node, document = graph; what it shares with the other
decoder stacks is models/decoder.py).

The ``num_conv_layers`` layers held here run ``total_ut_steps`` times a step
under ONE set of weights, and every pass ends in an exit. With ``N(.)`` an
RMSNorm at ``rms_norm_eps`` with its own gain:

- layer, each sublayer normalised going in and coming out (four gains a
  layer): ``x <- x + N_2(Attn(N_1(x)))``; ``x <- x + N_4(MLP(N_3(x)))``;
- attention, ``u = N_1(x)``: ``q = W_q u`` -> ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k u``, ``v = W_v u`` -> ``num_key_value_heads`` heads;
  RoPE over the whole head (rotate-half, ``rope_theta``) from the index in
  the document; causal attention within the document
  (``decoder.causal_attention``); ``y = W_o o``. No bias;
- MLP: ``W_down (silu(W_gate u) * W_up u)`` (``decoder.gated_mlp``);
- passes: ``h_0 = Emb(ids)``, unscaled; ``h_t = N_f(F(h_{t-1}))`` for ``t =
  1 .. total_ut_steps``, ``F`` the held layers in order: the normed output is
  both the exit's input and the next pass's;
- exits: one gate shared by the passes, ``lambda_t = sigmoid(w_g . h_t +
  b_g)``; the exit distribution ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t}
  (1 - lambda_j)``, the last exit taking what is left (``exit_distribution``).
  train/loss.py ``loop_exit_loss`` reads each exit through the untied head and
  weighs its next-token cross-entropy by ``p``, less ``ENTROPY_WEIGHT`` times
  the entropy of ``p``.

The passes are ONE ``lax.scan`` over a closure of the held layers'
parameters: the step traces and lowers the stack once, not once a pass.
Inside a pass the layers are a Python loop: each keeps its own leaves
(``layers_<i>``), as the other stacks and the reference name them, and the
held layers trace once each. Every layer application is rematerialised
(``decoder.remat_in_training``): the scan keeps each application's incoming
stream and its flash launch's ``o`` and ``lse``, so that the forward kernel
runs once an application. Initial scales: LeCun-normal for every matrix (the
outgoing norm sets what a sublayer writes into the stream), every gain 1,
the gate's bias 0. The plain reference of these equations is
benchmarks/reference/ouro.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..utils import tracer as tr
from . import decoder as dc

ARCH_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta",
    "total_ut_steps", "vocab_size", "rms_norm_eps", "loss_chunk_rows",
)
# the weight of the exit distribution's entropy in the loss (the LoopLM
# paper's Stage I objective); the published configuration names none
ENTROPY_WEIGHT = 0.1


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The ``OURO`` keys of ``Architecture`` (docs/CONFIG.md), named as the
    published ``config.json`` names them."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    total_ut_steps: int = 4
    rope_theta: float = 1.0e6
    rms_norm_eps: float = 1.0e-6
    loss_chunk_rows: int = 4096

    @staticmethod
    def from_arch(arch: Dict) -> "OuroConfig":
        missing = [k for k in ARCH_KEYS if arch.get(k) is None]
        if missing:
            raise ValueError(f"mpnn_type OURO needs Architecture keys {missing}")
        z = OuroConfig(
            **{k: float(arch[k]) for k in ("rope_theta", "rms_norm_eps")},
            **{k: int(arch[k]) for k in (
                "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
                "total_ut_steps", "loss_chunk_rows")},
        )
        if z.num_attention_heads % z.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if z.head_dim % 2:
            raise ValueError("head_dim must be even (RoPE pairs its channels)")
        if z.total_ut_steps < 1:
            raise ValueError("total_ut_steps (the passes over the held layers) must be at least 1")
        return z


def exit_distribution(gate_logits):
    """``gate_logits [P, T]`` (float32) -> (p, log p), each ``[P, T]``: ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < P`` and ``p_P = prod_{j<P} (1
    - lambda_j)``, ``lambda = sigmoid(logit)``, summed in the log domain. The
    last pass's logit takes no part: one pass gives ``p = 1``."""
    # log of the chance that no earlier exit was taken
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(gate_logits[:1]), stay])
    logp = before + jax.nn.log_sigmoid(gate_logits).at[-1].set(0.0)
    return jnp.exp(logp), logp


def layer_param_shapes(hidden: int, z: OuroConfig) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of one layer's parameter leaves (the kinds:
    ``models/decoder.py INIT``)."""
    d, f = hidden, z.intermediate_size
    wide, narrow = z.num_attention_heads * z.head_dim, z.num_key_value_heads * z.head_dim
    shapes = {n: ((d,), "ones") for n in ("attn_in_norm", "attn_out_norm", "mlp_in_norm", "mlp_out_norm")}
    shapes.update({
        "attn_q": ((d, wide), "lecun"), "attn_k": ((d, narrow), "lecun"), "attn_v": ((d, narrow), "lecun"),
        "attn_o": ((wide, d), "lecun"),
        "mlp_gate": ((d, f), "lecun"), "mlp_up": ((d, f), "lecun"), "mlp_down": ((f, d), "lecun"),
    })
    return shapes


class OuroLayerParams(nn.Module):
    """One held layer's leaves, as ``layers_<i>``: the scan's body applies them."""

    hidden: int
    z: OuroConfig

    @nn.compact
    def __call__(self):
        return dc.layer_params(self, layer_param_shapes(self.hidden, self.z))


def layer(p: Dict, x, aux: Dict, z: OuroConfig, max_nodes: int):
    """One application of a held layer to the stream ``x [T, D]``."""
    t = x.shape[0]
    h, hk, d = z.num_attention_heads, z.num_key_value_heads, z.head_dim
    norm = lambda a, name: dc.rms_norm(a, p[name], z.rms_norm_eps)
    u = norm(x, "attn_in_norm")
    q = dc.rope(dc.dense(u, p["attn_q"]).reshape(t, h, d), aux["pos"], d, z.rope_theta)
    k = dc.rope(dc.dense(u, p["attn_k"]).reshape(t, hk, d), aux["pos"], d, z.rope_theta)
    v = dc.dense(u, p["attn_v"]).reshape(t, hk, d)
    o = dc.causal_attention(q, k, v, aux, max_nodes).reshape(t, h * d)
    x = x + norm(dc.dense(o, p["attn_o"]), "attn_out_norm")
    y = dc.gated_mlp(norm(x, "mlp_in_norm"), p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    return x + norm(y, "mlp_out_norm")


class OuroModel(nn.Module):
    """Embedding, the passes over the held layers, an exit after each.
    ``__call__`` returns the passes' normalised outputs ``[passes, N,
    hidden]`` under the head's name, the exit gate's logits ``[passes, N]``
    under ``decoder.EXIT_GATE`` and the step's counters under
    ``tr.COUNTER_PREFIX`` names. The one ``batch_stats`` leaf, ``exit_weight
    [passes]``, holds each exit's weight ``sum_i follows_i p_t(i)`` of the
    latest training step for whoever watches the gate, and nothing reads it."""

    cfg: "ModelConfig"  # noqa: F821 - models/base.py

    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg, z = self.cfg, self.cfg.ouro
        d_model, held, passes = cfg.hidden_dim, cfg.num_conv_layers, z.total_ut_steps
        if batch.z is None:
            raise ValueError("mpnn_type OURO reads node ids from batch.z (int32)")
        emb = self.param("embedding", dc.INIT["lecun"], (d_model, z.vocab_size))
        # the untied head: train/loss.py reads it
        self.param("head", dc.INIT["lecun"], (d_model, z.vocab_size))
        x, _ = dc.embed_tokens(emb, batch.z, z.vocab_size)
        aux = dc.batch_aux(batch)
        layers = [OuroLayerParams(d_model, z, name=f"layers_{i}")() for i in range(held)]
        final = self.param("final_norm", nn.initializers.ones, (d_model,))
        gate_w = self.param("exit_gate", dc.INIT["lecun"], (d_model, 1))
        gate_b = self.param("exit_gate_bias", nn.initializers.zeros, (1,))
        apply = dc.remat_in_training(functools.partial(layer, z=z, max_nodes=cfg.max_nodes_per_graph), train)

        def one_pass(x, _):
            for p in layers:
                x = apply(p, x, aux)
            with tr.scope(tr.HG_LOOP_EXIT):
                h = dc.rms_norm(x, final, z.rms_norm_eps)
                logit = jnp.dot(h.astype(jnp.float32), gate_w.astype(jnp.float32))[:, 0]
            return h, (h, logit + gate_b.astype(jnp.float32)[0])

        _, (exits, logits) = jax.lax.scan(one_pass, x, None, length=passes)
        weight = self.variable("batch_stats", "exit_weight", lambda: jnp.zeros((passes,), jnp.float32))
        if train and not self.is_initializing():
            follows = dc.follows(batch.node_graph, batch.node_mask, 1).astype(jnp.float32)
            weight.value = jax.lax.stop_gradient(jnp.sum(exit_distribution(logits)[0] * follows, axis=1))
        real = jnp.sum(batch.node_mask.astype(jnp.float32))
        return {
            cfg.output_names[0]: dc.poison(exits, dc.graphs_overflow(batch, cfg.max_nodes_per_graph)),
            dc.EXIT_GATE: logits,
            tr.CT_TOKENS: real * held * passes,
            tr.CT_LOOP_LAYERS_HELD: jnp.float32(held),
            tr.CT_LOOP_LAYER_APPLICATIONS: jnp.float32(held * passes),
            tr.CT_CAUSAL_PAIRS: dc.causal_pairs(batch),
            **dc.flash_blocks(held * passes, train),
        }
