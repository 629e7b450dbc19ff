"""Plain reference of the Ouro-2.6B configuration (``model_type: ouro``, the
LoopLM of arXiv:2510.25741): a decoder whose held layers run
``total_ut_steps`` times under one set of weights, an exit after every pass
(the final norm, an untied head, one learned exit gate), trained on the
gate-weighted expected loss over the exits with an entropy term; on packed
documents (token = node, document = graph). ``jax.numpy``, float32, every
matrix product through ``common.dense`` or an ``einsum`` at ``HIGHEST``; the
passes and the layers as Python loops; attention as a masked softmax over
``[T, T]`` a block of queries at a time (``afmoe.causal_attention``); the
head a block of rows at a time. No kernel, no scan over the passes, no
chunked head of the program's, nothing of ``hydragnn_tpu``; written from the
equations below, which follow the keys of
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json.

Ids are read from ``x[:, 0]``, positions from ``node_graph``. With ``N(.)`` an
RMSNorm at ``rms_norm_eps`` with its own gain, the stream ``x [T, D]``:

- embedding: ``h_0 = Emb(t)``, unscaled;
- layer: ``x <- x + N_2(Attn(N_1(x)))``; ``x <- x + N_4(MLP(N_3(x)))``;
- attention, ``u = N_1(x)``: ``q = W_q u`` -> H heads of d, ``k = W_k u``, ``v =
  W_v u`` -> Hk heads; RoPE over the whole head (channel i paired with ``i +
  d / 2``, angle ``pos * theta ** (-2 i / d)``) on q and k; scores at
  ``1/sqrt(d)``; query i sees key j iff same document and ``j <= i``; ``y = W_o
  o``;
- MLP: ``W_down (silu(W_gate u) * W_up u)``, ``u = N_3(x)``;
- passes: ``h_t = N_f(F(h_{t-1}))`` for ``t = 1 .. P`` (``P = total_ut_steps``),
  ``F`` the held layers in order;
- exits: ``l_t = W_head h_t``; ``lambda_t = sigmoid(w_g . h_t + b_g)``; ``p_1 =
  lambda_1``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``1 < t < P``,
  ``p_P = prod_{j<P} (1 - lambda_j)``, per token;
- loss: ``(1/den) sum_i follows_i [sum_t p_t(i) ce_t(i) - BETA H(p(i))]``,
  ``ce_t`` exit t's next-token cross-entropy, ``H(p) = -sum_t p_t log p_t``,
  ``den`` the (token, next token) pairs within documents.

Assumed (each in the configuration file's ``assumed``): (O1) the sandwich
norm: four norms a layer, each sublayer normalised going in and coming out;
(O2) the normed output ``h_t`` feeds the next pass; (O3) ``BETA`` 0.1 and the
gate: one linear map ``D -> 1`` shared by the passes, LeCun-normal, bias 0;
(O4) initial scales: LeCun-normal for every matrix, every gain 1.
Departures: none of the mathematics; ``early_exit_threshold`` is an
inference setting, and training runs every pass.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import common as c
from .afmoe import causal_attention, cross_entropy_sum, follows, gated, positions, rms_norm, rope_halves

BETA = 0.1  # (O3) the entropy term's weight


def _dims(arch: dict) -> dict:
    i = lambda k: int(arch[k])
    return {
        "D": i("hidden_dim"), "layers": i("num_conv_layers"), "passes": i("total_ut_steps"),
        "H": i("num_attention_heads"), "Hk": i("num_key_value_heads"), "d": i("head_dim"),
        "theta": float(arch["rope_theta"]), "F": i("intermediate_size"), "V": i("vocab_size"),
        "eps": float(arch["rms_norm_eps"]),
    }


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    m = _dims(arch)
    D, wide, narrow, F = m["D"], m["H"] * m["d"], m["Hk"] * m["d"], m["F"]
    rows: List[tuple] = [(("params", "embedding"), (D, m["V"]), "lecun"), (("params", "head"), (D, m["V"]), "lecun")]
    for layer_i in range(m["layers"]):
        at = lambda leaf, shape, kind, name=f"layers_{layer_i}": (("params", name, leaf), tuple(shape), kind)
        rows += [at(n, (D,), "ones") for n in ("attn_in_norm", "attn_out_norm", "mlp_in_norm", "mlp_out_norm")]
        rows += [at("attn_q", (D, wide), "lecun"), at("attn_k", (D, narrow), "lecun"),
                 at("attn_v", (D, narrow), "lecun"), at("attn_o", (wide, D), "lecun"),
                 at("mlp_gate", (D, F), "lecun"), at("mlp_up", (D, F), "lecun"), at("mlp_down", (F, D), "lecun")]
    rows += [(("params", "final_norm"), (D,), "ones"), (("params", "exit_gate"), (D, 1), "lecun"),
             (("params", "exit_gate_bias"), (1,), "zeros"),
             (("batch_stats", "exit_weight"), (m["passes"],), "zeros")]
    return rows


def layer(p: Dict, x, b: Dict, m: Dict, mode: str):
    t, H, Hk, d = x.shape[0], m["H"], m["Hk"], m["d"]
    norm = lambda a, name: c.act_round(rms_norm(c.act_round(a, mode), p[name], m["eps"]), mode)
    dense = lambda a, w: c.dense(a, w, None, mode)
    u = norm(x, "attn_in_norm")
    q = rope_halves(dense(u, p["attn_q"]).reshape(t, H, d), b["positions"], m["theta"])
    k = rope_halves(dense(u, p["attn_k"]).reshape(t, Hk, d), b["positions"], m["theta"])
    v = dense(u, p["attn_v"]).reshape(t, Hk, d)
    o = causal_attention(c.act_round(q, mode), c.act_round(k, mode), v, b["node_graph"], b["node_w"], None, mode)
    x = c.act_round(x + norm(dense(o.reshape(t, H * d), p["attn_o"]), "attn_out_norm"), mode)
    y = gated(norm(x, "mlp_in_norm"), p["mlp_gate"], p["mlp_up"], p["mlp_down"], mode)
    return c.act_round(x + norm(y, "mlp_out_norm"), mode)


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32"):
    """-> (the passes' normalised outputs [P, T, D], the exit gate's logits
    [P, T])."""
    m = _dims(arch)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    b = dict(b, positions=positions(b["node_graph"]))
    h = c.act_round(c._round(params["embedding"], mode).T[ids], mode)
    exits, logits = [], []
    for _ in range(m["passes"]):
        for layer_i in range(m["layers"]):
            h = jax.checkpoint(lambda p, x_: layer(p, x_, b, m, mode))(params[f"layers_{layer_i}"], h)
        h = c.act_round(rms_norm(h, params["final_norm"], m["eps"]), mode)
        exits.append(h)
        logits.append(c.dense(h, params["exit_gate"], params["exit_gate_bias"], mode)[:, 0])
    return jnp.stack(exits), jnp.stack(logits)


def exit_distribution(logits):
    """``[P, T]`` gate logits -> ``p [P, T]`` as the equations write it."""
    lam = jax.nn.sigmoid(logits)
    p, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def balance(buffers: Dict, loads, arch: dict) -> Dict:
    """The buffer is each exit's weight over the step's pairs, ``[P]``;
    nothing reads it."""
    return {"exit_weight": loads}


def loss_fn(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    return loss_and_loads(params, b, arch, mode, buffers)[0]


def loss_and_loads(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """The gate-weighted expected next-token loss over the exits less
    ``BETA`` times the exit distribution's entropy, over the (token, next
    token) pairs within documents; and each exit's weight ``sum_i follows_i
    p_t(i)`` ``[P]``."""
    m = _dims(arch)
    exits, logits = forward(params, b, arch, mode)
    p = exit_distribution(logits)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    w1 = follows(b, 1).astype(jnp.float32)
    pairs = jnp.maximum(jnp.sum(w1), 1.0)
    expected = sum(cross_entropy_sum(exits[t], params["head"], jnp.roll(ids, -1), p[t] * w1, mode)
                   for t in range(m["passes"]))
    entropy = -jnp.sum(w1 * jnp.sum(p * jnp.log(p), axis=0))
    return (expected - BETA * entropy) / pairs, jax.lax.stop_gradient(jnp.sum(p * w1, axis=1))


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float) -> float:
    """Matrix products of one forward pass on REAL tokens: every held layer
    once a pass (``total_ut_steps`` applications of each), and the head and
    the exit gate once a pass; the attention's score and value products are
    left out (they depend on the documents' lengths), so a share of the peak
    from this count reads low, never high."""
    m = _dims(arch)
    D, wide, narrow = m["D"], m["H"] * m["d"], m["Hk"] * m["d"]
    layer_flops = 2.0 * D * (2 * wide + 2 * narrow) + 6.0 * D * m["F"]
    exit_flops = 2.0 * D * m["V"] + 2.0 * D
    return nodes * m["passes"] * (m["layers"] * layer_flops + exit_flops)
