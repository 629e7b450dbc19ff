"""Plain reference of the Keye-VL-2.0 language model (``model_type:
KeyeVL2``): a decoder whose every layer attends only to the keys a learned
indexer selects (DeepSeek-V3.2's sparse attention), QK-normed grouped-query
heads, softmax-routed top-k experts with no shared expert, an untied head; on
packed documents (token = node, document = graph). ``jax.numpy``, float32,
every matrix product through ``common.dense`` or an ``einsum`` at
``HIGHEST``; the indexer's scores, the selection (``lax.top_k``) and the
attention over it computed a block of queries at a time (32 heads x 128
queries x 32,768 keys of float32 scores are 537 MB a block), the experts held
as a loop with a weight a token. No kernel, no cache, nothing of
``hydragnn_tpu``; written from the equations below, which follow the keys
of https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json.

Ids are read from ``x[:, 0]``, positions from ``node_graph``. With ``N(.)`` an
RMSNorm at ``rms_norm_eps``, the stream ``x [T, D]``:

- embedding: ``x_0 = Emb(t)``;
- layer: ``x <- x + Attn(N_1(x))``; ``x <- x + MoE(N_2(x))``;
- attention, ``u = N_1(x)``: ``q = W_q u`` -> H heads of d, ``k = W_k u``, ``v =
  W_v u`` -> Hk heads (query head h reads key-value head ``h // (H / Hk)``);
  ``q <- N_q(q)``, ``k <- N_k(k)`` over each head's d numbers, then RoPE over
  the whole head (channel i paired with ``i + d / 2``, angle ``pos * theta **
  (-2 i / d)``); ``y = W_o o``, ``o`` the softmax at ``1/sqrt(d)`` over the
  query's SELECTED keys;
- indexer, on ``u`` with no gradient: ``qI = W_qI u`` -> HI heads of dI, ``kI =
  LayerNorm(W_kI u)`` (one head of dI, eps 1e-6), ``w = W_w u`` [HI]; RoPE on
  ``qI`` and ``kI``; ``I[t, s] = (HI dI)^-1/2 sum_j w[t, j] ReLU(qI[t, j] .
  kI[s])``; the selection ``S_t``: the ``min(n_t, topk)`` keys of its document
  with ``s <= t`` and the largest ``I`` (``lax.top_k``: the lower position
  first at a tie);
- indexer loss: ``L_I = sum over real t of KL(p_t || softmax over S_t of
  I[t, .])``, ``p_t`` the attention's probabilities over ``S_t`` averaged over
  the H heads, detached; summed over layers, divided by the count of (token,
  next token) pairs, times ``INDEX_LOSS_WEIGHT``;
- expert layer, ``u = N_2(x)``: ``s = softmax(W_r u)`` over all experts in
  float32; choice the k largest; ``g_e = s_e / sum of the chosen s`` under
  ``norm_topk_prob``; ``y = sum over chosen e of g_e expert_e(u)``;
- balancing: ``AUX_LOSS_COEF * E * sum_e f_e P_e``, ``f_e`` the chosen
  (token, slot) entries of expert e over all layers and real tokens divided by
  their (token, layer) count, ``P_e`` the mean of ``s_e`` over the same;
- head: ``logits = W_head N_f(x_L)``; the token loss is the mean next-token
  cross-entropy over the (token, next token) pairs within documents; the loss
  is its sum with the two terms above.

Assumed (each in the configuration file's ``assumed``):

(K1) the language model alone: no vision tower, text positions;
(K2) QK-norm as Qwen3-MoE has it: RMSNorm per head before the rotation;
(K3) the indexer as DeepSeek-V3.2's public ``Indexer``: queries, key and
     weights from the layer's normalised input, the key's LayerNorm, the
     scale ``(HI dI)^-1/2``, RoPE over all dI channels at the model's theta; no
     FP8, no Hadamard rotation (orthogonal: it leaves ``qI . kI`` unchanged);
(K4) ``q_chunk_size`` / ``kv_chunk_size`` are the computation's tiles: the
     selection is per key;
(K5) a tie at the threshold goes to the lower position;
(K6) ``L_I`` at weight 1, detached (its gradient reaches the indexer's
     weights alone), over the step's real tokens, on the token loss's
     denominator;
(K7) the auxiliary loss at 0.001 over the batch the loss is given;
(K8) initial scales: LeCun-normal; what writes into the stream (``attn_o``,
     ``experts_down``) near zero (``common.py``'s "gate" kind); gains 1,
     LayerNorm bias 0;
(K9) sparse attention on every layer.

Departures: the harness differentiates a step's documents a group at a time
(``drive_train_tokens.py``); the auxiliary loss's ``f`` and ``P`` are then
each group's, where the program's are the step's. Given the step's loads
(``buffers["step_loads"]`` ``[layers, E]``, summed over the step's groups by a
first pass: ``benchmarks/tests/calibrate_keyevl2.py`` measures the departure
so) ``f`` is the step's; ``P`` stays the group's, whose weight in the step is
its share of the pairs.

The expert share: ``arch["experts_held"]`` lists the experts computed here;
the router scores all ``num_experts``; a chosen expert that is not held adds
nothing; attention, the indexer and the router are whole.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import common as c
from .afmoe import cross_entropy_sum, follows, gated, positions, rms_norm, rope_halves

NEG = -1.0e30
QUERY_BLOCK = 128
INDEX_NORM_EPS = 1.0e-6
INDEX_LOSS_WEIGHT = 1.0  # (K6)
AUX_LOSS_COEF = 0.001  # (K7)


def _dims(arch: dict) -> dict:
    i = lambda k: int(arch[k])
    return {
        "D": i("hidden_dim"), "layers": i("num_conv_layers"), "H": i("num_attention_heads"),
        "Hk": i("num_key_value_heads"), "d": i("head_dim"), "theta": float(arch["rope_theta"]),
        "F": i("moe_intermediate_size"), "E": i("num_experts"), "k": i("num_experts_per_tok"),
        "norm": bool(arch["norm_topk_prob"]), "held": [int(e) for e in arch["experts_held"]],
        "HI": i("indexer_num_heads"), "dI": i("indexer_head_dim"), "topk": i("indexer_topk"),
        "V": i("vocab_size"), "eps": float(arch["rms_norm_eps"]),
    }


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    m = _dims(arch)
    D, wide, narrow, held = m["D"], m["H"] * m["d"], m["Hk"] * m["d"], len(m["held"])
    rows: List[tuple] = [(("params", "embedding"), (D, m["V"]), "lecun"), (("params", "head"), (D, m["V"]), "lecun")]
    for layer in range(m["layers"]):
        at = lambda leaf, shape, kind, name=f"layers_{layer}": (("params", name, leaf), tuple(shape), kind)
        rows += [at("attn_norm", (D,), "ones"), at("mlp_norm", (D,), "ones"),
                 at("attn_q", (D, wide), "lecun"), at("attn_k", (D, narrow), "lecun"),
                 at("attn_v", (D, narrow), "lecun"), at("attn_o", (wide, D), "gate"),
                 at("attn_q_norm", (m["d"],), "ones"), at("attn_k_norm", (m["d"],), "ones"),
                 at("index_q", (D, m["HI"] * m["dI"]), "lecun"), at("index_k", (D, m["dI"]), "lecun"),
                 at("index_w", (D, m["HI"]), "lecun"), at("index_k_norm", (m["dI"],), "ones"),
                 at("index_k_bias", (m["dI"],), "zeros"),
                 at("router", (D, m["E"]), "lecun"),
                 at("experts_gate", (held, D, m["F"]), "lecun"), at("experts_up", (held, D, m["F"]), "lecun"),
                 at("experts_down", (held, m["F"], D), "gate")]
    rows.append((("params", "final_norm"), (D,), "ones"))
    # the program's one buffer: each layer's expert loads of the latest step
    rows.append((("batch_stats", "expert_loads"), (m["layers"], m["E"]), "zeros"))
    return rows


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def sparse_attention(q, k, v, qi, ki, w, node_graph, node_w, topk: int, mode: str):
    """-> (o [T, H, d], the sum over real queries of the indexer's KL), a
    block of queries at a time. q [T, H, d], k, v [T, Hk, d]; qi [T, HI, dI],
    ki [T, dI], w [T, HI]."""
    t, h, d = q.shape
    hk, hi, di = k.shape[1], qi.shape[1], qi.shape[2]
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    idx = jnp.arange(t, dtype=jnp.int32)
    real = node_w > 0
    kr, vr, kir = c._round(k, mode), c._round(v, mode), c._round(ki, mode)
    n_sel = min(int(topk), t)

    def one(args):
        qb, qib, wb, ib, gb, rb = args
        ok = (gb[:, None] == node_graph[None, :]) & (rb[:, None] & real[None, :]) & (idx[None, :] <= ib[:, None])
        a = jnp.einsum("chd,sd->chs", c._round(qib, mode), kir, precision=c.HIGHEST)
        index = jnp.einsum("ch,chs->cs", wb, jnp.maximum(a, 0.0), precision=c.HIGHEST) / math.sqrt(hi * di)
        _, top = jax.lax.top_k(jnp.where(ok, index, -jnp.inf), n_sel)
        sel = jnp.zeros(ok.shape, bool).at[jnp.arange(ok.shape[0])[:, None], top].set(True) & ok
        qg = c._round(qb, mode).reshape(qb.shape[0], hk, h // hk, d)
        s = jnp.einsum("ikgd,jkd->kgij", qg, kr, precision=c.HIGHEST) / jnp.sqrt(float(d))
        s = jnp.where(sel[None, None], s, NEG)
        p = jnp.where(sel[None, None], jax.nn.softmax(s, axis=-1), 0.0)
        o = jnp.einsum("kgij,jkd->ikgd", c._round(p, mode), vr, precision=c.HIGHEST).reshape(qb.shape)
        # the indexer's loss: p averaged over the heads, detached
        pm = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        logq = jnp.where(sel, jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1), 0.0)
        kl = jnp.where(pm > 0, pm * (jnp.log(jnp.where(pm > 0, pm, 1.0)) - logq), 0.0)
        return o, jnp.sum(kl)

    padded = lambda a, fill: jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]) if pad else a
    blocks = lambda a: a.reshape((-1, block) + a.shape[1:])
    out, kl = jax.lax.map(jax.checkpoint(one), (
        blocks(padded(q, 0.0)), blocks(padded(qi, 0.0)), blocks(padded(w, 0.0)), blocks(padded(idx, 0)),
        blocks(padded(node_graph, -1)), blocks(padded(real, False))))
    return c.act_round(out.reshape((-1, h, d))[:t], mode), jnp.sum(kl)


def attention(p: Dict, u, b: Dict, m: Dict, mode: str):
    t, H, Hk, d, HI, dI = u.shape[0], m["H"], m["Hk"], m["d"], m["HI"], m["dI"]
    dense = lambda a, w_: c.dense(a, w_, None, mode)
    pos = b["positions"]
    q = rope_halves(rms_norm(dense(u, p["attn_q"]).reshape(t, H, d), p["attn_q_norm"], m["eps"]), pos, m["theta"])
    k = rope_halves(rms_norm(dense(u, p["attn_k"]).reshape(t, Hk, d), p["attn_k_norm"], m["eps"]), pos, m["theta"])
    v = dense(u, p["attn_v"]).reshape(t, Hk, d)
    ui = jax.lax.stop_gradient(u)  # the indexer trains on its own loss alone
    qi = rope_halves(dense(ui, p["index_q"]).reshape(t, HI, dI), pos, m["theta"])
    ki = layer_norm(dense(ui, p["index_k"]), p["index_k_norm"], p["index_k_bias"], INDEX_NORM_EPS)
    ki = rope_halves(ki[:, None, :], pos, m["theta"])[:, 0]
    w = dense(ui, p["index_w"])
    o, kl = sparse_attention(c.act_round(q, mode), c.act_round(k, mode), v, c.act_round(qi, mode),
                             c.act_round(ki, mode), w, b["node_graph"], b["node_w"], m["topk"], mode)
    return dense(o.reshape(t, H * d), p["attn_o"]), kl


def experts(p: Dict, u, b: Dict, m: Dict, mode: str):
    """-> (y [T, D], every expert's load [E], every expert's summed
    probability [E] over real tokens); the router in float32 whatever the
    mode."""
    s = jax.nn.softmax(c.dense(u, p["router"]), axis=-1)
    gate, choice = jax.lax.top_k(s, m["k"])
    if m["norm"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    real = (b["node_w"] > 0).astype(jnp.float32)
    weights = jnp.stack([jnp.sum(jnp.where(choice == e, gate, 0.0), axis=-1) * real for e in m["held"]])

    def expert(y_, xs):
        w_tok, w_gate, w_up, w_down = xs
        return y_ + w_tok[:, None] * gated(u, w_gate, w_up, w_down, mode), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                        (weights, p["experts_gate"], p["experts_up"], p["experts_down"]))
    loads = jnp.zeros((m["E"],), jnp.float32).at[choice.reshape(-1)].add(jnp.repeat(real, m["k"]))
    return c.act_round(y, mode), loads, jnp.sum(s * real[:, None], axis=0)


def layer(p: Dict, x, b: Dict, m: Dict, mode: str):
    norm = lambda a, name: c.act_round(rms_norm(a, p[name], m["eps"]), mode)
    y, kl = attention(p, norm(x, "attn_norm"), b, m, mode)
    x = c.act_round(x + c.act_round(y, mode), mode)
    y, loads, probs = experts(p, norm(x, "mlp_norm"), b, m, mode)
    return c.act_round(x + y, mode), loads, probs, kl


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32"):
    """-> (final normalised hidden [T, D], loads [layers, E], summed
    probabilities [layers, E], the indexer's KL summed over real tokens
    [layers])."""
    m = _dims(arch)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    b = dict(b, positions=positions(b["node_graph"]))
    x = c.act_round(c._round(params["embedding"], mode).T[ids], mode)
    loads, probs, kls = [], [], []
    for layer_i in range(m["layers"]):
        step = jax.checkpoint(lambda p, x_: layer(p, x_, b, m, mode))
        x, ld, pr, kl = step(params[f"layers_{layer_i}"], x)
        loads.append(ld)
        probs.append(pr)
        kls.append(kl)
    h = c.act_round(rms_norm(x, params["final_norm"], m["eps"]), mode)
    return h, jnp.stack(loads), jnp.stack(probs), jnp.stack(kls)


def balance(buffers: Dict, loads, arch: dict) -> Dict:
    """The buffer is the step's loads, ``[layers, E]``; nothing balances
    through it."""
    return {"expert_loads": loads}


def loss_fn(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    return loss_and_loads(params, b, arch, mode, buffers)[0]


def loss_and_loads(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """The token loss plus the indexer's and the balancing terms; and every
    expert's load in every layer ``[layers, E]`` on the real tokens."""
    m = _dims(arch)
    h, loads, probs, kls = forward(params, b, arch, mode)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    w1 = follows(b, 1).astype(jnp.float32)
    pairs = jnp.maximum(jnp.sum(w1), 1.0)
    token = cross_entropy_sum(h, params["head"], jnp.roll(ids, -1), w1, mode) / pairs
    n = jnp.maximum(jnp.sum(b["node_w"]) * m["layers"], 1.0)
    f, prob = jax.lax.stop_gradient(jnp.sum(loads, axis=0)) / n, jnp.sum(probs, axis=0) / n
    step = (buffers or {}).get("step_loads")
    if step is not None:  # every (token, layer) of the step chose k experts
        f = jnp.sum(step, axis=0) / jnp.maximum(jnp.sum(step) / m["k"], 1.0)
    aux = AUX_LOSS_COEF * m["E"] * jnp.sum(f * prob)
    return token + INDEX_LOSS_WEIGHT * jnp.sum(kls) / pairs + aux, jax.lax.stop_gradient(loads)


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float,
                  rows_routed: Optional[float] = None) -> float:
    """Matrix products of one forward pass on REAL tokens; the routed experts
    at the rows computed here (``rows_routed``, summed over layers, where a
    counter gives it; else ``k held / num_experts`` a token); the attention's
    score and value products and the indexer's scores are left out (they
    depend on the documents' lengths), so a share of the peak from this count
    reads low, never high."""
    m = _dims(arch)
    D, wide, narrow = m["D"], m["H"] * m["d"], m["Hk"] * m["d"]
    attn = 2.0 * D * (2 * wide + 2 * narrow)
    index = 2.0 * D * (m["HI"] * m["dI"] + m["dI"] + m["HI"])
    per_token = m["layers"] * (attn + index + 2.0 * D * m["E"]) + 2.0 * D * m["V"]
    if rows_routed is None:
        rows_routed = nodes * m["layers"] * m["k"] * len(m["held"]) / m["E"]
    return nodes * per_token + rows_routed * 6.0 * D * m["F"]
