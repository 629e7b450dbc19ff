"""Plain reference of the JoyAI-LLM-Flash configuration: a decoder language
model of pre-norm layers, each latent attention (MLA) followed by a dense
SiLU-gated MLP (the leading layers) or by sigmoid-routed top-k experts beside
a shared expert, an untied head, and one multi-token-prediction module, on
packed documents (token = node, document = graph). ``jax.numpy``, float32,
every matrix product through ``common.dense`` at ``HIGHEST``; attention as a
masked softmax over ``[T, T]`` a block of queries at a time, the experts held
as a loop with a weight a token. No kernel, no cache, nothing of
``hydragnn_tpu``; written from the equations of ISSUE 33, which follow the
keys of https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json
(``model_type: joyai_llm_flash``) and, where a key names a mechanism without
its equation, DeepSeek-V2 (arXiv:2405.04434, MLA) and DeepSeek-V3
(arXiv:2412.19437: sigmoid scores, ``noaux_tc`` bias, multi-token prediction).

Ids are read from ``x[:, 0]``, positions from ``node_graph``. For the
normalised stream ``u = RMSNorm(x)``:

- MLA: ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` -> heads of ``[q_nope ;
  q_rope]``; ``[c_kv ; k_rope] = W_kva u``; ``c_kv <- RMSNorm(c_kv)``;
  ``[k_nope ; v]`` a head ``= W_kvb c_kv``; RoPE in interleaved pairs on
  ``q_rope`` and on the one ``k_rope`` every head shares; causal attention
  within the document at ``1/sqrt(qk width)``; ``y = W_o concat(heads)``.
- dense layer: ``W_down (silu(W_gate u) * W_up u)``.
- expert layer: ``s = sigmoid(W_r u)``; choice = the k largest of ``s + b``;
  ``g_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``;
  ``y = shared(u) + sum over chosen e of g_e expert_e(u)``.
- module: ``h' = W_eh [RMSNorm_e(Emb(t_(i+1))) ; RMSNorm_h(h_i)]`` with ``h``
  the last stream before the final norm, one expert layer, a norm, the head.

Assumed (no key in the config; each in the configuration file's ``assumed``):

(B1) the balancing rule of ``b``, once a training step, outside the gradient:
     b_e <- b_e + 0.01 * (mean load - load_e) / mean load over the loads of all
     experts on the step's real tokens (``balance``; the publication's sign
     rule at 0.001 is what PR 29 found outrun here);
(B2) the module's weight ``lambda`` 0.3 (``mtp_loss_weight``) and the order of
     the join, embedding first;
(B3) both losses are sums of cross-entropies over one count, the step's (token,
     next token) pairs (DeepSeek-V3 eq. 24-25 divide both by the sequence
     length): loss = (sum_main + lambda sum_module) / pairs, the module's sum
     over the nodes whose two successors lie in their document; the next
     token's embedding is zero where the document ends;
(B4) initial scales: LeCun-normal; the projections that write into the stream
     (``mla_o``, ``mlp_down``, ``shared_down``, ``experts_down``) near zero
     (``common.py``'s "gate" kind), the router's matrix at LeCun scale (scores
     spread over 0.27 .. 0.73: neither saturated nor level).

Departures: the sequence-wise auxiliary loss has no key and is left out;
``n_group`` 1 / ``topk_group`` 1 make the group limit empty.

The expert share: ``arch["experts_held"]`` lists the experts computed here;
the router scores all ``n_routed_experts``; a chosen expert that is not held
adds nothing; the shared expert, router and attention are whole.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import common as c

NEG = -1.0e30
# 32 heads x 256 queries x 8,704 keys of float32 scores are 285 MB a block
QUERY_BLOCK = 256
LOSS_BLOCK = 4096
BIAS_GAIN = 0.01


def _dims(arch: dict) -> dict:
    i = lambda k: int(arch[k])
    return {
        "D": i("hidden_dim"), "layers": i("num_conv_layers"), "H": i("num_attention_heads"),
        "Rq": i("q_lora_rank"), "Rkv": i("kv_lora_rank"), "dn": i("qk_nope_head_dim"),
        "dr": i("qk_rope_head_dim"), "dv": i("v_head_dim"), "theta": float(arch["rope_theta"]),
        "Fd": i("intermediate_size"), "F": i("moe_intermediate_size"), "E": i("n_routed_experts"),
        "k": i("num_experts_per_tok"), "shared": i("n_shared_experts"), "first": i("first_k_dense_replace"),
        "scale": float(arch["routed_scaling_factor"]), "mtp": i("num_nextn_predict_layers"),
        "lam": float(arch["mtp_loss_weight"]), "held": [int(e) for e in arch["experts_held"]],
        "V": i("vocab_size"), "eps": float(arch["rms_norm_eps"]),
    }


def layer_names(m: dict) -> List[tuple]:
    """(name in the tree, name of its bias buffer or None for a dense layer),
    the module's layer last."""
    rows = [(f"layers_{l}", None if l < m["first"] else f"router_bias_{l}") for l in range(m["layers"])]
    return rows + ([("mtp_layer", "router_bias_mtp")] if m["mtp"] else [])


def bias_names(arch: dict) -> List[str]:
    return [b for _, b in layer_names(_dims(arch)) if b]


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    m = _dims(arch)
    D, H, held = m["D"], m["H"], len(m["held"])
    rows: List[tuple] = [(("params", "embedding"), (D, m["V"]), "lecun"), (("params", "head"), (D, m["V"]), "lecun")]
    for name, bias in layer_names(m):
        at = lambda leaf, shape, kind, name=name: (("params", name, leaf), tuple(shape), kind)
        rows += [at("attn_norm", (D,), "ones"), at("mlp_norm", (D,), "ones"),
                 at("mla_q_a", (D, m["Rq"]), "lecun"), at("mla_q_norm", (m["Rq"],), "ones"),
                 at("mla_q_b", (m["Rq"], H * (m["dn"] + m["dr"])), "lecun"),
                 at("mla_kv_a", (D, m["Rkv"] + m["dr"]), "lecun"), at("mla_kv_norm", (m["Rkv"],), "ones"),
                 at("mla_kv_b", (m["Rkv"], H * (m["dn"] + m["dv"])), "lecun"),
                 at("mla_o", (H * m["dv"], D), "gate")]
        if bias is None:
            rows += [at("mlp_gate", (D, m["Fd"]), "lecun"), at("mlp_up", (D, m["Fd"]), "lecun"),
                     at("mlp_down", (m["Fd"], D), "gate")]
            continue
        rows += [at("router", (D, m["E"]), "lecun"),
                 at("experts_gate", (held, D, m["F"]), "lecun"), at("experts_up", (held, D, m["F"]), "lecun"),
                 at("experts_down", (held, m["F"], D), "gate")]
        if m["shared"]:
            fs = m["F"] * m["shared"]
            rows += [at("shared_gate", (D, fs), "lecun"), at("shared_up", (D, fs), "lecun"),
                     at("shared_down", (fs, D), "gate")]
        rows.append((("batch_stats", bias), (m["E"],), "zeros"))
    if m["mtp"]:
        rows += [(("params", "mtp_enorm"), (D,), "ones"), (("params", "mtp_hnorm"), (D,), "ones"),
                 (("params", "mtp_proj"), (2 * D, D), "lecun"), (("params", "mtp_final_norm"), (D,), "ones")]
    rows.append((("params", "final_norm"), (D,), "ones"))
    return rows


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def positions(node_graph):
    """0-based index of each node within its graph (graphs contiguous)."""
    n = node_graph.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    start = jnp.full((n,), n, jnp.int32).at[node_graph].min(idx)
    return idx - start[node_graph]


def rope_pairs(x, pos, theta: float):
    """RoPE over all of ``x [T, H, d]``'s channels in interleaved pairs
    (2 i, 2 i + 1), angle ``pos * theta ** (-2 i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def causal_attention(q, k, v, node_graph, node_w, mode: str):
    """Masked softmax over [T, T], one block of queries at a time. q, k
    [T, H, d], v [T, H, dv]; node i sees the real nodes j <= i of its own
    graph; scale 1/sqrt(d)."""
    t, h, d = q.shape
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    idx = jnp.arange(t, dtype=jnp.int32)
    real = node_w > 0
    kr, vr = c._round(k, mode), c._round(v, mode)

    def one(args):
        qb, ib, gb, rb = args
        s = jnp.einsum("ihd,jhd->hij", c._round(qb, mode), kr, precision=c.HIGHEST) / jnp.sqrt(float(d))
        ok = (gb[:, None] == node_graph[None, :]) & (rb[:, None] & real[None, :]) & (idx[None, :] <= ib[:, None])
        s = jnp.where(ok[None], s, NEG)
        p = jnp.where(ok[None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("hij,jhd->ihd", c._round(p, mode), vr, precision=c.HIGHEST)

    padded = lambda a, fill: jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]) if pad else a
    blocks = lambda a: a.reshape((-1, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(one), (blocks(padded(q, 0.0)), blocks(padded(idx, 0)),
                                            blocks(padded(node_graph, -1)), blocks(padded(real, False))))
    return c.act_round(out.reshape((-1, h, v.shape[-1]))[:t], mode)


def mla(p: Dict, u, b: Dict, m: Dict, mode: str):
    t, H, dn, dr, dv = u.shape[0], m["H"], m["dn"], m["dr"], m["dv"]
    dense = lambda a, w: c.dense(a, w, None, mode)
    c_q = c.act_round(rms_norm(dense(u, p["mla_q_a"]), p["mla_q_norm"], m["eps"]), mode)
    q = dense(c_q, p["mla_q_b"]).reshape(t, H, dn + dr)
    kv = dense(u, p["mla_kv_a"])
    c_kv = c.act_round(rms_norm(kv[:, :m["Rkv"]], p["mla_kv_norm"], m["eps"]), mode)
    kv_b = dense(c_kv, p["mla_kv_b"]).reshape(t, H, dn + dv)
    q_rope = rope_pairs(q[..., dn:], b["positions"], m["theta"])
    k_rope = rope_pairs(kv[:, None, m["Rkv"]:], b["positions"], m["theta"])
    q = c.act_round(jnp.concatenate([q[..., :dn], q_rope], axis=-1), mode)
    k = c.act_round(jnp.concatenate([kv_b[..., :dn], jnp.broadcast_to(k_rope, (t, H, dr))], axis=-1), mode)
    o = causal_attention(q, k, kv_b[..., dn:], b["node_graph"], b["node_w"], mode)
    return dense(o.reshape(t, H * dv), p["mla_o"])


def gated(u, w_gate, w_up, w_down, mode: str):
    hid = c.act_round(jax.nn.silu(c.dense(u, w_gate, None, mode)) * c.dense(u, w_up, None, mode), mode)
    return c.dense(hid, w_down, None, mode)


def experts(p: Dict, beta, u, b: Dict, m: Dict, mode: str):
    """-> (y [T, D], choice [T, k]); the router in float32 whatever the mode."""
    s = jax.nn.sigmoid(c.dense(u, p["router"]))
    _, choice = jax.lax.top_k(s + jax.lax.stop_gradient(beta), m["k"])
    chosen = jnp.take_along_axis(s, choice, axis=-1)
    gate = m["scale"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    real = (b["node_w"] > 0).astype(jnp.float32)
    # the weight each held expert has on each token: its gate where chosen, else 0
    weights = jnp.stack([jnp.sum(jnp.where(choice == e, gate, 0.0), axis=-1) * real for e in m["held"]])

    def expert(y_, xs):
        w_tok, w_gate, w_up, w_down = xs
        return y_ + w_tok[:, None] * gated(u, w_gate, w_up, w_down, mode), None

    # a loop over the experts held, each on every row: one expert's [T, F]
    # arrays alive at a time, forward and backward
    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                        (weights, p["experts_gate"], p["experts_up"], p["experts_down"]))
    if m["shared"]:
        y = y + gated(u, p["shared_gate"], p["shared_up"], p["shared_down"], mode)
    return c.act_round(y, mode), choice


def layer(p: Dict, beta, x, dense_mlp: bool, b: Dict, m: Dict, mode: str):
    u = c.act_round(rms_norm(x, p["attn_norm"], m["eps"]), mode)
    x = c.act_round(x + mla(p, u, b, m, mode), mode)
    u = c.act_round(rms_norm(x, p["mlp_norm"], m["eps"]), mode)
    if dense_mlp:
        return c.act_round(x + gated(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], mode), mode), None
    y, choice = experts(p, beta, u, b, m, mode)
    return c.act_round(x + y, mode), choice


def follows(b: Dict, ahead: int):
    """Real nodes whose node ``ahead`` places on is real and in their graph."""
    w = (jnp.roll(b["node_graph"], -ahead) == b["node_graph"]) & (jnp.roll(b["node_w"], -ahead) > 0) & (
        b["node_w"] > 0)
    return w.at[-ahead:].set(False)


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """-> (final normalised hidden [T, D], [expert layers, T, k] choices in
    ``bias_names`` order, the module's hidden or None)."""
    m = _dims(arch)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    b = dict(b, positions=positions(b["node_graph"]))
    emb = c.act_round(c._round(params["embedding"], mode).T[ids], mode)
    zero_bias = jnp.zeros((m["E"],), jnp.float32)
    choices = []

    def run(x, name, bias):
        beta = zero_bias if bias is None else (buffers or {}).get(bias, zero_bias)
        step = jax.checkpoint(lambda p, x_, beta_: layer(p, beta_, x_, bias is None, b, m, mode))
        x, choice = step(params[name], x, beta)
        if choice is not None:
            choices.append(choice)
        return x

    x = emb
    for name, bias in layer_names(m)[:m["layers"]]:
        x = run(x, name, bias)
    h_mtp = None
    if m["mtp"]:
        e_next = jnp.where(follows(b, 1)[:, None], jnp.roll(emb, -1, axis=0), 0.0)
        joined = jnp.concatenate([rms_norm(e_next, params["mtp_enorm"], m["eps"]),
                                  rms_norm(x, params["mtp_hnorm"], m["eps"])], axis=-1)
        h = c.act_round(c.dense(c.act_round(joined, mode), params["mtp_proj"], None, mode), mode)
        h_mtp = c.act_round(rms_norm(run(h, "mtp_layer", "router_bias_mtp"), params["mtp_final_norm"], m["eps"]), mode)
    return c.act_round(rms_norm(x, params["final_norm"], m["eps"]), mode), jnp.stack(choices), h_mtp


def balance(buffers: Dict, loads, arch: dict) -> Dict:
    """(B1) the balancing rule: ``loads [expert layers, experts]`` of one
    training step move each layer's bias by the gain times the load's
    shortfall against the mean load, as a share of the mean."""
    mean = jnp.mean(loads, axis=1, keepdims=True)
    step = BIAS_GAIN * (mean - loads) / jnp.maximum(mean, 1.0)
    return {name: buffers[name] + step[i] for i, name in enumerate(bias_names(arch))}


def loss_fn(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    return loss_and_loads(params, b, arch, mode, buffers)[0]


def cross_entropy_sum(h, head, targets, w, mode: str):
    """sum of w * (logsumexp(h @ head) - logit[target]), a block of rows at a
    time."""
    block = min(LOSS_BLOCK, h.shape[0])
    pad = (-h.shape[0]) % block
    padded = lambda a: jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a

    def one(args):
        hb, tb, wb = args
        logits = c.dense(hb, head, None, mode).astype(jnp.float32)
        return jnp.sum(wb * (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]))

    blocks = lambda a: padded(a).reshape((-1, block) + a.shape[1:])
    return jnp.sum(jax.lax.map(jax.checkpoint(one), (blocks(h), blocks(targets), blocks(w))))


def loss_and_loads(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """(B3) (sum of next-token cross-entropies + lambda x sum of the module's
    two-ahead cross-entropies) / (token, next token) pairs, both through the
    untied head; and every expert's load in every expert layer ``[expert
    layers, experts]`` on the real tokens."""
    m = _dims(arch)
    h, choices, h_mtp = forward(params, b, arch, mode, buffers)
    k = choices.shape[-1]
    loads = jax.vmap(lambda ch: jnp.zeros((m["E"],), jnp.float32).at[ch.reshape(-1)].add(
        jnp.repeat(b["node_w"], k)))(choices)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    w1 = follows(b, 1).astype(jnp.float32)
    total = cross_entropy_sum(h, params["head"], jnp.roll(ids, -1), w1, mode)
    if h_mtp is not None:
        w2 = follows(b, 2).astype(jnp.float32)
        total = total + m["lam"] * cross_entropy_sum(h_mtp, params["head"], jnp.roll(ids, -2), w2, mode)
    return total / jnp.maximum(jnp.sum(w1), 1.0), jax.lax.stop_gradient(loads)


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float,
                  rows_routed: Optional[float] = None) -> float:
    """Matrix products of one forward pass on REAL tokens; the routed experts
    at the rows computed here (``rows_routed``, summed over layers, where a
    counter gives it; else ``k held / n_routed_experts`` a token); the
    attention's score and value products are left out (they depend on the
    documents' lengths), so a share of the peak from this count reads low,
    never high."""
    m = _dims(arch)
    D, H = m["D"], m["H"]
    attn = 2.0 * (D * m["Rq"] + m["Rq"] * H * (m["dn"] + m["dr"]) + D * (m["Rkv"] + m["dr"])
                  + m["Rkv"] * H * (m["dn"] + m["dv"]) + H * m["dv"] * D)
    blocks = m["layers"] + m["mtp"]
    expert_layers = blocks - m["first"]
    per_token = blocks * attn + m["first"] * 6.0 * D * m["Fd"]
    per_token += expert_layers * (2.0 * D * m["E"] + 6.0 * D * m["F"] * m["shared"])
    per_token += (1 + m["mtp"]) * 2.0 * D * m["V"] + m["mtp"] * 2.0 * (2 * D) * D
    if rows_routed is None:
        rows_routed = nodes * expert_layers * m["k"] * len(m["held"]) / m["E"]
    return nodes * per_token + rows_routed * 6.0 * D * m["F"]
