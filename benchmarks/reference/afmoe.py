"""Plain reference of the Trinity-Mini configuration (``model_type: afmoe``): a
decoder language model whose layers attend within a sliding window or over
the whole document, three to one, each sublayer between two norms, gated
attention with per-head query and key norms, a dense SiLU-gated MLP (the
leading layers) or sigmoid-routed top-k experts beside a shared expert, an
untied head; on packed documents (token = node, document = graph).
``jax.numpy``, float32, every matrix product through ``common.dense`` at
``HIGHEST``; attention as a masked softmax over ``[T, T]`` a block of queries
at a time (blocking: 32 heads x 16,384 x 16,384 float32 scores would be 34
GB), the experts held as a loop with a weight a token. No kernel, no cache,
nothing of ``hydragnn_tpu``; written from the equations of ISSUE 35, which
follow the keys of
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json.

Ids are read from ``x[:, 0]``, positions from ``node_graph``. With ``N(.)``
an RMSNorm at ``rms_norm_eps`` with its own gain, the stream ``x [T, D]``:

- embedding: ``x_0 = sqrt(D) * Emb(t)``;
- layer: ``x <- x + N_2(Attn(N_1(x)))``; ``x <- x + N_4(MLP(N_3(x)))``;
- attention, ``u = N_1(x)``: ``q = W_q u`` -> H heads of d; ``k = W_k u``, ``v =
  W_v u`` -> Hk heads of d (query head h reads key-value head ``h // (H /
  Hk)``); ``q <- N_q(q)``, ``k <- N_k(k)`` over each head's d numbers; on a
  SLIDING layer only, RoPE over the whole head (channel i paired with ``i + d
  / 2``, angle ``pos * theta ** (-2 i / d)``) on q and k, none on a FULL layer;
  scores at ``1/sqrt(d)``; query i sees key j iff same document, ``j <= i`` and,
  on a sliding layer, ``i - j < sliding_window``; ``o = softmax(scores) v``;
  ``y = W_o (o * sigmoid(W_g u))``;
- dense layer: ``W_down (silu(W_gate u) * W_up u)``, ``u = N_3(x)``;
- expert layer: ``s = sigmoid(W_r u)`` over all experts; choice = the k largest
  of ``s + b``; ``g_e = route_scale * s_e / (sum of the chosen s + 1e-20)``;
  ``y = shared(u) + sum over chosen e of g_e expert_e(u)``;
- head: ``logits = W_head N_f(x_L)``; loss = mean next-token cross-entropy over
  the (token, next token) pairs within documents.

Assumed (what the config's keys name without an equation; each in the
configuration file's ``assumed``). T1-T5 are the public ``afmoe`` modeling
code's:

(T1) the attention output is multiplied by ``sigmoid(W_g u)``, ``W_g: D -> H
     d``, elementwise on the concatenated heads, before ``W_o``;
(T2) queries and keys are RMS-normalised per head, gains ``[d]``, before any
     rotation;
(T3) RoPE on the sliding layers only; a full layer carries no position;
(T4) four norms a layer: each sublayer normalised going in and coming out,
     before the residual add;
(T5) ``mup_enabled``: the embedding is multiplied by ``sqrt(hidden_size)``;
(T6) ``load_balance_coeff`` is the rate of loss-free balancing's sign rule
     (arXiv:2408.15664), once a training step outside the gradient:
     ``b_e <- b_e + 0.001 * sign(mean load - load_e)`` over the loads of all
     experts on the step's real tokens (``balance``);
(T7) initial scales: LeCun-normal; what writes into the stream near zero:
     the projections ``attn_o``, ``mlp_down``, ``shared_down``,
     ``experts_down`` (``common.py``'s "gate" kind) as joyai_flash_ep16 has
     them, AND the gains of the two OUTGOING norms a layer, because an
     outgoing norm rescales a near-zero projection to its gain: ``N_2`` and
     ``N_4`` multiply by ``OUT_GAIN + w`` with ``OUT_GAIN`` 0.1 and the
     leaves ``attn_out_norm``, ``mlp_out_norm`` (``w [D]``) zero at the start;
     the router's matrix at LeCun scale on a normalised input (scores spread
     over 0.27 .. 0.73); every other gain 1. ISSUE 35 said "every gain 1":
     read on the chip, that sends the attention's prefix mean to the router
     at the token's own size and the top-8 choice collapses (12 of 34 steps
     overran the row budget).

Departures: none of the mathematics; ``n_group`` 1 / ``topk_group`` 1 make the
group limit empty; ``rope_scaling`` null.

The expert share: ``arch["experts_held"]`` lists the experts computed here;
the router scores all ``num_experts``; a chosen expert that is not held adds
nothing; the shared expert, router and attention are whole.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import common as c

NEG = -1.0e30
# 32 heads x 128 queries x 16,384 keys of float32 scores are 268 MB a block
QUERY_BLOCK = 128
LOSS_BLOCK = 4096
SLIDING = "sliding_attention"
OUT_GAIN = 0.1  # (T7) an outgoing norm's gain is OUT_GAIN + its leaf


def _dims(arch: dict) -> dict:
    i = lambda k: int(arch[k])
    return {
        "D": i("hidden_dim"), "layers": i("num_conv_layers"), "H": i("num_attention_heads"),
        "Hk": i("num_key_value_heads"), "d": i("head_dim"), "theta": float(arch["rope_theta"]),
        "kinds": [str(t) for t in arch["layer_types"]], "W": int(arch["sliding_window"] or 0),
        "Fd": i("intermediate_size"), "F": i("moe_intermediate_size"), "E": i("num_experts"),
        "k": i("num_experts_per_tok"), "shared": i("num_shared_experts"), "first": i("num_dense_layers"),
        "scale": float(arch["route_scale"]), "norm": bool(arch["route_norm"]),
        "rate": float(arch["load_balance_coeff"]), "mup": bool(arch["mup_enabled"]),
        "held": [int(e) for e in arch["experts_held"]], "V": i("vocab_size"), "eps": float(arch["rms_norm_eps"]),
    }


def layer_names(m: dict) -> List[tuple]:
    """(name in the tree, name of its bias buffer or None for a dense layer,
    its sliding window or None for a full layer)."""
    return [(f"layers_{l}", None if l < m["first"] else f"router_bias_{l}",
             m["W"] if m["kinds"][l] == SLIDING else None) for l in range(m["layers"])]


def bias_names(arch: dict) -> List[str]:
    return [b for _, b, _ in layer_names(_dims(arch)) if b]


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    m = _dims(arch)
    D, wide, narrow, held = m["D"], m["H"] * m["d"], m["Hk"] * m["d"], len(m["held"])
    rows: List[tuple] = [(("params", "embedding"), (D, m["V"]), "lecun"), (("params", "head"), (D, m["V"]), "lecun")]
    for name, bias, _ in layer_names(m):
        at = lambda leaf, shape, kind, name=name: (("params", name, leaf), tuple(shape), kind)
        rows += [at(n, (D,), "ones") for n in ("attn_in_norm", "mlp_in_norm")]
        # (T7) the outgoing norms write into the stream: gain OUT_GAIN + w, w zero at the start
        rows += [at(n, (D,), "zeros") for n in ("attn_out_norm", "mlp_out_norm")]
        rows += [at("attn_q", (D, wide), "lecun"), at("attn_k", (D, narrow), "lecun"),
                 at("attn_v", (D, narrow), "lecun"), at("attn_gate", (D, wide), "lecun"),
                 at("attn_o", (wide, D), "gate"),
                 at("attn_q_norm", (m["d"],), "ones"), at("attn_k_norm", (m["d"],), "ones")]
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


def rope_halves(x, pos, theta: float):
    """RoPE over all of ``x [T, H, d]``'s channels, channel i paired with
    ``i + d / 2``, angle ``pos * theta ** (-2 i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lo, hi = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def causal_attention(q, k, v, node_graph, node_w, window: Optional[int], mode: str):
    """Masked softmax over [T, T], one block of queries at a time. q [T, H, d],
    k, v [T, Hk, d]; query head h reads key-value head ``h // (H / Hk)``; node
    i sees the real nodes j <= i of its own graph, under ``window`` only those
    with ``i - j < window``; scale 1/sqrt(d)."""
    t, h, d = q.shape
    hk = k.shape[1]
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    idx = jnp.arange(t, dtype=jnp.int32)
    real = node_w > 0
    kr, vr = c._round(k, mode), c._round(v, mode)

    def one(args):
        qb, ib, gb, rb = args
        qg = c._round(qb, mode).reshape(qb.shape[0], hk, h // hk, d)
        s = jnp.einsum("ikgd,jkd->kgij", qg, kr, precision=c.HIGHEST) / jnp.sqrt(float(d))
        ok = (gb[:, None] == node_graph[None, :]) & (rb[:, None] & real[None, :]) & (idx[None, :] <= ib[:, None])
        if window is not None:
            ok = ok & (ib[:, None] - idx[None, :] < window)
        s = jnp.where(ok[None, None], s, NEG)
        p = jnp.where(ok[None, None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("kgij,jkd->ikgd", c._round(p, mode), vr, precision=c.HIGHEST).reshape(qb.shape)

    padded = lambda a, fill: jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]) if pad else a
    blocks = lambda a: a.reshape((-1, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(one), (blocks(padded(q, 0.0)), blocks(padded(idx, 0)),
                                            blocks(padded(node_graph, -1)), blocks(padded(real, False))))
    return c.act_round(out.reshape((-1, h, d))[:t], mode)


def attention(p: Dict, u, b: Dict, m: Dict, window: Optional[int], mode: str):
    t, H, Hk, d = u.shape[0], m["H"], m["Hk"], m["d"]
    dense = lambda a, w: c.dense(a, w, None, mode)
    q = rms_norm(dense(u, p["attn_q"]).reshape(t, H, d), p["attn_q_norm"], m["eps"])
    k = rms_norm(dense(u, p["attn_k"]).reshape(t, Hk, d), p["attn_k_norm"], m["eps"])
    v = dense(u, p["attn_v"]).reshape(t, Hk, d)
    if window is not None:  # (T3) a sliding layer rotates, a full layer does not
        q, k = rope_halves(q, b["positions"], m["theta"]), rope_halves(k, b["positions"], m["theta"])
    o = causal_attention(c.act_round(q, mode), c.act_round(k, mode), v, b["node_graph"], b["node_w"], window, mode)
    gated = c.act_round(o.reshape(t, H * d) * jax.nn.sigmoid(dense(u, p["attn_gate"])), mode)
    return dense(gated, p["attn_o"])


def gated(u, w_gate, w_up, w_down, mode: str):
    hid = c.act_round(jax.nn.silu(c.dense(u, w_gate, None, mode)) * c.dense(u, w_up, None, mode), mode)
    return c.dense(hid, w_down, None, mode)


def experts(p: Dict, beta, u, b: Dict, m: Dict, mode: str):
    """-> (y [T, D], choice [T, k]); the router in float32 whatever the mode."""
    s = jax.nn.sigmoid(c.dense(u, p["router"]))
    _, choice = jax.lax.top_k(s + jax.lax.stop_gradient(beta), m["k"])
    gate = jnp.take_along_axis(s, choice, axis=-1)
    if m["norm"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = m["scale"] * gate
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


def layer(p: Dict, beta, x, dense_mlp: bool, window: Optional[int], b: Dict, m: Dict, mode: str):
    norm = lambda a, name: c.act_round(rms_norm(a, p[name], m["eps"]), mode)
    norm_out = lambda a, name: c.act_round(rms_norm(c.act_round(a, mode), OUT_GAIN + p[name], m["eps"]), mode)
    y = attention(p, norm(x, "attn_in_norm"), b, m, window, mode)
    x = c.act_round(x + norm_out(y, "attn_out_norm"), mode)
    u = norm(x, "mlp_in_norm")
    if dense_mlp:
        y, choice = gated(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], mode), None
    else:
        y, choice = experts(p, beta, u, b, m, mode)
    return c.act_round(x + norm_out(y, "mlp_out_norm"), mode), choice


def follows(b: Dict, ahead: int):
    """Real nodes whose node ``ahead`` places on is real and in their graph."""
    w = (jnp.roll(b["node_graph"], -ahead) == b["node_graph"]) & (jnp.roll(b["node_w"], -ahead) > 0) & (
        b["node_w"] > 0)
    return w.at[-ahead:].set(False)


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """-> (final normalised hidden [T, D], [expert layers, T, k] choices in
    ``bias_names`` order)."""
    m = _dims(arch)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    b = dict(b, positions=positions(b["node_graph"]))
    x = c._round(params["embedding"], mode).T[ids]
    if m["mup"]:
        x = x * jnp.sqrt(float(m["D"]))
    x = c.act_round(x, mode)
    zero_bias = jnp.zeros((m["E"],), jnp.float32)
    choices = []
    for name, bias, window in layer_names(m):
        beta = zero_bias if bias is None else (buffers or {}).get(bias, zero_bias)
        step = jax.checkpoint(lambda p, x_, beta_, bias=bias, window=window: layer(
            p, beta_, x_, bias is None, window, b, m, mode))
        x, choice = step(params[name], x, beta)
        if choice is not None:
            choices.append(choice)
    return c.act_round(rms_norm(x, params["final_norm"], m["eps"]), mode), jnp.stack(choices)


def balance(buffers: Dict, loads, arch: dict) -> Dict:
    """(T6) the sign rule: ``loads [expert layers, experts]`` of one training
    step move each layer's bias by the rate towards the mean load."""
    step = float(arch["load_balance_coeff"]) * jnp.sign(jnp.mean(loads, axis=1, keepdims=True) - loads)
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
    """The mean next-token cross-entropy over the (token, next token) pairs
    within documents, through the untied head; and every expert's load in
    every expert layer ``[expert layers, experts]`` on the real tokens."""
    m = _dims(arch)
    h, choices = forward(params, b, arch, mode, buffers)
    k = choices.shape[-1]
    loads = jax.vmap(lambda ch: jnp.zeros((m["E"],), jnp.float32).at[ch.reshape(-1)].add(
        jnp.repeat(b["node_w"], k)))(choices)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    w1 = follows(b, 1).astype(jnp.float32)
    total = cross_entropy_sum(h, params["head"], jnp.roll(ids, -1), w1, mode)
    return total / jnp.maximum(jnp.sum(w1), 1.0), jax.lax.stop_gradient(loads)


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float,
                  rows_routed: Optional[float] = None) -> float:
    """Matrix products of one forward pass on REAL tokens; the routed experts
    at the rows computed here (``rows_routed``, summed over layers, where a
    counter gives it; else ``k held / num_experts`` a token); the attention's
    score and value products are left out (they depend on the documents'
    lengths and on each layer's window), so a share of the peak from this
    count reads low, never high."""
    m = _dims(arch)
    D, wide, narrow = m["D"], m["H"] * m["d"], m["Hk"] * m["d"]
    attn = 2.0 * D * (2 * wide + 2 * narrow) + 2.0 * wide * D
    expert_layers = m["layers"] - m["first"]
    per_token = m["layers"] * attn + m["first"] * 6.0 * D * m["Fd"]
    per_token += expert_layers * (2.0 * D * m["E"] + 6.0 * D * m["F"] * m["shared"])
    per_token += 2.0 * D * m["V"]
    if rows_routed is None:
        rows_routed = nodes * expert_layers * m["k"] * len(m["held"]) / m["E"]
    return nodes * per_token + rows_routed * 6.0 * D * m["F"]
