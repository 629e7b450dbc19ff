"""Plain reference of the ZAYA configuration: a decoder language model whose
layer is a compressed-convolutional-attention (CCA) sublayer followed by a
top-1 expert sublayer behind an MLP router, on packed documents (token =
node, document = graph). ``jax.numpy``, float32, every matrix product through
``common.dense`` at ``HIGHEST``; attention as a masked softmax over ``[T, T]``
in query blocks, experts as a loop over the experts held with a mask. No
kernel, nothing of ``hydragnn_tpu``.

Ids are read from ``x[:, 0]``, positions from ``node_graph``. "Previous node"
is ``t - 1`` if it is in ``t``'s graph, else zero.

Source: https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json
(``model_type: zaya``). The config has no key for the items below; they
follow the CCA paper (arXiv:2510.04476) and the ZAYA1 report
(arXiv:2511.17127) as recalled, and are listed in the configuration file's
``assumed``:

(A1) sublayer add ("residual scaling"): x <- (a_r x + b_r) + (a_y y + b_y),
     four learned [D] vectors a sublayer;
(A2) value shift: v_t = [W_v1 u_t ; W_v2 u_{t-1}], each half of the
     key-value latent;
(A3) the second convolution is block-diagonal, one d x d block a head over
     the Hq + Hk heads of [q~ ; k~];
(A4) q-k mean: q_t[h] = c2_t[h] + (q~_t[h] + k~_t[kv(h)]) / 2,
     k_t[i] = c2_t[Lq + i] + (mean of the group's q~_t + k~_t[i]) / 2;
(A5) q <- sqrt(d) q/|q|, k <- tau_i sqrt(d) k/|k| per head, tau a learned
     [Hk] (the temperature's place);
(A6) the router path in float32; s_t = W_d u_t + b_d, and after the first
     layer s_t <- s_t + gamma * s_t(previous layer) ("EDA"), the mixed s
     handed on;
(A7) router MLP: W_3 gelu(W_2 gelu(W_1 RMSNorm(s) + b_1) + b_2), exact gelu,
     softmax over all experts, choice argmax(P + beta) with beta a buffer
     that takes no gradient (zero unless ``buffers`` gives it), gate P[choice];
(A8) the buffer's rule, once a training step, outside the gradient (loss-free
     balancing, arXiv:2408.15664, its proportional variant): beta_e <- beta_e +
     0.01 * (mean load - load_e) / mean load over the loads of all experts on
     the step's real tokens (``balance``).

One departure: ``described_as`` names a mixture-of-depths skip expert; no key
gives it a width or a place, and it is left out.

The expert share: ``arch["experts_held"]`` lists the experts computed here;
the router's softmax is over all ``num_experts``; a token whose expert is not
held gets y = 0 from this sublayer.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from . import common as c

NEG = -1.0e30
QUERY_BLOCK = 1024


def _dims(arch: dict):
    hq, hk, d = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]), int(arch["head_dim"])
    return {
        "D": int(arch["hidden_dim"]), "layers": int(arch["num_conv_layers"]), "Hq": hq, "Hk": hk, "d": d,
        "Lq": hq * d, "Lk": hk * d, "E": int(arch["num_experts"]), "held": [int(e) for e in arch["experts_held"]],
        "F": int(arch["moe_intermediate_size"]), "R": int(arch["router_hidden_size"]),
        "V": int(arch["vocab_size"]), "t0": int(arch["cca_time0"]), "t1": int(arch["cca_time1"]),
        "eps": float(arch["rms_norm_eps"]), "theta": float(arch["rope_theta"]),
        "rot": int(d * float(arch["partial_rotary_factor"])),
    }


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    m = _dims(arch)
    D, Lq, Lk, R, E, F = m["D"], m["Lq"], m["Lk"], m["R"], m["E"], m["F"]
    C, held = Lq + Lk, len(m["held"])
    rows: List[tuple] = [(("params", "embedding"), (D, m["V"]), "lecun")]
    for l in range(m["layers"]):
        p = ("params", f"layers_{l}")
        vec = lambda name, n, kind: (p + (name,), (n,), kind)
        mat = lambda name, *shape: (p + (name,), tuple(shape), "lecun")
        rows += [vec("attn_norm", D, "ones"), mat("cca_q", D, Lq), mat("cca_k", D, Lk),
                 mat("cca_v1", D, Lk // 2), mat("cca_v2", D, Lk // 2),
                 mat("cca_conv1", m["t0"], C), vec("cca_conv1_bias", C, "zeros"),
                 mat("cca_conv2", m["t1"], m["Hq"] + m["Hk"], m["d"], m["d"]), vec("cca_conv2_bias", C, "zeros"),
                 vec("cca_temperature", m["Hk"], "ones"), (p + ("cca_o",), (Lq, D), "gate"),
                 vec("moe_norm", D, "ones")]
        for sub in ("attn", "moe"):
            rows += [vec(f"{sub}_res_scale", D, "ones"), vec(f"{sub}_res_bias", D, "zeros"),
                     vec(f"{sub}_out_scale", D, "ones"), vec(f"{sub}_out_bias", D, "zeros")]
        rows += [mat("router_down", D, R), vec("router_down_bias", R, "zeros"), vec("router_norm", R, "ones"),
                 mat("router_fc1", R, R), vec("router_fc1_bias", R, "zeros"),
                 mat("router_fc2", R, R), vec("router_fc2_bias", R, "zeros"), mat("router_out", R, E)]
        if l > 0:
            rows.append(vec("router_eda", R, "ones"))
        # the two projections that write into the residual stream start near zero
        # (common.py's "gate" kind), so that the stream carries the token and the
        # router spreads its choices; LeCun scale everywhere made every token of a
        # batch pick one expert (PERF.md section 6, PR 29)
        rows += [mat("experts_gate", held, D, F), mat("experts_up", held, D, F),
                 (p + ("experts_down",), (held, F, D), "gate")]
        rows.append((("batch_stats", f"router_bias_{l}"), (E,), "zeros"))
    rows.append((("params", "final_norm"), (D,), "ones"))
    return rows


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def positions(node_graph):
    """0-based index of each node within its graph (graphs contiguous)."""
    n = node_graph.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    g = int(n)  # more segments than graphs: ids are below the node count
    start = jnp.full((g,), n, jnp.int32).at[node_graph].min(idx)
    return idx - start[node_graph]


def shift(a, pos, j: int):
    """a[t - j] where that node is in t's graph, else zero."""
    if j == 0:
        return a
    keep = (pos >= j).reshape((-1,) + (1,) * (a.ndim - 1))
    return jnp.where(keep, jnp.roll(a, j, axis=0), 0.0)


def rope(x, pos, rot: int, theta: float):
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_attention(q, k, v, node_graph, node_w, mode: str):
    """Masked softmax over [T, T], one block of queries at a time. q
    [T, Hq, d], k/v [T, Hk, d]; node i sees the real nodes j <= i of its own
    graph."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    kf, vf = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    idx = jnp.arange(t, dtype=jnp.int32)
    real = node_w > 0

    def one(args):
        qb, ib, gb, rb = args  # [B, Hq, d], [B], [B], [B]
        s = jnp.einsum("ihd,jhd->hij", c._round(qb, mode), c._round(kf, mode), precision=c.HIGHEST) / jnp.sqrt(float(d))
        ok = (gb[:, None] == node_graph[None, :]) & (rb[:, None] & real[None, :]) & (idx[None, :] <= ib[:, None])
        s = jnp.where(ok[None], s, NEG)
        p = jnp.where(ok[None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("hij,jhd->ihd", c._round(p, mode), c._round(vf, mode), precision=c.HIGHEST)

    padded = lambda a, fill: jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]) if pad else a
    blocks = lambda a: a.reshape((-1, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(one), (blocks(padded(q, 0.0)), blocks(padded(idx, 0)),
                                            blocks(padded(node_graph, -1)), blocks(padded(real, False))))
    return c.act_round(out.reshape((-1, hq, d))[:t], mode)


def layer(p: Dict, beta, x, s_prev, first: bool, b: Dict, m: Dict, mode: str):
    D, Hq, Hk, d, Lq, Lk = m["D"], m["Hq"], m["Hk"], m["d"], m["Lq"], m["Lk"]
    pos, t, grp = b["positions"], x.shape[0], Hq // Hk
    dense = lambda a, w, bias=None: c.dense(a, w, bias, mode)
    res = lambda sub, x_, y_: (p[f"{sub}_res_scale"] * x_ + p[f"{sub}_res_bias"]) + (
        p[f"{sub}_out_scale"] * y_ + p[f"{sub}_out_bias"])

    # ---- CCA
    u = c.act_round(rms_norm(x, p["attn_norm"], m["eps"]), mode)
    q_lat, k_lat = dense(u, p["cca_q"]), dense(u, p["cca_k"])
    v = jnp.concatenate([dense(u, p["cca_v1"]), shift(dense(u, p["cca_v2"]), pos, 1)], axis=-1).reshape(t, Hk, d)
    z = jnp.concatenate([q_lat, k_lat], axis=-1)
    c1 = p["cca_conv1_bias"] + sum(p["cca_conv1"][j] * shift(z, pos, j) for j in range(m["t0"]))
    c1 = c.act_round(c1, mode).reshape(t, Hq + Hk, d)
    c2 = p["cca_conv2_bias"] + sum(
        jnp.einsum("thc,hcd->thd", c._round(shift(c1, pos, j), mode), c._round(p["cca_conv2"][j], mode),
                   precision=c.HIGHEST) for j in range(m["t1"])).reshape(t, Lq + Lk)
    c2 = c.act_round(c2, mode)
    qh, kh = q_lat.reshape(t, Hk, grp, d), k_lat.reshape(t, Hk, d)
    q = c2[:, :Lq].reshape(t, Hk, grp, d) + 0.5 * (qh + kh[:, :, None, :])
    k = c2[:, Lq:].reshape(t, Hk, d) + 0.5 * (jnp.mean(qh, axis=2) + kh)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-12) * jnp.sqrt(float(d))
    q = c.act_round(unit(q).reshape(t, Hq, d), mode)
    k = c.act_round(unit(k) * p["cca_temperature"][None, :, None], mode)
    q, k = rope(q, pos, m["rot"], m["theta"]), rope(k, pos, m["rot"], m["theta"])
    o = causal_attention(c.act_round(q, mode), c.act_round(k, mode), v, b["node_graph"], b["node_w"], mode)
    x = c.act_round(res("attn", x, dense(o.reshape(t, Lq), p["cca_o"])), mode)

    # ---- experts; the router in float32 whatever the mode
    u = c.act_round(rms_norm(x, p["moe_norm"], m["eps"]), mode)
    s = c.dense(u, p["router_down"], p["router_down_bias"])
    if not first:
        s = s + p["router_eda"] * s_prev
    h = rms_norm(s, p["router_norm"], m["eps"])
    h = jax.nn.gelu(c.dense(h, p["router_fc1"], p["router_fc1_bias"]), approximate=False)
    h = jax.nn.gelu(c.dense(h, p["router_fc2"], p["router_fc2_bias"]), approximate=False)
    probs = jax.nn.softmax(c.dense(h, p["router_out"]), axis=-1)
    choice = jnp.argmax(probs + jax.lax.stop_gradient(beta), axis=-1)
    gate = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]
    def expert(y_, xs):
        mine, w_gate, w_up, w_down = xs
        hid = c.act_round(jax.nn.silu(dense(u, w_gate)) * dense(u, w_up), mode)
        return jnp.where(mine[:, None], dense(hid, w_down), y_), None

    # a loop over the experts held, each on every row under its mask: one
    # expert's [T, F] arrays alive at a time, forward and backward
    real = b["node_w"] > 0
    masks = jnp.stack([(choice == e) & real for e in m["held"]])
    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        (masks, p["experts_gate"], p["experts_up"], p["experts_down"]))
    y = c.act_round(y * gate[:, None], mode)
    return c.act_round(res("moe", x, y), mode), s, choice


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """-> (final normalised hidden [T, D], [layers, T] expert choices)."""
    m = _dims(arch)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    b = dict(b, positions=positions(b["node_graph"]))
    x = c.act_round(c._round(params["embedding"], mode).T[ids], mode)
    s = jnp.zeros((x.shape[0], m["R"]), jnp.float32)
    choices = []
    for l in range(m["layers"]):
        beta = (buffers or {}).get(f"router_bias_{l}", jnp.zeros((m["E"],), jnp.float32))
        step = jax.checkpoint(lambda p, x_, s_, beta_, l=l: layer(p, beta_, x_, s_, l == 0, b, m, mode))
        x, s, choice = step(params[f"layers_{l}"], x, s, beta)
        choices.append(choice)
    return c.act_round(rms_norm(x, params["final_norm"], m["eps"]), mode), jnp.stack(choices)


BIAS_GAIN = 0.01


def balance(buffers: Dict, loads, arch: dict) -> Dict:
    """(A8) the balancing rule: ``loads [layers, experts]`` of one training
    step move each layer's bias by the gain times the load's shortfall against
    the mean load, as a share of the mean."""
    mean = jnp.mean(loads, axis=1, keepdims=True)
    step = BIAS_GAIN * (mean - loads) / jnp.maximum(mean, 1.0)
    return {f"router_bias_{l}": buffers[f"router_bias_{l}"] + step[l]
            for l in range(int(arch["num_conv_layers"]))}


def loss_fn(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    return loss_and_loads(params, b, arch, mode, buffers)[0]


def loss_and_loads(params: Dict, b: Dict, arch: dict, mode: str = "f32", buffers: Optional[Dict] = None):
    """Mean cross-entropy of the next node's id over the real nodes whose next
    node is in the same graph, logits through the tied embedding; and every
    expert's load in every layer ``[layers, experts]`` on the real tokens."""
    m = _dims(arch)
    h, choices = forward(params, b, arch, mode, buffers)
    loads = jax.vmap(lambda ch: jnp.zeros((m["E"],), jnp.float32).at[ch].add(b["node_w"]))(choices)
    ids = jnp.clip(b["x"][:, 0].astype(jnp.int32), 0, m["V"] - 1)
    nxt = jnp.roll(ids, -1)
    w = (jnp.roll(b["node_graph"], -1) == b["node_graph"]) & (jnp.roll(b["node_w"], -1) > 0) & (b["node_w"] > 0)
    w = w.at[-1].set(False).astype(jnp.float32)
    block = min(QUERY_BLOCK * 4, h.shape[0])
    pad = (-h.shape[0]) % block
    padded = lambda a: jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a

    def one(args):
        hb, tb, wb = args
        logits = c.dense(hb, params["embedding"], None, mode).astype(jnp.float32)
        return jnp.sum(wb * (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]))

    blocks = lambda a: padded(a).reshape((-1, block) + a.shape[1:])
    total = jnp.sum(jax.lax.map(jax.checkpoint(one), (blocks(h), blocks(nxt), blocks(w))))
    return total / jnp.maximum(jnp.sum(w), 1.0), jax.lax.stop_gradient(loads)


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float,
                  rows_routed: Optional[float] = None) -> float:
    """Matrix products of one forward pass on REAL tokens; the experts at the
    rows routed here (``rows_routed``, summed over layers, where a counter
    gives it; else ``held / num_experts`` of the tokens); the attention's
    score and value products are left out (they depend on the documents'
    lengths), so a share of the peak from this count reads low, never high."""
    m = _dims(arch)
    D, Lq, Lk, R, E, F = m["D"], m["Lq"], m["Lk"], m["R"], m["E"], m["F"]
    per_token = 2.0 * D * (Lq + Lk + Lk) + 2.0 * m["t1"] * (m["Hq"] + m["Hk"]) * m["d"] * m["d"] + 2.0 * Lq * D
    per_token += 2.0 * D * R + 4.0 * R * R + 2.0 * R * E
    if rows_routed is None:
        rows_routed = nodes * m["layers"] * len(m["held"]) / E
    return nodes * m["layers"] * per_token + rows_routed * 6.0 * D * F + nodes * 2.0 * D * m["V"]
