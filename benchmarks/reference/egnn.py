"""Plain reference of the EGNN configuration (E(n)-equivariant graph conv
stack, masked batch norm after every conv, graph head on the mean-pooled
features, node head on the node features, weighted L1 loss).

Follows Satorras et al. 2021 as HydraGNN's EGCLStack spells it: message MLP
over [h_i, h_j, |x_i - x_j|] (first layer distributed over its concat inputs),
sum aggregation, node MLP over [h, agg]; the equivariant layers (all but the
last) displace coordinates along edge vectors normalised by (length + 1),
gated by a tanh-bounded MLP, mean-aggregated and scaled by 3 * coords_range.
Decoder MLPs use leaky ReLU 0.1 and the mirrored first-layer init.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import common as c


def weight_spec(arch: dict, input_dim: int) -> List[tuple]:
    h, layers = int(arch["hidden_dim"]), int(arch["num_conv_layers"])
    rows: List[tuple] = []
    for l in range(layers):
        p = ("params", f"graph_convs_{l}")
        fin = input_dim if l == 0 else h
        equivariant = bool(arch.get("equivariance")) and l != layers - 1
        rows += c.dense_spec(p + ("edge_lin_recv",), fin, h)
        rows += c.dense_spec(p + ("edge_lin_send",), fin, h, bias=False)
        rows += c.dense_spec(p + ("edge_lin_len",), 1, h, bias=False)
        rows += c.dense_spec(p + ("edge_lin2",), h, h)
        node_mlp = "MLP_0"
        if equivariant:
            rows += c.mlp_spec(p + ("MLP_0",), h, (h,))
            rows += c.dense_spec(p + ("Dense_0",), h, 1, bias=False, kind="gate")
            rows.append((p + ("coords_range",), (1,), "ones"))
            node_mlp = "MLP_1"
        rows += c.mlp_spec(p + (node_mlp,), fin + h, (h, h))
        rows.append((("params", f"feature_layers_{l}", "scale"), (h,), "ones"))
        rows.append((("params", f"feature_layers_{l}", "bias"), (h,), "zeros"))
        rows.append((("batch_stats", f"feature_layers_{l}", "mean"), (h,), "zeros"))
        rows.append((("batch_stats", f"feature_layers_{l}", "var"), (h,), "ones"))
        rows.append((("batch_stats", f"feature_layers_{l}", "count"), (), "zeros"))
    gh, nh = arch["output_heads"]["graph"], arch["output_heads"]["node"]
    ds = int(gh["dim_sharedlayers"])
    rows += c.mlp_spec(("params", "graph_shared"), h, (ds,) * int(gh["num_sharedlayers"]),
                       mirror=True, final_activation=True, bank=True)
    rows += c.mlp_spec(("params", "heads_NN_0"), ds, tuple(gh["dim_headlayers"]) + (1,),
                       mirror=True, bank=True)
    rows += c.mlp_spec(("params", "heads_NN_1", "MLP_0"), h, tuple(nh["dim_headlayers"]) + (3,),
                       mirror=True, bank=True)
    return rows


def forward(params: Dict, b: Dict, arch: dict, mode: str = "f32"):
    """-> (graph energy [G, 1], node forces [N, 3]), batch norm in training
    mode (batch statistics over the real rows)."""
    h_layers = int(arch["num_conv_layers"])
    relu = jax.nn.relu
    inv, pos = b["x"], b["pos"]
    if mode != "f32":  # a low-precision step rounds its input channels too
        inv, pos = c.act_round(inv, mode), c.act_round(pos, mode)
    s, r, n = b["senders"], b["receivers"], b["x"].shape[0]
    ew = b["edge_w"][:, None]
    def conv(l, p, f, inv, pos):
        equivariant = bool(arch.get("equivariance")) and l != h_layers - 1
        vec, length = c.edge_geometry(pos, s, r)
        unit = vec / (length + 1.0)
        pre = (
            c.dense(inv, p["edge_lin_recv"]["kernel"], p["edge_lin_recv"]["bias"], mode)[r]
            + c.dense(inv, p["edge_lin_send"]["kernel"], None, mode)[s]
            + c.dense(length, p["edge_lin_len"]["kernel"], None, mode)
        )
        edge_feat = relu(c.dense(relu(pre), p["edge_lin2"]["kernel"], p["edge_lin2"]["bias"], mode))
        node_mlp = "MLP_0"
        if equivariant:
            gate = c.mlp(p["MLP_0"], edge_feat, 1, relu, True, mode)
            coef = jnp.tanh(c.dense(gate, p["Dense_0"]["kernel"], None, mode))
            trans = jnp.clip(unit * coef, -100.0, 100.0) * ew
            count = jnp.maximum(c.segment_sum(b["edge_w"], r, n), 1.0)
            delta = c.segment_sum(trans, r, n) / count[:, None]
            pos = pos + delta * p["coords_range"] * 3.0
            node_mlp = "MLP_1"
        agg = c.segment_sum(edge_feat * ew, r, n)
        out = c.mlp(p[node_mlp], jnp.concatenate([inv, agg], axis=-1), 2, relu, False, mode)
        inv = relu(c.batch_norm_train(out, b["node_w"], f["scale"], f["bias"]))
        return c.act_round(inv, mode), pos

    for l in range(h_layers):
        # one layer's per-edge residuals at a time, so that float32 at the
        # cell's own size fits the chip: recomputed in the backward pass
        inv, pos = jax.checkpoint(conv, static_argnums=0)(
            l, params[f"graph_convs_{l}"], params[f"feature_layers_{l}"], inv, pos)
    g = b["graph_w"].shape[0]
    nodes_per = jnp.maximum(c.segment_sum(b["node_w"], b["node_graph"], g), 1.0)
    pooled = c.segment_sum(inv * b["node_w"][:, None], b["node_graph"], g) / nodes_per[:, None]
    lk = lambda v: c.leaky(v, 0.1)
    gh = arch["output_heads"]["graph"]
    shared = c.mlp(c.unbank(params["graph_shared"]), pooled, int(gh["num_sharedlayers"]), lk, True, mode)
    energy = c.mlp(c.unbank(params["heads_NN_0"]), shared, len(gh["dim_headlayers"]) + 1, lk, False, mode)
    nh = arch["output_heads"]["node"]
    forces = c.mlp(c.unbank(params["heads_NN_1"]["MLP_0"]), inv, len(nh["dim_headlayers"]) + 1, lk, False, mode)
    return energy, forces


def loss_fn(params: Dict, b: Dict, arch: dict, mode: str = "f32"):
    energy, forces = forward(params, b, arch, mode)
    w = [abs(float(x)) for x in arch["task_weights"]]
    w = [x / sum(w) for x in w]
    return (w[0] * c.masked_mae(energy, b["energy"], b["graph_w"])
            + w[1] * c.masked_mae(forces, b["forces"], b["node_w"]))


def forward_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float) -> float:
    """Matrix products of one forward pass (``flops.py`` multiplies by the
    passes a step needs)."""
    from flops import mlp_flops

    h, layers = int(arch["hidden_dim"]), int(arch["num_conv_layers"])
    total = 0.0
    for l in range(layers):
        fin = input_dim if l == 0 else h
        total += 2 * (2.0 * nodes * fin * h)          # receiver and sender projections, node-sized
        total += 2.0 * edges * 1 * h                  # edge length term
        total += 2.0 * edges * h * h                  # second edge layer
        if arch.get("equivariance") and l != layers - 1:
            total += 2.0 * edges * h * h + 2.0 * edges * h  # coordinate gate
        total += mlp_flops(nodes, fin + h, (h, h))    # node MLP
    gh, nh = arch["output_heads"]["graph"], arch["output_heads"]["node"]
    ds = int(gh["dim_sharedlayers"])
    total += mlp_flops(graphs, h, (ds,) * int(gh["num_sharedlayers"]))
    total += mlp_flops(graphs, ds, tuple(gh["dim_headlayers"]) + (1,))
    total += mlp_flops(nodes, h, tuple(nh["dim_headlayers"]) + (3,))
    return total
