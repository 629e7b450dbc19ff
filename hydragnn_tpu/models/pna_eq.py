"""PNAEq: equivariant PNA (PaiNN-style vector channel + PNA scalar aggregation).

TPU re-design of the reference's PNAEqStack (hydragnn/models/PNAEqStack.py:
224-493): scalar messages go through PNA pre-MLP + degree-scaler aggregation,
gated by a Bessel radial projection split three ways (scalar message / vector
gate / edge-vector gate); vector messages aggregate by sum; a PaiNN update
block follows.
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from ..ops.radial import bessel_basis_enveloped, edge_vectors
from ..ops.segment import segment_sum
from .base import register_conv
from .layers import MLP, hoisted_pair_dense
from .painn import _vector_state, painn_update
from .pna import pna_aggregate


class PNAEqConv(nn.Module):
    node_size: int
    deg_hist: tuple
    num_radial: int
    radius: float
    edge_dim: int = 0
    last_layer: bool = False
    sorted_agg: bool = False
    max_in_degree: int = 0
    # multi-output fused aggregation (cfg.fused_edge_kernel): the scalar
    # message here is post-MLP/post-gate (not factorable into the kernel's
    # in-kernel gather), so [E, C] exists once — but the four aggregation
    # moments still fuse into ONE pass over it instead of four separate
    # segment reductions re-reading it (ops/pallas_multi_agg.py)
    multi_agg: bool = False
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, inv, equiv, batch, train: bool = False):
        n = batch.num_nodes
        x = inv
        if x.shape[-1] != self.node_size:
            x = nn.Dense(self.node_size, name="x_proj")(x)
        v = _vector_state(equiv, n, self.node_size)

        vec, length = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                   batch.edge_shifts)
        r = length[:, 0]
        unit = vec / length
        rbf = bessel_basis_enveloped(r, self.radius, self.num_radial)

        # pre-MLP over [x_i, x_j, rbf_emb(, edge)] (PNAEqStack.py:268-344),
        # distributed over the concat and hoisted before the edge gather
        # (node matmuls on [N, C], not [E, 2C]; same function class)
        terms = [("pre_rbf", nn.tanh(nn.Dense(self.node_size)(rbf)))]
        if self.edge_dim and batch.edge_attr is not None:
            terms.append(("pre_attr", nn.Dense(self.node_size)(batch.edge_attr)))
        msg = hoisted_pair_dense(
            self.node_size, x, batch, "pre_recv", "pre_send", terms,
            sorted_ids=self.sorted_agg, max_degree=self.max_in_degree,
        )
        msg = MLP((self.node_size, self.node_size, 3 * self.node_size),
                  "silu")(nn.tanh(msg))
        # Hadamard with rbf projection, then split for scalar/vector duty
        msg = msg * nn.Dense(3 * self.node_size, use_bias=False)(rbf)
        gate_v, gate_edge, msg_s = jnp.split(msg, 3, axis=-1)

        msg_v = v[batch.senders] * gate_v[:, None, :]
        msg_v = msg_v + gate_edge[:, None, :] * unit[:, :, None]
        v = v + segment_sum(msg_v, batch.receivers, n, batch.edge_mask)

        # PNA aggregation of scalar messages (aggregators x scalers)
        scaled = pna_aggregate(msg_s, batch, self.deg_hist,
                               self.sorted_agg, self.max_in_degree,
                               multi_agg=self.multi_agg,
                               remat_policy=self.remat_policy)
        delta = nn.Dense(self.node_size)(jnp.concatenate([x, scaled], axis=-1))
        x = x + delta

        # PaiNN-style update block (PNAEqStack.py:400-470)
        x, v = painn_update(x, v, self.node_size, self.last_layer)
        return x, v


@register_conv("PNAEq", is_edge_model=True)
def make_pna_eq(cfg, in_dim, out_dim, last_layer):
    return PNAEqConv(
        node_size=out_dim,
        deg_hist=cfg.pna_deg,
        num_radial=cfg.num_radial or 5,
        radius=cfg.radius or 5.0,
        edge_dim=cfg.edge_dim,
        last_layer=last_layer,
        sorted_agg=cfg.sorted_aggregation,
        max_in_degree=cfg.max_in_degree,
        multi_agg=cfg.fused_edge_kernel,
        remat_policy=cfg.remat_policy,
    )
