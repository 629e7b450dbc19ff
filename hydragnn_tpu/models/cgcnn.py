"""CGCNN (crystal graph) convolution.

(reference: hydragnn/models/CGCNNStack.py:20-113 wrapping PyG ``CGConv`` with
aggr='add', batch_norm=False; dimension-preserving, so the config pins
hidden_dim = input_dim unless GPS is on, config_utils.py:80-87.)

x_i' = x_i + sum_j sigmoid(z_ij W_f + b_f) * softplus(z_ij W_s + b_s),
z_ij = [x_i, x_j(, e_ij)].
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from ..ops.segment import segment_sum
from .base import register_conv
from .layers import hoisted_pair_dense


class CGConv(nn.Module):
    output_dim: int  # must equal input dim (dimension-preserving residual)
    edge_dim: int = 0
    sorted_agg: bool = False
    max_in_degree: int = 0

    @nn.compact
    def __call__(self, inv, equiv, batch, train: bool = False):
        # both z-projections distributed over the concat and hoisted before
        # the edge gather (node matmuls on [N, C], not [E, 2C]; same
        # function class as Dense(concat[x_i, x_j, e]))
        def z_proj(name):
            terms = (
                [(f"{name}_edge", batch.edge_attr)]
                if self.edge_dim and batch.edge_attr is not None
                else []
            )
            return hoisted_pair_dense(
                self.output_dim, inv, batch, f"{name}_recv", f"{name}_send",
                terms, sorted_ids=self.sorted_agg,
                max_degree=self.max_in_degree,
            )

        gate = nn.sigmoid(z_proj("gate"))
        core = nn.softplus(z_proj("core"))
        agg = segment_sum(gate * core, batch.receivers, batch.num_nodes,
                          batch.edge_mask, sorted_ids=self.sorted_agg,
                          max_degree=self.max_in_degree)
        return inv + agg, equiv


@register_conv("CGCNN", is_edge_model=True)
def make_cgcnn(cfg, in_dim, out_dim, last_layer):
    return CGConv(output_dim=out_dim, edge_dim=cfg.edge_dim,
                  sorted_agg=cfg.sorted_aggregation,
                  max_in_degree=cfg.max_in_degree)
