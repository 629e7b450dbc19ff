"""E(n)-equivariant graph conv (EGNN).

TPU re-design of the reference's EGCLStack (hydragnn/models/EGCLStack.py:175-298):
message MLP over [h_i, h_j, |x_i-x_j| (, e_ij)], sum aggregation, node MLP over
[h, agg]; the equivariant variant also displaces coordinates along normalized
edge vectors gated by a small MLP (tanh-bounded, mean-aggregated).

The coordinate path reads/writes the ``equiv`` slot so stacked layers see the
updated positions (reference recomputes distances from the running ``coord``
each layer). PBC shifts are honored only in the invariant path, matching the
reference's zero-shift override for positional updates (EGCLStack.py:278-281).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from ..ops.radial import edge_vectors
from ..ops.segment import segment_mean, segment_sum
from .base import register_conv
from .layers import MLP, fused_pair_dense_sum, hoisted_pair_dense


def coordinate_displacement(unit, gate_feat, batch, hidden_dim, tanh=False,
                            sorted_agg=False, max_in_degree=0):
    """Mean-aggregated coordinate displacement along (normalized) edge vectors,
    gated by a small MLP whose final layer starts near zero (gain 0.001).
    Shared by EGNN and equivariant SchNet (reference: E_GCL.coord_model,
    EGCLStack.py:263-271; CFConv.coord_model, SCFStack.py:243-254).
    Must be called from inside a ``@nn.compact`` ``__call__``."""
    coef = MLP((hidden_dim,), "relu", final_activation=True)(gate_feat)
    coef = nn.Dense(
        1, use_bias=False,
        kernel_init=nn.initializers.variance_scaling(0.001, "fan_avg", "uniform"),
    )(coef)
    if tanh:
        # bounded displacement with a learnable range (E_GCL tanh mode)
        coef = jnp.tanh(coef)
    trans = jnp.clip(unit * coef, -100.0, 100.0)
    return segment_mean(trans, batch.receivers, batch.num_nodes,
                        batch.edge_mask, sorted_ids=sorted_agg,
                        max_degree=max_in_degree)


class EGCL(nn.Module):
    output_dim: int
    hidden_dim: int
    edge_dim: int = 0
    equivariant: bool = False
    tanh: bool = True
    # Pallas sorted-segment aggregation (cfg.sorted_aggregation)
    sorted_agg: bool = False
    max_in_degree: int = 0
    # fully fused edge hot path (cfg.fused_edge_kernel): gather -> edge
    # dense -> segment sum in one VMEM-resident Pallas kernel
    # (layers.fused_pair_dense_sum). Applies only when the per-edge
    # messages have a SINGLE consumer — the aggregation. Equivariant
    # layers feed edge_feat to the coordinate gate too, so they keep the
    # materialized path (see the ceiling analysis in docs/PERFORMANCE.md).
    fused_edge: bool = False
    # Training.remat_policy save rule at the kernel call site (ops/remat.py)
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, inv, equiv, batch, train: bool = False):
        pos = equiv
        # The reference zeroes PBC shifts inside every E_GCL layer — positional
        # update models have no PBC support (EGCLStack.py:278-281) — so edge
        # vectors come from bare positions for all layers.
        vec, length = edge_vectors(pos, batch.senders, batch.receivers)
        # normalize=True with eps=1.0 (reference E_GCL norm_diff, operations.py)
        unit = vec / (length + 1.0)

        terms = [("edge_lin_len", length)]
        if self.edge_dim and batch.edge_attr is not None:
            terms.append(("edge_lin_attr", batch.edge_attr))

        if (self.fused_edge and self.sorted_agg and self.max_in_degree > 0
                and not self.equivariant):
            # one fused op for the whole edge path — per-edge messages never
            # touch HBM; identical function and parameter tree to the
            # unfused spelling below (asserted by tests/test_fused_edge.py)
            agg = fused_pair_dense_sum(
                self.hidden_dim, inv, batch, "edge_lin_recv",
                "edge_lin_send", "edge_lin2", terms,
                max_in_degree=self.max_in_degree,
                remat_policy=self.remat_policy,
            )
        else:
            # matmul-before-gather first edge-MLP layer
            # (layers.hoisted_pair_dense; reference computes the same layer
            # post-concat, EGCLStack.py:238-247)
            pre = hoisted_pair_dense(
                self.hidden_dim, inv, batch, "edge_lin_recv",
                "edge_lin_send", terms, sorted_ids=self.sorted_agg,
                max_degree=self.max_in_degree,
            )
            act = nn.relu
            edge_feat = act(
                nn.Dense(self.hidden_dim, name="edge_lin2")(act(pre))
            )

            if self.equivariant:
                delta = coordinate_displacement(
                    unit, edge_feat, batch, self.hidden_dim, tanh=self.tanh,
                    sorted_agg=self.sorted_agg,
                    max_in_degree=self.max_in_degree,
                )
                if self.tanh:
                    rng_scale = self.param(
                        "coords_range", nn.initializers.ones, (1,)
                    )
                    delta = delta * rng_scale * 3.0
                pos = pos + delta

            agg = segment_sum(edge_feat, batch.receivers, batch.num_nodes,
                              batch.edge_mask, sorted_ids=self.sorted_agg,
                              max_degree=self.max_in_degree)
        out = MLP((self.hidden_dim, self.output_dim), "relu")(
            jnp.concatenate([inv, agg], axis=-1)
        )
        return out, pos


@register_conv("EGNN", is_edge_model=True)
def make_egnn(cfg, in_dim, out_dim, last_layer):
    return EGCL(
        output_dim=out_dim,
        hidden_dim=cfg.hidden_dim,
        edge_dim=cfg.edge_dim,
        equivariant=cfg.equivariance and not last_layer,
        sorted_agg=cfg.sorted_aggregation,
        max_in_degree=cfg.max_in_degree,
        fused_edge=cfg.fused_edge_kernel,
        remat_policy=cfg.remat_policy,
    )
