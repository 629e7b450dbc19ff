"""Principal Neighbourhood Aggregation convolution.

(reference: hydragnn/models/PNAStack.py:19-71 wrapping PyG ``PNAConv`` with
aggregators [mean, min, max, std], scalers [identity, amplification,
attenuation, linear], degree histogram from the dataset, pre_layers=1,
post_layers=1, towers=1, divide_input=False.)

Message: pre-MLP over [x_i, x_j(, edge)] -> aggregate 4 ways -> scale by 3
degree scalers (+identity) -> post-MLP over [x_i, scaled] -> out.

The message is kept FACTORED at the call sites — receiver projection
node-sized ([N, C], never gathered by the model), sender projection + edge
terms as one edge-aligned operand — so the multi-output moment kernel
(ops/pallas_multi_agg.py, routed by ``pna_aggregate`` below when
``use_fused_edge_kernel`` rides sorted aggregation) can run the receiver
gather in-kernel and emit all four aggregation moments in one pass: the
[E, C] messages never round-trip HBM. The dense spelling (gather + four
segment reductions) stays as the oracle and the fallback.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..ops.remat import kernel_remat, tag as remat_tag
from ..ops.segment import (
    gather,
    multi_moment_agg,
    segment_count,
    segment_max,
    segment_mean,
    segment_min,
    segment_std,
)
from .base import register_conv
from .layers import pair_message_factored


def _avg_deg_stats(deg_hist: Tuple[int, ...]) -> Tuple[float, float]:
    """(avg_log_deg, avg_lin_deg) from the dataset degree histogram, the
    normalizers PyG precomputes from ``deg``."""
    if not deg_hist:
        return 1.0, 1.0
    total = float(sum(deg_hist)) or 1.0
    avg_log = sum(n * math.log(d + 1) for d, n in enumerate(deg_hist)) / total
    avg_lin = sum(n * d for d, n in enumerate(deg_hist)) / total
    return max(avg_log, 1e-6), max(avg_lin, 1e-6)


def pna_pre_message(dim, inv, batch, edge_terms=()):
    """PNA's pre-MLP (pre_layers=1) in FACTORED form
    (layers.pair_message_factored — the one spelling of the
    recv-bias/send-no-bias convention): the receiver projection stays
    node-sized ([N, C] — gathered in-kernel by the fused route, or by
    ``pna_aggregate``'s dense branch), the sender projection and the
    edge-local terms collapse into one edge-aligned operand. Same
    parameter names and tree as the old ``hoisted_pair_dense`` spelling,
    so checkpoints are interchangeable."""
    return pair_message_factored(
        dim, inv, batch, "pre_recv", "pre_send", edge_terms
    )


def pna_aggregate(msg, batch, deg_hist, sorted_agg=False, max_in_degree=0,
                  node_recv=None, gate=None, multi_agg=False,
                  remat_policy="full"):
    """PNA aggregate-and-scale: [mean,min,max,std] aggregation x
    [identity, amplification, attenuation, linear] degree scalers.
    Shared by PNA / PNAPlus / PNAEq (reference: DegreeScalerAggregation).

    The per-edge message is ``(node_recv[recv] + msg) * gate`` with
    ``node_recv``/``gate`` optional. With ``multi_agg`` (the
    ``use_fused_edge_kernel`` route) on a sorted, degree-bounded batch,
    all four aggregators derive from ONE fused multi-moment pass
    (ops/segment.py ``multi_moment_agg`` -> ops/pallas_multi_agg.py):
    mean = sum/count, std via the zero-clamped E[x²]−E[x]² form — the
    same guard ``segment_std`` applies — and the op is remat-wrapped per
    ``remat_policy`` so the backward recomputes the messages instead of
    storing [E, C] residuals. Otherwise the dense oracle runs: gather +
    the four masked segment reductions, exactly the historical spelling.
    """
    n = batch.num_nodes
    if multi_agg and sorted_agg and max_in_degree > 0:
        def moments(edge_in, nrecv, g):
            return remat_tag(multi_moment_agg(
                edge_in, batch.receivers, n, node_recv=nrecv, gate=g,
                sorted_ids=True, max_degree=max_in_degree,
            ), "multi_agg_moments")

        s, cnt, mn, mx, ssq = kernel_remat(moments, remat_policy)(
            msg, node_recv, gate
        )
        cnt1 = jnp.maximum(cnt, 1.0)[:, None]
        mean = s / cnt1
        var = jnp.maximum(ssq / cnt1 - mean**2, 0.0)
        std = jnp.sqrt(var + 1e-5)
        aggs = [a.astype(msg.dtype) for a in (mean, mn, mx, std)]
        deg = cnt[:, None]
    else:
        if node_recv is not None:
            msg = gather(node_recv, batch.receivers, sorted_agg,
                         max_in_degree) + msg
        if gate is not None:
            msg = msg * gate
        aggs = [
            segment_mean(msg, batch.receivers, n, batch.edge_mask,
                         sorted_ids=sorted_agg, max_degree=max_in_degree),
            segment_min(msg, batch.receivers, n, batch.edge_mask),
            segment_max(msg, batch.receivers, n, batch.edge_mask),
            segment_std(msg, batch.receivers, n, batch.edge_mask),
        ]
        deg = segment_count(batch.receivers, n, batch.edge_mask)[:, None]
    agg = jnp.concatenate(aggs, axis=-1)
    avg_log, avg_lin = _avg_deg_stats(deg_hist)
    log_deg = jnp.log(deg + 1.0)
    return jnp.concatenate(
        [agg, agg * (log_deg / avg_log),
         agg * (avg_log / jnp.maximum(log_deg, 1e-6)),
         agg * (deg / avg_lin)],
        axis=-1,
    )


class PNAConv(nn.Module):
    output_dim: int
    deg_hist: Tuple[int, ...]
    edge_dim: int = 0
    sorted_agg: bool = False
    max_in_degree: int = 0
    # multi-output fused aggregation (cfg.fused_edge_kernel): one Pallas
    # pass emits (sum, count, min, max, sumsq) per node — the r6 "four
    # consumers need [E, C] in HBM" decision record is retired
    multi_agg: bool = False
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, inv, equiv, batch, train: bool = False):
        # pre-MLP (pre_layers=1), factored: node-sized receiver projection
        # + one edge-aligned operand (pna_pre_message; reference computes
        # the same layer post-concat, PNAStack.py)
        f_in = inv.shape[-1]
        terms = (
            [("pre_edge", batch.edge_attr)]
            if self.edge_dim and batch.edge_attr is not None
            else []
        )
        node_recv, edge_in = pna_pre_message(f_in, inv, batch, terms)

        scaled = pna_aggregate(
            edge_in, batch, self.deg_hist, self.sorted_agg,
            self.max_in_degree, node_recv=node_recv,
            multi_agg=self.multi_agg, remat_policy=self.remat_policy,
        )
        # post-MLP, post_layers=1, then final linear projection
        out = nn.Dense(self.output_dim)(jnp.concatenate([inv, scaled], axis=-1))
        out = nn.Dense(self.output_dim)(out)
        return out, equiv


@register_conv("PNA", is_edge_model=True)
def make_pna(cfg, in_dim, out_dim, last_layer):
    return PNAConv(output_dim=out_dim, deg_hist=cfg.pna_deg,
                   edge_dim=cfg.edge_dim, sorted_agg=cfg.sorted_aggregation,
                   max_in_degree=cfg.max_in_degree,
                   multi_agg=cfg.fused_edge_kernel,
                   remat_policy=cfg.remat_policy)
