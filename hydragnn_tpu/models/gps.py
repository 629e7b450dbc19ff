"""GPS (GraphGPS) global attention layer.

(reference: hydragnn/globalAtt/gps.py:32-159 — local MPNN + residual + norm,
dense-batch global attention via ``to_dense_batch``/``key_padding_mask``, sum
of local+global, 2-layer MLP block, three norms.)

TPU re-design: attention is block-diagonal over graphs. With a static
per-graph node bound ``max_nodes_per_graph`` (data-derived at config
completion, like the reference's ``to_dense_batch`` Nmax) the multihead path
gathers nodes into a per-graph dense ``[G, Nmax, C]`` layout inside jit —
cost G*Nmax^2, matching the reference's per-graph dense attention
(gps.py:125-141) — then scatters back to the flat node array. Shapes stay
static because graphs are laid out contiguously by the batcher. Without the
bound it falls back to one masked attention over the flat padded batch
(cost N^2). The ``performer`` variant exploits the block-diagonal structure
exactly: linear attention's KV moments are segment-sums per graph, giving
O(N) work with no attention matrix at all.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..ops.segment import segment_sum
from .layers import MaskedBatchNorm


class MultiheadSelfAttention(nn.Module):
    """torch.nn.MultiheadAttention equivalent (in-proj QKV, out-proj),
    restricted to same-graph pairs.

    With ``max_nodes_per_graph > 0`` the block-diagonal structure is
    exploited: nodes are gathered per graph into [G, Nmax, H, d] and dense
    attention runs within each graph — B*Nmax^2 work, the reference's
    ``to_dense_batch`` semantics (gps.py:125-141). The gather/scatter indices
    derive from ``node_graph`` alone (graphs are contiguous in the flat
    layout), so everything stays static-shaped under jit. Numerics match the
    flat-masked fallback exactly: every real node attends to exactly the real
    nodes of its own graph either way.

    ``use_flash_attention`` (Architecture.use_flash_attention, auto-on for
    TPU jit targets in config completion) routes the same math through the
    segment-masked Pallas flash kernel (ops/pallas_flash_attention.py):
    online-softmax tiling over the flat node array with a block-sparse
    schedule — cross-graph tiles are never visited and the score matrix
    never touches HBM. The dense layouts below stay as the equivalence
    oracle (and the route wherever the kernel cannot engage:
    ``HYDRAGNN_PALLAS_FLASH=0``, no static node bound, or an attention-prob
    dropout request — the probabilities the dropout would mask never exist
    on the flash path, so flash configs carry prob-dropout 0 on EVERY
    backend; GPSConv's output dropout is unchanged).
    """

    channels: int
    heads: int
    dropout: float = 0.0
    max_nodes_per_graph: int = 0
    use_flash_attention: bool = False
    # Training.remat_policy save rule at the kernel call site (ops/remat.py)
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, x, batch: GraphBatch, train: bool = False):
        H = self.heads
        C = self.channels
        assert C % H == 0, f"channels {C} not divisible by heads {H}"
        d = C // H
        qkv = nn.Dense(3 * C)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        scale = jnp.sqrt(d).astype(x.dtype)

        from ..ops.pallas_flash_attention import _flash_route_enabled

        prob_dropout = self.dropout > 0 and train
        if (
            self.use_flash_attention
            and self.max_nodes_per_graph > 0
            and not prob_dropout
            and _flash_route_enabled()
        ):
            from ..ops.pallas_flash_attention import flash_self_attention

            N = x.shape[0]
            Nmax = self.max_nodes_per_graph
            interpret = jax.default_backend() != "tpu"

            # remat per Training.remat_policy (ops/remat.py; default =
            # bare jax.checkpoint) keeps the tangent rule's residuals
            # (per-graph probability blocks) out of the training forward:
            # the forward stays VMEM-resident, the backward recomputes
            # gathered-dense
            from ..ops.remat import kernel_remat, tag as remat_tag

            def attend(qf, kf, vf):
                return remat_tag(flash_self_attention(
                    qf, kf, vf, batch.node_graph, batch.node_mask,
                    batch.num_graphs, Nmax, interpret=interpret,
                ), "flash_attention_out")

            out = kernel_remat(attend, self.remat_policy)(
                q.reshape(N, H, d), k.reshape(N, H, d), v.reshape(N, H, d)
            ).reshape(N, C)
            # same poison contract as the gathered layout below: a graph
            # past the static bound under-covers its key window — surface
            # as NaN loss, never as silently wrong numbers
            overflow = jnp.any(
                (batch.nodes_per_graph > Nmax) & batch.graph_mask
            )
            out = jnp.where(overflow, jnp.nan, out)
        elif self.max_nodes_per_graph > 0:
            N = x.shape[0]
            G = batch.num_graphs
            Nmax = self.max_nodes_per_graph
            counts = batch.nodes_per_graph  # [G]
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
            )
            slot = jnp.arange(Nmax, dtype=jnp.int32)
            valid = (slot[None, :] < counts[:, None]) & batch.graph_mask[:, None]
            # flat node id of slot r in graph g; invalid slots hit the last
            # node, which the pad spec guarantees is a padding node
            idx = jnp.where(valid, starts[:, None] + slot[None, :], N - 1)
            qg = q[idx].reshape(G, Nmax, H, d)
            kg = k[idx].reshape(G, Nmax, H, d)
            vg = v[idx].reshape(G, Nmax, H, d)
            logits = jnp.einsum("gihd,gjhd->ghij", qg, kg) / scale
            logits = jnp.where(
                valid[:, None, None, :], logits, jnp.finfo(x.dtype).min
            )
            probs = jax.nn.softmax(logits, axis=-1)
            if self.dropout > 0 and train:
                probs = nn.Dropout(self.dropout, deterministic=not train)(probs)
            og = jnp.einsum("ghij,gjhd->gihd", probs, vg).reshape(G * Nmax, C)
            out = jnp.zeros((N, C), x.dtype).at[idx.reshape(-1)].add(
                og * valid.reshape(-1, 1)
            )
            # a real graph larger than the static bound would be silently
            # truncated (its overflow nodes never gathered); poison the output
            # instead so the error surfaces as NaN loss, not wrong numbers
            overflow = jnp.any((counts > Nmax) & batch.graph_mask)
            out = jnp.where(overflow, jnp.nan, out)
        else:
            qf = q.reshape(-1, H, d)
            kf = k.reshape(-1, H, d)
            vf = v.reshape(-1, H, d)
            # same-graph attention mask [N, N]
            same = (batch.node_graph[:, None] == batch.node_graph[None, :]) & (
                batch.node_mask[:, None] & batch.node_mask[None, :]
            )
            logits = jnp.einsum("ihd,jhd->hij", qf, kf) / scale
            logits = jnp.where(same[None], logits, jnp.finfo(x.dtype).min)
            probs = jax.nn.softmax(logits, axis=-1)
            # rows with no valid key (padding nodes) produce uniform garbage;
            # they are masked out downstream.
            if self.dropout > 0 and train:
                probs = nn.Dropout(self.dropout, deterministic=not train)(probs)
            out = jnp.einsum("hij,jhd->ihd", probs, vf).reshape(-1, C)
        return nn.Dense(C)(out)


class RingSelfAttention(nn.Module):
    """Global attention for ONE graph spanning the device mesh
    (``global_attn_type: "ring"``): exact softmax attention with K/V blocks
    ring-rotated over the SP mesh axis (parallel/ring_attention.py), so the
    [N, N] score matrix never materializes on any one chip — node counts are
    bounded by total-mesh HBM, not one chip's (the reference's dense
    per-graph attention requires the whole graph on one device,
    hydragnn/globalAtt/gps.py:125-141).

    Inside a ``parallel.sp.sp_context`` the node axis is sharded and the
    ring runs over ICI; outside one it falls back to the SAME math computed
    densely (one device), so a checkpoint moves freely between modes.
    Restriction: attention spans every real node in the batch (no per-graph
    block mask) — the batch must hold a single real graph, the SP regime.

    With ``use_flash_attention`` the per-chip block-attend inside the ring
    runs the flash kernel's inner loop (ops/pallas_flash_attention.py
    ``flash_block_summary``) instead of a dense einsum: the local
    [n_q, n_k] score block stays in VMEM, and the online-softmax merge
    across ring steps happens in plain jnp (parallel/ring_attention.py).
    """

    channels: int
    heads: int
    use_flash_attention: bool = False

    @nn.compact
    def __call__(self, x, batch: GraphBatch, train: bool = False):
        from ..parallel.sp import current_sp

        H, C = self.heads, self.channels
        d = C // H
        qkv = nn.Dense(3 * C)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(-1, H, d)
        k = k.reshape(-1, H, d)
        v = v.reshape(-1, H, d)
        mesh, axis = current_sp()
        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from ..parallel.ring_attention import ring_self_attention

            use_flash = self.use_flash_attention
            # graftlint: disable=sharding_rules -- ring attention's collective lives with the model's attention math, not the state-placement rule table
            out = shard_map(
                lambda q_, k_, v_, m_: ring_self_attention(
                    q_, k_, v_, m_, axis_name=axis, use_flash=use_flash
                ),
                mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis), P(axis)),
                out_specs=P(axis),
                check_vma=False,
            )(q, k, v, batch.node_mask)
        else:
            # dense fallback: same numbers as the ring (up to reassociation)
            scale = 1.0 / jnp.sqrt(jnp.asarray(d, x.dtype))
            logits = jnp.einsum("ihd,jhd->hij", q, k) * scale
            logits = jnp.where(
                batch.node_mask[None, None, :], logits, jnp.finfo(x.dtype).min
            )
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("hij,jhd->ihd", probs, v)
        # ring attention spans EVERY real node — correct only for a batch
        # holding one real graph (the SP spanning-graph regime). A
        # multi-graph batch would silently mix molecules, so poison the
        # output and let the error surface as NaN loss (the house pattern
        # for silent-wrong-number risks, cf. the Nmax overflow above).
        multi = jnp.sum(batch.graph_mask.astype(jnp.int32)) > 1
        out = jnp.where(multi, jnp.nan, out)
        return nn.Dense(C)(out.reshape(-1, C))


class PerformerSelfAttention(nn.Module):
    """Linear (Performer-style) attention per graph segment.

    (reference option: PyG PerformerAttention, gps.py:62-67.) Uses the relu
    feature map; per-graph KV moments via segment_sum — O(N d^2), no softmax
    matrix. Exact for the block-diagonal same-graph mask.
    """

    channels: int
    heads: int

    @nn.compact
    def __call__(self, x, batch: GraphBatch, train: bool = False):
        H = self.heads
        C = self.channels
        d = C // H
        q = nn.relu(nn.Dense(C)(x)).reshape(-1, H, d) + 1e-6
        k = nn.relu(nn.Dense(C)(x)).reshape(-1, H, d) + 1e-6
        v = nn.Dense(C)(x).reshape(-1, H, d)
        kv = jnp.einsum("nhd,nhe->nhde", k, v)  # [N, H, d, d]
        G = batch.num_graphs
        kv_sum = segment_sum(kv, batch.node_graph, G, batch.node_mask)
        k_sum = segment_sum(k, batch.node_graph, G, batch.node_mask)
        num = jnp.einsum("nhd,nhde->nhe", q, kv_sum[batch.node_graph])
        den = jnp.einsum("nhd,nhd->nh", q, k_sum[batch.node_graph])
        out = num / jnp.maximum(den[..., None], 1e-6)
        return nn.Dense(C)(out.reshape(-1, C))


class GPSConv(nn.Module):
    """(reference: GPSConv.forward, gps.py:103-151)"""

    channels: int
    conv: Optional[Any]
    heads: int = 1
    dropout: float = 0.0
    attn_type: str = "multihead"
    max_nodes_per_graph: int = 0
    use_flash_attention: bool = False
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, inv, equiv, batch: GraphBatch, train: bool = False):
        hs = []
        # local MPNN + dropout + residual + norm1
        if self.conv is not None:
            h, equiv = self.conv(inv, equiv, batch, train)
            h = nn.Dropout(self.dropout, deterministic=not train)(h)
            h = h + inv
            h = MaskedBatchNorm()(h, batch.node_mask, train)
            hs.append(h)

        # global attention + dropout + residual + norm2
        if self.attn_type == "performer":
            h = PerformerSelfAttention(self.channels, self.heads)(inv, batch, train)
        elif self.attn_type == "ring":
            h = RingSelfAttention(
                self.channels,
                self.heads,
                use_flash_attention=self.use_flash_attention,
            )(inv, batch, train)
        elif self.attn_type == "multihead":
            h = MultiheadSelfAttention(
                self.channels,
                self.heads,
                # attention-PROB dropout is incompatible with the flash
                # kernel (the probabilities never exist to mask); flash
                # configs zero it on every backend so the Pallas route and
                # the dense oracle train identically — the module-output
                # dropout below regularizes either way
                0.0 if self.use_flash_attention else self.dropout,
                self.max_nodes_per_graph,
                use_flash_attention=self.use_flash_attention,
                remat_policy=self.remat_policy,
            )(inv, batch, train)
        else:
            raise ValueError(f"attn_type {self.attn_type!r} not supported")
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        h = h + inv
        h = MaskedBatchNorm()(h, batch.node_mask, train)
        hs.append(h)

        out = sum(hs)
        # MLP block + norm3
        mlp = nn.Sequential(
            [
                nn.Dense(2 * self.channels),
                nn.relu,
                nn.Dropout(self.dropout, deterministic=not train),
                nn.Dense(self.channels),
                nn.Dropout(self.dropout, deterministic=not train),
            ]
        )
        out = out + mlp(out)
        out = MaskedBatchNorm()(out, batch.node_mask, train)
        return out, equiv
