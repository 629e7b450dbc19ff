"""Multi-headed encoder/decoder base model (flax).

TPU-native re-design of the reference's ``Base`` torch module
(hydragnn/models/Base.py:31-752): a functional flax module over statically
padded ``GraphBatch``es. Key departures from the reference, chosen for XLA:

- branch selection (``data.dataset_name`` masking, Base.py:486-570) is done as
  *dense* compute-all-branches + ``jnp.where`` select — boolean indexing is a
  dynamic shape, masked select is one fused elementwise op;
- batch norm is the masked variant (padding rows excluded from statistics);
- the conv stack and heads are built from a frozen ``ModelConfig`` so the
  whole model hashes/stages cleanly under ``jax.jit``.

Every conv layer implements ``(inv, equiv, batch, train) -> (inv, equiv)``
mirroring the reference's ``inv_node_feat/equiv_node_feat`` plumbing
(Base.py:452-458).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.graph import GraphBatch
from ..obs.numerics import probe
from ..ops.segment import masked_global_mean_pool
from .layers import MLP, MaskedBatchNorm, get_activation


@dataclasses.dataclass(frozen=True)
class GraphHeadConfig:
    """One graph-level output branch head (reference: output_heads.graph)."""

    num_sharedlayers: int = 2
    dim_sharedlayers: int = 10
    num_headlayers: int = 2
    dim_headlayers: Tuple[int, ...] = (10, 10)


@dataclasses.dataclass(frozen=True)
class NodeHeadConfig:
    """Node-level output head (reference: output_heads.node)."""

    nn_type: str = "mlp"  # mlp | mlp_per_node | conv
    num_headlayers: int = 2
    dim_headlayers: Tuple[int, ...] = (10, 10)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Frozen hyperparameter record driving model construction.

    Field names track the reference's Architecture config keys
    (config_utils.py:25-161) so the JSON surface maps 1:1.
    """

    mpnn_type: str
    input_dim: int
    hidden_dim: int
    num_conv_layers: int
    output_names: Tuple[str, ...]
    output_dim: Tuple[int, ...]
    output_type: Tuple[str, ...]
    task_weights: Tuple[float, ...]
    graph_head: Optional[GraphHeadConfig] = None
    node_head: Optional[NodeHeadConfig] = None
    num_branches: int = 1
    # static per-branch loss balancing (GFM mixture training, mix/balance.py;
    # planted into the Architecture section by the Mixture config section):
    # every graph's loss contribution is weighted by its branch's entry
    # (normalized to mean 1), and branch_loss_metrics adds per-branch loss
    # scalars (`branch<i>` task entries) for the drift monitor
    branch_loss_weights: Optional[Tuple[float, ...]] = None
    branch_loss_metrics: bool = False
    activation: str = "relu"
    loss_function_type: str = "mse"
    # --- GPS global attention
    global_attn_engine: str = ""
    global_attn_type: str = ""
    global_attn_heads: int = 0
    pe_dim: int = 0
    # static bound on nodes per graph (data-derived); >0 lets GPS multihead
    # attention use the per-graph dense [B, Nmax] layout instead of the
    # batch-wide [N, N] mask
    max_nodes_per_graph: int = 0
    # segment-masked Pallas flash attention for GPS global attention
    # (Architecture.use_flash_attention; auto-on for TPU jit targets in
    # config completion): online-softmax tiling over the flat node array,
    # cross-graph tiles never visited, logits never in HBM
    # (ops/pallas_flash_attention.py). Consumed by the multihead and ring
    # attention types; the dense layouts stay as the equivalence oracle.
    use_flash_attention: bool = False
    dropout: float = 0.25
    # --- geometry / radial basis
    edge_dim: int = 0
    radius: Optional[float] = None
    num_gaussians: Optional[int] = None
    num_filters: Optional[int] = None
    num_radial: Optional[int] = None
    num_spherical: Optional[int] = None
    envelope_exponent: Optional[int] = None
    radial_type: Optional[str] = None
    distance_transform: Optional[str] = None
    basis_emb_size: Optional[int] = None
    int_emb_size: Optional[int] = None
    out_emb_size: Optional[int] = None
    num_before_skip: Optional[int] = None
    num_after_skip: Optional[int] = None
    # --- PNA / MACE
    pna_deg: Tuple[int, ...] = ()
    avg_num_neighbors: Optional[float] = None
    max_ell: Optional[int] = None
    node_max_ell: Optional[int] = None
    correlation: Optional[int] = None
    # --- misc
    equivariance: bool = False
    num_nodes: Optional[int] = None
    var_output: bool = False
    conv_checkpointing: bool = False
    freeze_conv_layers: bool = False
    initial_bias: Optional[float] = None
    periodic_boundary_conditions: bool = False
    max_neighbours: Optional[int] = None
    # receiver-sorted edge arrays + static in-degree bound: lets the TPU
    # backend aggregate messages with the Pallas MXU kernel instead of a
    # scatter (ops/segment.py segment_sum; loader sort_edges=True)
    sorted_aggregation: bool = False
    max_in_degree: int = 0
    # fused edge-hot-path Pallas kernels (Architecture.use_fused_edge_kernel;
    # auto-on with sorted aggregation in config completion). Consumed by the
    # EGNN stack's single-consumer messages (gather -> dense -> segment sum,
    # ops/pallas_fused_edge.py) AND by the PNA family's multi-consumer
    # messages through the multi-output moment kernel
    # (ops/pallas_multi_agg.py — one pass emits sum/count/min/max/sumsq, so
    # "four aggregators need [E, C] in HBM" no longer holds). Gated
    # two-projection convs (CGCNN) still materialize messages for their
    # second consumer, so the flag is inert there.
    fused_edge_kernel: bool = False
    # Training.remat_policy (none|dots|names|full): the save rule every
    # remat wrap uses — kernel call sites and the whole-loss
    # conv_checkpointing wrap (ops/remat.py). 'full' = the historical bare
    # jax.checkpoint at every site.
    remat_policy: str = "full"
    # --- decoder seed-robustness knobs (Architecture.decoder_mirror_init /
    # Architecture.decoder_recovery_slope). Defaults are the seed-robust
    # behavior (mirrored (w,-w) decoder init + leaky-ReLU(0.1) decoder hidden
    # activations); set mirror_init=False, recovery_slope=0.0 for exact
    # parity with the reference's plain-ReLU MLP decoders (Base.py:372-392,
    # 692-752). See layers.MLP and docs/MIGRATION.md.
    decoder_mirror_init: bool = True
    decoder_recovery_slope: float = 0.1
    # --- the decoder stack (mpnn_type "ZAYA", models/zaya.py): its own keys,
    # and with them the token head (train/loss.py token_loss) in place of the
    # regression heads
    zaya: Optional[Any] = None
    # --- the second decoder stack (mpnn_type "JOYAI", models/joyai.py)
    joyai: Optional[Any] = None
    # --- the third decoder stack (mpnn_type "AFMOE", models/afmoe.py)
    afmoe: Optional[Any] = None
    # --- the fourth decoder stack (mpnn_type "KEYEVL2", models/keyevl2.py)
    keyevl2: Optional[Any] = None
    # --- the fifth decoder stack (mpnn_type "OURO", models/ouro.py)
    ouro: Optional[Any] = None

    @property
    def decoder(self) -> Optional[Any]:
        """The keys of whichever decoder stack this is, or None."""
        return self.zaya or self.joyai or self.afmoe or self.keyevl2 or self.ouro

    @property
    def num_heads(self) -> int:
        return len(self.output_dim)

    @property
    def normalized_task_weights(self) -> Tuple[float, ...]:
        """Weights normalized by abs-sum (reference: Base.py:112-115)."""
        s = sum(abs(w) for w in self.task_weights)
        return tuple(w / s for w in self.task_weights)

    @property
    def use_edge_attr(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    @property
    def use_global_attn(self) -> bool:
        return bool(self.global_attn_engine)


# conv registry: mpnn_type -> (is_edge_model, ctor(cfg, in_dim, out_dim, last_layer) -> nn.Module)
_CONV_REGISTRY: Dict[str, Tuple[bool, Callable]] = {}


def register_conv(name: str, is_edge_model: bool = False):
    def deco(ctor):
        _CONV_REGISTRY[name] = (is_edge_model, ctor)
        return ctor

    return deco


def get_conv_ctor(name: str):
    try:
        return _CONV_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"Unknown mpnn_type {name!r}; registered: {sorted(_CONV_REGISTRY)}"
        )


def conv_registry() -> Tuple[str, ...]:
    return tuple(sorted(_CONV_REGISTRY))


def _branch_bank(module_cls, num_branches: int, in_axes):
    """A module class lifted over the branch axis: parameters (and running
    batch-norm statistics) gain a leading [num_branches] axis, each branch
    initialized with its own rng (matching the per-branch modules of the
    reference, MultiTaskModelMP.py:172-201). ``in_axes`` follows jax.vmap:
    ``None`` broadcasts an argument to every branch, ``0`` maps a stacked
    per-branch input."""
    return nn.vmap(
        module_cls,
        in_axes=in_axes,
        out_axes=0,
        variable_axes={"params": 0, "batch_stats": 0},
        split_rngs={"params": True, "dropout": True},
        axis_size=num_branches,
    )


class NodeConvHead(nn.Module):
    """One branch's conv-chain node head: hidden convs + output conv, each
    followed by masked batch norm (reference: Base._init_node_conv,
    Base.py:260-341). Lifted over branches by ``_branch_bank``."""

    cfg: "ModelConfig"
    out_dim: int

    @nn.compact
    def __call__(self, x, equiv, batch: GraphBatch, train: bool):
        cfg = self.cfg
        _, ctor = get_conv_ctor(cfg.mpnn_type)
        act = get_activation(cfg.activation)
        nh = cfg.node_head or NodeHeadConfig()
        inv, eq = x, equiv
        in_d = cfg.hidden_dim
        dims = tuple(nh.dim_headlayers) + (self.out_dim,)
        for i, hd in enumerate(dims):
            conv = ctor(cfg, in_d, hd, i == len(dims) - 1)
            inv, eq = conv(inv, eq, batch, train)
            inv = act(MaskedBatchNorm()(inv, batch.node_mask, train))
            in_d = hd
        return inv


class HydraModel(nn.Module):
    """Encoder (conv stack (+GPS)) + multi-head, multi-branch decoders.

    ``__call__(batch, train)`` returns ``{head_name: predictions}`` with graph
    heads shaped [G, d] and node heads [N, d] (padding rows are garbage;
    always reduce with the batch masks). When ``cfg.var_output`` the dict also
    contains ``f"{name}__var"`` entries (reference outputs_var, Base.py:568).
    """

    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        is_edge_model, ctor = get_conv_ctor(cfg.mpnn_type)
        self.is_edge_model = is_edge_model

        embed_dim = cfg.hidden_dim if cfg.use_global_attn else cfg.input_dim
        convs = []
        for i in range(cfg.num_conv_layers):
            in_dim = embed_dim if i == 0 else cfg.hidden_dim
            # Under GPS every conv output must match `channels` (the residual
            # in GPSConv), so width-expanding convs (GAT concat) take their
            # final-layer form; otherwise only the last layer does.
            final_form = cfg.use_global_attn or i == cfg.num_conv_layers - 1
            mpnn = ctor(cfg, in_dim, cfg.hidden_dim, final_form)
            if cfg.use_global_attn:
                from .gps import GPSConv

                mpnn = GPSConv(
                    channels=cfg.hidden_dim,
                    conv=mpnn,
                    heads=cfg.global_attn_heads,
                    dropout=cfg.dropout,
                    attn_type=cfg.global_attn_type or "multihead",
                    max_nodes_per_graph=cfg.max_nodes_per_graph,
                    use_flash_attention=cfg.use_flash_attention,
                    remat_policy=cfg.remat_policy,
                )
            convs.append(mpnn)
        self.graph_convs = convs
        self.feature_layers = [MaskedBatchNorm() for _ in range(cfg.num_conv_layers)]

        # learnable embeddings for GPS (reference: Base.py:160-174)
        if cfg.use_global_attn:
            self.pos_emb = nn.Dense(cfg.hidden_dim, use_bias=False)
            if cfg.input_dim:
                self.node_emb = nn.Dense(cfg.hidden_dim, use_bias=False)
                self.node_lin = nn.Dense(cfg.hidden_dim, use_bias=False)
            if is_edge_model:
                self.rel_pos_emb = nn.Dense(cfg.hidden_dim, use_bias=False)
                if cfg.use_edge_attr:
                    self.edge_emb = nn.Dense(cfg.hidden_dim, use_bias=False)
                    self.edge_lin = nn.Dense(cfg.hidden_dim, use_bias=False)

        # ---- decoders (reference: Base._multihead, Base.py:342-440)
        # Every decoder is a BRANCH BANK: one flax module whose parameter
        # (and batch_stats) leaves carry a leading [num_branches] axis,
        # built with nn.vmap over the branch dimension. Dense decode stays
        # the default (compute all branches + masked select), but the
        # stacked leaves are what makes decoder params/compute shardable
        # over the mesh's `branch` axis (parallel/branch.py — the analog of
        # the reference's MultiTaskModelMP decoder groups,
        # hydragnn/models/MultiTaskModelMP.py:203-230).
        B = cfg.num_branches
        if any(t == "graph" for t in cfg.output_type):
            gh = cfg.graph_head or GraphHeadConfig()
            self.graph_shared = _branch_bank(MLP, B, in_axes=(None,))(
                (gh.dim_sharedlayers,) * gh.num_sharedlayers,
                cfg.activation,
                final_activation=True,
                mirror_init=cfg.decoder_mirror_init,
                recovery_slope=cfg.decoder_recovery_slope,
            )
        heads = []
        for ihead, (t, d) in enumerate(zip(cfg.output_type, cfg.output_dim)):
            out_d = d * (2 if cfg.var_output else 1)
            if t == "graph":
                gh = cfg.graph_head or GraphHeadConfig()
                heads.append(
                    _branch_bank(MLP, B, in_axes=(0,))(
                        tuple(gh.dim_headlayers) + (out_d,),
                        cfg.activation,
                        mirror_init=cfg.decoder_mirror_init,
                        recovery_slope=cfg.decoder_recovery_slope,
                    )
                )
            elif t == "node":
                nh = cfg.node_head or NodeHeadConfig()
                if nh.nn_type in ("mlp", "mlp_per_node"):
                    heads.append(
                        _branch_bank(MLPNode, B, in_axes=(None, None))(
                            output_dim=out_d,
                            hidden_dims=tuple(nh.dim_headlayers),
                            nn_type=nh.nn_type,
                            num_nodes=cfg.num_nodes or 0,
                            activation=cfg.activation,
                            mirror_init=cfg.decoder_mirror_init,
                            recovery_slope=cfg.decoder_recovery_slope,
                        )
                    )
                elif nh.nn_type == "conv":
                    heads.append(
                        _branch_bank(
                            NodeConvHead, B, in_axes=(None, None, None, None)
                        )(cfg=cfg, out_dim=out_d)
                    )
                else:
                    raise ValueError(f"unknown node head type {nh.nn_type!r}")
            else:
                raise ValueError(f"unknown head type {t!r}")
        self.heads_NN = heads

    def _embedding(self, batch: GraphBatch):
        """(reference: Base._embedding, Base.py:217-245)"""
        cfg = self.cfg
        x = batch.x
        edge_attr = batch.edge_attr if cfg.use_edge_attr else None
        if cfg.use_global_attn:
            pe = self.pos_emb(batch.pe)
            if cfg.input_dim:
                pe = self.node_lin(jnp.concatenate([self.node_emb(x), pe], axis=1))
            x = pe
            if self.is_edge_model:
                e = self.rel_pos_emb(batch.rel_pe)
                if cfg.use_edge_attr:
                    e = self.edge_lin(
                        jnp.concatenate([self.edge_emb(batch.edge_attr), e], axis=1)
                    )
                edge_attr = e
        if edge_attr is not None:
            batch = batch.replace(edge_attr=edge_attr)
        return x, batch.pos, batch

    def encode(self, batch: GraphBatch, train: bool = False):
        """Conv stack -> final invariant node features [N, hidden]."""
        cfg = self.cfg
        act = get_activation(cfg.activation)
        inv, equiv, batch = self._embedding(batch)
        # numerics taps (obs/numerics.py): named intermediates for the
        # in-graph layer statistics + NaN provenance drill-down. Exact
        # no-ops (absent from the jaxpr) unless a collection context is
        # active at trace time — i.e. unless Telemetry.numerics is on.
        # Masked: padding rows carry garbage by contract (see class doc).
        probe("embedding", inv, batch.node_mask)
        # Activation rematerialization (the reference's per-conv torch
        # checkpoint, Base.py:459-465) is applied by the training step via
        # jax.checkpoint over the whole loss when cfg.conv_checkpointing.
        for i, (conv, feat_layer) in enumerate(
            zip(self.graph_convs, self.feature_layers)
        ):
            inv, equiv = conv(inv, equiv, batch, train)
            inv = act(feat_layer(inv, batch.node_mask, train))
            probe(f"conv{i}", inv, batch.node_mask)
        return inv, equiv, batch

    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg = self.cfg
        x, equiv, batch = self.encode(batch, train)
        x_graph = masked_global_mean_pool(
            x, batch.node_graph, batch.num_graphs, batch.node_mask
        )
        probe("pooled", x_graph, batch.graph_mask)

        outputs: Dict[str, jnp.ndarray] = {}
        for ihead, (name, t, d) in enumerate(
            zip(cfg.output_names, cfg.output_type, cfg.output_dim)
        ):
            if t == "graph":
                out = self._graph_head(ihead, x_graph, batch.dataset_id)
            else:
                out = self._node_head(ihead, x, equiv, batch, train)
            outputs[name] = out[..., :d]
            probe(
                f"head:{name}",
                outputs[name],
                batch.graph_mask if t == "graph" else batch.node_mask,
            )
            if cfg.var_output:
                outputs[f"{name}__var"] = out[..., d:] ** 2
        return outputs

    def _graph_head(self, ihead, x_graph, dataset_id):
        """Dense all-branch compute + mask select (vs reference's boolean
        indexing per dataset ID, Base.py:495-509). The branch bank computes
        every branch in one vmapped call over stacked [B, ...] params."""
        cfg = self.cfg
        shared = self.graph_shared(x_graph)  # [B, G, ds]
        stacked = self.heads_NN[ihead](shared)  # [B, G, d]
        if cfg.num_branches == 1:
            return stacked[0]
        return jnp.take_along_axis(
            stacked, dataset_id[None, :, None].astype(jnp.int32), axis=0
        )[0]

    def _node_head(self, ihead, x, equiv, batch, train):
        cfg = self.cfg
        nh = cfg.node_head or NodeHeadConfig()
        if nh.nn_type == "conv":
            stacked = self.heads_NN[ihead](x, equiv, batch, train)  # [B, N, d]
        else:
            stacked = self.heads_NN[ihead](x, batch)  # [B, N, d]
        if cfg.num_branches == 1:
            return stacked[0]
        node_ds = batch.dataset_id[batch.node_graph]
        return jnp.take_along_axis(
            stacked, node_ds[None, :, None].astype(jnp.int32), axis=0
        )[0]


class MLPNode(nn.Module):
    """Per-node MLP head (reference: MLPNode, Base.py:692-752).

    ``mlp`` shares one MLP across all nodes; ``mlp_per_node`` keeps one MLP per
    node index (only valid for fixed-size graphs) — implemented as vmapped
    per-node parameter banks.
    """

    output_dim: int
    hidden_dims: Tuple[int, ...]
    nn_type: str
    num_nodes: int
    activation: str = "relu"
    mirror_init: bool = True
    recovery_slope: float = 0.1

    @nn.compact
    def __call__(self, x, batch: GraphBatch):
        feats = tuple(self.hidden_dims) + (self.output_dim,)
        if self.nn_type == "mlp":
            return MLP(feats, self.activation, mirror_init=self.mirror_init,
                       recovery_slope=self.recovery_slope)(x)
        # mlp_per_node: a separate MLP per node position within each graph
        assert self.num_nodes > 0, "mlp_per_node requires fixed graph size"
        node_pos = _node_position_in_graph(batch)
        mlps = nn.vmap(
            MLP,
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )(feats, self.activation, mirror_init=self.mirror_init,
          recovery_slope=self.recovery_slope)
        # evaluate all per-node MLPs on gathered inputs ordered by node pos
        onehot = jax.nn.one_hot(node_pos % self.num_nodes, self.num_nodes, axis=0)
        xs = jnp.einsum("pn,nf->pnf", onehot, x)
        ys = mlps(xs)  # [num_nodes, N, out]
        return jnp.einsum("pn,pnf->nf", onehot, ys)


def _node_position_in_graph(batch: GraphBatch) -> jnp.ndarray:
    """Index of each node within its own graph (0..n_g-1)."""
    n = batch.num_nodes
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jnp.full((batch.num_graphs,), n, jnp.int32)
    seg_start = seg_start.at[batch.node_graph].min(idx, mode="drop")
    return idx - seg_start[batch.node_graph]
