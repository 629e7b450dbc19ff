"""Model factory: completed JSON config -> ``HydraModel`` + initial variables.

TPU analog of the reference factory (hydragnn/models/create.py:35-519). The
reference's giant per-model switch with PyG ``Sequential`` arg-strings is
replaced by the conv registry (models/base.py): each model file registers a
constructor; everything else (heads, GPS wrapping, checkpointing) is uniform
in ``HydraModel``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..data.graph import GraphBatch
from .base import (
    GraphHeadConfig,
    HydraModel,
    ModelConfig,
    NodeHeadConfig,
    conv_registry,
)

# import model files for their registry side effects
from . import cgcnn as _cgcnn  # noqa: F401
from . import dimenet as _dimenet  # noqa: F401
from . import egnn as _egnn  # noqa: F401
from . import gat as _gat  # noqa: F401
from . import gin as _gin  # noqa: F401
from . import mfc as _mfc  # noqa: F401
from . import painn as _painn  # noqa: F401
from . import pna as _pna  # noqa: F401
from . import pna_eq as _pna_eq  # noqa: F401
from . import pna_plus as _pna_plus  # noqa: F401
from . import sage as _sage  # noqa: F401
from . import schnet as _schnet  # noqa: F401


def normalize_output_heads(heads: Dict[str, Any]) -> Dict[str, List[Dict[str, Any]]]:
    """Upgrade legacy single-branch head configs to the multibranch list form
    (reference: update_multibranch_heads, hydragnn/utils/model/model.py:152-187)."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    for key, val in heads.items():
        if isinstance(val, list):
            out[key] = val
        else:
            out[key] = [{"type": "branch-0", "architecture": dict(val)}]
    return out


def num_branches_from(arch: Dict[str, Any]) -> int:
    """Branch count as the model factory derives it (list-form graph heads;
    single source of truth for loader routing and model construction)."""
    heads = normalize_output_heads(arch.get("output_heads", {}))
    return len(heads["graph"]) if "graph" in heads else 1


def model_config_from(config: Dict[str, Any]) -> ModelConfig:
    """Build the frozen ModelConfig from a *completed* config dict
    (i.e. after ``hydragnn_tpu.config.update_config``)."""
    nn_cfg = config["NeuralNetwork"]
    arch = nn_cfg["Architecture"]
    training = nn_cfg["Training"]
    var = nn_cfg["Variables_of_interest"]

    heads = normalize_output_heads(arch["output_heads"])
    graph_head = None
    node_head = None
    num_branches = 1
    if "graph" in heads:
        num_branches = len(heads["graph"])
        a = heads["graph"][0]["architecture"]
        graph_head = GraphHeadConfig(
            num_sharedlayers=a.get("num_sharedlayers", 2),
            dim_sharedlayers=a.get("dim_sharedlayers", 10),
            num_headlayers=a.get("num_headlayers", 2),
            dim_headlayers=tuple(a.get("dim_headlayers", (10, 10))),
        )
    if "node" in heads:
        a = heads["node"][0]["architecture"]
        node_head = NodeHeadConfig(
            nn_type=a.get("type", "mlp"),
            num_headlayers=a.get("num_headlayers", 2),
            dim_headlayers=tuple(a.get("dim_headlayers", (10, 10))),
        )

    loss_type = training.get("loss_function_type", "mse")
    return ModelConfig(
        mpnn_type=arch["mpnn_type"],
        input_dim=int(arch["input_dim"]),
        hidden_dim=int(arch["hidden_dim"]),
        num_conv_layers=int(arch["num_conv_layers"]),
        output_names=tuple(var["output_names"]),
        output_dim=tuple(int(d) for d in arch["output_dim"]),
        output_type=tuple(arch["output_type"]),
        task_weights=tuple(float(w) for w in arch["task_weights"]),
        graph_head=graph_head,
        node_head=node_head,
        num_branches=num_branches,
        branch_loss_weights=(
            tuple(float(w) for w in arch["branch_loss_weights"])
            if arch.get("branch_loss_weights")
            else None
        ),
        branch_loss_metrics=bool(arch.get("branch_loss_metrics", False)),
        activation=arch.get("activation_function", "relu"),
        loss_function_type=loss_type,
        global_attn_engine=arch.get("global_attn_engine") or "",
        global_attn_type=arch.get("global_attn_type") or "",
        global_attn_heads=int(arch.get("global_attn_heads") or 0),
        pe_dim=int(arch.get("pe_dim") or 0),
        max_nodes_per_graph=int(arch.get("max_nodes_per_graph") or 0),
        use_flash_attention=bool(arch.get("use_flash_attention", False)),
        # `or 0.25` would turn an intentional 0.0 into the default; only
        # null/absent falls back (the GPSConv/attention dropout rate —
        # bench's GPS A/B cells pin it 0 so the attention route is the
        # only moving part)
        dropout=float(
            0.25 if arch.get("dropout") is None else arch["dropout"]
        ),
        edge_dim=int(arch.get("edge_dim") or 0),
        radius=arch.get("radius"),
        num_gaussians=arch.get("num_gaussians"),
        num_filters=arch.get("num_filters"),
        num_radial=arch.get("num_radial"),
        num_spherical=arch.get("num_spherical"),
        envelope_exponent=arch.get("envelope_exponent"),
        radial_type=arch.get("radial_type"),
        distance_transform=arch.get("distance_transform"),
        basis_emb_size=arch.get("basis_emb_size"),
        int_emb_size=arch.get("int_emb_size"),
        out_emb_size=arch.get("out_emb_size"),
        num_before_skip=arch.get("num_before_skip"),
        num_after_skip=arch.get("num_after_skip"),
        pna_deg=tuple(arch.get("pna_deg") or ()),
        avg_num_neighbors=arch.get("avg_num_neighbors"),
        max_ell=arch.get("max_ell"),
        node_max_ell=arch.get("node_max_ell"),
        correlation=arch.get("correlation"),
        equivariance=bool(arch.get("equivariance", False)),
        num_nodes=arch.get("num_nodes"),
        var_output=loss_type == "GaussianNLLLoss",
        conv_checkpointing=bool(training.get("conv_checkpointing", False)),
        remat_policy=str(training.get("remat_policy", "full")),
        freeze_conv_layers=bool(arch.get("freeze_conv_layers", False)),
        sorted_aggregation=bool(arch.get("use_sorted_aggregation", False)),
        max_in_degree=int(arch.get("max_in_degree") or 0),
        fused_edge_kernel=bool(arch.get("use_fused_edge_kernel", False)),
        decoder_mirror_init=bool(
            True if arch.get("decoder_mirror_init") is None
            else arch["decoder_mirror_init"]
        ),
        # `or 0.1` would turn an intentional 0.0 into 0.1; only null/absent
        # falls back to the default
        decoder_recovery_slope=float(
            0.1 if arch.get("decoder_recovery_slope") is None
            else arch["decoder_recovery_slope"]
        ),
        initial_bias=arch.get("initial_bias"),
        periodic_boundary_conditions=bool(arch.get("periodic_boundary_conditions", False)),
        max_neighbours=arch.get("max_neighbours"),
        zaya=_zaya_config(arch),
        joyai=_joyai_config(arch),
        afmoe=_afmoe_config(arch),
        keyevl2=_keyevl2_config(arch),
        ouro=_ouro_config(arch),
    )


def _zaya_config(arch: Dict[str, Any]):
    if arch["mpnn_type"] != "ZAYA":
        return None
    from .zaya import ZayaConfig

    return ZayaConfig.from_arch(arch)


def _joyai_config(arch: Dict[str, Any]):
    if arch["mpnn_type"] != "JOYAI":
        return None
    from .joyai import JoyaiConfig

    return JoyaiConfig.from_arch(arch)


def _afmoe_config(arch: Dict[str, Any]):
    if arch["mpnn_type"] != "AFMOE":
        return None
    from .afmoe import AfmoeConfig

    return AfmoeConfig.from_arch(arch)


def _keyevl2_config(arch: Dict[str, Any]):
    if arch["mpnn_type"] != "KEYEVL2":
        return None
    from .keyevl2 import KeyeConfig

    return KeyeConfig.from_arch(arch)


def _ouro_config(arch: Dict[str, Any]):
    if arch["mpnn_type"] != "OURO":
        return None
    from .ouro import OuroConfig

    return OuroConfig.from_arch(arch)


def create_model(config: Dict[str, Any]):
    """Completed config dict -> flax model (reference: create_model_config,
    create.py:35-82). MACE gets its own module class because its n-body
    per-layer readout structure replaces the shared encoder/decoder split
    (reference: create.py:473-512 -> MACEStack)."""
    cfg = model_config_from(config)
    if cfg.mpnn_type == "MACE":
        from .mace import MACEModel

        assert cfg.radius is not None, "MACE requires radius"
        assert cfg.num_radial is not None, "MACE requires num_radial"
        assert (cfg.max_ell or 0) >= 1, "MACE requires max_ell >= 1"
        assert (cfg.node_max_ell or 0) >= 1, "MACE requires node_max_ell >= 1"
        assert not cfg.use_global_attn, (
            "GPS global attention is not supported with MACE"
        )
        return MACEModel(cfg=cfg)
    if cfg.mpnn_type == "ZAYA":
        # a decoder stack: no message passing, its own embedding and the
        # token head (models/zaya.py, train/loss.py token_loss)
        from .zaya import ZayaModel

        return ZayaModel(cfg=cfg)
    if cfg.mpnn_type == "JOYAI":
        # the second decoder stack: latent attention, top-k experts beside a
        # shared one, an untied head and a second token loss (models/joyai.py)
        from .joyai import JoyaiModel

        return JoyaiModel(cfg=cfg)
    if cfg.mpnn_type == "AFMOE":
        # the third decoder stack: sliding-window and full attention layers
        # in one stack, gated attention between sandwich norms (models/afmoe.py)
        from .afmoe import AfmoeModel

        return AfmoeModel(cfg=cfg)
    if cfg.mpnn_type == "KEYEVL2":
        # the fourth decoder stack: every layer attends the keys a learned
        # indexer selects, softmax top-k experts (models/keyevl2.py)
        from .keyevl2 import KeyeModel

        return KeyeModel(cfg=cfg)
    if cfg.mpnn_type == "OURO":
        # the fifth decoder stack: the held layers run several times a step
        # under one set of weights, an exit after each pass (models/ouro.py)
        from .ouro import OuroModel

        return OuroModel(cfg=cfg)
    return HydraModel(cfg=cfg)


def init_model(
    model: HydraModel, sample_batch: GraphBatch, seed: int = 0
) -> Dict[str, Any]:
    """Initialize variables deterministically (reference seeds construction
    with torch.manual_seed(0), create.py:131)."""
    rngs = {"params": jax.random.PRNGKey(seed), "dropout": jax.random.PRNGKey(seed + 1)}
    return model.init(rngs, sample_batch, train=False)


def available_models() -> Tuple[str, ...]:
    return conv_registry() + ("MACE", "ZAYA", "JOYAI", "AFMOE", "KEYEVL2", "OURO")
