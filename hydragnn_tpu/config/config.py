"""JSON config system: defaults, data-derived completion, merge, save.

Same JSON surface as the reference (four top sections ``Verbosity``,
``Dataset``, ``NeuralNetwork`` {Architecture, Variables_of_interest, Training},
``Visualization``) and the same "config is completed from data" behavior
(reference: hydragnn/utils/input_config_parsing/config_utils.py:25-161).
"""

from __future__ import annotations

import copy
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..data.graph import Graph
from ..data.pipeline import VariablesOfInterest
from ..utils import envflags


def _jit_target_inference() -> tuple:
    """(is_tpu, source): whether jitted steps will run on a TPU, asked of
    the backend JAX actually initialises — never of the environment. A
    machine whose chip did not come up has libtpu importable and
    ``JAX_PLATFORMS`` unset, and JAX lands on ``cpu`` there without
    raising; a completed config saying ``use_sorted_aggregation: true``
    beside a CPU run is the case this rules out (ADVICE r5 #1).

    Touching the backend must not pre-empt the multi-host rendezvous
    (``jax.distributed.initialize`` has to come first), so the rendezvous
    runs here when the entry point has not done it already —
    ``setup_distributed`` is idempotent and a no-op single-process."""
    import jax

    from ..parallel.mesh import setup_distributed

    setup_distributed()
    backend = jax.default_backend()
    return backend == "tpu", f"initialized backend {backend!r}"


# Architecture keys defaulted to None when absent
# (reference: config_utils.py:98-156 one-by-one ifs).
_ARCH_NONE_DEFAULTS = (
    "radius",
    "radial_type",
    "distance_transform",
    "num_gaussians",
    "num_filters",
    "envelope_exponent",
    "num_after_skip",
    "num_before_skip",
    "basis_emb_size",
    "int_emb_size",
    "out_emb_size",
    "num_radial",
    "num_spherical",
    "correlation",
    "max_ell",
    "node_max_ell",
    "initial_bias",
)

EQUIVARIANT_MODELS = ("EGNN", "SchNet", "PNAEq", "PAINN", "MACE")
PNA_MODELS = ("PNA", "PNAPlus", "PNAEq")


def merge_config(base: Dict[str, Any], overlay: Dict[str, Any]) -> Dict[str, Any]:
    """Recursive deep-merge; overlay wins (reference: config_utils.py:380-388)."""
    out = copy.deepcopy(base)
    for k, v in overlay.items():
        if k in out and isinstance(out[k], dict) and isinstance(v, dict):
            out[k] = merge_config(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def degree_histogram(graphs: Sequence[Graph], max_deg: int = 64) -> List[int]:
    """In-degree histogram over all nodes of the dataset, used by PNA scalers
    (reference: gather_deg, graph_samples_checks_and_updates.py:433-490)."""
    hist = np.zeros(max_deg + 1, np.int64)
    top = 0
    for g in graphs:
        deg = np.bincount(g.receivers, minlength=1)
        deg = np.concatenate([deg, np.zeros(g.num_nodes - deg.shape[0], np.int64)])
        h = np.bincount(deg.astype(np.int64), minlength=max_deg + 1)
        if h.shape[0] > hist.shape[0]:
            hist = np.concatenate([hist, np.zeros(h.shape[0] - hist.shape[0], np.int64)])
        hist[: h.shape[0]] += h
        top = max(top, int(deg.max(initial=0)))
    return hist[: top + 1].tolist()


def average_degree(graphs: Sequence[Graph]) -> float:
    """Average in-degree (MACE avg_num_neighbors, reference: model.py:253-276)."""
    e = sum(g.num_edges for g in graphs)
    n = sum(g.num_nodes for g in graphs)
    return float(e) / max(n, 1)


def check_if_graph_size_variable(*datasets: Sequence[Graph]) -> bool:
    """(reference: graph_samples_checks_and_updates.py:32-87)"""
    env = envflags.env_flag("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE")
    if env is not None:
        return env
    sizes = {g.num_nodes for ds in datasets for g in ds}
    return len(sizes) > 1


def voi_from_config(config: Dict[str, Any]) -> VariablesOfInterest:
    """Build the VariablesOfInterest selector from a (completed) config."""
    var = config["NeuralNetwork"]["Variables_of_interest"]
    ds = config.get("Dataset", {})
    node_dims = ds.get("node_features", {}).get("dim", [1])
    graph_dims = ds.get("graph_features", {}).get("dim", [])
    return VariablesOfInterest(
        input_node_features=var["input_node_features"],
        output_names=var["output_names"],
        output_types=var["type"],
        output_index=var["output_index"],
        node_feature_dims=node_dims,
        graph_feature_dims=graph_dims,
    )


def update_config(
    config: Dict[str, Any],
    trainset: Sequence[Graph],
    valset: Sequence[Graph],
    testset: Sequence[Graph],
) -> Dict[str, Any]:
    """Complete a user config from the data, in place of the reference's
    ``update_config`` (config_utils.py:25-161). Returns a new dict.

    Derived fields: input_dim, per-head output dims/types, PNA degree
    histogram, MACE avg_num_neighbors, GPS defaults, edge_dim, ~20 optional
    keys, equivariance checks.
    """
    config = copy.deepcopy(config)
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    var = config["NeuralNetwork"]["Variables_of_interest"]

    # one pass over the datasets: size variability + the static per-graph
    # node bound (the latter lets GPS attention run per-graph dense
    # [B, Nmax, C] instead of batch-wide [N, N] — reference semantics:
    # to_dense_batch in hydragnn/globalAtt/gps.py:125-141)
    sizes = {g.num_nodes for ds in (trainset, valset, testset) for g in ds}
    env = envflags.env_flag("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE")
    graph_size_variable = env if env is not None else len(sizes) > 1
    arch["graph_size_variable"] = graph_size_variable
    arch["max_nodes_per_graph"] = max(sizes, default=0)

    # ---- the decoder stack (models/zaya.py): its keys are validated here,
    # at once; there are no edges to aggregate, so the sorted-aggregation
    # route stays off unless asked for
    if arch["mpnn_type"] == "ZAYA":
        from ..models.zaya import ZayaConfig

        arch.setdefault("cca_time0", 2)
        arch.setdefault("cca_time1", 2)
        arch.setdefault("partial_rotary_factor", 0.5)
        arch.setdefault("rope_theta", 5.0e6)
        arch.setdefault("rms_norm_eps", 1.0e-5)
        arch.setdefault("loss_chunk_rows", 4096)
        if arch.get("num_experts") is not None:
            arch.setdefault("experts_held", list(range(int(arch["num_experts"]))))
        ZayaConfig.from_arch(arch)
        arch.setdefault("use_sorted_aggregation", False)
    if arch["mpnn_type"] == "JOYAI":
        from ..models.joyai import JoyaiConfig

        for key, default in (
                ("rope_theta", 1.0e4), ("rope_interleave", True), ("n_shared_experts", 1),
                ("first_k_dense_replace", 1), ("routed_scaling_factor", 1.0), ("norm_topk_prob", True),
                ("num_nextn_predict_layers", 0), ("mtp_loss_weight", 0.3), ("expert_row_capacity", None),
                ("rms_norm_eps", 1.0e-6), ("loss_chunk_rows", 4096)):
            arch.setdefault(key, default)
        if arch.get("n_routed_experts") is not None:
            arch.setdefault("experts_held", list(range(int(arch["n_routed_experts"]))))
        JoyaiConfig.from_arch(arch)
        arch.setdefault("use_sorted_aggregation", False)
    if arch["mpnn_type"] == "AFMOE":
        from ..models.afmoe import FULL, AfmoeConfig

        for key, default in (
                ("rope_theta", 1.0e4), ("sliding_window", None), ("num_shared_experts", 1),
                ("num_dense_layers", 1), ("route_scale", 1.0), ("route_norm", True),
                ("load_balance_coeff", 0.001), ("mup_enabled", True), ("expert_row_capacity", None),
                ("rms_norm_eps", 1.0e-5), ("loss_chunk_rows", 4096)):
            arch.setdefault(key, default)
        if arch.get("num_conv_layers") is not None:
            arch.setdefault("layer_types", [FULL] * int(arch["num_conv_layers"]))
        if arch.get("num_experts") is not None:
            arch.setdefault("experts_held", list(range(int(arch["num_experts"]))))
        AfmoeConfig.from_arch(arch)
        arch.setdefault("use_sorted_aggregation", False)
    if arch["mpnn_type"] == "KEYEVL2":
        from ..models.keyevl2 import KeyeConfig

        for key, default in (
                ("rope_theta", 1.0e7), ("norm_topk_prob", True), ("expert_row_capacity", None),
                ("indexer_num_kv_heads", 1),
                ("rms_norm_eps", 1.0e-6), ("loss_chunk_rows", 4096)):
            arch.setdefault(key, default)
        if arch.get("num_experts") is not None:
            arch.setdefault("experts_held", list(range(int(arch["num_experts"]))))
        KeyeConfig.from_arch(arch)
        arch.setdefault("use_sorted_aggregation", False)
    if arch["mpnn_type"] == "OURO":
        from ..models.ouro import OuroConfig

        for key, default in (
                ("rope_theta", 1.0e6), ("total_ut_steps", 4), ("rms_norm_eps", 1.0e-6), ("loss_chunk_rows", 4096)):
            arch.setdefault(key, default)
        OuroConfig.from_arch(arch)
        arch.setdefault("use_sorted_aggregation", False)

    # GPS defaults (reference: config_utils.py:40-47)
    arch.setdefault("global_attn_engine", None)
    arch.setdefault("global_attn_type", None)
    arch.setdefault("global_attn_heads", 0)
    arch.setdefault("pe_dim", 0)

    training.setdefault("compute_grad_energy", False)
    # pad-spec bucketing (SURVEY §5.7): >1 builds a SpecLadder in the loaders
    training.setdefault("num_pad_buckets", 4 if graph_size_variable else 1)

    # ---- outputs (reference: update_config_NN_outputs, config_utils.py:219-260)
    voi = voi_from_config(config)
    sample = trainset[0]
    output_dim: List[int] = []
    if training["compute_grad_energy"]:
        # energy-force training: dims taken verbatim from the config
        # (reference: config_utils.py:223-224)
        if "output_dim" not in var:
            raise KeyError(
                "Training.compute_grad_energy requires "
                "Variables_of_interest.output_dim (the nodal-energy head "
                "dims, usually [1]) since they cannot be derived from data"
            )
        output_dim = [int(d) for d in var["output_dim"]]
    else:
        for t, idx in zip(voi.output_types, voi.output_index):
            if t == "graph":
                output_dim.append(int(voi.graph_feature_dims[idx]))
            elif t == "node":
                dim = int(voi.node_feature_dims[idx])
                node_head = arch["output_heads"].get("node", {})
                if isinstance(node_head, list):  # multibranch list form
                    node_head = node_head[0].get("architecture", {}) if node_head else {}
                if not graph_size_variable and node_head.get("type") == "mlp_per_node":
                    dim *= sample.num_nodes
                output_dim.append(dim)
            else:
                raise ValueError(f"output type {t!r} not graph or node")
    arch["output_dim"] = output_dim
    arch["output_type"] = list(voi.output_types)
    arch["num_nodes"] = sample.num_nodes
    var.setdefault("denormalize_output", False)

    arch["input_dim"] = voi.input_dim

    # ---- PNA degree histogram / MACE average degree
    if arch["mpnn_type"] in PNA_MODELS:
        deg = degree_histogram(trainset)
        arch["pna_deg"] = deg
        arch["max_neighbours"] = len(deg) - 1
    else:
        arch["pna_deg"] = None
    if arch["mpnn_type"] == "MACE":
        arch["avg_num_neighbors"] = average_degree(trainset)
    else:
        arch["avg_num_neighbors"] = None

    # ---- Pallas sorted-segment aggregation: static in-degree bound over
    # EVERY split (eval batches must satisfy the cap too; the kernel gives
    # unspecified sums for real segments past it — ops/pallas_segment.py)
    #
    # Default: ON when the initialised backend is a TPU (the route won the
    # r5 A/B at the SC25 shape — history, PERF.md). Non-TPU backends keep the
    # default off: the Pallas route never activates there
    # (ops/segment.py:_pallas_route_enabled) and leaving the edge order
    # unsorted keeps CPU batches byte-stable with earlier rounds.
    # Explicit true/false in the config always wins.
    #
    # Grad-energy configs are INCLUDED since r6: the kernels differentiate
    # through a custom-JVP whose tangent rule is plain jnp
    # (ops/pallas_segment.py, ops/pallas_fused_edge.py), so the
    # energy-force objective's grad-of-grad composes; the r5 first-order
    # custom-VJP guard (which raised here) is gone. fused==dense on the
    # energy+force loss is asserted by tests/test_fused_edge.py and the
    # multichip dryrun (__graft_entry__._dryrun_sorted_agg).
    if "use_sorted_aggregation" not in arch or arch["use_sorted_aggregation"] is None:
        on, source = _jit_target_inference()
        arch["use_sorted_aggregation"] = on
        if on:
            # say which backend flipped the default, so the persisted
            # sorted=true is traceable from the log. stderr: never mixes
            # into stdout protocols.
            print(
                "[hydragnn_tpu.config] use_sorted_aggregation auto-enabled: "
                f"{source}",
                file=sys.stderr,
            )
    if arch.get("use_sorted_aggregation"):
        top = 1
        for g in (*trainset, *valset, *testset):
            if g.num_edges:
                top = max(top, int(np.bincount(np.asarray(g.receivers)).max()))
        supplied = arch.get("max_in_degree")
        if supplied and int(supplied) < top:
            # a stale bound copied from another run would make the kernel
            # silently drop messages — fail loudly instead
            raise ValueError(
                f"max_in_degree={supplied} is below the dataset's actual "
                f"max in-degree {top}; remove the key to auto-measure"
            )
        arch["max_in_degree"] = int(supplied or top)
    arch.setdefault("max_in_degree", 0)

    # ---- fused edge hot path: auto-on wherever sorted aggregation is on —
    # it shares the sorted-receivers + max_in_degree contract and falls
    # back to the identical dense computation off-TPU (ops/segment.py
    # routing), so the flag is safe to carry on any backend. ONE knob, two
    # kernels: EGNN's single-consumer messages ride the gather -> dense ->
    # segment-sum kernel (ops/pallas_fused_edge.py, models/egnn.py); the
    # PNA family's multi-consumer messages ride the multi-output moment
    # kernel (ops/pallas_multi_agg.py, models/pna*.py — one pass emits
    # sum/count/min/max/sumsq, HYDRAGNN_PALLAS_MULTIAGG overrides).
    # Explicit true/false wins for A/B (bench.py BENCH_FUSED / BENCH_PNA).
    if ("use_fused_edge_kernel" not in arch
            or arch["use_fused_edge_kernel"] is None):
        arch["use_fused_edge_kernel"] = bool(arch["use_sorted_aggregation"])
    elif arch["use_fused_edge_kernel"] and not arch["use_sorted_aggregation"]:
        # without receiver-sorted batches + the degree bound the fused path
        # can never engage (models/egnn.py) — a silent no-op here would let
        # an A/B "measure" the fused kernel against itself; fail loudly,
        # mirroring the stale-max_in_degree treatment above
        raise ValueError(
            "use_fused_edge_kernel requires use_sorted_aggregation: the "
            "fused edge kernel rides the sorted-receivers + max_in_degree "
            "contract. Enable use_sorted_aggregation (or drop the explicit "
            "use_fused_edge_kernel, which then follows it automatically)."
        )

    # ---- GPS flash attention (ops/pallas_flash_attention.py): the
    # segment-masked online-softmax kernel for global attention. Auto-on
    # when jitting for TPU and GPS global attention is configured — same
    # inference + logging contract as use_sorted_aggregation above; the
    # dense layouts remain the oracle and the route on every other
    # backend (the model falls back automatically when the kernel cannot
    # engage). NOTE flash configs carry attention-PROB dropout 0 on every
    # backend (the probabilities never exist to mask — models/gps.py);
    # GPSConv's output dropout is unchanged. Explicit true/false wins
    # (bench.py BENCH_GPS A/B cells pin it).
    if "use_flash_attention" not in arch or arch["use_flash_attention"] is None:
        if arch.get("global_attn_engine"):
            on, source = _jit_target_inference()
            arch["use_flash_attention"] = on
            if on:
                # unlike the aggregation kernels this auto-flip is NOT
                # numerics-neutral under training (prob-dropout goes to 0)
                # — say so, so a changed-regularization run is diagnosable
                # from the log
                print(
                    "[hydragnn_tpu.config] use_flash_attention auto-enabled:"
                    f" {source}; NOTE GPS"
                    " attention-prob dropout runs at 0 under this flag"
                    " (Architecture.dropout still drives the module-output"
                    " dropout; set use_flash_attention: false for reference"
                    " prob-dropout semantics)",
                    file=sys.stderr,
                )
        else:
            arch["use_flash_attention"] = False

    # CGCNN keeps hidden dim = input dim without global attention
    # (reference: config_utils.py:80-87)
    if arch["mpnn_type"] == "CGCNN" and not arch["global_attn_engine"]:
        arch["hidden_dim"] = arch["input_dim"]

    for key in _ARCH_NONE_DEFAULTS:
        arch.setdefault(key, None)

    # ---- edge dim (reference: update_config_edge_dim, config_utils.py:190-216)
    # (reference: config_utils.py:190-192 — GAT/PNA included)
    edge_models = (
        "GAT", "PNA", "PNAPlus", "PNAEq", "PAINN", "GPS",
        "CGCNN", "SchNet", "EGNN", "DimeNet", "MACE",
    )
    from ..data.transforms import descriptor_edge_dim

    _edge_dim = descriptor_edge_dim(config.get("Dataset", {}))
    if _edge_dim:
        assert (
            arch["mpnn_type"] in edge_models or arch["global_attn_engine"]
        ), "edge features can only be used with edge-aware models"
        # edge_features columns + Descriptors columns (Spherical: 3, PPF: 4)
        arch["edge_dim"] = _edge_dim
    elif arch["mpnn_type"] == "CGCNN":
        arch["edge_dim"] = 0
    else:
        arch.setdefault("edge_dim", None)

    # ---- equivariance (reference: update_config_equivariance, :164-177)
    if arch.get("equivariance"):
        assert arch["mpnn_type"] in EQUIVARIANT_MODELS, (
            "E(3) equivariance can only be ensured for "
            + ", ".join(EQUIVARIANT_MODELS)
        )
    arch.setdefault("equivariance", False)

    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("activation_function", "relu")
    arch.setdefault("SyncBatchNorm", False)
    arch.setdefault("periodic_boundary_conditions", False)
    arch.setdefault("max_neighbours", None)
    arch.setdefault("num_conv_layers", 1)
    training.setdefault("conv_checkpointing", False)
    # ---- rematerialization policy (docs/PERFORMANCE.md "Multi-aggregate
    # kernel"): which save rule every remat wrap uses — the kernel call
    # sites (fused edge / multi-agg / flash attention) and the whole-loss
    # conv_checkpointing wrap. Default 'full' preserves the historical
    # bare-jax.checkpoint behavior at every site.
    training.setdefault("remat_policy", "full")
    from ..ops.remat import REMAT_POLICIES

    if training["remat_policy"] not in REMAT_POLICIES:
        raise ValueError(
            f"Training.remat_policy {training['remat_policy']!r} must be "
            f"one of {REMAT_POLICIES}"
        )
    training.setdefault("loss_function_type", "mse")
    training.setdefault("batch_size", 32)
    training.setdefault("num_epoch", 1)
    training.setdefault("perc_train", 0.7)
    training.setdefault("patience", 10)
    training.setdefault("EarlyStopping", False)
    training.setdefault("Checkpoint", False)
    training.setdefault("checkpoint_warmup", 0)
    # ---- fault tolerance (docs/ROBUSTNESS.md): the in-graph non-finite
    # step guard's policy + the verified-checkpoint retention chain
    training.setdefault("non_finite_policy", "warn_skip")
    if training["non_finite_policy"] not in ("error", "warn_skip", "rollback"):
        raise ValueError(
            f"Training.non_finite_policy {training['non_finite_policy']!r} "
            "must be 'error', 'warn_skip' or 'rollback'"
        )
    training.setdefault("non_finite_rollback_after", 3)
    training.setdefault("non_finite_lr_backoff", 0.5)
    training.setdefault("non_finite_max_rollbacks", 3)
    # 0 = keep every per-epoch checkpoint (historical behavior); N > 0
    # prunes to the newest N, bounding disk and the corruption-fallback walk
    training.setdefault("checkpoint_retention", 0)
    # ---- compile plane (docs/PERFORMANCE.md "Compile plane"): persistent
    # XLA compilation cache (None = on, false disables; placed by
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/logs/xla_cache —
    # train/compile_plane.py compile_cache_dir), AOT warm-up of the pad-bucket
    # ladder, and the retrace sentinel's response to a trace outside the
    # warmed specialization budget
    training.setdefault("compile_cache_dir", None)
    training.setdefault("precompile", "background")
    from ..train.compile_plane import PRECOMPILE_MODES, RETRACE_POLICIES

    if training["precompile"] not in PRECOMPILE_MODES:
        raise ValueError(
            f"Training.precompile {training['precompile']!r} must be one of "
            f"{PRECOMPILE_MODES}"
        )
    training.setdefault("retrace_policy", "warn")
    if training["retrace_policy"] not in RETRACE_POLICIES:
        raise ValueError(
            f"Training.retrace_policy {training['retrace_policy']!r} must "
            f"be one of {RETRACE_POLICIES}"
        )
    # ---- data plane (docs/ROBUSTNESS.md "Data plane"): what a sample that
    # fails validation (non-finite features, degenerate edges, budget
    # overflow, corrupt bytes) means, and how long the loader's prefetch
    # consumer waits on a silent producer before raising LoaderStallError
    # (0 disables the stall clock; producer DEATH is always detected)
    ds_cfg = config.setdefault("Dataset", {})
    ds_cfg.setdefault("bad_sample_policy", "warn_skip")
    # LapPE eigendecomposition disk cache (data/lappe.py): true (default,
    # ./logs/lappe_cache), false, or an explicit directory;
    # HYDRAGNN_LAPPE_CACHE overrides
    ds_cfg.setdefault("lappe_cache", True)
    from ..data.validate import POLICIES

    if ds_cfg["bad_sample_policy"] not in POLICIES:
        raise ValueError(
            f"Dataset.bad_sample_policy {ds_cfg['bad_sample_policy']!r} "
            f"must be one of {POLICIES}"
        )
    training.setdefault("loader_stall_timeout", 600.0)
    if float(training["loader_stall_timeout"] or 0) < 0:
        raise ValueError(
            "Training.loader_stall_timeout must be >= 0 (seconds; 0 "
            f"disables), got {training['loader_stall_timeout']!r}"
        )
    # ---- double-buffered device staging (ROADMAP #3 H2D overlap): true
    # (default) = a 2-deep background device_put queue, false = inline
    # transfers, an int = that queue depth; HYDRAGNN_DEVICE_PREFETCH wins
    training.setdefault("double_buffer", True)
    db = training["double_buffer"]
    if not isinstance(db, (bool, int)) or (not isinstance(db, bool) and int(db) < 0):
        raise ValueError(
            "Training.double_buffer must be true/false or a queue depth "
            f">= 0, got {db!r}"
        )
    # ---- elastic fleet operation (docs/GFM.md "Multi-host and elastic
    # operation", train/elastic.py): ``enabled`` arms the driver-side
    # coordinator that turns watchdog detections / SIGTERM notices into
    # shrink-grow plans, ``min_hosts`` is the floor below which a shrink is
    # refused (fail the run instead of overloading survivors), ``grace_s``
    # bounds how long a preempted host may checkpoint before it counts as
    # dead. Checkpoint-restart semantics: progress since the coordinated
    # checkpoint is lost, never silently recomputed under a stale layout.
    el = training.setdefault("elastic", {})
    if not isinstance(el, dict):
        raise ValueError(
            f"Training.elastic must be a dict of elastic-fleet keys, got {el!r}"
        )
    el.setdefault("enabled", False)
    el.setdefault("min_hosts", 1)
    el.setdefault("grace_s", 30.0)
    if int(el["min_hosts"]) < 1:
        raise ValueError(
            f"Training.elastic.min_hosts must be >= 1, got {el['min_hosts']!r}"
        )
    if float(el["grace_s"]) < 0:
        raise ValueError(
            "Training.elastic.grace_s must be >= 0 (seconds), got "
            f"{el['grace_s']!r}"
        )
    if training["non_finite_policy"] == "rollback" and not training["Checkpoint"]:
        # rollback restores the last verified checkpoint — without best-val
        # checkpointing only the preemption/end-of-run saves exist, so the
        # first rollback of a fresh run would find nothing to restore
        print(
            "[hydragnn_tpu.config] non_finite_policy=rollback without "
            "Training.Checkpoint: enable checkpointing or the first "
            "rollback of a fresh run will fail with no checkpoint to "
            "restore",
            file=sys.stderr,
        )
    training.setdefault("Optimizer", {"type": "AdamW", "learning_rate": 1e-3})
    training["Optimizer"].setdefault("type", "AdamW")
    training["Optimizer"].setdefault("learning_rate", 1e-3)
    arch.setdefault("task_weights", [1.0] * len(output_dim))
    assert len(arch["task_weights"]) == len(output_dim), (
        f"task_weights {arch['task_weights']} must match number of heads {len(output_dim)}"
    )

    # ---- serving plane (docs/SERVING.md): validate the ``Serving`` section
    # eagerly when present so a typo'd policy fails at load time, not when
    # the server comes up under traffic. The section is optional — absent
    # means "all defaults" and nothing is added to the saved config.
    if config.get("Serving"):
        from ..serve.config import ServeConfig

        ServeConfig.from_config(config)

    # ---- telemetry plane (docs/OBSERVABILITY.md): same eager-validation
    # contract as ``Serving`` — a typo'd Telemetry key/value fails at load
    # time, not after the first epoch has already run unmeasured. Optional:
    # absent means disabled and nothing is added to the saved config; a
    # PRESENT section is completed to its resolved form (defaults filled,
    # unknown keys warned-and-dropped here, ONCE — the loop's later
    # resolve of the completed section is then warning-free).
    if config.get("Telemetry"):
        from ..obs.telemetry import resolve_telemetry

        config["Telemetry"] = resolve_telemetry(config)

    # ---- mixture plane (docs/GFM.md): same eager-validation contract as
    # the sections above; the completed section additionally plants the
    # static per-branch loss-balancing weights into the Architecture so
    # the jitted multibranch step sees them (train/loss.py)
    if config.get("Mixture"):
        from ..mix import branch_loss_weights_from, resolve_mixture
        from ..models.create import num_branches_from

        config["Mixture"] = resolve_mixture(config)
        nb = num_branches_from(arch)
        if nb > 1:
            blw = branch_loss_weights_from(config["Mixture"], nb)
            if blw is not None:
                arch["branch_loss_weights"] = list(blw)
                arch["branch_loss_metrics"] = True

    config.setdefault("Verbosity", {"level": 0})
    config.setdefault("Visualization", {})
    return config


def get_log_name_config(config: Dict[str, Any]) -> str:
    """Human-readable run name from key hyperparameters
    (reference: config_utils.py:314-349, abbreviated)."""
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    return (
        f"{arch['mpnn_type']}"
        f"-r-{arch.get('radius')}"
        f"-ncl-{arch.get('num_conv_layers')}"
        f"-hd-{arch.get('hidden_dim')}"
        f"-ne-{training.get('num_epoch')}"
        f"-lr-{training.get('Optimizer', {}).get('learning_rate')}"
        f"-bs-{training.get('batch_size')}"
    )


def save_config(config: Dict[str, Any], log_name: str, path: str = "./logs") -> str:
    """Dump the completed config next to the run logs
    (reference: config_utils.py:352-358; rank-0 gating is the caller's job)."""
    run_dir = os.path.join(path, log_name)
    os.makedirs(run_dir, exist_ok=True)
    fname = os.path.join(run_dir, "config.json")
    with open(fname, "w") as f:
        json.dump(config, f, indent=2)
    return fname


def load_config(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
