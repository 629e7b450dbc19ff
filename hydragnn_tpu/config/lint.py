"""Config migration lint: audit a (reference-style) JSON config against this
framework's config surface.

The JSON surface is intentionally the reference's (SURVEY §2 row 2;
config completion in config/config.py), so most reference configs run
unchanged. This tool makes the remainder explicit instead of silent: for
every key it reports whether it is HANDLED here, NOT-APPLICABLE by design
on TPU (with the equivalent to use instead), a LEGACY reference key with a
direct replacement, or UNKNOWN (likely a typo — unknown keys are otherwise
ignored by config completion, which is how the reference behaves too).

Usage:
    python -m hydragnn_tpu.config.lint path/to/config.json
    >>> from hydragnn_tpu.config.lint import lint_config
    >>> findings = lint_config(json.load(open("config.json")))

Reference key census: union of /root/reference/examples/*/*.json and
tests/inputs/*.json key paths (see docs/MIGRATION.md).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List

# sub-dicts whose members are schema'd elsewhere (heads/optimizer/features)
# or are free-form — lint stops descending at these paths
_OPAQUE = {
    "NeuralNetwork.Architecture.output_heads",
    "NeuralNetwork.Training.Optimizer",
    "NeuralNetwork.Training.Checkpoint",
    # elastic-fleet sub-dict (enabled/min_hosts/grace_s; train/elastic.py)
    "NeuralNetwork.Training.elastic",
    "Dataset.node_features",
    "Dataset.graph_features",
    "Dataset.path",
    "Dataset.synthetic",
    "Dataset.lennard_jones",
    "Dataset.Descriptors",
    "Mixture.weights",
    "Mixture.branch_loss_weights",
    # the resolved rule table api.py records for restore replay
    # (parallel/rules.py table_from_recorded)
    "Parallel.resolved_rules",
    # int8 quantization sub-dict — schema'd strictly by
    # serve/config.py QuantizationSpec.resolve (unknown keys FAIL there)
    "Serving.quantization",
}

# exact key paths this framework consumes (config/config.py completion,
# models/create.py, api.py, train/loop.py, docs/CONFIG.md)
_HANDLED = {
    "Verbosity.level",
    "Dataset.name",
    "Dataset.format",
    "Dataset.path",
    "Dataset.node_features",
    "Dataset.graph_features",
    "Dataset.compositional_stratified_splitting",
    "Dataset.rotational_invariance",
    "Dataset.normalize",
    "Dataset.synthetic",
    "Dataset.lennard_jones",
    "Dataset.bad_sample_policy",
    "Dataset.lappe_cache",
    "Dataset.edge_features",
    "Dataset.Descriptors",
    "Dataset.charge_density_correction",
    "Dataset.mode",
    "NeuralNetwork.Profile",
    "NeuralNetwork.Profile.enable",
    "NeuralNetwork.Profile.target_epoch",
    "NeuralNetwork.Architecture.mpnn_type",
    "NeuralNetwork.Architecture.activation_function",
    "NeuralNetwork.Architecture.equivariance",
    "NeuralNetwork.Architecture.radius",
    "NeuralNetwork.Architecture.max_neighbours",
    "NeuralNetwork.Architecture.periodic_boundary_conditions",
    "NeuralNetwork.Architecture.hidden_dim",
    "NeuralNetwork.Architecture.num_conv_layers",
    "NeuralNetwork.Architecture.output_heads",
    "NeuralNetwork.Architecture.task_weights",
    "NeuralNetwork.Architecture.output_dim",
    "NeuralNetwork.Architecture.output_type",
    "NeuralNetwork.Architecture.input_dim",
    "NeuralNetwork.Architecture.edge_dim",
    "NeuralNetwork.Architecture.edge_features",
    "NeuralNetwork.Architecture.num_nodes",
    "NeuralNetwork.Architecture.pna_deg",
    "NeuralNetwork.Architecture.num_gaussians",
    "NeuralNetwork.Architecture.num_filters",
    "NeuralNetwork.Architecture.num_radial",
    "NeuralNetwork.Architecture.num_spherical",
    "NeuralNetwork.Architecture.envelope_exponent",
    "NeuralNetwork.Architecture.radial_type",
    "NeuralNetwork.Architecture.distance_transform",
    "NeuralNetwork.Architecture.basis_emb_size",
    "NeuralNetwork.Architecture.int_emb_size",
    "NeuralNetwork.Architecture.out_emb_size",
    "NeuralNetwork.Architecture.num_before_skip",
    "NeuralNetwork.Architecture.num_after_skip",
    "NeuralNetwork.Architecture.max_ell",
    "NeuralNetwork.Architecture.node_max_ell",
    "NeuralNetwork.Architecture.correlation",
    "NeuralNetwork.Architecture.avg_num_neighbors",
    "NeuralNetwork.Architecture.global_attn_engine",
    "NeuralNetwork.Architecture.global_attn_type",
    "NeuralNetwork.Architecture.global_attn_heads",
    "NeuralNetwork.Architecture.pe_dim",
    "NeuralNetwork.Architecture.max_nodes_per_graph",
    "NeuralNetwork.Architecture.freeze_conv_layers",
    "NeuralNetwork.Architecture.initial_bias",
    "NeuralNetwork.Architecture.use_sorted_aggregation",
    "NeuralNetwork.Architecture.max_in_degree",
    "NeuralNetwork.Architecture.use_fused_edge_kernel",
    "NeuralNetwork.Architecture.use_flash_attention",
    # the decoder stack (mpnn_type ZAYA, models/zaya.py)
    "NeuralNetwork.Architecture.num_attention_heads",
    "NeuralNetwork.Architecture.num_key_value_heads",
    "NeuralNetwork.Architecture.head_dim",
    "NeuralNetwork.Architecture.cca_time0",
    "NeuralNetwork.Architecture.cca_time1",
    "NeuralNetwork.Architecture.partial_rotary_factor",
    "NeuralNetwork.Architecture.rope_theta",
    "NeuralNetwork.Architecture.num_experts",
    "NeuralNetwork.Architecture.experts_held",
    "NeuralNetwork.Architecture.moe_intermediate_size",
    "NeuralNetwork.Architecture.router_hidden_size",
    "NeuralNetwork.Architecture.vocab_size",
    "NeuralNetwork.Architecture.rms_norm_eps",
    "NeuralNetwork.Architecture.loss_chunk_rows",
    # the second decoder stack (mpnn_type JOYAI, models/joyai.py)
    "NeuralNetwork.Architecture.q_lora_rank",
    "NeuralNetwork.Architecture.kv_lora_rank",
    "NeuralNetwork.Architecture.qk_nope_head_dim",
    "NeuralNetwork.Architecture.qk_rope_head_dim",
    "NeuralNetwork.Architecture.v_head_dim",
    "NeuralNetwork.Architecture.rope_interleave",
    "NeuralNetwork.Architecture.intermediate_size",
    "NeuralNetwork.Architecture.n_routed_experts",
    "NeuralNetwork.Architecture.num_experts_per_tok",
    "NeuralNetwork.Architecture.n_shared_experts",
    "NeuralNetwork.Architecture.first_k_dense_replace",
    "NeuralNetwork.Architecture.routed_scaling_factor",
    "NeuralNetwork.Architecture.norm_topk_prob",
    "NeuralNetwork.Architecture.num_nextn_predict_layers",
    "NeuralNetwork.Architecture.mtp_loss_weight",
    "NeuralNetwork.Architecture.expert_row_capacity",
    # the third decoder stack (mpnn_type AFMOE, models/afmoe.py)
    "NeuralNetwork.Architecture.layer_types",
    "NeuralNetwork.Architecture.sliding_window",
    "NeuralNetwork.Architecture.num_dense_layers",
    "NeuralNetwork.Architecture.num_shared_experts",
    "NeuralNetwork.Architecture.route_scale",
    "NeuralNetwork.Architecture.route_norm",
    "NeuralNetwork.Architecture.load_balance_coeff",
    "NeuralNetwork.Architecture.mup_enabled",
    # the fourth decoder stack (mpnn_type KEYEVL2, models/keyevl2.py)
    "NeuralNetwork.Architecture.indexer_num_heads",
    "NeuralNetwork.Architecture.indexer_head_dim",
    "NeuralNetwork.Architecture.indexer_num_kv_heads",
    "NeuralNetwork.Architecture.indexer_topk",
    # the fifth decoder stack (mpnn_type OURO, models/ouro.py)
    "NeuralNetwork.Architecture.total_ut_steps",
    "NeuralNetwork.Architecture.branch_loss_weights",
    "NeuralNetwork.Architecture.branch_loss_metrics",
    "NeuralNetwork.Architecture.dropout",
    "NeuralNetwork.Architecture.decoder_mirror_init",
    "NeuralNetwork.Architecture.decoder_recovery_slope",
    "NeuralNetwork.Variables_of_interest.input_node_features",
    "NeuralNetwork.Variables_of_interest.output_names",
    "NeuralNetwork.Variables_of_interest.output_index",
    "NeuralNetwork.Variables_of_interest.output_dim",
    "NeuralNetwork.Variables_of_interest.type",
    "NeuralNetwork.Variables_of_interest.denormalize_output",
    "NeuralNetwork.Variables_of_interest.graph_feature_names",
    "NeuralNetwork.Variables_of_interest.graph_feature_dims",
    "NeuralNetwork.Variables_of_interest.node_feature_names",
    "NeuralNetwork.Variables_of_interest.node_feature_dims",
    "NeuralNetwork.Training.num_epoch",
    "NeuralNetwork.Training.batch_size",
    "NeuralNetwork.Training.perc_train",
    "NeuralNetwork.Training.loss_function_type",
    "NeuralNetwork.Training.EarlyStopping",
    "NeuralNetwork.Training.patience",
    "NeuralNetwork.Training.seed",
    "NeuralNetwork.Training.continue",
    "NeuralNetwork.Training.startfrom",
    "NeuralNetwork.Training.Checkpoint",
    "NeuralNetwork.Training.checkpoint_warmup",
    "NeuralNetwork.Training.checkpoint_backend",
    "NeuralNetwork.Training.checkpoint_retention",
    "NeuralNetwork.Training.non_finite_policy",
    "NeuralNetwork.Training.non_finite_rollback_after",
    "NeuralNetwork.Training.non_finite_lr_backoff",
    "NeuralNetwork.Training.non_finite_max_rollbacks",
    "NeuralNetwork.Training.loader_stall_timeout",
    "NeuralNetwork.Training.compile_cache_dir",
    "NeuralNetwork.Training.precompile",
    "NeuralNetwork.Training.retrace_policy",
    "NeuralNetwork.Training.compute_grad_energy",
    "NeuralNetwork.Training.conv_checkpointing",
    "NeuralNetwork.Training.remat_policy",
    "NeuralNetwork.Training.Optimizer",
    "NeuralNetwork.Training.elastic",
    "NeuralNetwork.Training.mixed_precision",
    "NeuralNetwork.Training.pack_batches",
    "NeuralNetwork.Training.pack_node_slots",
    "NeuralNetwork.Training.pack_graph_slots",
    "NeuralNetwork.Training.num_pad_buckets",
    "NeuralNetwork.Training.size_bucketed_batching",
    "NeuralNetwork.Training.branch_parallel",
    "NeuralNetwork.Training.double_buffer",
    "NeuralNetwork.Training.warmup_epochs",
    "NeuralNetwork.Training.walltime_minutes",
    "NeuralNetwork.Training.return_best",
    "NeuralNetwork.Training.oversampling",
    "NeuralNetwork.Training.num_samples",
    "NeuralNetwork.Training.balance_branch_sampling",
    "NeuralNetwork.Training.CheckRemainingTime",
    "Visualization.create_plots",
    "Serving.max_queue_requests",
    "Serving.micro_batch_graphs",
    "Serving.batch_window_s",
    "Serving.default_deadline_s",
    "Serving.slo_p99_s",
    "Serving.expected_latency_per_graph_s",
    "Serving.step_timeout_s",
    "Serving.retrace_policy",
    "Serving.hot_reload",
    "Serving.reload_poll_s",
    "Serving.drain_timeout_s",
    "Serving.http_port",
    "Serving.http_host",
    "Serving.weights_dtype",
    "Serving.drain_grace_s",
    "Serving.fleet_replicas",
    "Serving.fleet_restart_backoff_s",
    "Serving.fleet_restart_backoff_max_s",
    "Serving.fleet_flap_window_s",
    "Serving.fleet_flap_max_restarts",
    "Serving.fleet_ready_floor",
    "Serving.router_timeout_s",
    "Serving.router_retries",
    "Serving.router_backoff_s",
    "Serving.router_hedge_factor",
    "Serving.router_hedge_min_s",
    "Serving.breaker_failures",
    "Serving.breaker_cooldown_s",
    "Serving.prediction_cache",
    "Serving.quantization",
    "Serving.reload_error_spike",
    "Serving.reload_probe_requests",
    "Telemetry.enabled",
    "Telemetry.interval_steps",
    "Telemetry.http_port",
    "Telemetry.http_host",
    "Telemetry.mfu",
    "Telemetry.jsonl",
    "Telemetry.profile_trigger",
    "Telemetry.profile_steps",
    "Telemetry.trace",
    "Telemetry.trace_sample",
    "Telemetry.trace_interval_steps",
    "Telemetry.flight_recorder",
    "Telemetry.numerics",
    "Telemetry.fleet",
    "Telemetry.fleet_collector",
    "Telemetry.fleet_collector_port",
    "Telemetry.fleet_collector_host",
    "Telemetry.fleet_straggler_factor",
    "Telemetry.fleet_max_step_lag",
    "Telemetry.fleet_stale_after_s",
    "Telemetry.fleet_collective_budget",
    "Telemetry.fleet_sharding_audit_bytes",
    "Mixture.temperature",
    "Mixture.weights",
    "Mixture.draws_per_epoch",
    "Mixture.balance",
    "Mixture.branch_loss_weights",
    "Mixture.drift_ema_decay",
    "Mixture.drift_threshold",
    "Mixture.demote_after",
    "Mixture.seed",
    # sharding rule engine (parallel/rules.py resolve; docs/PARALLELISM.md)
    "Parallel.rules",
    "Parallel.min_size",
    "Parallel.model_size",
    "Parallel.routed",
    "Parallel.name",
    "Parallel.resolved_rules",
}

# reference keys that are intentionally NOT consumed here, with the
# TPU-native answer a migrating user needs
_NOT_APPLICABLE = {
    "NeuralNetwork.Architecture.SyncBatchNorm": (
        "no DDP process groups to sync: batch-norm statistics are computed "
        "over the (masked) global batch inside the jitted step "
        "(models/layers.py MaskedBatchNorm); multi-device runs reduce via "
        "the mesh, so the torch SyncBatchNorm wrapper has no analog to "
        "enable"
    ),
}

# a couple of reference tests/inputs configs predate the NeuralNetwork
# nesting and put Architecture at the top level — one uniform rename
_LEGACY_TOPLEVEL_ARCH = (
    "legacy top-level 'Architecture' section (pre-NeuralNetwork layout, "
    "reference tests/inputs/ci_periodic.json) — nest the keys under "
    "NeuralNetwork.Architecture ('periodic' becomes "
    "'periodic_boundary_conditions'; 'predicted_value_option' is "
    "superseded by Variables_of_interest.output_index/type)"
)

# legacy/renamed reference keys -> what to use here
_LEGACY = {
    "NeuralNetwork.Training.early_stopping": (
        "use 'EarlyStopping' (capitalized, the reference's current key)"
    ),
    "NeuralNetwork.Training.epoch_start": (
        "resume is 'Training.continue: 1' (+ optional 'startfrom'); the "
        "epoch counter restores from the checkpoint"
    ),
    "NeuralNetwork.Architecture.predicted_value_option": (
        "superseded by Variables_of_interest.output_index/type (the "
        "reference itself migrated off this key)"
    ),
    "Visualization.plot_init_solution": (
        "visualizer plot families are selected by the postprocess API "
        "(postprocess/visualizer.py); 'create_plots' gates them all"
    ),
    "Visualization.plot_hist_solution": (
        "visualizer plot families are selected by the postprocess API "
        "(postprocess/visualizer.py); 'create_plots' gates them all"
    ),
}

# top-level Dataset/Architecture synonyms appearing in some reference
# example configs at non-standard paths ("Serving", "Telemetry", "Mixture"
# and "Parallel" are this framework's own sections — no reference analog;
# docs/SERVING.md, docs/OBSERVABILITY.md, docs/GFM.md, docs/PARALLELISM.md)
_TOPLEVEL_SECTIONS = (
    "Verbosity", "Dataset", "NeuralNetwork", "Visualization", "Serving",
    "Telemetry", "Mixture", "Parallel",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    status: str  # handled | not-applicable | legacy | unknown | invalid
    path: str
    message: str = ""


def _walk(d: Dict[str, Any], prefix: str = "") -> List[str]:
    # never descends into _OPAQUE subtrees, so no yielded path has an
    # opaque entry as a proper prefix (their children are schema'd elsewhere)
    out = []
    for k, v in d.items():
        p = f"{prefix}{k}" if not prefix else f"{prefix}.{k}"
        out.append(p)
        if isinstance(v, dict) and p not in _OPAQUE:
            out.extend(_walk(v, p))
    return out


def _afmoe_rules(config: Dict[str, Any]) -> List[Finding]:
    """The AFMOE stack's two rules between keys (models/afmoe.py refuses the
    same at config completion): one ``layer_types`` entry a layer, and a
    ``sliding_window`` on a stack that has a sliding layer."""
    arch = (config.get("NeuralNetwork") or {}).get("Architecture") or {}
    kinds = arch.get("layer_types")
    if arch.get("mpnn_type") != "AFMOE" or not isinstance(kinds, list):
        return []
    out, at = [], "NeuralNetwork.Architecture."
    layers = arch.get("num_conv_layers")
    if layers is not None and len(kinds) != int(layers):
        out.append(Finding("invalid", at + "layer_types",
                           f"{len(kinds)} entries for num_conv_layers {int(layers)}: one entry a layer"))
    if "sliding_attention" in kinds and not arch.get("sliding_window"):
        out.append(Finding("invalid", at + "sliding_window",
                           "layer_types has a sliding_attention layer: give its window (a count of keys)"))
    return out


def lint_config(config: Dict[str, Any]) -> List[Finding]:
    findings: List[Finding] = _afmoe_rules(config)
    for path in _walk(config):
        if path in _NOT_APPLICABLE:
            findings.append(Finding("not-applicable", path, _NOT_APPLICABLE[path]))
        elif path == "Architecture" or path.startswith("Architecture."):
            findings.append(Finding("legacy", path, _LEGACY_TOPLEVEL_ARCH))
        elif path in _LEGACY:
            findings.append(Finding("legacy", path, _LEGACY[path]))
        elif path in _HANDLED or path in _TOPLEVEL_SECTIONS:
            findings.append(Finding("handled", path))
        elif path in (
            "NeuralNetwork.Architecture",
            "NeuralNetwork.Variables_of_interest",
            "NeuralNetwork.Training",
            "NeuralNetwork.Profile",
        ):
            findings.append(Finding("handled", path))
        else:
            findings.append(
                Finding(
                    "unknown",
                    path,
                    "not consumed by this framework (config completion "
                    "ignores unknown keys, matching the reference's "
                    "behavior) — check for a typo or see docs/CONFIG.md",
                )
            )
    return findings


def format_report(findings: List[Finding]) -> str:
    order = {"invalid": -1, "unknown": 0, "legacy": 1, "not-applicable": 2, "handled": 3}
    lines = []
    counts: Dict[str, int] = {}
    for f in sorted(findings, key=lambda f: (order[f.status], f.path)):
        counts[f.status] = counts.get(f.status, 0) + 1
        if f.status == "handled":
            continue
        lines.append(f"[{f.status}] {f.path}: {f.message}")
    lines.append(
        "summary: "
        + ", ".join(f"{counts.get(s, 0)} {s}" for s in order)
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    import sys

    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m hydragnn_tpu.config.lint config.json")
        return 2
    # exit codes: 0 = clean, 1 = unknown or invalid keys found, 2 = could not lint —
    # migration scripts branch on 1 vs 2
    try:
        with open(argv[0]) as fh:
            config = json.load(fh)
    except OSError as e:
        print(f"hydragnn_tpu.config.lint: cannot read {argv[0]}: {e}")
        return 2
    except json.JSONDecodeError as e:
        print(f"hydragnn_tpu.config.lint: {argv[0]} is not valid JSON: {e}")
        return 2
    if not isinstance(config, dict):
        print(
            f"hydragnn_tpu.config.lint: {argv[0]} is a JSON "
            f"{type(config).__name__}, expected an object"
        )
        return 2
    findings = lint_config(config)
    print(format_report(findings))
    return 1 if any(f.status in ("unknown", "invalid") for f in findings) else 0


if __name__ == "__main__":
    raise SystemExit(main())
