"""Benchmark: training throughput (graphs/sec/chip) + MFU on the current chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline metric (PERF.md): OC20-S2EF-shaped training
throughput with the SC25 production model shape — EGNN hidden 866, 4 conv
layers, radius 5, max 20 neighbours, energy (graph) + forces (node) heads
with 3x889 MLPs, MAE loss, task weights [1, 100]
(reference: examples/multibranch/multibranch_GFM260_SC25.json). The dataset
is the OC20-shaped generator (lognormal ~73-atom slabs, capped degree ~20 —
the real data is not downloadable in this image) through the full bucketed
loader pipeline. MFU = XLA-counted step FLOPs / elapsed / chip peak (bf16).

``vs_baseline`` regresses the round-1 recorded measurement honestly: the
same synthetic-PNA workload round 1 measured (68,055 graphs/sec/chip) is
re-run and its ratio reported.

A stage that raises ends the run: the exception propagates and the exit
code is non-zero. Nothing is caught and reported as data.
"""

import json
import os
import time

# jax's persistent-cache write floor; read by jax at import, so it must be
# set before the first jax import (all jax imports here are lazy). The cache
# DIRECTORY is placed by setup_compile_cache() in main(), never here.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

# graphs/sec/chip recorded at round 1 (a set-up that no longer exists) for the
# synthetic-PNA workload; used for the vs_baseline regression ratio
RECORDED_BASELINE = 68055.28

_PROD_METRIC = (
    "OC20-S2EF-shaped train throughput, SC25 production shape "
    "(EGNN hidden 866, 4 conv layers, r=5, max_neigh=20, "
    "energy+forces heads; bf16 + sorted-agg + packed batching — "
    "the recommended production recipe)"
)


def _peak_flops(device_kind: str) -> float:
    """Peak dense bf16 FLOP/s by TPU generation — ONE table shared with the
    live telemetry plane's MFU gauge (hydragnn_tpu/obs/telemetry.py), so
    the banked cells and a scraped `hydragnn_mfu_estimate` can never
    disagree about the denominator. A device the table does not list (the
    CPU included) has no peak: a timed cell on it is an error, not an MFU."""
    from hydragnn_tpu.obs.telemetry import peak_flops

    peak = peak_flops(device_kind)
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s listed for device kind {device_kind!r} "
            "(obs/telemetry.py PEAK_FLOPS): timed bench cells run on a "
            "listed TPU only"
        )
    return peak


def _flops_of(step, *args) -> float:
    """XLA's own FLOP count for one compiled step (fwd+bwd+opt)."""
    try:
        cost = step.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception:
        return 0.0


def _oc20_workload(arch, batch_size, num_configs, mixed_precision,
                   pack_batches=False):
    """Shared bench-config scaffold: OC20-shaped dataset + energy/forces
    heads + the bench Training block around a caller-supplied Architecture.
    One builder so the EGNN production cell and the MACE/DimeNet/GPS cells
    cannot drift on the non-Architecture knobs."""
    from hydragnn_tpu.api import prepare_data
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.data.synthetic import oc20_shaped_dataset

    graphs = oc20_shaped_dataset(num_configs)
    if arch.get("global_attn_engine"):
        # GPS consumes Laplacian PE channels; the explicit-datasets path of
        # prepare_data does not attach them (api.py does it only for the
        # config-loaded path), so the bench scaffold does
        from hydragnn_tpu.data import add_dataset_pe

        graphs = add_dataset_pe(graphs, int(arch.get("pe_dim") or 1))
    tr, va, te = split_dataset(graphs, 0.9, seed=0)
    config = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "oc20_shaped",
            "node_features": {
                "name": ["atomic_number", "cartesian_coordinates", "forces"],
                "dim": [1, 3, 3],
            },
            "graph_features": {"name": ["energy"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": arch,
            "Variables_of_interest": {
                "input_node_features": [0, 1],
                "output_names": ["energy", "forces"],
                "output_index": [0, 2],
                "type": ["graph", "node"],
            },
            "Training": {
                "batch_size": batch_size,
                "num_epoch": 1,
                "loss_function_type": "mae",
                # fill measured on the OC20-shaped distribution: 6 levels
                # reach 97% node / 96% edge occupancy vs 92/90 at 3 (random
                # batching + quantile ladder; see docs/PERFORMANCE.md)
                "num_pad_buckets": int(os.getenv("BENCH_PAD_BUCKETS", "6")),
                # BENCH_PACK=1: packed batching — ONE spec (one compile) at
                # ~95% fill
                "pack_batches": pack_batches,
                # bf16 compute vs f32 master weights (BENCH_MP=0 for f32)
                "mixed_precision": mixed_precision,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        },
    }
    config, (train_loader, _, _), _ = prepare_data(config, datasets=(tr, va, te))
    return config, train_loader


def _default_mp() -> bool:
    return os.getenv("BENCH_MP", "1") == "1"


def _default_sorted() -> bool:
    # default ON since the r5 live A/B measured the Pallas sorted route
    # +16.5% at this exact shape (logs/ab_matrix.jsonl) and it became the
    # shipping TPU default (config/config.py) — the headline must measure
    # the config users get
    return os.getenv("BENCH_SORTED", "1") == "1"


def _default_pack() -> bool:
    # headline default ON: parity alone, +2.7% with the sorted route, at
    # ONE jit specialization (r5 A/B) — the recommended production recipe
    return os.getenv("BENCH_PACK", "1") == "1"


def _production_workload(mixed_precision=None, sorted_aggregation=None):
    """SC25-shaped EGNN on the OC20-shaped dataset, via the real pipeline."""
    if mixed_precision is None:
        mixed_precision = _default_mp()
    if sorted_aggregation is None:
        sorted_aggregation = _default_sorted()
    batch_size = int(os.getenv("BENCH_BATCH_SIZE", "32"))
    hidden = int(os.getenv("BENCH_HIDDEN", "866"))
    head_dim = int(os.getenv("BENCH_HEAD_DIM", "889"))
    num_configs = int(os.getenv("BENCH_NUM_CONFIGS", str(max(4 * batch_size, 128))))
    arch = {
        "mpnn_type": "EGNN",
        # BENCH_EQUIV=0: equivariance off — isolates the fused edge kernel
        # at FULL layer coverage (equivariant layers keep the materialized
        # path because edge_feat also feeds the coordinate gate; see
        # models/egnn.py and docs/PERFORMANCE.md)
        "equivariance": os.getenv("BENCH_EQUIV", "1") == "1",
        "radius": 5.0,
        "max_neighbours": 20,
        "hidden_dim": hidden,
        "num_conv_layers": 4,
        # Pallas sorted-segment aggregation A/B (BENCH_SORTED=1)
        "use_sorted_aggregation": sorted_aggregation,
        "task_weights": [1.0, 100.0],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 50,
                "num_headlayers": 3,
                "dim_headlayers": [head_dim, head_dim, head_dim],
            },
            "node": {
                "num_headlayers": 3,
                "dim_headlayers": [head_dim, head_dim, head_dim],
                "type": "mlp",
            },
        },
    }
    # BENCH_FUSED=0/1: fused gather->dense->segment-sum edge kernel A/B
    # (ops/pallas_fused_edge.py). Unset -> config completion's default
    # (auto-on with sorted aggregation), which is what the headline must
    # measure; explicit env pins a cell for the A/B matrix.
    fused_env = os.getenv("BENCH_FUSED")
    if fused_env is not None:
        arch["use_fused_edge_kernel"] = fused_env == "1"
    # packed batching default ON for the headline (see _default_pack;
    # examples/open_catalyst_2020 ships the same recipe)
    return _oc20_workload(
        arch, batch_size, num_configs, mixed_precision,
        pack_batches=_default_pack(),
    )


def _model_cell_workload(model_name: str, mixed_precision=None):
    """MACE / DimeNet A/B cells (VERDICT r4 #3): the two riskiest TPU
    mappings in the zoo — recursive Clebsch-Gordan contractions and the
    padded triplet channel — at SC25-class shapes on the same OC20-shaped
    data + heads as the production EGNN cell, so their graphs/sec/chip and
    MFU land in logs/ab_matrix.jsonl next to it. Reference counterparts are
    the heaviest stacks in its zoo (MACEStack.py:546, DIMEStack.py:305)."""
    if mixed_precision is None:
        mixed_precision = _default_mp()
    per_model = {
        # hidden 256, lmax 2 (VERDICT's floor); correlation 3 = the paper's
        # production 4-body order
        "MACE": {
            "mpnn_type": "MACE",
            "hidden_dim": int(os.getenv("BENCH_MACE_HIDDEN", "256")),
            "num_conv_layers": 2,
            "num_radial": 8,
            "max_ell": 2,
            "node_max_ell": 2,
            "correlation": 3,
            "radial_type": "bessel",
            "envelope_exponent": 5,
        },
        # DimeNet++ block sizes at production scale; the triplet channel is
        # budgeted by the loader's pad spec (data/pipeline.py with_triplets)
        "DimeNet": {
            "mpnn_type": "DimeNet",
            "hidden_dim": int(os.getenv("BENCH_DIMENET_HIDDEN", "128")),
            "num_conv_layers": 2,
            "num_radial": 6,
            "num_spherical": 7,
            "basis_emb_size": 8,
            "int_emb_size": 64,
            "out_emb_size": 256,
            "num_before_skip": 1,
            "num_after_skip": 2,
            "envelope_exponent": 5,
        },
    }
    arch = dict(per_model[model_name])
    arch.update(
        radius=5.0,
        max_neighbours=20,
        # BENCH_CELL_SORTED=1: sorted-aggregation variant of a model cell
        # (run-scripts/r5_followup_cells.py banks mace_sorted this way)
        use_sorted_aggregation=os.getenv("BENCH_CELL_SORTED", "0") == "1",
        task_weights=[1.0, 100.0],
        output_heads={
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 50,
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
                "type": "mlp",
            },
        },
    )
    batch_size = int(os.getenv("BENCH_CELL_BATCH_SIZE", "16"))
    num_configs = int(os.getenv("BENCH_NUM_CONFIGS", str(max(4 * batch_size, 128))))
    return _oc20_workload(arch, batch_size, num_configs, mixed_precision)


def _pna_cell_workload(spec: str, mixed_precision=None):
    """PNA-family cells (BENCH_PNA=1): the multi-output fused aggregation
    kernel's A/B (ops/pallas_multi_agg.py — the r11 tentpole). ``spec`` is
    ``"<model>_<route>"``: PNA_dense / PNA_fused / PNAPlus_dense /
    PNAPlus_fused. Both routes run ON the sorted route (sorted aggregation
    pinned on) so the ONLY moving part is the moment kernel vs the four
    dense segment reductions; same OC20-shaped data + energy/forces heads
    as every other cell, so graphs/sec/chip + MFU land in
    logs/ab_matrix.jsonl next to them with a ``multi_agg`` banked field."""
    if mixed_precision is None:
        mixed_precision = _default_mp()
    model_name, route = spec.rsplit("_", 1)
    assert model_name in ("PNA", "PNAPlus") and route in ("dense", "fused"), spec
    batch_size = int(os.getenv("BENCH_PNA_BATCH_SIZE", "16"))
    hidden = int(os.getenv("BENCH_PNA_HIDDEN", "256"))
    arch = {
        "mpnn_type": model_name,
        "hidden_dim": hidden,
        "num_conv_layers": 4,
        "radius": 5.0,
        "max_neighbours": 20,
        # both cells ride the sorted route — the kernel-vs-dense delta must
        # not be confounded with the (already-banked) sorted-vs-scatter one
        "use_sorted_aggregation": True,
        "use_fused_edge_kernel": route == "fused",
        "task_weights": [1.0, 100.0],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 50,
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
                "type": "mlp",
            },
        },
    }
    if model_name == "PNAPlus":
        arch.update(num_radial=5, envelope_exponent=5)
    num_configs = int(os.getenv("BENCH_NUM_CONFIGS", str(max(4 * batch_size, 128))))
    return _oc20_workload(arch, batch_size, num_configs, mixed_precision)


def _gps_cell_workload(attn_variant: str, mixed_precision=None):
    """GPS global-attention cells (BENCH_GPS=1) — the fork's headline
    feature (SURVEY §0 pillar 5) finally gets banked graphs/sec/chip + MFU
    numbers. Same OC20-shaped data + energy/forces heads as every other
    cell; GIN local MPNN (the mesoscale GPS recipe) so the attention route
    is the only moving part across the three variants:

    - ``flash``: multihead through the segment-masked Pallas flash kernel
      (ops/pallas_flash_attention.py) — the r6 tentpole;
    - ``dense``: multihead through the incumbent per-graph gathered dense
      layout ([G, H, Nmax, Nmax] logits in HBM) — the oracle A/B side;
    - ``performer``: the linear-attention variant (segment-sum KV moments).

    Sorted aggregation rides BENCH_CELL_SORTED like the MACE/DimeNet cells
    (default off — the attention delta must not be confounded)."""
    if mixed_precision is None:
        mixed_precision = _default_mp()
    batch_size = int(os.getenv("BENCH_GPS_BATCH_SIZE", "16"))
    hidden = int(os.getenv("BENCH_GPS_HIDDEN", "256"))
    arch = {
        "mpnn_type": "GIN",
        "hidden_dim": hidden,
        "num_conv_layers": 4,
        "radius": 5.0,
        "max_neighbours": 20,
        "global_attn_engine": "GPS",
        "global_attn_type": (
            "performer" if attn_variant == "performer" else "multihead"
        ),
        "global_attn_heads": int(os.getenv("BENCH_GPS_HEADS", "8")),
        "pe_dim": 4,
        # dropout pinned 0 across ALL three variants: flash configs run
        # attention-prob dropout at 0 by design (models/gps.py), so a
        # dense cell at the 0.25 default would train different numerics
        # AND pay dropout-rng work flash skips — the A/B must isolate the
        # attention route, nothing else
        "dropout": 0.0,
        "use_flash_attention": attn_variant == "flash",
        "use_sorted_aggregation": os.getenv("BENCH_CELL_SORTED", "0") == "1",
        "task_weights": [1.0, 100.0],
        "output_heads": {
            "graph": {
                "num_sharedlayers": 2,
                "dim_sharedlayers": 50,
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
            },
            "node": {
                "num_headlayers": 2,
                "dim_headlayers": [256, 256],
                "type": "mlp",
            },
        },
    }
    num_configs = int(os.getenv("BENCH_NUM_CONFIGS", str(max(4 * batch_size, 128))))
    return _oc20_workload(arch, batch_size, num_configs, mixed_precision)


def _bench_production(mixed_precision=None, sorted_aggregation=None,
                      profile=None, env_overrides=None, workload=None):
    import jax
    import numpy as np

    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    if profile is None:
        profile = os.getenv("BENCH_PROFILE", "0") == "1"
    saved = {}
    for k, v in (env_overrides or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        if workload is None:
            config, loader = _production_workload(
                mixed_precision, sorted_aggregation
            )
        elif workload.startswith("GPS_"):
            config, loader = _gps_cell_workload(
                workload.split("_", 1)[1], mixed_precision
            )
        elif workload.startswith("PNA"):
            config, loader = _pna_cell_workload(workload, mixed_precision)
        else:
            config, loader = _model_cell_workload(workload, mixed_precision)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    batches = list(loader)
    model = create_model(config)
    variables = init_model(model, batches[0], seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    # non-finite step guard A/B (BENCH_GUARD cells): resolved from the
    # cell's env_overrides explicitly because those are restored right
    # after the workload build, before the step traces
    step_guard = (env_overrides or {}).get(
        "HYDRAGNN_STEP_GUARD", os.environ.get("HYDRAGNN_STEP_GUARD", "1")
    ) == "1"
    step = make_train_step(
        model,
        tx,
        mixed_precision=config["NeuralNetwork"]["Training"]["mixed_precision"],
        guard=step_guard,
    )
    rng = jax.random.PRNGKey(0)

    # compile observability (hydragnn_tpu/train/compile_plane.py): cache
    # hit/miss + backend-compile seconds attributed to THIS cell, and
    # time-to-first-step banked separately from the steady-state step time
    # (the old first-step pass conflated trace+compile+execute into the
    # warmup)
    from hydragnn_tpu.train import compile_plane as _cp

    _cp.install_metrics_listeners()
    m0 = _cp.compile_metrics()
    t0 = time.perf_counter()
    state, tot, _ = step(state, batches[0], rng)
    jax.block_until_ready(tot)
    time_to_first_step = time.perf_counter() - t0

    # FLOPs per distinct batch shape, from the compiled executables
    flops_by_shape = {}
    for b in batches:
        key = (b.num_nodes, b.num_edges)
        if key not in flops_by_shape:
            flops_by_shape[key] = _flops_of(step, state, b, rng)
    # real-graph counts up front: a per-step D2H mask readback would force a
    # host sync inside the timed loop and serialize the dispatch pipeline
    counts = [int(np.asarray(b.graph_mask).sum()) for b in batches]
    rngs = [jax.random.fold_in(rng, i) for i in range(len(batches))]

    # warmup: compile every remaining specialization, then one full extra
    # pass, so first-touch queue/transfer warm-up stays out of the timing
    for b in batches[1:]:
        state, tot, _ = step(state, b, rng)
    for b, r in zip(batches, rngs):
        state, tot, _ = step(state, b, r)
    jax.block_until_ready(tot)
    mdelta = {
        k: v - m0[k] for k, v in _cp.compile_metrics().items()
    }

    # BENCH_PROFILE=1: one xprof trace of a few steady-state steps into
    # logs/bench_profile (drives the MFU work — find the top non-matmul op)
    if profile:
        os.makedirs("logs/bench_profile", exist_ok=True)
        # perfetto trace alongside the xplane pb — loadable in Perfetto
        # UI for the device-op rollup; stage-level decomposition comes
        # from `python -m hydragnn_tpu.obs.doctor trace` over trace.jsonl
        with jax.profiler.trace(
            "logs/bench_profile", create_perfetto_trace=True
        ):
            for b, r in list(zip(batches, rngs))[:8]:
                state, tot, _ = step(state, b, r)
            jax.block_until_ready(tot)

    # several timed trials, best one reported (kept for the benchmark PR
    # that rebuilds this file around medians — ROADMAP Speed 1)
    n_passes = int(os.getenv("BENCH_PASSES", "4"))
    n_trials = int(os.getenv("BENCH_TRIALS", "3"))
    graphs_done = sum(counts) * n_passes
    flops_done = (
        sum(flops_by_shape[(b.num_nodes, b.num_edges)] for b in batches) * n_passes
    )
    best_dt = None
    trial_dts = []
    for _ in range(n_trials):
        t0 = time.perf_counter()
        for p in range(n_passes):
            for b, r in zip(batches, rngs):
                state, tot, _ = step(state, b, r)
        jax.block_until_ready(tot)
        dt = time.perf_counter() - t0
        trial_dts.append(dt)
        if best_dt is None or dt < best_dt:
            best_dt = dt

    gps = graphs_done / best_dt
    device_kind = jax.devices()[0].device_kind
    peak = _peak_flops(device_kind)
    mfu = (flops_done / best_dt) / peak
    # telemetry-comparable fields, banked in EVERY cell so the ROADMAP-3
    # hardware round gets them for free: padding_waste is the node-slot
    # occupancy complement over the epoch's batches (nodes dominate
    # compute; flops_audit.py prints the same census), mfu_est is the
    # live-telemetry MFU formula over the MEAN trial — what a scrape of
    # hydragnn_mfu_estimate would show, vs `mfu` which keeps the
    # best-trial convention of the banked history
    from hydragnn_tpu.obs.telemetry import mfu_estimate as _mfu_estimate

    nodes_real = sum(int(np.asarray(b.node_mask).sum()) for b in batches)
    nodes_padded = sum(int(b.num_nodes) for b in batches)
    padding_waste = 1.0 - nodes_real / max(nodes_padded, 1)
    mean_dt = sum(trial_dts) / len(trial_dts)
    mfu_est = _mfu_estimate(flops_done, mean_dt, device_kind)
    arch_done = config["NeuralNetwork"]["Architecture"]
    return {
        "graphs_per_sec": gps,
        "mfu": mfu,
        "padding_waste": padding_waste,
        "mfu_est": mfu_est,
        "flops_per_graph": flops_done / max(graphs_done, 1),
        "device": jax.devices()[0].device_kind,
        "peak_flops_assumed": peak,
        "loss": float(tot),
        # compile plane: first-step latency and this cell's XLA compile
        # bill (backend-compile seconds incl. cache retrievals) + the
        # persistent-cache hit/miss counts the BENCH_COMPILE A/B banks
        "time_to_first_step": time_to_first_step,
        "compile_time_s": mdelta["backend_compile_s"],
        "cache_hits": int(mdelta["cache_hits"]),
        "cache_misses": int(mdelta["cache_misses"]),
        # the routes that can actually engage, not the raw flag: both fused
        # paths need sorted receivers + a degree bound, and each has its own
        # consumer set — EGNN's single-consumer messages ride the
        # gather->dense->sum kernel (fused_edge), the PNA family's
        # multi-consumer messages ride the multi-output moment kernel
        # (multi_agg, ops/pallas_multi_agg.py). A MACE/DimeNet cell with the
        # auto-following flag set must bank both false.
        "fused_edge": bool(
            arch_done.get("mpnn_type") == "EGNN"
            and arch_done.get("use_fused_edge_kernel", False)
            and arch_done.get("use_sorted_aggregation", False)
            and int(arch_done.get("max_in_degree") or 0) > 0
        ),
        "multi_agg": bool(
            arch_done.get("mpnn_type") in ("PNA", "PNAPlus", "PNAEq")
            and arch_done.get("use_fused_edge_kernel", False)
            and arch_done.get("use_sorted_aggregation", False)
            and int(arch_done.get("max_in_degree") or 0) > 0
        ),
        "equivariance": bool(arch_done.get("equivariance", False)),
        "step_guard": step_guard,
        # the attention route that can actually engage: flash needs GPS +
        # the static per-graph node bound (models/gps.py routing)
        "flash_attention": bool(
            arch_done.get("global_attn_engine")
            and arch_done.get("use_flash_attention", False)
            and int(arch_done.get("max_nodes_per_graph") or 0) > 0
        ),
        "global_attn_type": arch_done.get("global_attn_type"),
    }


def _bench_synthetic_pna():
    """The exact round-1 workload, for the vs_baseline regression ratio."""
    import jax

    import __graft_entry__ as ge
    from hydragnn_tpu.models import init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    batch_size = 64
    config, model, loader, batch = ge._build(
        mpnn_type="PNA", hidden_dim=64, num_conv_layers=3,
        batch_size=batch_size, num_configs=128,
    )
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    step = make_train_step(model, tx)
    rng = jax.random.PRNGKey(0)
    state, tot, _ = step(state, batch, rng)
    jax.block_until_ready(tot)
    n_steps = 50
    rngs = [jax.random.fold_in(rng, i) for i in range(n_steps)]
    best = 0.0
    for _ in range(int(os.getenv("BENCH_TRIALS", "3"))):
        t0 = time.perf_counter()
        for r in rngs:
            state, tot, _ = step(state, batch, r)
        jax.block_until_ready(tot)
        best = max(best, n_steps * batch_size / (time.perf_counter() - t0))
    return best


def main_ab():
    """All four mixed_precision x sorted_aggregation cells in ONE process
    (a chip belongs to one process at a time). Emits one JSON line per cell
    (same schema as main()) plus a final summary line; appends to
    logs/ab_matrix.jsonl as it goes. A cell that raises ends the run."""
    import gc

    os.makedirs("logs", exist_ok=True)
    out_path = os.path.join("logs", "ab_matrix.jsonl")
    # a device without a listed peak cannot bank an MFU: fail before timing
    _peak_flops(_device_kind())
    # small leg first: the big HBM footprint would skew it, not vice versa
    syn = _bench_synthetic_pna()
    # 4-cell mixed_precision x sorted_aggregation matrix, then the packed-
    # batching and batch-64 cells on the winning precision (extra levers
    # from VERDICT r2 #3: batch size and padding occupancy)
    # base matrix pins BENCH_PACK=0 so mp x sorted is measured on the
    # bucket-ladder loader; the pack variant isolates packing itself
    # (the headline default is pack ON — see _model_cell_workload note)
    cells = [
        # base mp x sorted matrix: BENCH_FUSED=0 pins the r5 semantics so
        # the historical comparison stays apples-to-apples (config
        # completion would otherwise auto-on the fused kernel with sorted)
        {"mp": True, "sorted": False, "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"}},
        {"mp": True, "sorted": True, "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"}},
        {"mp": False, "sorted": False, "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"}},
        {"mp": False, "sorted": True, "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"}},
        # fused edge-kernel A/B (the r6 tentpole): fused vs unfused on the
        # sorted route, production (equivariant) shape — only the last conv
        # layer fuses there — and equivariance-off, where every layer fuses
        # (the kernel's full-coverage number; see docs/PERFORMANCE.md)
        {"mp": True, "sorted": True,
         "env": {"BENCH_PACK": "0", "BENCH_FUSED": "1"}, "tag": "fused"},
        {"mp": True, "sorted": True,
         "env": {"BENCH_PACK": "0", "BENCH_FUSED": "1", "BENCH_EQUIV": "0"},
         "tag": "noneq_fused"},
        {"mp": True, "sorted": True,
         "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0", "BENCH_EQUIV": "0"},
         "tag": "noneq_unfused"},
        {"mp": True, "sorted": False, "env": {"BENCH_PACK": "1"}, "tag": "pack"},
        # production recipe cell: defaults (fused auto-on via completion)
        {"mp": True, "sorted": True, "env": {"BENCH_PACK": "1"},
         "tag": "sorted_pack"},
        {"mp": True, "sorted": False,
         "env": {"BENCH_BATCH_SIZE": "64", "BENCH_PACK": "0"}, "tag": "bs64"},
        # the two riskiest TPU mappings get their own banked cells
        # (VERDICT r4 #3)
        {"mp": True, "sorted": False, "model": "MACE", "tag": "mace"},
        {"mp": True, "sorted": False, "model": "DimeNet", "tag": "dimenet"},
    ]
    if os.getenv("BENCH_GUARD", "0") == "1":
        # non-finite step guard A/B (the r7 fault-tolerance tentpole):
        # bound the guard's cost (one global-norm pass + a lax.cond) on the
        # production EGNN shape. Pinned for the next hardware round; the
        # CPU-side loss-equality proof is BENCH_GUARD_SMOKE (ci.sh).
        cells += [
            {"mp": True, "sorted": False, "tag": "guard_on",
             "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0",
                     "HYDRAGNN_STEP_GUARD": "1"}},
            {"mp": True, "sorted": False, "tag": "guard_off",
             "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0",
                     "HYDRAGNN_STEP_GUARD": "0"}},
        ]
    if os.getenv("BENCH_GPS", "0") == "1":
        # GPS attention A/B (the r6 tentpole): flash vs the incumbent
        # gathered-dense multihead, plus the performer linear variant —
        # the first on-chip numbers for the fork's headline feature.
        cells += [
            {"mp": True, "sorted": False, "model": "GPS_dense",
             "tag": "gps_dense"},
            {"mp": True, "sorted": False, "model": "GPS_flash",
             "tag": "gps_flash"},
            {"mp": True, "sorted": False, "model": "GPS_performer",
             "tag": "gps_performer"},
        ]
    if os.getenv("BENCH_PNA", "0") == "1":
        # multi-output fused PNA aggregation A/B (the r11 tentpole,
        # ops/pallas_multi_agg.py): moment kernel vs the four dense segment
        # reductions, both ON the sorted route, for PNA and PNAPlus (the
        # rbf-gated variant streams the gate through the kernel).
        # Pinned for the ROADMAP item 4 hardware round; the CPU-side
        # fused==dense proof is BENCH_PNA_SMOKE (ci.sh).
        cells += [
            {"mp": True, "sorted": True, "model": "PNA_dense",
             "tag": "pna_dense"},
            {"mp": True, "sorted": True, "model": "PNA_fused",
             "tag": "pna_fused"},
            {"mp": True, "sorted": True, "model": "PNAPlus_dense",
             "tag": "pnaplus_dense"},
            {"mp": True, "sorted": True, "model": "PNAPlus_fused",
             "tag": "pnaplus_fused"},
        ]
    if os.getenv("BENCH_COMPILE", "0") == "1":
        # cold-vs-warm persistent-cache A/B (the r8 compile-plane tentpole):
        # the SAME production-shaped cell twice — first against a scrubbed
        # cache directory, then against the directory the cold cell just
        # filled. Each cell builds fresh step objects, so both re-trace;
        # the warm cell's XLA compiles collapse into cache retrievals
        # (banked: cache_hits > 0, reduced compile_time_s and
        # time_to_first_step). Appended LAST so the cache-dir flip cannot
        # perturb the historical cells.
        cells += [
            {"mp": True, "sorted": False, "tag": "compile_cold",
             "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"},
             "compile_cache": "cold"},
            {"mp": True, "sorted": False, "tag": "compile_warm",
             "env": {"BENCH_PACK": "0", "BENCH_FUSED": "0"},
             "compile_cache": "warm"},
        ]
    n_done = 0
    for cell in cells:
        mp, sorted_agg = cell["mp"], cell["sorted"]
        # model cells route sorted aggregation via BENCH_CELL_SORTED inside
        # _model_cell_workload — the banked record must say what actually
        # ran, and a cell's own env_overrides take precedence over the
        # outer-process environment (ADVICE r5 #2: _bench_production applies
        # env_overrides around the workload build, so a future model cell
        # setting BENCH_CELL_SORTED via env would otherwise bank wrong)
        if "model" in cell and not cell["model"].startswith("PNA"):
            # PNA cells pin sorted aggregation ON inside their workload
            # builder (the kernel-vs-dense A/B must not be confounded), so
            # only the MACE/DimeNet/GPS cells route via BENCH_CELL_SORTED
            sorted_agg = cell.get("env", {}).get(
                "BENCH_CELL_SORTED", os.environ.get("BENCH_CELL_SORTED", "0")
            ) == "1"
        cc = cell.get("compile_cache")
        if cc:
            # cold: scrub the A/B cache dir; warm: reuse what cold wrote.
            # min_compile_secs=0 so every specialization is cached even on
            # fast-compiling backends (jax's default 1s floor would skip
            # CPU-sized programs and the warm cell would bank zero hits)
            import shutil

            from hydragnn_tpu.train import compile_plane as _cp

            # a private directory beside the placed cache, so the A/B's
            # scrub never empties the cache real runs share
            cache_ab_dir = _cp.compile_cache_dir() + "_compile_ab"
            if cc == "cold":
                shutil.rmtree(cache_ab_dir, ignore_errors=True)
            _cp.set_cache_dir(cache_ab_dir, min_compile_secs=0.0)
        prod = _bench_production(
            mixed_precision=mp,
            sorted_aggregation=sorted_agg,
            # profile only the production-recipe cell (mp + sorted + pack —
            # what main() measures as the headline)
            profile=(cell.get("tag") == "sorted_pack"
                     and os.getenv("BENCH_PROFILE", "0") == "1"),
            env_overrides=cell.get("env"),
            workload=cell.get("model"),
        )
        line = json.dumps(
            {
                "metric": "OC20-S2EF-shaped A/B cell",
                "value": round(prod["graphs_per_sec"], 2),
                "unit": "graphs/sec/chip",
                "mfu": round(prod["mfu"], 4),
                "padding_waste": round(prod["padding_waste"], 4),
                "mfu_est": round(prod["mfu_est"], 4),
                "flops_per_graph": round(prod["flops_per_graph"]),
                "train_loss": round(prod["loss"], 5),
                "mixed_precision": mp,
                "sorted_aggregation": sorted_agg,
                "fused_edge": prod["fused_edge"],
                "multi_agg": prod["multi_agg"],
                "equivariance": prod["equivariance"],
                "step_guard": prod["step_guard"],
                "flash_attention": prod["flash_attention"],
                "time_to_first_step": round(prod["time_to_first_step"], 3),
                "compile_time_s": round(prod["compile_time_s"], 3),
                **({"compile_cache": cc,
                    "cache_hits": prod["cache_hits"],
                    "cache_misses": prod["cache_misses"]} if cc else {}),
                **({"global_attn_type": prod["global_attn_type"]}
                   if prod["global_attn_type"] else {}),
                **({"variant": cell["tag"]} if "tag" in cell else {}),
                "vs_baseline": round(syn / RECORDED_BASELINE, 3),
                "synthetic_pna_graphs_per_sec": round(syn, 2),
            }
        )
        print(line, flush=True)
        with open(out_path, "a") as fh:
            fh.write(line + "\n")
        n_done += 1
        gc.collect()
    print(json.dumps({"metric": "ab_matrix_done", "cells": n_done}))


def smoke_gps():
    """BENCH_GPS_SMOKE=1: CPU-runnable proof that every BENCH_GPS cell
    builds and trains — one jitted step per attention variant at tiny
    shapes, with the flash cell FORCED through the Pallas kernel
    (interpret mode, HYDRAGNN_PALLAS_FLASH=1) and asserted loss-equal to
    the gathered-dense cell from identical init. This is the CI tier's
    guard that the bench cells cannot rot between hardware rounds
    (run-scripts/ci.sh invokes it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    os.environ.setdefault("BENCH_GPS_BATCH_SIZE", "4")
    os.environ.setdefault("BENCH_GPS_HIDDEN", "32")
    os.environ.setdefault("BENCH_GPS_HEADS", "4")
    os.environ.setdefault("BENCH_NUM_CONFIGS", "24")
    losses = {}
    for variant in ("dense", "performer", "flash"):
        config, loader = _gps_cell_workload(variant, mixed_precision=False)
        batch = next(iter(loader))
        model = create_model(config)
        variables = init_model(model, batch, seed=0)
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        if variant != "flash":
            state = TrainState.create(variables, tx)
            _, tot, _ = make_train_step(model, tx)(
                state, batch, jax.random.PRNGKey(0)
            )
            jax.block_until_ready(tot)
            losses[variant] = float(tot)
            assert np.isfinite(losses[variant]), (variant, losses)
            continue
        # flash cell: ONE model (the flash-flagged one — identical module
        # structure and rng stream on both routes), env-flipped between the
        # Pallas kernel (interpret mode on CPU) and the gathered-dense
        # oracle; the jitted step donates its buffers, so each route gets a
        # fresh state from a copy of the same init
        for route, flag in (("flash", "1"), ("flash_dense_oracle", "0")):
            os.environ["HYDRAGNN_PALLAS_FLASH"] = flag
            try:
                state = TrainState.create(
                    jax.tree_util.tree_map(
                        lambda x: jnp.array(x, copy=True), variables
                    ),
                    tx,
                )
                _, tot, _ = make_train_step(model, tx)(
                    state, batch, jax.random.PRNGKey(0)
                )
                jax.block_until_ready(tot)
            finally:
                os.environ.pop("HYDRAGNN_PALLAS_FLASH", None)
            losses[route] = float(tot)
            assert np.isfinite(losses[route]), (route, losses)
    delta = abs(losses["flash"] - losses["flash_dense_oracle"])
    assert delta <= 1e-4 * max(1.0, abs(losses["flash_dense_oracle"])), losses
    print(json.dumps({
        "metric": "BENCH_GPS smoke (CPU, one step per attention variant)",
        "losses": {k: round(v, 6) for k, v in losses.items()},
        "flash_vs_dense_delta": delta,
        "ok": True,
    }))


def smoke_pna():
    """BENCH_PNA_SMOKE=1: CPU-runnable proof that every BENCH_PNA cell
    builds and trains — one jitted step per (model, route) at tiny shapes,
    with the fused cells FORCED through the multi-moment Pallas kernel
    (interpret mode, HYDRAGNN_PALLAS_MULTIAGG=1) and asserted loss-equal
    to the dense cells from identical init. This is the CI tier's guard
    that the bench cells cannot rot between hardware rounds
    (run-scripts/ci.sh invokes it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    os.environ.setdefault("BENCH_PNA_BATCH_SIZE", "4")
    os.environ.setdefault("BENCH_PNA_HIDDEN", "32")
    os.environ.setdefault("BENCH_NUM_CONFIGS", "24")
    report = {}
    for model_name in ("PNA", "PNAPlus"):
        losses = {}
        variables = None
        for route in ("dense", "fused"):
            config, loader = _pna_cell_workload(
                f"{model_name}_{route}", mixed_precision=False
            )
            batch = next(iter(loader))
            model = create_model(config)
            if variables is None:
                variables = init_model(model, batch, seed=0)
            # the fused route runs the interpret-mode kernel; the dense
            # route is the oracle — identical init, one step each
            flips = [("", None)] if route == "dense" else [
                ("", "1"), ("_dense_fallback", "0"),
            ]
            for suffix, flag in flips:
                if flag is not None:
                    os.environ["HYDRAGNN_PALLAS_MULTIAGG"] = flag
                try:
                    state = TrainState.create(
                        jax.tree_util.tree_map(
                            lambda x: jnp.array(x, copy=True), variables
                        ),
                        tx := make_optimizer(
                            config["NeuralNetwork"]["Training"]["Optimizer"]
                        ),
                    )
                    _, tot, _ = make_train_step(model, tx)(
                        state, batch, jax.random.PRNGKey(0)
                    )
                    jax.block_until_ready(tot)
                finally:
                    os.environ.pop("HYDRAGNN_PALLAS_MULTIAGG", None)
                losses[route + suffix] = float(tot)
                assert np.isfinite(losses[route + suffix]), (
                    model_name, route, losses
                )
        delta = abs(losses["fused"] - losses["dense"])
        assert delta <= 1e-4 * max(1.0, abs(losses["dense"])), (
            model_name, losses
        )
        report[model_name] = {
            "losses": {k: round(v, 6) for k, v in losses.items()},
            "fused_vs_dense_delta": delta,
        }
    print(json.dumps({
        "metric": "BENCH_PNA smoke (CPU, one step per model x route; "
                  "fused==dense)",
        **report,
        "ok": True,
    }))


def smoke_guard():
    """BENCH_GUARD_SMOKE=1: CPU-runnable proof for the BENCH_GUARD A/B —
    the guarded step is numerically IDENTICAL to the unguarded step on
    finite batches (f32 and bf16; acceptance for the r7 tentpole), plus a
    small timed A/B so the cell shape cannot rot between hardware rounds
    (run-scripts/ci.sh invokes it; the banked on-chip numbers come from
    BENCH_AB=1 BENCH_GUARD=1 next hardware round)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    os.environ.setdefault("BENCH_BATCH_SIZE", "4")
    os.environ.setdefault("BENCH_HIDDEN", "32")
    os.environ.setdefault("BENCH_HEAD_DIM", "32")
    os.environ.setdefault("BENCH_NUM_CONFIGS", "16")
    os.environ.setdefault("BENCH_PACK", "0")
    out = {}
    for mp in (False, True):
        config, loader = _production_workload(
            mixed_precision=mp, sorted_aggregation=False
        )
        batch = next(iter(loader))
        model = create_model(config)
        variables = init_model(model, batch, seed=0)
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        losses, times = {}, {}
        for guard in (True, False):
            state = TrainState.create(
                jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True), variables
                ),
                tx,
            )
            step = make_train_step(model, tx, mixed_precision=mp, guard=guard)
            ls = []
            for i in range(3):  # compile + step into updated params
                state, tot, _ = step(state, batch, jax.random.PRNGKey(i))
                ls.append(float(tot))
            t0 = time.perf_counter()
            for i in range(5):
                state, tot, _ = step(state, batch, jax.random.PRNGKey(10 + i))
            jax.block_until_ready(tot)
            times[guard] = (time.perf_counter() - t0) / 5
            losses[guard] = ls
            assert all(np.isfinite(l) for l in ls), (guard, ls)
        # identical, not close: the guard's taken branch IS the unguarded
        # update arithmetic
        assert losses[True] == losses[False], (mp, losses)
        out["bf16" if mp else "f32"] = {
            "losses_equal": True,
            "guarded_step_secs": round(times[True], 6),
            "unguarded_step_secs": round(times[False], 6),
        }
    print(json.dumps({
        "metric": "BENCH_GUARD smoke (CPU, guarded==unguarded)",
        **out,
        "ok": True,
    }))


def _serve_world():
    """Small synthetic serve deployment for the BENCH_SERVE cells: model +
    optimizer-free inference state + the dataset's SpecLadder, shapes via
    BENCH_SERVE_* envs (defaults CPU-runnable for the ci.sh smoke;
    hardware rounds raise them to the production shape)."""
    from hydragnn_tpu.config import update_config, voi_from_config
    from hydragnn_tpu.data import deterministic_graph_dataset, split_dataset
    from hydragnn_tpu.data.graph import SpecLadder
    from hydragnn_tpu.data.pipeline import extract_variables, spec_template_batches
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train.state import InferenceState

    hidden = int(os.getenv("BENCH_SERVE_HIDDEN", "16"))
    num_configs = int(os.getenv("BENCH_SERVE_NUM_CONFIGS", "96"))
    batch = int(os.getenv("BENCH_SERVE_BATCH", "8"))
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "bench_serve",
            "format": "synthetic",
            "synthetic": {"number_configurations": num_configs},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["s"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN",
                "radius": 2.0,
                "max_neighbours": 100,
                "hidden_dim": hidden,
                "num_conv_layers": 2,
                "task_weights": [1.0],
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": hidden,
                        "num_headlayers": 2,
                        "dim_headlayers": [hidden, hidden],
                    }
                },
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["s"],
                "output_index": [0],
                "type": ["graph"],
                "denormalize_output": False,
            },
            "Training": {
                "num_epoch": 1,
                "batch_size": batch,
                "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
    }
    raw = deterministic_graph_dataset(
        num_configs, seed=7, radius=2.0, max_neighbours=100
    )
    tr, va, te = split_dataset(raw, 0.7, seed=0)
    cfg = update_config(cfg, tr, va, te)
    ready = [extract_variables(g, voi_from_config(cfg)) for g in raw]
    ladder = SpecLadder.for_dataset(ready, batch, num_buckets=2)
    model = create_model(cfg)
    tmpl = spec_template_batches(ready, ladder)[0][1]
    state = InferenceState.create(init_model(model, tmpl, seed=0))
    return model, state, ladder, ready


def _serve_load_cell(server, graphs, offered_gps, duration_s):
    """Open-loop load: submit at ``offered_gps`` for ``duration_s``; returns
    latency percentiles over completed requests plus the shed/backpressure
    tally. Latency = submit -> outcome via the handle's ``done_at`` stamp (no
    waiter thread per request)."""
    import numpy as np

    from hydragnn_tpu.serve import RequestError

    t_start = time.perf_counter()
    handles, t0s = [], []
    rejected = {}
    i = 0
    while True:
        target = t_start + i / offered_gps
        now = time.perf_counter()
        if now - t_start >= duration_s:
            break
        if target > now:
            time.sleep(target - now)
        t0 = time.perf_counter()
        try:
            handles.append(server.submit(graphs[i % len(graphs)]))
            t0s.append(t0)
        except RequestError as e:
            rejected[e.code] = rejected.get(e.code, 0) + 1
        i += 1
    for h in handles:
        h.wait(120)
    elapsed = time.perf_counter() - t_start
    lats = np.array(
        [h.done_at - t0 for h, t0 in zip(handles, t0s)
         if h.done_at is not None and h.error(0) is None]
    )
    submitted = i
    completed = len(lats)
    shed = rejected.get("shed", 0) + rejected.get("queue_full", 0)
    return {
        "offered_gps": round(offered_gps, 1),
        "achieved_gps": round(completed / elapsed, 1),
        "submitted": submitted,
        "completed": completed,
        "shed": shed,
        "shed_rate": round(shed / max(submitted, 1), 4),
        "deadline_expired": rejected.get("deadline_exceeded", 0)
        + sum(1 for h in handles if h.error(0) is not None),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3) if completed else None,
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3) if completed else None,
    }


def main_serve():
    """BENCH_SERVE=1: serving-plane cells — p50/p99 latency and achieved
    throughput vs offered load, and shed rate under overload at a p99 SLO
    (the r6 serving tentpole; docs/SERVING.md "Benchmarks").

    Three open-loop cells against a live ``GraphServer`` with the sentinel
    in error mode (any retrace mid-cell aborts the bench — serving latency
    measured across a recompile would be a lie): ``light`` (0.5x measured
    capacity) and ``at_slo`` (0.9x) must not shed; ``overload`` (3x) runs
    with ``slo_p99_s`` armed and MUST shed rather than queue without bound.
    CPU-runnable at the default tiny shapes (run-scripts/ci.sh invokes it
    as a smoke); hardware rounds raise BENCH_SERVE_HIDDEN / _NUM_CONFIGS /
    _BATCH / _SECS to the production shape. Cells append to
    logs/serve_cells.jsonl as they complete."""
    from hydragnn_tpu.serve import GraphServer, ServeConfig

    duration = float(os.getenv("BENCH_SERVE_SECS", "3"))
    model, state, ladder, graphs = _serve_world()
    os.makedirs("logs", exist_ok=True)
    out_path = os.path.join("logs", "serve_cells.jsonl")

    def _bank(line):
        print(line, flush=True)
        with open(out_path, "a") as fh:
            fh.write(line + "\n")

    # calibration server: measure closed-loop capacity (no SLO, no shedding)
    server = GraphServer(
        model, state, ladder,
        ServeConfig(micro_batch_graphs=int(os.getenv("BENCH_SERVE_BATCH", "8")),
                    batch_window_s=0.002, retrace_policy="error",
                    max_queue_requests=0),
        template_graphs=graphs,
    ).start()
    try:
        assert server.wait_ready(600), f"serve warm-up failed: {server.failed}"
        t0 = time.perf_counter()
        n_cal = min(len(graphs) * 4, 256)
        out = server.predict(
            [graphs[j % len(graphs)] for j in range(n_cal)], timeout=120
        )
        assert all(isinstance(o, dict) for o in out), "calibration failed"
        capacity = n_cal / (time.perf_counter() - t0)
    finally:
        server.close(drain=False)

    per_graph_s = 1.0 / capacity
    slo_p99_s = float(os.getenv("BENCH_SERVE_SLO_S", str(20 * per_graph_s)))
    cells = [
        ("light", 0.5, 0.0),  # headroom: latency floor, zero shed
        ("at_slo", 0.9, slo_p99_s),  # throughput at the p99 SLO
        ("overload", 3.0, slo_p99_s),  # must shed, not queue unboundedly
    ]
    results = {}
    for tag, factor, slo in cells:
        server = GraphServer(
            model, state, ladder,
            ServeConfig(
                micro_batch_graphs=int(os.getenv("BENCH_SERVE_BATCH", "8")),
                batch_window_s=0.002,
                retrace_policy="error",
                slo_p99_s=slo,
                expected_latency_per_graph_s=per_graph_s,
                max_queue_requests=1024,
            ),
            template_graphs=graphs,
        ).start()
        try:
            assert server.wait_ready(600), server.failed
            cell = _serve_load_cell(
                server, graphs, max(capacity * factor, 1.0), duration
            )
            stats = server.stats()
        finally:
            server.close(drain=False)
        assert stats["retrace_violations"] == 0, (
            f"cell {tag}: retraces under sustained load: "
            f"{stats['retrace_violations']}"
        )
        cell.update(
            variant=tag,
            slo_p99_s=round(slo, 6),
            batches=stats["batches"],
            metric="serve load cell (GraphServer, error-mode sentinel)",
            unit="graphs/sec",
            value=cell["achieved_gps"],
            capacity_gps=round(capacity, 1),
            device_kind=_device_kind(),
        )
        results[tag] = cell
        _bank(json.dumps(cell))
    # structural sanity — the cells' claims, enforced where they're made
    assert results["overload"]["shed"] > 0, (
        "overload cell did not shed with the SLO armed: "
        f"{results['overload']}"
    )
    for tag in ("light", "at_slo"):
        c = results[tag]
        assert c["completed"] > 0 and c["p50_ms"] <= c["p99_ms"], (tag, c)

    # ---- weights_dtype A/B (ISSUE 16 satellite; docs/SERVING.md): the
    # bf16 inference-weights cast vs the float32 default, closed-loop
    # throughput on the same calibration workload. Recorded, not asserted:
    # the win is a TPU memory-bandwidth effect, CPU may show none.
    wdt_ab = {}
    for wdt in ("float32", "bfloat16"):
        server = GraphServer(
            model, state, ladder,
            ServeConfig(
                micro_batch_graphs=int(os.getenv("BENCH_SERVE_BATCH", "8")),
                batch_window_s=0.002, retrace_policy="error",
                max_queue_requests=0, weights_dtype=wdt,
            ),
            template_graphs=graphs,
        ).start()
        try:
            assert server.wait_ready(600), (wdt, server.failed)
            t0 = time.perf_counter()
            out = server.predict(
                [graphs[j % len(graphs)] for j in range(n_cal)], timeout=120
            )
            assert all(isinstance(o, dict) for o in out), (wdt, "A/B failed")
            wdt_ab[wdt] = n_cal / (time.perf_counter() - t0)
        finally:
            server.close(drain=False)
    _bank(json.dumps({
        "metric": "serve weights_dtype A/B "
                  "(Serving.weights_dtype: float32 vs bfloat16 cast)",
        "unit": "graphs/sec",
        "f32_gps": round(wdt_ab["float32"], 1),
        "bf16_gps": round(wdt_ab["bfloat16"], 1),
        "bf16_vs_f32": round(
            wdt_ab["bfloat16"] / max(wdt_ab["float32"], 1e-9), 3
        ),
        "graphs": n_cal,
        "device_kind": _device_kind(),
        "ok": True,
    }))

    # ---- int8 quantized cells (ISSUE 20 tentpole; docs/SERVING.md
    # "Quantization"): weight-only and w8a8 serving on the same workload —
    # closed-loop capacity, open-loop p50/p99 at half that capacity, HBM
    # weight bytes vs the fp32 tree, and the accuracy gate's certified
    # relative max error. The speed columns are recorded (int8 wins are a
    # TPU memory-bandwidth/MXU effect; CPU emulation may show none), the
    # error column is gated lower-is-better round-over-round.
    import jax as _jax

    f32_weight_bytes = sum(
        int(a.size) * int(a.dtype.itemsize)
        for a in _jax.tree_util.tree_leaves(state.params)
    )
    quant_max_err = float(os.getenv("BENCH_SERVE_QUANT_MAX_ERR", "0.1"))
    int8_cells = {}
    for mode in ("weight_only", "w8a8"):
        server = GraphServer(
            model, state, ladder,
            ServeConfig(
                micro_batch_graphs=int(os.getenv("BENCH_SERVE_BATCH", "8")),
                batch_window_s=0.002, retrace_policy="error",
                max_queue_requests=1024, weights_dtype="int8",
                quantization={"mode": mode, "calibration_batches": 2,
                              "max_error": quant_max_err},
            ),
            template_graphs=graphs,
        ).start()
        try:
            assert server.wait_ready(600), (mode, server.failed)
            t0 = time.perf_counter()
            out = server.predict(
                [graphs[j % len(graphs)] for j in range(n_cal)], timeout=120
            )
            assert all(isinstance(o, dict) for o in out), (mode, "failed")
            int8_capacity = n_cal / (time.perf_counter() - t0)
            cell = _serve_load_cell(
                server, graphs, max(int8_capacity * 0.5, 1.0), duration
            )
            q_report = server.stats().get("quantization") or {}
            int8_weight_bytes = server._state.weight_nbytes()
        finally:
            server.close(drain=False)
        cell.update(
            variant=f"int8_{mode}",
            metric="serve int8 quantized cell (Serving.weights_dtype: "
                   "int8, accuracy-gated)",
            unit="graphs/sec",
            value=cell["achieved_gps"],
            capacity_gps=round(int8_capacity, 1),
            weight_bytes_int8=int(int8_weight_bytes),
            weight_bytes_f32=int(f32_weight_bytes),
            weight_bytes_ratio=round(
                int8_weight_bytes / max(f32_weight_bytes, 1), 3
            ),
            # NOTE "quant_rel_error", not *max_error*: only the combined
            # gate record below may carry bench_gate-matching key names —
            # the mix gate compares the newest two matching records, so a
            # second matching record per invocation would derail it
            quant_rel_error=q_report.get("max_error"),
            quant_mode=mode,
            quant_source=q_report.get("source"),
            device_kind=_device_kind(),
        )
        int8_cells[mode] = cell
        _bank(json.dumps(cell))
    # round-over-round gate keys, merged into the single gate record the
    # fleet section banks (bench_gate.py --mix-cells on serve_cells.jsonl):
    # capacity must not collapse (higher-is-better *graphs_per_sec*), the
    # certified quantization error must not grow (lower-is-better
    # *max_error*)
    int8_gate_keys = {
        **{
            f"int8_{m}_graphs_per_sec": c["capacity_gps"]
            for m, c in int8_cells.items()
        },
        **{
            f"int8_{m}_quant_max_error": c["quant_rel_error"]
            for m, c in int8_cells.items()
            if c["quant_rel_error"] is not None
        },
    }

    # ---- fleet cells (ISSUE 19 tentpole; docs/SERVING.md "Fleet"): the
    # failover router fronting {1, 2, 4} replicas — aggregate closed-loop
    # graphs/sec and client-side p99 vs replica count, plus the
    # prediction-cache hit-rate cell. Replicas are in-process GraphServers
    # behind LocalReplicaClients so the cells measure the ROUTER's scaling
    # (balancing + dispatch overhead), not subprocess spawn/warm-up cost —
    # run-scripts/serve_fleet_smoke.py covers the subprocess path.
    import tempfile
    import threading

    import numpy as np

    from hydragnn_tpu.serve import (
        FleetRouter, LocalReplicaClient, PredictionCache,
    )

    def _fleet_cell(n_replicas, cache=None, closed_passes=None):
        """One fleet measurement: ``closed_passes`` (when set) drives that
        many sequential passes over the graph set through one worker (the
        deterministic cache cell); otherwise 2x``n_replicas`` workers run
        closed-loop for ``duration`` seconds."""
        servers = [
            GraphServer(
                model, state, ladder,
                ServeConfig(
                    micro_batch_graphs=int(
                        os.getenv("BENCH_SERVE_BATCH", "8")
                    ),
                    batch_window_s=0.002, retrace_policy="error",
                    max_queue_requests=1024,
                ),
                template_graphs=graphs,
            ).start()
            for _ in range(n_replicas)
        ]
        try:
            for s in servers:
                assert s.wait_ready(600), s.failed
            router = FleetRouter(
                {
                    f"replica{k + 1}": LocalReplicaClient(
                        s, name=f"replica{k + 1}"
                    )
                    for k, s in enumerate(servers)
                },
                cfg=ServeConfig(router_timeout_s=120.0),
                cache=cache,
            )
            lats, lock = [], threading.Lock()
            t_start = time.perf_counter()
            if closed_passes:
                for _ in range(closed_passes):
                    for g in graphs:
                        t0 = time.perf_counter()
                        router.predict(g, timeout_s=120.0)
                        lats.append(time.perf_counter() - t0)
            else:
                n_workers = max(2 * n_replicas, 2)
                stop_at = t_start + duration

                def pump(wid):
                    j, mine = wid, []
                    while time.perf_counter() < stop_at:
                        t0 = time.perf_counter()
                        router.predict(
                            graphs[j % len(graphs)], timeout_s=120.0
                        )
                        mine.append(time.perf_counter() - t0)
                        j += n_workers
                    with lock:
                        lats.extend(mine)

                workers = [
                    threading.Thread(target=pump, args=(w,))
                    for w in range(n_workers)
                ]
                for w in workers:
                    w.start()
                for w in workers:
                    w.join()
            elapsed = time.perf_counter() - t_start
            stats = router.stats()
            router.close()
        finally:
            for s in servers:
                s.close(drain=False)
        assert stats["failed"] == 0, (n_replicas, stats)
        arr = np.array(lats)
        return {
            "replicas": n_replicas,
            "aggregate_gps": round(len(lats) / elapsed, 1),
            "requests": len(lats),
            "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 3),
            "cache_hits": stats["cache_hits"],
            "cache_hit_rate": round(
                stats["cache_hits"] / max(stats["requests"], 1), 4
            ),
        }

    fleet_counts = [
        int(r) for r in os.getenv("BENCH_SERVE_FLEET", "1,2,4").split(",")
        if r.strip()
    ]
    fleet_cells = {}
    for r in fleet_counts:
        cell = _fleet_cell(r)
        fleet_cells[r] = cell
        cell.update(
            variant=f"fleet_r{r}",
            metric="serve fleet cell (FleetRouter over in-process "
                   "replicas, closed-loop aggregate)",
            unit="graphs/sec",
            value=cell["aggregate_gps"],
            device_kind=_device_kind(),
        )
        _bank(json.dumps(cell))
    # deterministic cache cell: two passes over the same graph set — the
    # second is served entirely from the content-addressed cache
    cache_cell = _fleet_cell(
        1, cache=PredictionCache(tempfile.mkdtemp(prefix="bench_pcache_")),
        closed_passes=2,
    )
    assert cache_cell["cache_hit_rate"] >= 0.45, cache_cell
    cache_cell.update(
        variant="fleet_cache",
        metric="serve fleet prediction-cache cell (two passes, second "
               "pass fully cached)",
        unit="hit_rate",
        device_kind=_device_kind(),
    )
    _bank(json.dumps(cache_cell))
    # round-over-round gate record (bench_gate.py --mix-cells on
    # logs/serve_cells.jsonl): *_graphs_per_sec keys must not collapse
    _bank(json.dumps({
        "metric": "serve fleet scaling + int8 quantization (gate record)",
        **{
            f"fleet_r{r}_graphs_per_sec": c["aggregate_gps"]
            for r, c in fleet_cells.items()
        },
        **int8_gate_keys,
        "fleet_cache_hit_rate": cache_cell["cache_hit_rate"],
        "ok": True,
    }))
    _bank(json.dumps({
        "metric": "serve_cells_done",
        "cells": len(results),
        "fleet_cells": len(fleet_cells) + 1,
        "capacity_gps": round(capacity, 1),
        "slo_p99_s": round(slo_p99_s, 6),
        "throughput_at_slo_gps": results["at_slo"]["achieved_gps"],
        "overload_shed_rate": results["overload"]["shed_rate"],
        "ok": True,
    }))


def main_mix():
    """BENCH_MIX=1: GFM mixture-plane cells (docs/GFM.md "Benchmarks").

    Two cells over an N-family synthetic mixture (``BENCH_MIX_FAMILIES``,
    default 3; hardware rounds raise families/configs/epochs to the
    OC20+ANI+QM9-shaped mix):

    - ``mix_stream``: host-side draw->validate->ladder-pack throughput of
      the MixturePlane alone (graphs/sec, plus per-source graphs/sec from
      the draw tallies) — the loader ceiling of the mixture path;
    - ``mix_train``: a short balanced multibranch training through the
      plane (graphs/sec end to end, final per-branch loss-drift maximum
      from the EMA monitor — the balanced-loss health number the gate
      watches: a drift that GROWS round-over-round means a branch is
      starving).

    One JSON record per invocation appends to ``logs/mix_cells.jsonl``;
    ``run-scripts/bench_gate.py --mix-cells`` compares the newest two
    records (throughput higher-better, drift lower-better)."""
    import dataclasses

    import numpy as np

    from hydragnn_tpu.api import prepare_data
    from hydragnn_tpu.data.pipeline import (
        MinMax,
        VariablesOfInterest,
        extract_variables,
        split_dataset,
    )
    from hydragnn_tpu.data.synthetic import deterministic_graph_dataset

    families = int(os.getenv("BENCH_MIX_FAMILIES", "3"))
    n_conf = int(os.getenv("BENCH_MIX_CONFIGS", "180"))
    epochs = int(os.getenv("BENCH_MIX_EPOCHS", "3"))
    batch = int(os.getenv("BENCH_MIX_BATCH", "16"))

    raw = deterministic_graph_dataset(n_conf, seed=11)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["s"], ["graph"], [0], [1, 1, 1], [1])
    ready = [
        dataclasses.replace(extract_variables(g, voi), dataset_id=i % families)
        for i, g in enumerate(raw)
    ]
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    gh = {"num_sharedlayers": 1, "dim_sharedlayers": 8,
          "num_headlayers": 2, "dim_headlayers": [8, 8]}
    config = {
        "Verbosity": {"level": 0},
        "Dataset": {"node_features": {"dim": [1, 1, 1]},
                    "graph_features": {"dim": [1]}},
        "Mixture": {"temperature": 2.0},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "hidden_dim": 8, "num_conv_layers": 2,
                "task_weights": [1.0],
                "output_heads": {"graph": [
                    {"type": f"branch-{b}", "architecture": dict(gh)}
                    for b in range(families)
                ]},
            },
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["s"],
                "output_index": [0], "type": ["graph"],
            },
            "Training": {
                "num_epoch": epochs, "batch_size": batch, "seed": 7,
                "precompile": "blocking", "retrace_policy": "error",
                "Optimizer": {"type": "AdamW", "learning_rate": 0.01},
            },
        },
    }
    config, (tr_l, va_l, te_l), _ = prepare_data(config, datasets=(tr, va, te))

    cells = {"ts": round(time.time(), 3), "metric": "mixture plane cells",
             "families": families, "device_kind": _device_kind()}
    # ---- mix_stream: host batching throughput of the plane alone
    tr_l.set_epoch(0)
    t0 = time.perf_counter()
    n_graphs = 0
    for b in tr_l:
        n_graphs += int(np.asarray(b.graph_mask).sum())
    dt = max(time.perf_counter() - t0, 1e-9)
    cells["mix_stream_graphs_per_sec"] = round(n_graphs / dt, 1)
    for sid in sorted(tr_l.sources):
        name = tr_l.sources[sid].name
        cells[f"mix_source_{name}_graphs_per_sec"] = round(
            tr_l.epoch_draws.get(sid, 0) / dt, 1
        )
    tr_l.epoch_draws, tr_l.epoch_skips = {}, {}

    # ---- mix_train: balanced multibranch training end to end
    from hydragnn_tpu.models.create import create_model, init_model
    from hydragnn_tpu.train import train_validate_test
    from hydragnn_tpu.train.optimizer import make_optimizer
    from hydragnn_tpu.train.state import TrainState

    prev_valtest = os.environ.get("HYDRAGNN_VALTEST")
    os.environ["HYDRAGNN_VALTEST"] = "0"
    try:
        from hydragnn_tpu.utils.timers import Timer

        model = create_model(config)
        variables = init_model(model, next(iter(tr_l)), seed=7)
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        state = TrainState.create(variables, tx)
        Timer.reset()
        t0 = time.perf_counter()
        state, hist = train_validate_test(
            model, state, tx, tr_l, va_l, te_l, config,
            log_name="bench_mix", seed=7,
        )
        dt = max(time.perf_counter() - t0, 1e-9)
        # gate steady-state goodput, not the (epoch-count-dependent) share
        # of the compile bill: first-step latency carries warm-up/compile
        ttfs = Timer.totals().get("time_to_first_step", 0.0)
        steady = max(dt - ttfs, 1e-9)
    finally:
        if prev_valtest is None:
            os.environ.pop("HYDRAGNN_VALTEST", None)
        else:
            os.environ["HYDRAGNN_VALTEST"] = prev_valtest
    total_graphs = len(tr_l) * batch * len(hist["train"])
    cells["mix_train_graphs_per_sec"] = round(
        max(total_graphs - batch, 0) / steady, 1
    )
    cells["mix_time_to_first_step_s"] = round(ttfs, 3)
    cells["mix_train_loss"] = round(float(hist["train"][-1]), 6)
    ema = tr_l.drift.ema
    if ema:
        vals = sorted(ema.values())
        median = vals[len(vals) // 2] or 1.0
        cells["mix_loss_drift_max"] = round(max(ema.values()) / median, 4)
    assert hist["train"][-1] < hist["train"][0], (
        f"mixture training did not learn: {hist['train']}"
    )

    os.makedirs("logs", exist_ok=True)
    line = json.dumps(cells)
    print(line, flush=True)
    with open(os.path.join("logs", "mix_cells.jsonl"), "a") as fh:
        fh.write(line + "\n")


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def main():
    # the persistent compilation cache, where the one placement rule says
    # (train/compile_plane.py compile_cache_dir): restarts and the driver's
    # run hit warm executables
    from hydragnn_tpu.train.compile_plane import setup_compile_cache

    setup_compile_cache()
    if os.getenv("BENCH_GPS_SMOKE", "0") == "1":
        smoke_gps()
        return
    if os.getenv("BENCH_GUARD_SMOKE", "0") == "1":
        smoke_guard()
        return
    if os.getenv("BENCH_PNA_SMOKE", "0") == "1":
        smoke_pna()
        return
    if os.getenv("BENCH_SERVE", "0") == "1":
        main_serve()
        return
    if os.getenv("BENCH_MIX", "0") == "1":
        main_mix()
        return
    if os.getenv("BENCH_AB", "0") == "1":
        main_ab()
        return
    # a device without a listed peak cannot bank an MFU: fail before timing
    _peak_flops(_device_kind())
    # synthetic-PNA leg first (small compile, regression guard): the
    # production leg's HBM footprint in the same process skews the small
    # workload ~5x (measured, not vice versa)
    syn = _bench_synthetic_pna()
    prod = _bench_production()
    print(
        json.dumps(
            {
                "metric": _PROD_METRIC,
                "value": round(prod["graphs_per_sec"], 2),
                "unit": "graphs/sec/chip",
                "vs_baseline": round(syn / RECORDED_BASELINE, 3),
                "mfu": round(prod["mfu"], 4),
                "padding_waste": round(prod["padding_waste"], 4),
                "mfu_est": round(prod["mfu_est"], 4),
                "flops_per_graph": round(prod["flops_per_graph"]),
                "time_to_first_step": round(prod["time_to_first_step"], 3),
                "compile_time_s": round(prod["compile_time_s"], 3),
                "device": prod["device"],
                "peak_flops_assumed": prod["peak_flops_assumed"],
                "synthetic_pna_graphs_per_sec": round(syn, 2),
                "synthetic_pna_round1": RECORDED_BASELINE,
                # finite loss = the bf16 step is numerically sane on-chip
                "train_loss": round(prod["loss"], 5),
                "mixed_precision": _default_mp(),
                "sorted_aggregation": _default_sorted(),
                "pack_batches": _default_pack(),
            }
        )
    )


if __name__ == "__main__":
    main()
