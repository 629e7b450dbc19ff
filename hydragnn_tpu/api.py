"""Public convenience API: ``run_training(config)`` / ``run_prediction(config)``.

Mirrors the reference's two-call surface (hydragnn/run_training.py:48-63,
hydragnn/run_prediction.py:34-49): accepts a config file path or dict, loads
and splits data, completes the config from it, builds the model, trains, and
checkpoints. The DDP/DeepSpeed wrapping steps of the reference are replaced by
mesh sharding (hydragnn_tpu/parallel) applied inside the jitted step.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Tuple

from .config import (
    get_log_name_config,
    load_config,
    save_config,
    update_config,
    voi_from_config,
)
from .data.graph import Graph, PadSpec, SpecLadder
from .data.pipeline import (
    GraphLoader,
    MinMax,
    extract_variables,
    select_input_columns,
    split_dataset,
)
from .data.synthetic import deterministic_graph_dataset
from .data.transforms import apply_dataset_transforms, wants_transforms
from .models.create import create_model, init_model
from .train.checkpoint import (
    clear_loader_state,
    load_existing_model,
    load_loader_state,
    load_mixture_state,
    save_loader_state,
    save_mixture_state,
    save_model,
)
from .train.loop import test_model, train_validate_test
from .train.optimizer import make_optimizer
from .train.state import TrainState
from .utils import envflags


def _localize_loader(loader: GraphLoader) -> GraphLoader:
    """Unstacked single-host view of a (possibly device-stacked) loader —
    prediction/visualization run per host with the plain jitted eval step,
    which expects batches without the leading device axis."""
    if loader.num_shards == 1:
        return loader
    return GraphLoader(
        loader.graphs,
        loader.batch_size,
        shuffle=False,
        host_count=loader.host_count,
        host_index=loader.host_index,
        # the Pallas sorted-segment route is baked into the model when
        # use_sorted_aggregation is on — the localized loader must keep
        # feeding receiver-sorted batches or its sums are unspecified
        sort_edges=loader.sort_edges,
    )


def _load_raw_dataset(config: Dict[str, Any]) -> List[Graph]:
    """Dataset from config. Formats: 'synthetic' (deterministic BCC fixture,
    the analog of the reference's unit_test format) and 'pickle'
    (reference: dataset_loading_and_splitting, load_data.py:206-222)."""
    ds = config.get("Dataset", {})
    fmt = ds.get("format", "synthetic")
    if fmt in ("synthetic", "unit_test"):
        opts = ds.get("synthetic", {})
        return deterministic_graph_dataset(
            number_configurations=opts.get("number_configurations", 300),
            linear_only=opts.get("linear_only", False),
            radius=config["NeuralNetwork"]["Architecture"].get("radius", 2.0) or 2.0,
            max_neighbours=config["NeuralNetwork"]["Architecture"].get("max_neighbours")
            or 100,
            seed=opts.get("seed", 97),
        )
    if fmt == "lennard_jones":
        from .data.synthetic import lennard_jones_dataset

        opts = dict(ds.get("lennard_jones", {}))
        arch = config["NeuralNetwork"]["Architecture"]
        opts.setdefault("radius", arch.get("radius", 2.5) or 2.5)
        if arch.get("max_neighbours"):
            opts.setdefault("max_neighbours", arch["max_neighbours"])
        return lennard_jones_dataset(**opts)
    if fmt == "pickle":
        from .data.datasets import SimplePickleDataset

        return list(SimplePickleDataset(ds["path"]["total"], ds["name"]))
    if fmt == "columnar":
        from .data.columnar import ColumnarDataset

        # samples are materialized as host Graphs for the split/normalize
        # pipeline; mmap/shmem modes bound the *raw array* residency during
        # the read, not the materialized working set
        return list(
            ColumnarDataset(ds["path"]["total"], mode=ds.get("mode", "mmap"))
        )
    if fmt in ("LSMS", "XYZ", "CFG"):
        from .data.raw import finalize_graphs, load_raw_dataset

        arch = config["NeuralNetwork"]["Architecture"]
        kwargs = {}
        if fmt == "LSMS":
            nf = ds.get("node_features", {})
            gf = ds.get("graph_features", {})
            if "column_index" in nf:
                kwargs["node_feature_cols"] = nf["column_index"]
                kwargs["node_feature_dims"] = nf["dim"]
            if "column_index" in gf:
                kwargs["graph_feature_cols"] = gf["column_index"]
                kwargs["graph_feature_dims"] = gf["dim"]
            kwargs["charge_density_correction"] = ds.get(
                "charge_density_correction", False
            )
        # warn_skip/quarantine extend to the file level: a truncated or
        # garbled raw dump drops that file (counted + warned) instead of
        # killing the run; 'error' keeps the historical fail-fast parse
        kwargs["on_error"] = (
            "raise"
            if ds.get("bad_sample_policy", "warn_skip") == "error"
            else "skip"
        )
        raw = load_raw_dataset(ds["path"]["total"], fmt, **kwargs)
        return finalize_graphs(
            raw,
            radius=arch.get("radius", 5.0) or 5.0,
            max_neighbours=arch.get("max_neighbours"),
            periodic=arch.get("periodic_boundary_conditions", False),
        )
    raise ValueError(f"unknown Dataset.format {fmt!r}")


def _zero_stage(training: Dict[str, Any]) -> int:
    """ZeRO stage from the Optimizer block (reference: DeepSpeed ds_config
    zero stage, run_training.py:136-149). ``use_zero_redundancy`` alone
    means stage 1."""
    opt = training.get("Optimizer", {})
    use_zero = opt.get("use_zero_redundancy", False)
    return int(opt.get("zero_stage", 1 if use_zero else 0))


def _wants_zero2_mesh(training: Dict[str, Any]) -> bool:
    """Whether a single-host multi-device run must take the mesh step for
    ZeRO-2 (the gradient constraint lives inside the mesh step)."""
    import jax

    if _zero_stage(training) < 2:
        return False
    if bool(training.get("branch_parallel", False)):
        # no silent downgrade: the branch-parallel step has no ZeRO path
        raise ValueError(
            "Optimizer.zero_stage >= 2 is not supported together with "
            "Training.branch_parallel (the branch-parallel step shards "
            "decoders, not gradients/moments); drop one of the two"
        )
    return jax.process_count() == 1 and jax.local_device_count() > 1


def _wants_mesh_step(config: Dict[str, Any]) -> bool:
    """Whether a single-host multi-device run takes the (unrouted) mesh
    step over every local device: ZeRO-2/3, or an EXPLICIT
    ``Parallel.rules`` table ("dp" included). ONE predicate shared by
    prepare_data's loader gate and run_training's step selection — they
    must agree or the mesh step sees unstacked batches.

    The implicit default (no ``Parallel.rules``, no ZeRO stage >= 2) stays
    on one device: run_training then says once how many it leaves idle."""
    import jax

    training = config["NeuralNetwork"]["Training"]
    if _wants_zero2_mesh(training):
        return True
    explicit = (config.get("Parallel") or {}).get("rules") is not None
    return (
        explicit
        and not bool(training.get("branch_parallel", False))
        and jax.process_count() == 1
        and jax.local_device_count() > 1
    )


def resolve_parallel(config: Dict[str, Any]):
    """Resolve the run's sharding rule table (parallel/rules.py) — the ONE
    placement decision every entry point shares (train / predict / serve,
    all via prepare_data, and run_training's step selection).

    ``Parallel.rules`` (preset name or inline table) wins; otherwise the
    table derives from the legacy ``Training`` keys. Validation is EAGER
    (bad regex / unknown axis / preset-vs-flag conflicts raise here, never
    from inside a trace), the resolved table is recorded under
    ``Parallel.resolved_rules`` so the saved run config replays the
    identical placement on restore, and the legacy gate keys are
    normalized to match the table so prepare_data's loader routing and
    run_training's step selection can never disagree:

    - a routed (branch/mp) table sets ``Training.branch_parallel``;
    - a non-routed table with grads/params/opt_state rules raises
      ``Optimizer.zero_stage`` to the implied stage (never lowers it).

    Idempotent — safe to call from prepare_data AND run_training."""
    from .parallel import rules as parallel_rules

    table = parallel_rules.resolve(config)
    section = config.setdefault("Parallel", {})
    section["resolved_rules"] = table.to_config()
    training = config.setdefault("NeuralNetwork", {}).setdefault(
        "Training", {}
    )
    if table.routed:
        training["branch_parallel"] = True
    else:
        implied = (
            3
            if table.shards("params")
            else 2
            if table.shards("grads")
            else 1
            if table.shards("opt_state")
            else 0
        )
        if implied > _zero_stage(training):
            training.setdefault("Optimizer", {})["zero_stage"] = implied
    return table


def _make_validator(config: Dict[str, Any]):
    """Run-level SampleValidator from ``Dataset.bad_sample_policy``
    (docs/ROBUSTNESS.md "Data plane"): one instance spans ingest filtering
    and every loader, so its per-reason tally is the run's complete
    skipped-sample record. Quarantine manifests land in the run dir."""
    from .data.validate import SampleValidator

    policy = str(
        config.get("Dataset", {}).get("bad_sample_policy", "warn_skip")
    )
    quarantine_dir = None
    if policy == "quarantine":
        quarantine_dir = os.path.join(
            "./logs", get_log_name_config(config), "quarantine"
        )
    return SampleValidator(policy, quarantine_dir=quarantine_dir)


def prepare_data(
    config: Dict[str, Any], datasets: Optional[Tuple[List[Graph], ...]] = None
):
    """Load -> normalize -> select variables -> split -> loaders; returns
    (completed config, loaders, minmax).

    Every sample passes the data-plane validation gate (data/validate.py)
    BEFORE normalization/splitting — one NaN feature reaching
    ``MinMax.fit`` would NaN the normalization of the whole dataset, so
    dirty samples are dropped (or raised on, per
    ``Dataset.bad_sample_policy``) at the door; the validator rides on the
    returned loaders so the epoch loop can log the tally."""
    # resolve + record the sharding rule table FIRST: it validates the
    # Parallel section eagerly and normalizes the Training gate keys the
    # loader-routing decisions below read (resolve_parallel)
    resolve_parallel(config)
    validator = _make_validator(config)
    from .utils import faultinject

    if datasets is None:
        raw = _load_raw_dataset(config)
        ds_cfg = config.get("Dataset", {})
        if wants_transforms(ds_cfg):
            # load-time geometric transforms (reference:
            # serialized_dataset_loader.py:130-180). Rotation is shift/cell
            # aware so applying it after edge construction is exact.
            (raw,) = apply_dataset_transforms(ds_cfg, raw)
        # chaos hook (exact no-op unarmed): NaN-poison armed sample indices
        # so the validation gate below is exercised end-to-end with a skip
        # tally that must match the injection plan
        raw = faultinject.poison_samples(raw)
        raw = validator.filter(raw, source="ingest")
        if config["NeuralNetwork"]["Training"].get("compute_grad_energy", False):
            # energy/forces ride on the graphs directly (no target extraction
            # or minmax scaling — physical units matter); input node-feature
            # column selection still applies
            mm = None
            voi = voi_from_config(config)
            ready = [select_input_columns(g, voi) for g in raw]
        else:
            mm = MinMax.fit(raw)
            if config.get("Dataset", {}).get("normalize", True):
                raw = mm.apply(raw)
            voi = voi_from_config(config)
            ready = [extract_variables(g, voi) for g in raw]
        arch = config["NeuralNetwork"]["Architecture"]
        if arch.get("global_attn_engine"):
            # Laplacian PE + relative edge PE feed GPS (reference:
            # serialized_dataset_loader.py:89-94,182-189)
            from .data.lappe import add_dataset_pe

            # eigendecomposition results ride a topology-keyed disk cache
            # (Dataset.lappe_cache, default on) so re-runs and resumes skip
            # the O(N^3) per-graph eigh sweep (data/lappe.py)
            ready = add_dataset_pe(
                ready,
                int(arch.get("pe_dim") or 1),
                cache=ds_cfg.get("lappe_cache", True),
            )
        trainset, valset, testset = split_dataset(
            ready,
            perc_train=config["NeuralNetwork"]["Training"].get("perc_train", 0.7),
            seed=0,
            stratified=config.get("Dataset", {}).get(
                "compositional_stratified_splitting", False
            ),
        )
    else:
        trainset, valset, testset = datasets
        mm = None
        ds_cfg = config.get("Dataset", {})
        if wants_transforms(ds_cfg):
            # explicit-datasets path gets the same transform chain, with one
            # edge-length max shared across the three splits
            trainset, valset, testset = apply_dataset_transforms(
                ds_cfg, trainset, valset, testset
            )
        # explicit datasets get the same validation gate, per split
        trainset = validator.filter(trainset, source="train")
        valset = validator.filter(valset, source="val")
        testset = validator.filter(testset, source="test")

    config = update_config(config, trainset, valset, testset)
    if validator.policy == "quarantine":
        # the run name is derived from COMPLETED config keys — retarget the
        # manifest to the real run dir (any ingest-time entries move along)
        validator.set_quarantine_dir(
            os.path.join("./logs", get_log_name_config(config), "quarantine")
        )
    training = config["NeuralNetwork"]["Training"]
    arch = config["NeuralNetwork"]["Architecture"]
    batch_size = training["batch_size"]
    # multi-host: each process loads a disjoint 1/host_count slice
    # (DistributedSampler semantics) and stacks one shard per local device
    # for the global-mesh DP step (docs/MULTIHOST.md)
    import jax

    from .parallel import local_host_info

    host_count, host_index = local_host_info()
    num_shards = jax.local_device_count() if jax.process_count() > 1 else 1
    # single-host branch-parallel still needs stacked, branch-routed rows
    from .models.create import num_branches_from

    num_branches = num_branches_from(arch)
    if (
        bool(training.get("branch_parallel", False))
        and num_branches > 1
        and jax.process_count() == 1
        and jax.local_device_count() > 1
    ):
        num_shards = jax.local_device_count()
    # single-host mesh-step runs (ZeRO-2/3, or an explicit Parallel.rules
    # table) need stacked batches too — _wants_mesh_step is the SAME
    # predicate run_training uses
    if _wants_mesh_step(config):
        num_shards = jax.local_device_count()
    if batch_size % num_shards != 0:
        raise ValueError(
            f"Training.batch_size {batch_size} must be divisible by the "
            f"{num_shards} local devices on multi-host runs"
        )
    # bucketed pad specs when graph sizes vary (SURVEY §5.7): a few jit
    # specializations instead of one worst-case padding for every batch
    # (default set by update_config)
    num_buckets = int(training["num_pad_buckets"])
    # opt-in size-homogeneous batch composition; measured on the OC20-shaped
    # distribution it LOSES to random batching at batch sizes >= 32 (CLT
    # already concentrates random batch totals; docs/PERFORMANCE.md), so the
    # default stays off — the ladder simulation must match the policy either
    # way, or bucketed small batches never fit a level
    size_bucketing = bool(training.get("size_bucketed_batching", False))
    # packed batching: greedy bin-packing into ONE fixed budget, variable
    # real-graph count per batch — a single jit specialization at ~95%
    # occupancy; multi-host epoch lengths agree communication-free via
    # simulated packing of every host's slice (docs/PERFORMANCE.md)
    pack = bool(training.get("pack_batches", False))
    if pack:
        # ONE budget over all three splits, so eval reuses the train step's
        # compilation (the whole point of pack mode; per-split auto budgets
        # would each be their own jit specialization)
        from .data.pipeline import _pack_spec

        spec = _pack_spec(
            trainset + valset + testset,
            max(batch_size // num_shards, 1),
            with_triplets=arch["mpnn_type"] == "DimeNet",
            node_slots=training.get("pack_node_slots"),
            graph_slots=training.get("pack_graph_slots"),
        )
    else:
        spec = SpecLadder.for_dataset(
            trainset + valset + testset,
            batch_size // num_shards,
            num_buckets=num_buckets,
            with_triplets=arch["mpnn_type"] == "DimeNet",
            size_bucketing=size_bucketing,
        )
    shard_kw = dict(
        spec=spec,
        pack=pack,
        host_count=host_count,
        host_index=host_index,
        num_shards=num_shards,
        size_bucketing=size_bucketing,
        # receiver-sorted edges feed the Pallas segment kernel (TPU). No
        # max_in_degree here: update_config already validated the dataset's
        # top in-degree against the bound (config.py:194-207); the loader
        # check exists for directly constructed loaders
        sort_edges=bool(arch.get("use_sorted_aggregation", False)),
        # data-plane fault tolerance: batch-time budget policing rides the
        # run's validator, and the prefetch watchdog turns a wedged producer
        # into an actionable LoaderStallError (docs/ROBUSTNESS.md)
        validator=validator,
        stall_timeout=float(training.get("loader_stall_timeout", 600.0) or 0.0),
    )
    # equal per-dataset step budget for GFM fleets: weighted draws with
    # replacement, the SPMD analog of the reference's uneven branch process
    # groups (examples/multibranch/train.py:166-213; data.branch_sample_weights)
    balance = bool(training.get("balance_branch_sampling", False))
    sample_weights = None
    if balance:
        from .data import branch_sample_weights

        ids = sorted({g.dataset_id for g in trainset})
        sample_weights = branch_sample_weights(
            trainset, {i: 1.0 for i in ids}
        )
    # GFM mixture plane (docs/GFM.md): a ``Mixture`` config section swaps
    # the train loader for the streaming temperature-sampled multi-source
    # scheduler; val/test stay plain ladder loaders over the merged splits
    # (deterministic eval), sharing the same spec ladder so every
    # specialization is reused across train and eval
    if config.get("Mixture"):
        if pack:
            raise ValueError(
                "the Mixture section is not supported with "
                "Training.pack_batches: mixture batches are drawn at a "
                "FIXED graph count and ladder-padded, while pack mode bins "
                "a variable graph count into one budget — the two batch "
                "composers are mutually exclusive by construction. Drop "
                "Training.pack_batches (use Training.num_pad_buckets for "
                "the few-specializations effect) or drop the Mixture "
                "section"
            )
        if balance:
            raise ValueError(
                "Training.balance_branch_sampling is subsumed by the "
                "Mixture section (Mixture.temperature/weights set the "
                "per-source draw shares); drop one of the two"
            )
        from .mix import MixturePlane, sources_from_graphs

        if (
            bool(training.get("branch_parallel", False))
            and num_branches > 1
            and num_shards > 1
        ):
            # routed rule tables need branch-routed shard rows: one
            # MixturePlane per served branch, rows stacked branch-major
            # (parallel/routing.py BranchRoutedMixture); per-branch
            # decoders are then placed by the branch rule preset
            # (parallel/rules.py -> parallel/engine.py)
            from .parallel.routing import (
                BranchRoutedLoader,
                BranchRoutedMixture,
            )

            route_kw = dict(
                branch_count=num_branches,
                num_shards=num_shards,
                host_count=host_count,
                host_index=host_index,
                sort_edges=shard_kw["sort_edges"],
                spec=spec,
            )
            train_loader = BranchRoutedMixture(
                sources_from_graphs(trainset),
                batch_size,
                settings=config["Mixture"],
                seed=int(training.get("seed", 0)),
                validator=validator,
                **route_kw,
            )
            val_loader = BranchRoutedLoader(
                valset, batch_size, shuffle=False, oversampling=False,
                **route_kw,
            )
            test_loader = BranchRoutedLoader(
                testset, batch_size, shuffle=False, oversampling=False,
                **route_kw,
            )
            train_loader.validator = validator
            return config, (train_loader, val_loader, test_loader), mm
        # flat (data-parallel) mixture: each host owns a disjoint draw
        # stripe of the SAME absolute draw sequence (mix/plane.py "host
        # loss"). Stripe identity comes from the fleet plane's view so a
        # simulated fleet (HYDRAGNN_FLEET_HOST_INDEX/_COUNT, one jax
        # process per child) stripes exactly like a real pod — on real
        # multi-host runs host_identity() equals local_host_info()
        from .obs.fleet import host_identity

        mix_host_index, mix_host_count = host_identity()
        train_loader = MixturePlane(
            sources_from_graphs(trainset),
            batch_size,
            settings=config["Mixture"],
            spec=spec,
            seed=int(training.get("seed", 0)),
            sort_edges=shard_kw["sort_edges"],
            validator=validator,
            num_shards=num_shards,
            host_count=mix_host_count,
            host_index=mix_host_index,
        )
        val_loader = GraphLoader(
            valset, batch_size, shuffle=False, source="val", **shard_kw
        )
        test_loader = GraphLoader(
            testset, batch_size, shuffle=False, source="test", **shard_kw
        )
        return config, (train_loader, val_loader, test_loader), mm
    if (
        bool(training.get("branch_parallel", False))
        and num_branches > 1
        and num_shards > 1
    ):
        if pack:
            raise ValueError(
                "Training.pack_batches is not supported with branch_parallel "
                "(branch-routed rows need fixed graph counts); use "
                "num_pad_buckets"
            )
        # routed rule tables need branch-routed shard rows
        # (parallel/routing.py BranchRoutedLoader); ONE ladder over all
        # splits so eval reuses the train step's compilations
        from .parallel.routing import BranchRoutedLoader

        route_kw = dict(
            branch_count=num_branches,
            num_shards=num_shards,
            host_count=host_count,
            host_index=host_index,
            sort_edges=shard_kw["sort_edges"],
            # the FULL ladder (shared across splits): each stacked batch
            # selects the smallest level fitting its largest row, and the
            # loader's per-branch template census warms every reachable
            # level (parallel/routing.py; multi-host collapses to worst-case
            # inside the loader — level choice cannot agree across hosts
            # without a collective)
            spec=spec,
        )
        train_loader = BranchRoutedLoader(
            trainset, batch_size, seed=0, shuffle=True, **route_kw
        )
        val_loader = BranchRoutedLoader(
            valset, batch_size, shuffle=False, oversampling=False, **route_kw
        )
        test_loader = BranchRoutedLoader(
            testset, batch_size, shuffle=False, oversampling=False, **route_kw
        )
        # branch-routed loaders did their validation at the ingest gate
        # above; carry the validator so the epoch loop still logs the tally
        train_loader.validator = validator
        return config, (train_loader, val_loader, test_loader), mm
    train_loader = GraphLoader(
        trainset,
        batch_size,
        shuffle=True,
        seed=0,
        # RandomSampler-with-replacement / fixed-draw modes
        # (reference: load_data.py:237-274)
        oversampling=bool(training.get("oversampling", False)) or balance,
        num_samples=training.get("num_samples"),
        sample_weights=sample_weights,
        # background batch building (HYDRAGNN_NUM_WORKERS=0 disables; the
        # reference's env of the same name sizes its thread-pool loader)
        prefetch=max(envflags.env_int("HYDRAGNN_NUM_WORKERS", 2), 0),
        # multi-host batches must stay full so every process steps in
        # lockstep with identical shard shapes
        drop_last=jax.process_count() > 1,
        source="train",
        **shard_kw,
    )
    val_loader = GraphLoader(
        valset, batch_size, shuffle=False, source="val", **shard_kw
    )
    test_loader = GraphLoader(
        testset, batch_size, shuffle=False, source="test", **shard_kw
    )
    return config, (train_loader, val_loader, test_loader), mm


@functools.singledispatch
def run_training(config, datasets=None, verbosity: Optional[int] = None):
    raise TypeError(f"config must be a dict or str path, got {type(config)}")


@run_training.register
def _(config: str, datasets=None, verbosity: Optional[int] = None):
    return run_training(load_config(config), datasets, verbosity)


@run_training.register
def _(config: dict, datasets=None, verbosity: Optional[int] = None):
    """(reference: run_training.py:62-182)"""
    from .parallel import setup_distributed
    from .utils import MetricsWriter, Timer, print_timers, setup_log
    from .utils import tracer as tr

    # multi-host rendezvous first — before anything touches the XLA backend
    # (reference: run_training.py:71 calls setup_ddp before load/model)
    setup_distributed()
    # fresh per-run accumulators (class/module-level state would otherwise
    # report cumulative totals across repeated runs in one process)
    Timer.reset()
    tr.reset()
    with Timer("load_data"):
        config, loaders, mm = prepare_data(config, datasets)
    train_loader, val_loader, test_loader = loaders
    verbosity = (
        verbosity if verbosity is not None else config["Verbosity"].get("level", 0)
    )
    import jax
    import numpy as np

    log_name = get_log_name_config(config)
    if verbosity > 0:
        setup_log(log_name)
    if jax.process_index() == 0:
        # rank-0 config dump (reference: save_config, config_utils.py:352-358)
        save_config(config, log_name)

    # persistent XLA compilation cache (train/compile_plane.py): activated
    # BEFORE the first jit touch (model init below compiles too), so
    # restarts/rollbacks/resumes deserialize executables instead of
    # recompiling. Placed by compile_cache_dir(): JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/logs/xla_cache.
    from .train.compile_plane import setup_compile_cache

    setup_compile_cache(config["NeuralNetwork"]["Training"])

    multihost = jax.process_count() > 1
    training = config["NeuralNetwork"]["Training"]
    arch = config["NeuralNetwork"]["Architecture"]
    # one seed drives init and the train rng stream (dropout etc.);
    # ``Training.seed`` pins runs for reproducibility studies
    run_seed = int(training.get("seed", 0))
    with Timer("create_model"):
        model = create_model(config)
        sample = next(iter(train_loader))
        if getattr(train_loader, "num_shards", 1) > 1:
            # loader emits stacked [local_shards, ...] batches: init on one
            sample = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], sample)
        variables = init_model(model, sample, seed=run_seed)
    from .utils import print_model

    # parameter summary (reference: print_model, model.py:289-297)
    print_model(variables, verbosity=verbosity)
    tx = make_optimizer(
        training["Optimizer"],
        freeze_conv=bool(arch.get("freeze_conv_layers", False)),
    )
    state = TrainState.create(variables, tx)

    # resume mid-run (reference: "continue"/"startfrom" keys,
    # hydragnn/utils/model/model.py:118-125, run_training.py:114) — restore
    # before any device placement so the loaded host arrays get re-placed
    if training.get("continue"):
        import warnings as _warnings

        startfrom = training.get("startfrom") or log_name
        state = load_existing_model(state, startfrom)
        # mid-epoch resume (docs/ROBUSTNESS.md "Data plane"): a loader-state
        # sidecar beside the checkpoint means the save happened BETWEEN
        # steps — arm the train loader to replay the interrupted epoch's
        # remaining batches in the same order, after guarding that the data
        # recipe still matches (a changed seed/batch count would replay the
        # wrong stream — then epoch-granularity resume is the honest choice)
        ls = load_loader_state(startfrom)
        if ls is not None:
            recipe_ok = hasattr(train_loader, "resume") and ls.seed == int(
                getattr(train_loader, "seed", 0) or 0
            )
            if recipe_ok:
                train_loader.resume(ls.epoch, ls.next_batch)
                if ls.mixture and hasattr(train_loader, "restore_mixture"):
                    # mid-epoch mixture resume: cursors + draw index + the
                    # source topology AT the checkpointed batch — BEFORE the
                    # batch-count guard below, which must compare against
                    # the sidecar's (possibly churned/demoted) active set,
                    # not the fresh all-sources topology (mix/plane.py)
                    train_loader.restore_mixture(ls.mixture, mid_epoch=True)
                # batch-count guard AFTER arming: pack-mode batch counts are
                # epoch-dependent, so len() is only comparable once the
                # loader sits at the sidecar's epoch. EXCEPTION: a mixture
                # sidecar written under a different (host_count, host_index)
                # stripe layout legitimately changes the per-host batch
                # count — the elastic re-deal (mix/plane.py restore_mixture)
                # already re-armed the loader at the mapped position
                relayout = (
                    isinstance(ls.mixture, dict)
                    and (
                        int(ls.mixture.get("host_count", 1))
                        != int(getattr(train_loader, "host_count", 1) or 1)
                        or int(ls.mixture.get("host_index", 0))
                        != int(getattr(train_loader, "host_index", 0) or 0)
                    )
                )
                if (
                    ls.num_batches
                    and ls.num_batches != len(train_loader)
                    and not relayout
                ):
                    train_loader.resume(0, 0)  # disarm: fresh epoch 0 start
                    recipe_ok = False
                if relayout and recipe_ok:
                    # record the survivor's re-layout as a typed event (the
                    # doctor's elastic rules read exactly this record); the
                    # driver that relaunched us may hand over the measured
                    # progress loss (run-scripts/elastic_smoke.py)
                    from .train.elastic import note_relayout

                    lost = envflags.env_str("HYDRAGNN_ELASTIC_LOST_STEPS")
                    note_relayout(
                        {
                            "host_count": int(
                                ls.mixture.get("host_count", 1) or 1
                            ),
                            "host_index": int(
                                ls.mixture.get("host_index", 0) or 0
                            ),
                            "epoch": int(ls.epoch),
                            "next_batch": int(ls.next_batch),
                        },
                        {
                            "host_count": int(
                                getattr(train_loader, "host_count", 1) or 1
                            ),
                            "host_index": int(
                                getattr(train_loader, "host_index", 0) or 0
                            ),
                            "epoch": int(
                                getattr(train_loader, "epoch", ls.epoch)
                            ),
                            "next_batch": int(
                                getattr(
                                    train_loader, "start_batch", 0
                                )
                            ),
                        },
                        trigger="resume",
                        progress_lost_steps=int(lost) if lost else None,
                    )
            if recipe_ok:
                if verbosity > 0:
                    print(
                        f"[{log_name}] resuming mid-epoch: replaying epoch "
                        f"{ls.epoch} from batch {ls.next_batch}"
                    )
            else:
                _warnings.warn(
                    f"loader-state sidecar of run {startfrom!r} does not "
                    "match the current loader (seed/batch-count drift, or a "
                    "loader without resume support); resuming at epoch "
                    "granularity instead of mid-epoch",
                    stacklevel=2,
                )
        elif hasattr(train_loader, "restore_mixture"):
            # epoch-boundary (or SIGKILL) resume: no loader sidecar, but the
            # mixture snapshot beside the checkpoint still carries the source
            # topology + the absolute epoch sequence to continue
            ms = load_mixture_state(startfrom)
            if ms is not None:
                train_loader.restore_mixture(ms)
                if isinstance(ms, dict) and (
                    int(ms.get("host_count", 1) or 1)
                    != int(getattr(train_loader, "host_count", 1) or 1)
                    or int(ms.get("host_index", 0) or 0)
                    != int(getattr(train_loader, "host_index", 0) or 0)
                ):
                    # an epoch-boundary re-layout (elastic shrink survivor
                    # finishing, or a re-grown host rejoining): the new
                    # epoch re-deals the stripes from position 0 by purity
                    # alone, but the typed event must still be recorded —
                    # it is the doctor's evidence of the topology change
                    from .train.elastic import note_relayout

                    lost = envflags.env_str("HYDRAGNN_ELASTIC_LOST_STEPS")
                    note_relayout(
                        {
                            "host_count": int(ms.get("host_count", 1) or 1),
                            "host_index": int(ms.get("host_index", 0) or 0),
                            "epoch": int(ms.get("epoch", 0) or 0),
                        },
                        {
                            "host_count": int(
                                getattr(train_loader, "host_count", 1) or 1
                            ),
                            "host_index": int(
                                getattr(train_loader, "host_index", 0) or 0
                            ),
                            "epoch": int(
                                getattr(train_loader, "epoch", 0) or 0
                            ),
                        },
                        trigger="resume",
                        progress_lost_steps=int(lost) if lost else None,
                    )
                if verbosity > 0:
                    print(
                        f"[{log_name}] mixture topology restored: epoch "
                        f"sequence continues at {train_loader.epoch}"
                    )

    # every device-placement transform applied to the state below is also
    # recorded here, so the rollback restore path (non_finite_policy:
    # rollback) can replay the SAME placement on a freshly deserialized
    # host-array state — a restored state must be indistinguishable from a
    # resumed one (train/loop.py restore_fn)
    placement_fns: List[Any] = []

    # sharding rule table (parallel/rules.py): prepare_data already
    # resolved + recorded it; re-resolving here is idempotent and hands
    # this function the table object driving placement AND step building.
    # ZeRO stage selection (reference: ZeroRedundancyOptimizer / DeepSpeed
    # stages, hydragnn/utils/optimizer/optimizer.py:43-113): stage 1 =
    # moment sharding (placement only — tx.update runs under the outer
    # jit, so XLA partitions the update by the moments' sharding), stage
    # 2/3 add in-step gradient/param rules and need the mesh step.
    rule_table = resolve_parallel(config)
    zero_stage = _zero_stage(training)
    use_zero = zero_stage >= 1
    # stage >= 2 needs the mesh step — same predicate prepare_data used
    # for the loader num_shards gate (unstacked batches would break it);
    # resolve_parallel normalized zero_stage from the table, so inline
    # tables with grads/params rules take this gate too
    single_host_mesh = _wants_mesh_step(config) and not multihost
    if (
        use_zero
        and zero_stage < 2
        and not multihost
        and not single_host_mesh
        and not training.get("branch_parallel", False)
        and len(jax.devices()) > 1
    ):
        # ZeRO-1 placement under the plain-jit loop step: moments sharded
        # P(data) by the table, everything else replicated
        from .parallel import make_mesh2d, place_state

        mesh = make_mesh2d()

        def _place_zero1(st, _mesh=mesh, _table=rule_table):
            return place_state(st, _table, _mesh)

        placement_fns.append(_place_zero1)
        state = _place_zero1(state)

    # mesh-step mode: multi-host DP (shard_map over the global (data,
    # model) mesh, grads psum over ICI/DCN) and/or routed decoder sharding
    # — single-host multi-device branch_parallel runs the same mesh steps
    # (promote_batch no-ops with one process)
    step_fn = eval_fn = None
    # routed decoder sharding (Training.branch_parallel / the branch-mp
    # rule presets): decoder banks sharded over the model axis, data
    # routed by branch — the MultiTaskModelMP analog (parallel/engine.py).
    # The predicate must MATCH prepare_data's loader-routing gate exactly
    # (resolve_parallel normalizes both from the same table): a routed
    # step on unrouted batches computes garbage.
    branch_parallel = bool(training.get("branch_parallel", False))
    if branch_parallel and (
        getattr(model.cfg, "num_branches", 1) < 2
        or jax.local_device_count() < 2
    ):
        raise ValueError(
            "Training.branch_parallel requires a multibranch model "
            f"(num_branches={getattr(model.cfg, 'num_branches', 1)}) and "
            f">=2 local devices (have {jax.local_device_count()}): "
            "prepare_data could not build branch-routed loaders"
        )
    if multihost or branch_parallel or single_host_mesh:
        # the ONE mesh-step path (parallel/engine.py): the rule table
        # decides placement, in-step constraints, and routing — dp /
        # ZeRO-2/3 / branch-parallel are presets, not code paths
        from .parallel import (
            Objective,
            make_mesh2d,
            make_mesh_eval_step,
            make_mesh_train_step,
            place_state,
            promote_batch,
        )

        cge = training.get("compute_grad_energy", False)
        mp = training.get("mixed_precision", False)
        # Telemetry.numerics changes the step program (in-graph probes ride
        # the outputs — obs/numerics.py), so the mesh builders must get the
        # same resolution the loop applies to its default builders
        from .obs.telemetry import resolve_telemetry as _resolve_telemetry

        numerics_on = bool(_resolve_telemetry(config)["numerics"])
        # 2D (data, model) mesh; model extent 1 unless the table routes
        # decoder banks over the model axis (branch/mp presets)
        mesh = make_mesh2d(
            model_size=rule_table.model_size if rule_table.routed else 1
        )

        def _place_rules(st, _mesh=mesh, _table=rule_table):
            # table-driven placement: moments/params/decoder banks land on
            # their rule's spec, unmatched non-scalar leaves replicate with
            # an audit finding (obs/sharding.py record_unmatched); restored
            # Adam moments are PLACED, never re-initialized
            return place_state(st, _table, _mesh)

        placement_fns.append(_place_rules)
        state = _place_rules(state)
        _obj = Objective(
            model=model,
            tx=tx,
            compute_grad_energy=cge,
            mixed_precision=mp,
            numerics=numerics_on,
        )
        _pstep = make_mesh_train_step(_obj, rule_table, mesh)
        _peval = make_mesh_eval_step(_obj, rule_table, mesh)
        # the wrappers hide the jit objects from the compile plane —
        # attach_lower_fn re-exposes them (same jit object + same batch
        # transform the loop uses) so warm-up lands the identical executable
        from .train.compile_plane import attach_lower_fn

        step_fn = attach_lower_fn(
            lambda s, b, r: _pstep(s, promote_batch(b, mesh), r),
            # a numerics-enabled builder returns a wrapper carrying the
            # true jit as _jitted (parallel/engine.py)
            getattr(_pstep, "_jitted", _pstep),
            lambda b: promote_batch(b, mesh),
        )
        for _attr in ("_numerics_meta", "_nan_diagnose"):
            # the numerics name tables + NaN drill-down travel with the
            # step function the loop receives (train/loop.py reads them)
            _val = getattr(_pstep, _attr, None)
            if _val is not None:
                setattr(step_fn, _attr, _val)
        # evaluate() expects (tot, tasks, aux) like make_eval_step
        eval_fn = attach_lower_fn(
            lambda s, b: _peval(s, promote_batch(b, mesh)) + (None,),
            _peval,
            lambda b: promote_batch(b, mesh),
        )

    if step_fn is None and not placement_fns and jax.local_device_count() > 1:
        # the implicit default trains on ONE device of a multi-device host
        # (tier-1 runs on 8 virtual devices and relies on it): say so once,
        # so idle chips are a visible choice and not a silent one
        import sys as _sys

        print(
            f"[hydragnn_tpu] training on 1 of {jax.local_device_count()} "
            "local devices; set Parallel.rules: \"dp\" (or "
            "Optimizer.zero_stage: 2) to train over all of them",
            file=_sys.stderr,
        )

    # sharding-layout inspector (obs/sharding.py): whenever a placement
    # was applied (zero1/2/3, mesh DP, branch decoders), tabulate the
    # placed state's param/optimizer leaf shardings, run the replicated-
    # above-threshold audit, publish the hydragnn_sharding_* gauges, and
    # record the report so every flight dump carries sharding.json — the
    # before/after oracle for the planned rule-table sharding refactor
    if placement_fns:
        from .obs import sharding as obs_sharding
        from .obs.telemetry import resolve_telemetry as _rt

        try:
            import sys as _sys

            _shard_report = obs_sharding.inspect_state(
                state,
                threshold_bytes=int(
                    _rt(config)["fleet_sharding_audit_bytes"]
                ),
                label=log_name,
                mesh=mesh,
            )
            obs_sharding.record(_shard_report)
            if verbosity > 0:
                # summary + audit at verbosity 1 (one grep-able line per
                # run), the full per-leaf table at 2+
                print(
                    obs_sharding.format_report(
                        _shard_report, leaves=verbosity > 1
                    ),
                    file=_sys.stderr,
                )
        except Exception as _e:  # the inspector must never block training
            import warnings as _warnings

            _warnings.warn(
                f"sharding inspector failed ({type(_e).__name__}: {_e}); "
                "the placement report is unavailable for this run",
                RuntimeWarning,
                stacklevel=2,
            )

    writer = MetricsWriter(log_name)

    def log_fn(epoch, scalars):
        # per-epoch scalars (reference: train_validate_test.py:198-205)
        writer.add_scalars(
            {f"loss/{k}": v for k, v in scalars.items() if k != "lr"}, epoch
        )
        writer.add_scalar("lr", scalars.get("lr", 0.0), epoch)

    retention = int(training.get("checkpoint_retention", 0) or 0)
    if training.get("checkpoint_backend", "msgpack") == "orbax":
        from .train.checkpoint import save_model_orbax

        _save_model = lambda s, e=None: save_model_orbax(
            s, log_name, epoch=e, retention=retention
        )
    else:
        _save_model = lambda s, e=None: save_model(
            s, log_name, epoch=e, retention=retention
        )

    def save_fn(s, e=None):
        out = _save_model(s, e)
        # any committed save invalidates an older mid-epoch cursor; the
        # mid-epoch preemption path re-publishes its sidecar right after
        # this (loader_state_fn below), so a PRESENT sidecar always
        # describes the checkpoint it sits beside
        clear_loader_state(log_name)
        if hasattr(train_loader, "mixture_state_dict"):
            # mixture snapshot beside every checkpoint: active/demoted
            # sources, weights, absolute epoch — what a SIGKILL resume
            # needs to continue the exact draw sequence (docs/GFM.md)
            save_mixture_state(train_loader.mixture_state_dict(), log_name)
        return out

    def loader_state_fn(d):
        from .train.state import LoaderState

        save_loader_state(LoaderState.from_dict(d), log_name)

    def restore_fn(template):
        # rollback path (Training.non_finite_policy: rollback): restore the
        # last VERIFIED checkpoint of THIS run (digest-checked, walking back
        # on corruption — train/checkpoint.py), then replay the recorded
        # device placement so the restored state matches the step's contract
        st = load_existing_model(template, log_name)
        for place in placement_fns:
            st = place(st)
        return st

    try:
        with Timer("train_validate_test"):
            state, hist = train_validate_test(
                model,
                state,
                tx,
                train_loader,
                val_loader,
                test_loader,
                config,
                log_name=log_name,
                verbosity=verbosity,
                seed=run_seed,
                save_fn=save_fn,
                log_fn=log_fn,
                step_fn=step_fn,
                eval_fn=eval_fn,
                restore_fn=restore_fn,
                loader_state_fn=loader_state_fn,
                # the loop routes guard/data/compile health counters (and
                # the Telemetry layer's TB mirror) through the same writer
                # the epoch scalars use (obs/telemetry.py)
                writer=writer,
            )
    finally:
        writer.close()
    # final save with the GLOBAL (possibly sharded) state — orbax writes
    # shard-parallel; skipped when the preemption path already checkpointed
    # (re-serializing identical state would burn the SIGTERM grace window).
    # Gate on the loop's cross-host AGREED decision, not the local SIGTERM
    # flag: under orbax the save is a collective, and skewed signal delivery
    # would otherwise hang the non-preempted hosts in it.
    from .parallel.mesh import materialize_replicated
    from .utils import preemption

    do_final_save = not preemption.global_stop_noted()
    final_epoch = len(hist["train"]) - 1
    orbax_backend = training.get("checkpoint_backend", "msgpack") == "orbax"
    if multihost and not orbax_backend:
        # localize BEFORE the msgpack save: save_model gathers sharded
        # leaves anyway (checkpoint.py), so gathering once here serves both
        # the save and the downstream consumers (prediction, plotting)
        state = materialize_replicated(state)
    if do_final_save:
        save_fn(state, final_epoch if final_epoch >= 0 else None)
    if multihost and orbax_backend:
        # orbax writes shard-parallel — save the SHARDED state first, then
        # localize for downstream consumers
        state = materialize_replicated(state)
    if config.get("Visualization", {}).get("create_plots") and jax.process_index() == 0:
        # parity/error/history plots (reference: train_validate_test.py:100-126,
        # 268-313 drives postprocess/visualizer.py)
        from .postprocess import Visualizer

        _, _, preds, trues = test_model(
            model,
            state,
            _localize_loader(test_loader),
            compute_grad_energy=config["NeuralNetwork"]["Training"].get(
                "compute_grad_energy", False
            ),
            mixed_precision=config["NeuralNetwork"]["Training"].get(
                "mixed_precision", False
            ),
        )
        viz = Visualizer(log_name)
        viz.create_scatter_plots(trues, preds)
        viz.create_error_histograms(trues, preds)
        viz.plot_history(hist)
        viz.create_plot_global(trues, preds)
        viz.num_nodes_plot(
            [g.num_nodes for g in test_loader.graphs]
        )
        for name in trues:
            arr = np.asarray(trues[name])
            if name == "forces" or (arr.ndim == 2 and arr.shape[-1] == 3):
                viz.create_parity_plot_per_node_vector(name, trues[name], preds[name])
            else:
                viz.create_plot_global_analysis(name, trues[name], preds[name])
                viz.create_parity_plot_and_error_histogram_scalar(
                    name, trues[name], preds[name]
                )
    print_timers(verbosity)
    return model, state, hist, config, loaders, mm


@functools.singledispatch
def run_prediction(config, model_state=None, datasets=None):
    raise TypeError(f"config must be a dict or str path, got {type(config)}")


@run_prediction.register
def _(config: str, model_state=None, datasets=None):
    return run_prediction(load_config(config), model_state, datasets)


def _restore_for_inference(config, variables):
    """Restore the run's newest verified checkpoint for inference into the
    pre-initialized ``variables``: an optimizer-free ``InferenceState``
    template through the msgpack chain (no AdamW moments allocated — 2x
    params of dead memory on large models), falling back to the full
    ``TrainState`` template only for orbax-backed runs (their
    shard-parallel restore needs it). Returns ``(state, loaded_entry)`` —
    the entry ACTUALLY restored, which the verified walk-back chain may
    have taken PAST a corrupt ``latest``."""
    from .train.checkpoint import latest_checkpoint_entry, load_inference_state
    from .train.state import InferenceState

    log_name = get_log_name_config(config)
    entry = latest_checkpoint_entry(log_name)
    if entry and entry.startswith("orbax/"):
        tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
        loaded: list = []
        state = load_existing_model(
            TrainState.create(variables, tx), log_name, loaded_entry=loaded
        )
        return state, (loaded[0] if loaded else entry)
    return load_inference_state(InferenceState.create(variables), log_name)


@run_prediction.register
def _(config: dict, model_state=None, datasets=None):
    """(reference: run_prediction.py:49-107): rebuild model, restore latest
    checkpoint, evaluate on the test split, optionally denormalize."""
    from .parallel import setup_distributed

    setup_distributed()  # (reference: run_prediction.py:56)
    config, loaders, mm = prepare_data(config, datasets)
    _, _, test_loader = loaders
    # prediction is per-host (plain jitted eval): drop any device stacking
    test_loader = _localize_loader(test_loader)
    # persistent compilation cache, same wiring as run_training: a serving/
    # prediction restart must deserialize its eval executables instead of
    # repaying the full compile bill (train/compile_plane.py)
    from .train.compile_plane import setup_compile_cache

    setup_compile_cache(config["NeuralNetwork"]["Training"])
    model = create_model(config)
    if model_state is None:
        variables = init_model(model, next(iter(test_loader)), seed=0)
        model_state, _ = _restore_for_inference(config, variables)
    tot, tasks, preds, trues = test_model(
        model,
        model_state,
        test_loader,
        compute_grad_energy=config["NeuralNetwork"]["Training"].get(
            "compute_grad_energy", False
        ),
        mixed_precision=config["NeuralNetwork"]["Training"].get(
            "mixed_precision", False
        ),
    )
    # multi-host: every process returns the FULL prediction set and a
    # globally reduced loss (reference: padded all-gather of test samples
    # train_validate_test.py:410-448 + reduce_values_ranks :382-407)
    import jax as _jax

    from .parallel import gather_across_hosts

    if _jax.process_count() > 1:
        import numpy as _np

        # per-host weight = number of real graphs this host evaluated (the
        # same weighting _weighted_avg used inside test_model) — NOT the
        # element count of the first head, which for a node-level head
        # scales with node count and would skew the merged loss when hosts
        # hold different-sized graphs
        w = float(len(test_loader.graphs))
        packed = {
            "w": _np.asarray([w]),
            "tot": _np.asarray([tot * w]),
            **{f"task_{k}": _np.asarray([v * w]) for k, v in tasks.items()},
        }
        g = gather_across_hosts(packed)
        W = float(g["w"].sum()) or 1.0
        tot = float(g["tot"].sum() / W)
        tasks = {k: float(g[f"task_{k}"].sum() / W) for k in tasks}
    preds = gather_across_hosts(preds)
    trues = gather_across_hosts(trues)
    var = config["NeuralNetwork"]["Variables_of_interest"]
    if var.get("denormalize_output") and mm is not None:
        # every head is denormalized, node-level included (reference:
        # output_denormalize, hydragnn/postprocess/postprocess.py:13-26)
        voi = voi_from_config(config)
        for name, t, idx in zip(var["output_names"], var["type"], var["output_index"]):
            if name not in preds:
                continue  # e.g. autograd-forces head replaces the node head
            if t == "graph":
                sl = voi.graph_feature_slice(idx)
                preds[name] = mm.denormalize_graph(preds[name], sl)
                trues[name] = mm.denormalize_graph(trues[name], sl)
            else:
                sl = voi.node_feature_slice(idx)
                preds[name] = mm.denormalize_node(preds[name], sl)
                trues[name] = mm.denormalize_node(trues[name], sl)
    return tot, tasks, preds, trues


@functools.singledispatch
def run_server(config, datasets=None, install_sigterm: bool = False):
    raise TypeError(f"config must be a dict or str path, got {type(config)}")


@run_server.register
def _(config: str, datasets=None, install_sigterm: bool = False):
    return run_server(load_config(config), datasets, install_sigterm)


@run_server.register
def _(config: dict, datasets=None, install_sigterm: bool = False):
    """Config-driven serving entry point (docs/SERVING.md): complete the
    config from data, restore the run's newest verified checkpoint into an
    optimizer-free inference state, and start a ``GraphServer`` whose
    micro-batcher packs requests into the run's SpecLadder pad buckets —
    every servable shape AOT-warmed before readiness flips, the retrace
    sentinel armed per ``Serving.retrace_policy`` (default ``error``).

    Returns the STARTED server; callers submit requests and ``close()`` it
    (it is also a context manager). ``install_sigterm=True`` wires SIGTERM
    to a graceful drain. With no checkpoint on disk the server serves the
    fresh initialization (warned — useful for smokes only).
    """
    import warnings as _warnings

    from .parallel import setup_distributed
    from .serve import CheckpointWatcher, GraphServer, ServeConfig
    from .train.state import InferenceState

    setup_distributed()
    config, loaders, mm = prepare_data(config, datasets)
    _, _, test_loader = loaders
    test_loader = _localize_loader(test_loader)
    log_name = get_log_name_config(config)
    # persistent compilation cache BEFORE any jit touch, like run_training:
    # a server restart deserializes the warmed ladder instead of recompiling
    from .train.compile_plane import setup_compile_cache

    setup_compile_cache(config["NeuralNetwork"]["Training"])
    model = create_model(config)
    variables = init_model(model, next(iter(test_loader)), seed=0)
    try:
        state, entry = _restore_for_inference(config, variables)
    except FileNotFoundError:
        _warnings.warn(
            f"run {log_name!r} has no checkpoint on disk; serving the fresh "
            "model initialization (train first for real predictions)",
            stacklevel=2,
        )
        state = InferenceState.create(variables)
        entry = None
    training = config["NeuralNetwork"]["Training"]
    arch = config["NeuralNetwork"]["Architecture"]
    serve_cfg = ServeConfig.from_config(config)
    # tracing plane (obs/trace.py, obs/flightrec.py; docs/OBSERVABILITY.md):
    # Telemetry.trace arms head-sampled request traces (trace_sample) to
    # logs/<run>/trace.jsonl; the flight recorder arms the serve-wedge /
    # unhandled-exception / SIGUSR2 black box. The server owns both and
    # tears them down at close().
    from .obs.telemetry import resolve_telemetry

    obs_settings = resolve_telemetry(config)
    run_dir = os.path.join("./logs", log_name)
    tracer = None
    if obs_settings["trace"]:
        from .obs import trace as obs_trace

        tracer = obs_trace.Tracer(
            run_dir, sample=float(obs_settings["trace_sample"])
        )
        obs_trace.install(tracer)
    flight = None
    if obs_settings["flight_recorder"] and (
        obs_settings["trace"] or obs_settings["enabled"]
    ):
        from .obs.flightrec import FlightRecorder

        flight = FlightRecorder(run_dir, tracer=tracer).install()
    if obs_settings["trace"] or obs_settings["enabled"]:
        # persistent incident stream (obs/events.py): shed/queue-full/
        # wedge/reload events land in logs/<run>/events.jsonl so the run
        # doctor (obs/doctor.py) can diagnose a serving deployment
        # post-hoc; last attach wins, matching the tracer install contract
        from .obs.events import attach_stream as _attach_events

        _attach_events(run_dir)
    server = GraphServer(
        model,
        state,
        test_loader.ladder,
        serve_cfg,
        template_graphs=test_loader.graphs,
        mixed_precision=bool(training.get("mixed_precision", False)),
        sort_edges=bool(arch.get("use_sorted_aggregation", False)),
        log_name=log_name,
        checkpoint_label=entry,
        # int8 plane: locates pre-quantized snapshot artifacts beside the
        # checkpoints (serve/quantize.py) — a replica that finds one skips
        # re-quantization and calibration entirely
        checkpoint_dir="./logs",
        tracer=tracer,
        flight_recorder=flight,
    )
    server.start(install_sigterm=install_sigterm)
    if serve_cfg.hot_reload:
        watcher = CheckpointWatcher(
            server,
            log_name,
            poll_s=serve_cfg.reload_poll_s,
            initial_entry=entry,
        ).start()
        server.attach_watcher(watcher)
    return server


def run_server_fleet(
    config,
    replicas: int = None,
    path: str = "./logs",
    per_replica_env=None,
    wait_ready_s: float = None,
):
    """Config-driven serving FLEET (docs/SERVING.md "Fleet"): spawn
    ``Serving.fleet_replicas`` (or ``replicas=``) worker processes, each a
    full ``run_server`` deployment on its own ephemeral port and device
    set, supervised by a ``ReplicaManager`` — crash restart with backoff,
    flap benching, wedge detection, rolling hot-reload with rollback —
    and fronted by its ``router()`` (retries, hedging, circuit breakers,
    optional prediction cache).

    ``config`` is a config dict or JSON path. ``per_replica_env`` maps a
    1-based replica index to extra environment for that worker (the hook
    for pinning device sets). ``wait_ready_s`` blocks until every replica
    passes /readyz (warm-up included) or raises; None returns immediately
    with replicas still warming. Returns the STARTED ``ReplicaManager``
    — call ``.router().predict(graph)`` to serve and ``.close()`` (or use
    it as a context manager) to drain the fleet.
    """
    from .serve.fleet import ReplicaManager

    manager = ReplicaManager(
        config, path=path, per_replica_env=per_replica_env,
        replicas=replicas,
    ).start()
    if wait_ready_s is not None:
        if not manager.wait_ready(timeout=float(wait_ready_s)):
            state = manager.replica_state()
            manager.close()
            raise RuntimeError(
                f"serving fleet failed to become ready within "
                f"{wait_ready_s}s: {state}"
            )
    return manager
