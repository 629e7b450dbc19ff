"""Dataset -> model-ready pipeline: feature selection, split, minmax, loader.

Covers the responsibilities of the reference's serialized loader and splitting
utilities (hydragnn/preprocess/serialized_dataset_loader.py:110-212,
hydragnn/preprocess/load_data.py:225-438) in a TPU-friendly way: everything
here is host-side numpy; the output of ``GraphLoader`` is a statically padded
``GraphBatch`` ready for ``jit``.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import tracer as tr
from .graph import (
    Graph,
    GraphBatch,
    PadSpec,
    SpecLadder,
    _round_up,
    _triplet_count,
    batch_graphs,
    batch_graphs_np,
    graph_batch_from_np,
)

# prefetch watchdog cadence: how often the consumer wakes to check producer
# liveness / the stall clock, and how long the teardown join waits before
# declaring the producer thread leaked (both module-level so tests can pin)
_WATCHDOG_TICK_S = 0.1
_PRODUCER_JOIN_TIMEOUT_S = 2.0


class LoaderStallError(RuntimeError):
    """The prefetch producer thread died without delivering its end-of-epoch
    sentinel, or produced nothing for longer than
    ``Training.loader_stall_timeout`` — a wedged worker (deadlocked fetch,
    hung filesystem) that would otherwise hang the run forever on a bare
    queue get. The message names the batch cursor so the stall is
    attributable."""


def _pack_spec(
    graphs: Sequence[Graph], per_shard: int, with_triplets: bool = False,
    node_slots: Optional[int] = None, graph_slots: Optional[int] = None,
) -> PadSpec:
    """Budget spec for packed batching: mean-size * per_shard (+5% headroom),
    never below the largest single graph, with 2x graph slots so bins of
    small graphs aren't cut short by the slot cap. ``with_triplets`` also
    budgets the DimeNet triplet channel (counted per graph, O(E) each).
    ``node_slots`` / ``graph_slots`` (``Training.pack_node_slots`` /
    ``pack_graph_slots``) state the budget outright, as a job that counts its
    batch in tokens does: that many node slots (one is the dummy node) with
    the edge budget in the data's edges-per-node proportion, that many graph
    slots (one is the dummy graph)."""
    ns = np.asarray([g.num_nodes for g in graphs])
    es = np.asarray([g.num_edges for g in graphs])
    budget_n = max(int(ns.mean() * per_shard * 1.05) + 2, int(ns.max()) + 2)
    budget_e = max(int(es.mean() * per_shard * 1.05) + 1, int(es.max()) + 1)
    if node_slots:
        if int(node_slots) < int(ns.max()) + 1:
            raise ValueError(
                f"Training.pack_node_slots {node_slots} cannot hold the largest "
                f"graph ({int(ns.max())} nodes) and the dummy node")
        budget_n = int(node_slots)
        budget_e = max(int(es.sum() / max(ns.sum(), 1) * budget_n * 1.05) + 1, int(es.max()) + 1)
    n_triplets = 0
    if with_triplets:
        ts = np.asarray([_triplet_count(g) for g in graphs])
        n_triplets = _round_up(
            max(int(ts.mean() * per_shard * 1.05) + 1, int(ts.max()) + 1), 128
        )
    return PadSpec(
        n_nodes=_round_up(budget_n, 8),
        n_edges=_round_up(budget_e, 128),
        n_graphs=int(graph_slots) if graph_slots else 2 * per_shard + 1,
        n_triplets=n_triplets,
    )


def selectable_levels(
    graphs: Sequence[Graph],
    ladder: SpecLadder,
    trip_count_of=None,
) -> List[Tuple[int, Graph]]:
    """(level index, one fitting graph) for every ladder level the graphs
    can land in. A level no single graph fits can never be selected by
    ``SpecLadder.select`` (every batch total is >= its smallest member), so
    this census is exactly the set of specializations batching over
    ``graphs`` can produce — the shared coverage primitive of the training
    compile plane, the serving plane, the branch-routed loader's per-branch
    ladders (parallel/branch.py), and the mixture plane (mix/plane.py).
    ``trip_count_of`` overrides the per-graph triplet counter (the loader
    passes its memoized table)."""
    tcf = trip_count_of if trip_count_of is not None else _triplet_count
    out: List[Tuple[int, Graph]] = []
    for li, spec in enumerate(ladder.specs):
        need_t = bool(spec.n_triplets)
        g = next(
            (
                c
                for c in graphs
                if c.num_nodes <= spec.n_nodes - 1
                and c.num_edges <= spec.n_edges
                and (not need_t or tcf(c) <= spec.n_triplets)
            ),
            None,
        )
        if g is not None:
            out.append((li, g))
    return out


def spec_template_batches(
    graphs: Sequence[Graph],
    ladder: SpecLadder,
    sort_edges: bool = False,
    trip_count_of=None,
) -> List[Tuple[PadSpec, GraphBatch]]:
    """One template ``GraphBatch`` per ladder level the dataset can emit —
    the warm-up inputs of both the training compile plane
    (train/compile_plane.py) and the serving plane (serve/server.py).

    Batch array SHAPES are fully determined by the pad spec plus the
    dataset's feature widths, so a single fitting graph padded to the level
    is abstractly identical to any real batch at that level; unreachable
    levels are skipped (``selectable_levels``) — warm-up covers exactly the
    specializations batching can produce, no more."""
    return [
        (
            ladder.specs[li],
            batch_graphs([g], ladder.specs[li], sort_edges=sort_edges),
        )
        for li, g in selectable_levels(graphs, ladder, trip_count_of)
    ]


def stack_shard_batches(
    shards: Sequence[Sequence[Graph]],
    spec: PadSpec,
    num_shards: int,
    sort_edges: bool = False,
) -> GraphBatch:
    """Stack per-shard padded batches into a leading device axis; missing
    shards become all-padding rows (padding edges point at the dummy node
    slot, padding nodes at the dummy graph slot). Shared by the stacked
    ``GraphLoader``, the mixture plane (mix/plane.py), and the
    branch-routed loaders (parallel/routing.py)."""
    arrs = [
        batch_graphs_np(list(s), spec, sort_edges=sort_edges)
        for s in shards
        if s
    ]
    template = {k: np.zeros_like(v) for k, v in arrs[0].items()}
    # padding edges must still point at the dummy node slot
    template["senders"] = np.full_like(arrs[0]["senders"], spec.n_nodes - 1)
    template["receivers"] = template["senders"].copy()
    template["node_graph"] = np.full_like(
        arrs[0]["node_graph"], spec.n_graphs - 1
    )
    while len(arrs) < num_shards:
        arrs.append(template)
    stacked = {k: np.stack([a[k] for a in arrs]) for k in arrs[0]}
    return graph_batch_from_np(stacked)


@dataclasses.dataclass
class VariablesOfInterest:
    """Selection of model inputs and per-head targets from raw feature tables.

    Mirrors config ``NeuralNetwork.Variables_of_interest`` +
    ``Dataset.{node,graph}_features`` (reference:
    hydragnn/utils/input_config_parsing/config_utils.py:219-260).
    """

    input_node_features: Sequence[int]
    output_names: Sequence[str]
    output_types: Sequence[str]  # "graph" | "node"
    output_index: Sequence[int]
    node_feature_dims: Sequence[int]
    graph_feature_dims: Sequence[int]

    def node_feature_slice(self, idx: int) -> slice:
        off = int(np.sum(self.node_feature_dims[:idx]))
        return slice(off, off + self.node_feature_dims[idx])

    def graph_feature_slice(self, idx: int) -> slice:
        off = int(np.sum(self.graph_feature_dims[:idx]))
        return slice(off, off + self.graph_feature_dims[idx])

    @property
    def input_dim(self) -> int:
        return int(sum(self.node_feature_dims[i] for i in self.input_node_features))

    def head_dims(self) -> List[int]:
        dims = []
        for t, i in zip(self.output_types, self.output_index):
            dims.append(
                self.graph_feature_dims[i] if t == "graph" else self.node_feature_dims[i]
            )
        return dims


def select_input_columns(graph: Graph, voi: VariablesOfInterest) -> Graph:
    """Keep only the configured input node-feature columns of ``graph.x``."""
    in_cols = np.concatenate(
        [np.arange(voi.node_feature_slice(i).start, voi.node_feature_slice(i).stop)
         for i in voi.input_node_features]
    )
    return dataclasses.replace(graph, x=np.asarray(graph.x)[:, in_cols])


def extract_variables(graph: Graph, voi: VariablesOfInterest) -> Graph:
    """Produce a model-ready graph: input columns + per-head target dicts."""
    graph_targets: Dict[str, np.ndarray] = {}
    node_targets: Dict[str, np.ndarray] = {}
    for name, t, idx in zip(voi.output_names, voi.output_types, voi.output_index):
        if t == "graph":
            graph_targets[name] = np.asarray(graph.graph_y)[voi.graph_feature_slice(idx)]
        else:
            node_targets[name] = np.asarray(graph.x)[:, voi.node_feature_slice(idx)]
    return dataclasses.replace(
        select_input_columns(graph, voi),
        graph_targets=graph_targets,
        node_targets=node_targets,
    )


@dataclasses.dataclass
class MinMax:
    """Per-column min/max used for feature/target normalization to [0, 1].

    The reference normalizes raw features in ``AbstractRawDataset.__normalize_dataset``
    and denormalizes predictions with ``output_denormalize``
    (hydragnn/postprocess/postprocess.py:13-26).
    """

    x_min: np.ndarray
    x_max: np.ndarray
    y_min: np.ndarray
    y_max: np.ndarray
    node_y_min: Optional[np.ndarray] = None
    node_y_max: Optional[np.ndarray] = None

    @staticmethod
    def fit(graphs: List[Graph]) -> "MinMax":
        xs = np.concatenate([g.x for g in graphs], axis=0)
        x_min, x_max = xs.min(0), xs.max(0)
        if graphs[0].graph_y is not None:
            ys = np.stack([np.asarray(g.graph_y) for g in graphs])
            y_min, y_max = ys.min(0), ys.max(0)
        else:
            y_min = y_max = np.zeros((0,), np.float32)
        return MinMax(x_min, x_max, y_min, y_max, x_min, x_max)

    def apply(self, graphs: List[Graph]) -> List[Graph]:
        out = []
        xr = np.where(self.x_max > self.x_min, self.x_max - self.x_min, 1.0)
        yr = np.where(self.y_max > self.y_min, self.y_max - self.y_min, 1.0)
        for g in graphs:
            x = (g.x - self.x_min) / xr
            gy = None if g.graph_y is None else (g.graph_y - self.y_min) / yr
            out.append(dataclasses.replace(g, x=x.astype(np.float32), graph_y=gy))
        return out

    def denormalize_graph(self, y: np.ndarray, idx: slice) -> np.ndarray:
        return y * (self.y_max[idx] - self.y_min[idx]) + self.y_min[idx]

    def denormalize_node(self, y: np.ndarray, idx: slice) -> np.ndarray:
        """Node heads are extracted from (normalized) ``graph.x`` columns, so
        their scale is the x min/max (reference: output_denormalize covers
        every head, hydragnn/postprocess/postprocess.py:13-26)."""
        lo = (self.node_y_min if self.node_y_min is not None else self.x_min)[idx]
        hi = (self.node_y_max if self.node_y_max is not None else self.x_max)[idx]
        rng = np.where(hi > lo, hi - lo, 1.0)
        return y * rng + lo


def branch_sample_weights(
    graphs: Sequence[Graph], branch_weights: Dict[int, float]
) -> np.ndarray:
    """Per-sample draw weights giving each dataset branch a total sampling
    share proportional to ``branch_weights[dataset_id]``.

    The SPMD analog of the reference's *uneven* branch process groups
    (examples/multibranch/train.py:166-213 sizes each branch's rank count
    by its dataset; MultiTaskModelMP then trains them in parallel): here
    one merged loader draws with replacement, and these weights set how
    much step budget each branch receives regardless of dataset size —
    e.g. weights {0: 1, 1: 1} equalize a large and a small dataset.
    """
    ids = np.asarray([g.dataset_id for g in graphs], np.int64)
    uncovered = sorted(set(ids.tolist()) - set(branch_weights))
    if uncovered:
        raise ValueError(f"dataset_id(s) {uncovered} not in branch_weights")
    w = np.zeros(ids.shape[0], np.float64)
    for ds_id, share in branch_weights.items():
        if share <= 0:
            raise ValueError(
                f"branch_weights[{ds_id}] must be positive, got {share}"
            )
        mask = ids == ds_id
        count = int(mask.sum())
        if count == 0:
            raise ValueError(f"no samples with dataset_id {ds_id}")
        w[mask] = float(share) / count
    return w


def split_dataset(
    graphs: List[Graph],
    perc_train: float,
    seed: int = 0,
    stratified: bool = False,
) -> Tuple[List[Graph], List[Graph], List[Graph]]:
    """Random train/val/test split; val and test share the remainder equally.

    (reference: hydragnn/preprocess/load_data.py:329-349; the compositional
    stratified variant lives in utils/datasets/compositional_data_splitting.py
    and is approximated here by stratifying on the node-type multiset hash.)
    """
    rng = np.random.default_rng(seed)
    idx = np.arange(len(graphs))
    if stratified:
        # group indices by composition signature, deal each group round-robin
        from collections import defaultdict

        groups = defaultdict(list)
        for i, g in enumerate(graphs):
            key = tuple(np.bincount(np.asarray(g.z, np.int64) if g.z is not None else [0]))
            groups[key].append(i)
        order = []
        for key in sorted(groups):
            sub = np.array(groups[key])
            rng.shuffle(sub)
            order.append(sub)
        idx = np.concatenate(order) if order else idx
        # interleave groups so each split sees every composition
        idx = idx[_deal_order(len(idx))]
    else:
        rng.shuffle(idx)
    n_train = int(len(idx) * perc_train)
    n_val = (len(idx) - n_train) // 2
    tr = [graphs[i] for i in idx[:n_train]]
    va = [graphs[i] for i in idx[n_train : n_train + n_val]]
    te = [graphs[i] for i in idx[n_train + n_val :]]
    return tr, va, te


def _deal_order(n: int) -> np.ndarray:
    """Round-robin dealing permutation: 0, k, 2k, ..., 1, k+1, ... with k=10."""
    k = 10
    cols = [np.arange(s, n, k) for s in range(k)]
    return np.concatenate(cols)


class GraphLoader:
    """Shuffling, statically-padded batch iterator over a list of graphs.

    Replaces DataLoader+DistributedSampler (reference: load_data.py:225-326).
    ``host_count``/``host_index`` shard samples across hosts for multi-host DP
    (DistributedSampler semantics: each host sees 1/host_count of the samples).
    """

    def __init__(
        self,
        graphs: List[Graph],
        batch_size: int,
        spec: Optional[PadSpec] = None,
        shuffle: bool = True,
        seed: int = 0,
        host_count: int = 1,
        host_index: int = 0,
        drop_last: bool = False,
        num_shards: int = 1,
        num_buckets: int = 1,
        oversampling: bool = False,
        num_samples: Optional[int] = None,
        sample_weights: Optional[np.ndarray] = None,
        sort_edges: bool = False,
        max_in_degree: Optional[int] = None,
        prefetch: int = 0,
        size_bucketing: bool = False,
        bucket_window: int = 16,
        pack: bool = False,
        with_triplets: bool = False,
        validator=None,
        source: str = "dataset",
        stall_timeout: float = 600.0,
    ):
        """``num_shards`` > 1 emits *stacked* batches with a leading device
        axis [num_shards, ...]: each shard is an independent padded batch with
        local indices, ready for ``shard_map`` data parallelism (``spec`` then
        describes one shard of batch_size/num_shards graphs).

        ``spec`` may be a single ``PadSpec`` (every batch padded to it) or a
        ``SpecLadder`` (each batch padded to the smallest fitting level);
        ``num_buckets`` > 1 with ``spec=None`` builds a ladder from the data
        (the variable-graph-size strategy, SURVEY §5.7).

        ``validator`` (data/validate.SampleValidator) gates bad samples at
        construction per ``Dataset.bad_sample_policy`` — non-finite
        channels, degenerate edge indices, and (under a fixed ``spec``)
        budget-overflow graphs are dropped-and-counted or raised instead of
        crashing mid-epoch; ``source`` labels this loader's rejects in the
        tally/manifest. ``stall_timeout`` (seconds; 0 disables) bounds how
        long the prefetch consumer waits on a silent producer before
        raising ``LoaderStallError``."""
        self.validator = validator
        self.source = source
        self.stall_timeout = float(stall_timeout or 0.0)
        if validator is not None:
            # content checks always; budget caps only when the spec is fixed
            # (auto-built ladders/budgets are derived from the data below and
            # fit every sample by construction)
            worst = (
                spec.specs[-1] if isinstance(spec, SpecLadder) else spec
            )
            graphs = validator.filter(
                graphs,
                source=source,
                max_nodes=worst.n_nodes - 1 if worst is not None else None,
                max_edges=worst.n_edges if worst is not None else None,
            )
        self.graphs = graphs
        self.batch_size = batch_size
        self.num_shards = num_shards
        if num_shards > 1 and batch_size % num_shards != 0:
            raise ValueError(
                f"batch_size {batch_size} must be divisible by num_shards "
                f"{num_shards} (each device takes batch_size/num_shards graphs)"
            )
        per_shard = max(batch_size // num_shards, 1)
        # packed mode: batches are formed by greedy bin-packing into ONE
        # fixed node/edge budget with a VARIABLE real-graph count (graph
        # slots are padded and masked like everything else). One PadSpec =
        # one jit specialization — no ladder, no per-level recompiles —
        # at ~the same occupancy the ladder reaches (docs/PERFORMANCE.md).
        self.pack = bool(pack)
        self._pack_cache = None  # (seed, epoch) -> (bins, agreed length)
        # per-graph triplet counts, computed at most ONCE per loader:
        # _triplet_count is O(E) interpreted python per graph, and the
        # packing/ladder paths would otherwise recompute it every epoch
        # (times host_count lockstep simulations) and again per batch
        self._trip_counts: Optional[np.ndarray] = None
        self._trip_by_id: Dict[int, int] = {}
        if self.pack:
            if isinstance(spec, SpecLadder):
                spec = spec.specs[-1]
            # with_triplets must reach the auto budget: a directly
            # constructed DimeNet pack loader would otherwise get
            # n_triplets=0 batches (the api.prepare_data path always
            # passes a spec)
            self.ladder = SpecLadder(
                (spec if spec is not None
                 else _pack_spec(graphs, per_shard,
                                 with_triplets=with_triplets),)
            )
        elif spec is None:
            self.ladder = SpecLadder.for_dataset(
                graphs,
                per_shard,
                num_buckets=num_buckets,
                # levels must be quantiles of the totals the active batch-
                # composition policy actually produces
                size_bucketing=size_bucketing,
                bucket_window=bucket_window,
                with_triplets=with_triplets,
            )
        elif isinstance(spec, SpecLadder):
            self.ladder = spec
        else:
            self.ladder = SpecLadder((spec,))
        # worst-case spec, kept for callers sizing buffers off loader.spec
        self.spec = self.ladder.specs[-1]
        self.shuffle = shuffle
        self.seed = seed
        self.host_count = host_count
        self.host_index = host_index
        self.drop_last = drop_last
        # RandomSampler-with-replacement / fixed-draw loader modes
        # (reference: create_dataloaders oversampling + num_samples,
        # hydragnn/preprocess/load_data.py:237-274)
        self.oversampling = oversampling
        self.num_samples = num_samples
        # per-sample draw weights (uneven-branch analog, see
        # branch_sample_weights); only meaningful with oversampling
        if sample_weights is not None:
            if not oversampling:
                raise ValueError("sample_weights requires oversampling=True")
            w = np.asarray(sample_weights, np.float64)
            if w.shape != (len(graphs),):
                raise ValueError(
                    f"sample_weights shape {w.shape} != ({len(graphs)},)"
                )
            sample_weights = w / w.sum()
        self.sample_weights = sample_weights
        # receiver-sorted edges (the Pallas sorted-segment-sum precondition,
        # ops/pallas_segment.py; also scatter-friendlier for XLA)
        self.sort_edges = sort_edges
        # the Pallas kernel leaves over-degree segments UNSPECIFIED
        # (ops/pallas_segment.py); fail loudly at loader build instead of
        # risking silently wrong aggregation sums on device
        if sort_edges and max_in_degree:
            for gi, g in enumerate(graphs):
                if g.num_edges:
                    top = int(
                        np.bincount(
                            np.asarray(g.receivers), minlength=g.num_nodes
                        ).max()
                    )
                    if top > int(max_in_degree):
                        raise ValueError(
                            f"graph {gi} (dataset_id "
                            f"{int(getattr(g, 'dataset_id', 0) or 0)}) has "
                            f"in-degree {top} > max_in_degree "
                            f"{max_in_degree}; raise Architecture.max_in_degree "
                            "(the Pallas sorted-segment kernel would produce "
                            "unspecified sums for over-degree nodes)"
                        )
        # background-thread batch building: host batching overlaps device
        # compute (the reference's HydraDataLoader thread-pool loader,
        # hydragnn/preprocess/load_data.py:93-203; its core-affinity pinning
        # has no analog here — XLA owns the host threads)
        self.prefetch = int(prefetch)
        # size-bucketed batch composition: batches drawn from a shuffled
        # window sorted by node count, so per-batch node totals concentrate
        # near window-median * batch_size instead of spreading over the full
        # batch-total distribution — most batches then *fill* their ladder
        # level and padding waste drops (the big padding-cost lever at
        # OC20-like size spreads; see docs/PERFORMANCE.md). Batch ORDER is
        # re-shuffled so SGD still sees random batch sequencing.
        self.size_bucketing = bool(size_bucketing)
        self.bucket_window = int(bucket_window)
        self._node_counts = (
            np.asarray([g.num_nodes for g in graphs], np.int64)
            if self.size_bucketing
            else None
        )
        self.epoch = 0
        # mid-epoch resume (docs/ROBUSTNESS.md "Data plane"): start_batch
        # skips the first k batches of the epoch WITHOUT building them — the
        # epoch permutation is a pure function of (seed, epoch), so (epoch,
        # cursor) is the loader's complete state and the remaining batches
        # replay in exactly the order an unkilled run would have seen
        self.start_batch = 0
        self._resume: Optional[Tuple[int, int]] = None

    def set_epoch(self, epoch: int) -> None:
        """Reseed the shuffle per epoch (DistributedSampler.set_epoch analog).

        The first call after ``resume()`` keeps the armed (epoch, cursor)
        instead — the resumed run's first training epoch replays the
        interrupted epoch's tail; later calls behave normally."""
        if self._resume is not None:
            self.epoch, self.start_batch = self._resume
            self._resume = None
        else:
            self.epoch = epoch
            self.start_batch = 0

    def resume(self, epoch: int, next_batch: int) -> None:
        """Arm deterministic mid-epoch resume at (``epoch``, ``next_batch``):
        applied immediately AND kept through the next ``set_epoch`` (the
        training loop's per-epoch reseed), one-shot."""
        self.epoch = int(epoch)
        self.start_batch = int(next_batch)
        self._resume = (int(epoch), int(next_batch))

    def state_dict(self, next_batch: int = 0) -> Dict[str, int]:
        """Loader state for checkpointing: the shuffle RNG is derived from
        (seed, epoch), so these four ints fully determine the remaining
        batch stream (train/checkpoint.py save_loader_state)."""
        return {
            "seed": int(self.seed),
            "epoch": int(self.epoch),
            "next_batch": int(next_batch),
            "num_batches": int(len(self)),
        }

    def __len__(self) -> int:
        if self.pack:
            return self._pack_state()[1]
        n = len(self._local_indices())
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def _count_from_ngroups(self, n_groups: int) -> int:
        """Batch count ``n_groups`` packed bins yield under the current
        shard/drop_last settings."""
        if self.num_shards == 1:
            return max(n_groups - 1, 0) if self.drop_last else n_groups
        if self.drop_last:
            return n_groups // self.num_shards
        return (n_groups + self.num_shards - 1) // self.num_shards

    def _trip_count_table(self) -> np.ndarray:
        """Lazy one-time scan: triplet count per dataset graph (also memoized
        by object id for the _make shard-spec lookup)."""
        if self._trip_counts is None:
            self._trip_counts = np.asarray(
                [_triplet_count(g) for g in self.graphs], np.int64
            )
            self._trip_by_id = {
                id(g): int(c) for g, c in zip(self.graphs, self._trip_counts)
            }
        return self._trip_counts

    def _trip_count_of(self, g: Graph) -> int:
        got = self._trip_by_id.get(id(g))
        return _triplet_count(g) if got is None else got

    def _pack_count_for(self, idx: np.ndarray) -> int:
        """Packed-batch count an index stream yields under current settings."""
        if self.size_bucketing and len(idx) > self.batch_size:
            idx = self._bucket_order(idx)
        return self._count_from_ngroups(len(self._pack_groups(idx)))

    def _pack_state(self) -> Tuple[List[List[int]], int]:
        """(local bins, agreed epoch length), computed once per (seed, epoch).

        The agreed length needs no communication: the epoch permutation is a
        pure function of (seed, epoch), so each host simulates every host's
        packing and takes the min — the packed analog of the equal-shard
        truncation in _global_indices (surplus bins on faster-packing hosts
        are dropped, like DistributedSampler's tail)."""
        key = (self.seed, self.epoch)
        if self._pack_cache is not None and self._pack_cache[0] == key:
            return self._pack_cache[1], self._pack_cache[2]
        idx = self._local_indices()
        if self.size_bucketing and len(idx) > self.batch_size:
            idx = self._bucket_order(idx)
        groups = self._pack_groups(idx)
        counts = [self._count_from_ngroups(len(groups))]
        if self.host_count > 1:
            gidx = self._global_indices()
            counts.extend(
                self._pack_count_for(gidx[h :: self.host_count])
                for h in range(self.host_count)
                if h != self.host_index
            )
        agreed = min(counts)
        self._pack_cache = (key, groups, agreed)
        return groups, agreed

    def _pack_groups(self, idx: np.ndarray) -> List[List[int]]:
        """Greedy stream packing: consecutive samples accumulate into a bin
        until the next one would overflow the node/edge/triplet budget or the
        graph-slot cap. Every bin fits ``self.spec`` by construction."""
        spec = self.spec
        cap_n, cap_e = spec.n_nodes - 1, spec.n_edges  # -1: dummy node slot
        cap_g, cap_t = spec.n_graphs - 1, spec.n_triplets
        trips = self._trip_count_table() if cap_t else None
        groups: List[List[int]] = []
        cur: List[int] = []
        n = e = t = 0
        for i in idx:
            g = self.graphs[i]
            gn, ge = g.num_nodes, g.num_edges
            gt = int(trips[i]) if cap_t else 0
            if gn > cap_n or ge > cap_e or (cap_t and gt > cap_t):
                if self.validator is not None:
                    # warn_skip/quarantine: drop-and-count instead of killing
                    # the run (dedup in the validator keeps the per-epoch
                    # re-pack from inflating the tally); error policy raises
                    # a BadSampleError naming the sample
                    self.validator.reject(
                        g, int(i), "budget_overflow", source=self.source,
                        detail=(
                            f"nodes={gn}, edges={ge}, triplets={gt} vs pack "
                            f"budget {spec}"
                        ),
                    )
                    continue
                raise ValueError(
                    f"graph {i} (dataset_id "
                    f"{int(getattr(g, 'dataset_id', 0) or 0)}, nodes={gn}, "
                    f"edges={ge}"
                    + (f", triplets={gt}" if cap_t else "")
                    + f") exceeds the pack budget {spec}; pass a larger spec "
                    "or set Dataset.bad_sample_policy to warn_skip/quarantine "
                    "to drop oversized samples"
                )
            if cur and (
                n + gn > cap_n
                or e + ge > cap_e
                or len(cur) >= cap_g
                or (cap_t and t + gt > cap_t)
            ):
                groups.append(cur)
                cur, n, e, t = [], 0, 0, 0
            cur.append(int(i))
            n, e, t = n + gn, e + ge, t + gt
        if cur:
            groups.append(cur)
        return groups

    def _global_indices(self) -> np.ndarray:
        """The full (permuted) epoch index stream BEFORE host slicing —
        identical on every host, which is what makes both the equal-shard
        truncation and the packed-mode lockstep agreement communication-free."""
        rng = np.random.default_rng(self.seed + self.epoch)
        if self.oversampling:
            n = self.num_samples or len(self.graphs)
            idx = rng.choice(
                len(self.graphs), size=n, replace=True, p=self.sample_weights
            )
        else:
            idx = np.arange(len(self.graphs))
            if self.shuffle:
                rng.shuffle(idx)
            if self.num_samples is not None:
                idx = idx[: self.num_samples]
        if self.host_count > 1:
            # equal shard sizes on every host, so multi-host training steps
            # stay in lockstep (a one-sample imbalance would leave one host
            # issuing an extra collective and deadlock the others)
            idx = idx[: len(idx) // self.host_count * self.host_count]
        return idx

    def _local_indices(self) -> np.ndarray:
        return self._global_indices()[self.host_index :: self.host_count]

    def _bucket_order(self, idx: np.ndarray) -> np.ndarray:
        """Reorder ``idx`` so contiguous ``batch_size`` slices are size-
        homogeneous: sort by node count within shuffled windows of
        ``bucket_window * batch_size`` samples (the whole set when not
        shuffling — eval wants maximal packing), then shuffle the order of
        the resulting full batches."""
        bs = self.batch_size
        # the remainder stays OUT of the sorting: a size-sorted tail would
        # make the final (dropped under drop_last) partial batch
        # systematically the largest graphs — the input order's tail is
        # unbiased (shuffled) or matches the plain loader (eval)
        n_full = len(idx) // bs
        head, tail = idx[: n_full * bs], idx[n_full * bs :]
        w = self.bucket_window * bs if self.shuffle else len(head)
        parts = []
        for s in range(0, len(head), max(w, bs)):
            win = head[s : s + max(w, bs)]
            order = np.argsort(self._node_counts[win], kind="stable")
            parts.append(win[order])
        head = np.concatenate(parts) if parts else head
        if self.shuffle and n_full > 1:
            rng = np.random.default_rng((self.seed + self.epoch) ^ 0x5EEDB)
            batch_order = rng.permutation(n_full)
            head = head.reshape(n_full, bs)[batch_order].reshape(-1)
        return np.concatenate([head, tail])

    def _batches(self) -> Iterator[GraphBatch]:
        # mid-epoch resume: the first ``start_batch`` batches of the epoch
        # are skipped WITHOUT being built (the index stream is deterministic
        # in (seed, epoch), so slicing the batch sequence is exact)
        start = max(int(self.start_batch), 0)
        if self.pack:
            yield from self._packed_batches(start)
            return
        idx = self._local_indices()
        if self.size_bucketing and len(idx) > self.batch_size:
            idx = self._bucket_order(idx)
        bs = self.batch_size
        n_full = len(idx) // bs
        for b in range(start, n_full):
            yield self._make([self.graphs[i] for i in idx[b * bs : (b + 1) * bs]])
        rem = len(idx) - n_full * bs
        if rem and not self.drop_last and start <= n_full:
            yield self._make([self.graphs[i] for i in idx[n_full * bs :]])

    def _packed_batches(self, start: int = 0) -> Iterator[GraphBatch]:
        # multi-host: stop at the globally agreed count so every host issues
        # the same number of (collective-bearing) steps
        groups, limit = self._pack_state()
        emitted = 0
        if self.num_shards == 1:
            if self.drop_last and len(groups) > 1:
                groups = groups[:-1]  # only the final bin can be sparse
            for grp in groups:
                if emitted >= limit:
                    return
                emitted += 1
                if emitted <= start:
                    continue
                yield batch_graphs(
                    [self.graphs[i] for i in grp],
                    self.spec,
                    sort_edges=self.sort_edges,
                )
            return
        for c in range(0, len(groups), self.num_shards):
            chunk = groups[c : c + self.num_shards]
            if emitted >= limit or (
                len(chunk) < self.num_shards and self.drop_last
            ):
                return
            emitted += 1
            if emitted <= start:
                continue
            yield self._make_stacked(
                [[self.graphs[i] for i in grp] for grp in chunk], self.spec
            )

    def _emit_stall_event(self, cause: str, batch_index: int) -> None:
        """Typed incident record for a stall verdict (obs/events.py) — the
        flight-recorder window sees WHICH batch wedged, not just a counter
        increment. Never allowed to fail the watchdog itself."""
        try:
            from ..obs.events import EV_LOADER_STALL
            from ..obs.events import emit as _emit_event

            _emit_event(
                EV_LOADER_STALL,
                severity="error",
                cause=cause,
                source=self.source,
                batch_index=int(batch_index),
                epoch=int(self.epoch),
            )
        except Exception:
            pass

    def _timed_batches(self) -> Iterator[GraphBatch]:
        """``_batches()`` with each batch's build (collate, pack or ladder
        choice, pad; the first also plans the epoch) under a ``batch_build``
        region on the building thread — not the wait on a full queue."""
        it = self._batches()
        k = max(int(self.start_batch), 0)
        while True:
            tr.start(tr.BATCH_BUILD, batch=k, epoch=int(self.epoch))
            batch = next(it, None)
            # the generator's end built nothing: closed, not counted
            tr.stop(tr.BATCH_BUILD, discard=batch is None)
            if batch is None:
                return
            yield batch
            k += 1

    def __iter__(self) -> Iterator[GraphBatch]:
        if self.prefetch <= 0:
            yield from self._timed_batches()
            return
        # bounded producer thread: up to ``prefetch`` batches built ahead
        import queue
        import threading

        from ..utils import faultinject

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        _END, _ERR, _NOTSET = object(), object(), object()
        epoch_start = int(self.start_batch)

        def put_or_stop(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for k, batch in enumerate(self._timed_batches()):
                    # chaos hooks (exact no-ops unarmed): a producer wedged
                    # in a slow build, or dead without its sentinel
                    if faultinject.maybe_loader_fault(epoch_start + k) == "die":
                        return
                    if not put_or_stop(batch):
                        return
                put_or_stop(_END)
            except BaseException as e:  # surfaced in the consumer
                put_or_stop((_ERR, e))

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        # exposed for tests asserting the thread is reaped after errors/break
        self._producer_thread = t
        # telemetry plane (obs/registry.py): prefetch-queue depth is the
        # live H2D-pipeline health signal — a depth pinned at 0 means the
        # device is waiting on host batch-build (the ROADMAP-3 H2D stall
        # axis); stalls are counted where they are raised
        from ..obs.registry import registry as _obs_registry

        g_depth = _obs_registry().gauge(
            "hydragnn_loader_prefetch_depth",
            "Prefetch queue depth observed at each batch handoff",
            labelnames=("source",),
        )
        c_stall = _obs_registry().counter(
            "hydragnn_loader_stalls_total",
            "LoaderStallError raised (dead or wedged prefetch producer)",
            labelnames=("source",),
        )
        c_stall.inc(0, source=self.source)  # materialize the series at 0
        timeout = float(self.stall_timeout or 0.0)
        delivered = 0
        try:
            while True:
                # timed wait + liveness watchdog instead of a bare blocking
                # get: a producer that died without the sentinel, or one
                # stalled past ``stall_timeout``, raises an actionable error
                # instead of hanging the run forever
                item = _NOTSET
                waited = 0.0
                while item is _NOTSET:
                    try:
                        item = q.get(timeout=_WATCHDOG_TICK_S)
                    except queue.Empty:
                        if not t.is_alive():
                            # the producer may have published a final item
                            # between our timeout and the liveness check
                            try:
                                item = q.get_nowait()
                                break
                            except queue.Empty:
                                c_stall.inc(source=self.source)
                                self._emit_stall_event(
                                    "producer_died", epoch_start + delivered
                                )
                                raise LoaderStallError(
                                    "prefetch producer thread exited without "
                                    "an end-of-epoch sentinel after batch "
                                    f"{epoch_start + delivered - 1} (epoch "
                                    f"{self.epoch}); the worker died outside "
                                    "python (or was killed) — restarting the "
                                    "epoch is required"
                                ) from None
                        waited += _WATCHDOG_TICK_S
                        if timeout and waited >= timeout:
                            c_stall.inc(source=self.source)
                            self._emit_stall_event(
                                "producer_wedged", epoch_start + delivered
                            )
                            raise LoaderStallError(
                                "prefetch producer produced nothing for "
                                f"{waited:.1f}s (> loader_stall_timeout="
                                f"{timeout}s) while building batch "
                                f"{epoch_start + delivered} of epoch "
                                f"{self.epoch}; the worker is wedged (hung "
                                "fetch/filesystem?) — raise "
                                "Training.loader_stall_timeout if batches "
                                "legitimately take this long"
                            ) from None
                if item is _END:
                    break
                if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                    raise item[1]
                delivered += 1
                g_depth.set(q.qsize(), source=self.source)
                yield item
        finally:
            # abandoned mid-epoch (break / exception): release the producer
            # and reap it with a bounded join — a producer blocked inside a
            # slow batch build cannot observe ``stop`` until it finishes, so
            # warn (daemon thread, leaked until process exit) instead of
            # blocking teardown indefinitely
            stop.set()
            t.join(timeout=_PRODUCER_JOIN_TIMEOUT_S)
            if t.is_alive():
                warnings.warn(
                    "prefetch producer thread still alive "
                    f"{_PRODUCER_JOIN_TIMEOUT_S}s after the epoch was "
                    "abandoned (blocked in a batch build?); leaking the "
                    "daemon thread",
                    RuntimeWarning,
                    stacklevel=2,
                )

    def spec_template_batches(self) -> List[Tuple[PadSpec, GraphBatch]]:
        """One template ``GraphBatch`` per ladder level this loader can emit
        — the compile plane's warm-up inputs (see the module-level
        ``spec_template_batches`` for the shape argument). Stacked
        (multi-shard) loaders pad the extra shard rows."""
        if self.num_shards == 1:
            return spec_template_batches(
                self.graphs,
                self.ladder,
                sort_edges=self.sort_edges,
                trip_count_of=self._trip_count_of,
            )
        out: List[Tuple[PadSpec, GraphBatch]] = []
        for li, g in selectable_levels(
            self.graphs, self.ladder, self._trip_count_of
        ):
            spec = self.ladder.specs[li]
            shards = [[g]] + [[] for _ in range(self.num_shards - 1)]
            out.append((spec, self._make_stacked(shards, spec)))
        return out

    def _make(self, graphs: List[Graph]) -> GraphBatch:
        with_trip = bool(self.spec.n_triplets)
        if with_trip:
            self._trip_count_table()  # populate the id memo once
        if self.num_shards == 1:
            spec = self.ladder.select(
                sum(g.num_nodes for g in graphs),
                sum(g.num_edges for g in graphs),
                sum(self._trip_count_of(g) for g in graphs) if with_trip else 0,
            )
            return batch_graphs(graphs, spec, sort_edges=self.sort_edges)
        shards = [graphs[s :: self.num_shards] for s in range(self.num_shards)]
        # one spec for the whole stacked batch: the smallest level fitting
        # the largest shard (all shards must share static shapes)
        spec = self.ladder.select(
            max(sum(g.num_nodes for g in s) for s in shards if s),
            max(sum(g.num_edges for g in s) for s in shards if s),
            max(
                (sum(self._trip_count_of(g) for g in s) for s in shards if s),
                default=0,
            )
            if with_trip
            else 0,
        )
        return self._make_stacked(shards, spec)

    def _make_stacked(
        self, shards: List[List[Graph]], spec: PadSpec
    ) -> GraphBatch:
        """Stack per-shard padded batches into a leading device axis;
        missing shards become all-padding rows."""
        return stack_shard_batches(
            shards, spec, self.num_shards, sort_edges=self.sort_edges
        )
