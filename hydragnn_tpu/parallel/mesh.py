"""Device mesh + sharding layer: the TPU replacement for torch DDP/NCCL.

The reference scales with ``DistributedDataParallel`` over NCCL/RCCL/oneCCL
process groups plus an mpi4py side plane (hydragnn/utils/distributed/
distributed.py:119-351, SURVEY §5.8). The TPU-native design is
single-controller SPMD:

- one ``jax.sharding.Mesh`` with axes ``("branch", "data")`` replaces process
  groups; pure data parallelism is the degenerate branch=1 case;
- batches are sharded over ``data`` (the ``GraphBatch`` leading axes), params
  are replicated; ``jax.jit`` then inserts the gradient ``psum`` over ICI
  automatically during backward — the analog of DDP's bucketed all-reduce,
  overlapped with compute by XLA's async collectives;
- the multi-branch task parallelism of ``MultiTaskModelMP``
  (hydragnn/models/MultiTaskModelMP.py:172-230) maps to the ``branch`` axis:
  each branch submesh consumes its own dataset shard, encoder gradients psum
  over the full mesh, decoder gradients over the branch submesh — expressed
  by the same jit program because unused branches contribute zero gradients
  under the dense masked-branch decoding (models/base.py _graph_head).

Multi-host: ``jax.distributed.initialize`` + per-host data sharding via
``GraphLoader(host_count, host_index)``; collectives ride ICI within a slice
and DCN across slices, chosen by XLA from the mesh axis order.
"""

from __future__ import annotations

import os
import warnings
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..utils import envflags

DATA_AXIS = "data"
BRANCH_AXIS = "branch"
MODEL_AXIS = "model"


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    branch_size: int = 1,
) -> Mesh:
    """Build a (branch, data) mesh over the available devices.

    branch_size=1 -> pure DP. Mirrors the 2-D ``init_device_mesh`` of the
    reference's task-parallel path (examples/multibranch/train.py:216-251).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    assert n % branch_size == 0, f"{n} devices not divisible by branch={branch_size}"
    arr = np.asarray(devices).reshape(branch_size, n // branch_size)
    return Mesh(arr, (BRANCH_AXIS, DATA_AXIS))


def make_mesh2d(
    devices: Optional[Sequence[jax.Device]] = None,
    model_size: int = 1,
) -> Mesh:
    """Build the engine's 2D ``(data, model)`` mesh (parallel/engine.py).

    Subsumes ``make_mesh``: ``model_size`` is the model/task-parallel
    extent (num_branches in the routed presets, 1 for pure DP/ZeRO).
    Device (d, m) is ``devices[m * data_n + d]`` — the transpose of the
    legacy ``(branch, data)`` layout — so the *physical* device holding
    (branch=m, data=d) work is identical between the two constructors and
    the engine's steps are bit-identical to the retired builders'.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    assert (
        n % model_size == 0
    ), f"{n} devices not divisible by model={model_size}"
    arr = np.asarray(devices).reshape(model_size, n // model_size)
    return Mesh(arr.transpose(1, 0), (DATA_AXIS, MODEL_AXIS))


def data_axis_size(mesh: Mesh) -> int:
    return int(dict(mesh.shape).get(DATA_AXIS, 1))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the GraphBatch leading dim shards over, in shard-row
    order: legacy meshes stack (branch-major, data-minor); the 2D mesh
    keeps the same row order as (model, data) so a given shard index
    lands on the same physical device under both constructors."""
    names = mesh.axis_names
    if MODEL_AXIS in names:
        return (MODEL_AXIS, DATA_AXIS)
    if BRANCH_AXIS in names:
        return (BRANCH_AXIS, DATA_AXIS)
    return (DATA_AXIS,)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for GraphBatch leaves: leading (node/edge/graph) axis over
    the mesh's batch axes (``batch_axes`` — model/branch-major, data-minor,
    identical shard->device mapping under both mesh constructors).
    Requires padded sizes divisible by the mesh size."""
    return NamedSharding(mesh, P(batch_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh):
    """Place a GraphBatch with leading axes sharded across the mesh."""
    sh = batch_sharding(mesh)
    rep = replicated(mesh)

    def place(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % mesh.size == 0:
            return jax.device_put(x, sh)
        return jax.device_put(x, rep)

    return jax.tree_util.tree_map(place, batch)


def promote_batch(batch, mesh: Mesh):
    """Host-local stacked GraphBatch ``[local_shards, ...]`` -> global array
    ``[global_shards, ...]`` sharded over the mesh's (branch, data) leading
    axis — the multi-controller input path: each process contributes the
    shards its own ``GraphLoader(host_count, host_index)`` built, and the
    shard_map'd step sees one coherent global batch (the DistributedSampler
    + DDP input contract, reference: load_data.py:256-274).

    No-op on single-process runs (the batch is already addressable).
    """
    if jax.process_count() == 1:
        return batch
    sharding = batch_sharding(mesh)

    def prom(x):
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    return jax.tree_util.tree_map(prom, batch)


def replicate_state(state, mesh: Mesh):
    rep = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), state)


def _zero_leaf_eligible(x, data_n: int, min_size: int) -> bool:
    """Shared ZeRO eligibility predicate: large leaves whose leading dim
    divides the data axis. ONE definition for both the stage-1 moment
    placement and the stage-2 gradient constraint so their slices always
    line up (a de-synced pair would leave some moment leaves sharded with
    replicated gradients, defeating the reduce-scatter lowering)."""
    return (
        hasattr(x, "ndim")
        and x.ndim >= 1
        and x.size >= min_size
        and x.shape[0] % data_n == 0
    )


def shard_optimizer_state(state, mesh: Mesh, min_size: int = 1024):
    """ZeRO-1 analog: shard large optimizer-moment arrays over the data axis
    (reference capability: DeepSpeed ZeRO stage 1 / ZeroRedundancyOptimizer,
    optimizer.py:43-101). Parameters stay replicated; only optimizer state
    pytree leaves whose leading dim divides the data axis are sharded."""
    data_n = mesh.shape[DATA_AXIS]
    sharded = NamedSharding(mesh, P(DATA_AXIS))
    rep = replicated(mesh)

    def place(x):
        if _zero_leaf_eligible(x, data_n, min_size):
            return jax.device_put(x, sharded)
        return jax.device_put(x, rep)

    return jax.tree_util.tree_map(place, state)


def shard_params_zero3(params, mesh: Mesh, min_size: int = 1024):
    """ZeRO-3/FSDP analog: store large PARAMETER leaves sharded ``P(data)``
    between steps (reference capability: DeepSpeed ZeRO stage 3, accepted
    by run_training.py:136-149).

    The mesh step's shard_map consumes params at spec ``P()`` — XLA
    inserts the transient all-gather at the program boundary (the FSDP
    gather-at-use), and the step's output constraint re-shards the
    updated params, so full parameters exist only inside one step's
    lifetime. Same eligibility predicate as the stage-1/2 placements so
    param, gradient, and moment slices all line up."""
    data_n = mesh.shape[DATA_AXIS]
    sharded = NamedSharding(mesh, P(DATA_AXIS))
    rep = replicated(mesh)

    def place(x):
        if _zero_leaf_eligible(x, data_n, min_size):
            return jax.device_put(x, sharded)
        return jax.device_put(x, rep)

    return jax.tree_util.tree_map(place, params)


def zero3_param_constraint(params, mesh: Mesh, min_size: int = 1024):
    """In-jit counterpart of ``shard_params_zero3``: pin updated parameter
    leaves back to ``P(data)`` at the end of the step so XLA frees the
    gathered full copies instead of keeping params replicated."""
    data_n = mesh.shape[DATA_AXIS]
    sharded = NamedSharding(mesh, P(DATA_AXIS))

    def place(x):
        if _zero_leaf_eligible(x, data_n, min_size):
            return jax.lax.with_sharding_constraint(x, sharded)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(place, params)


def zero2_grad_constraint(grads, mesh: Mesh, min_size: int = 1024):
    """ZeRO-2 analog: constrain large gradient leaves to ``P(data)`` sharding
    inside the jitted step (reference capability: DeepSpeed ZeRO stage 2,
    accepted by run_training.py:136-149).

    Applied between the gradient ``pmean`` and the optimizer update, XLA
    lowers the reduce+constraint pair to a reduce-scatter: each device then
    holds only its 1/data_n gradient slice, updates the matching ZeRO-1
    moment slice, and the replicated-params output constraint turns the
    param update into the all-gather — the full ZeRO-2 exchange, expressed
    as shardings instead of hand-written collectives. Eligibility matches
    ``shard_optimizer_state`` so gradient and moment slices line up.
    """
    sharded = NamedSharding(mesh, P(DATA_AXIS))
    data_n = mesh.shape[DATA_AXIS]

    def place(g):
        if _zero_leaf_eligible(g, data_n, min_size):
            return jax.lax.with_sharding_constraint(g, sharded)
        return g

    return jax.tree_util.tree_map(place, grads)


def leaf_sharding_info(x) -> Optional[dict]:
    """Placement facts of one state leaf for the sharding inspector
    (obs/sharding.py): PartitionSpec string, replicated-vs-sharded, total
    and per-device bytes. Pure metadata — no transfers, no compute. None
    for non-array leaves; host numpy arrays report as replicated with a
    ``host`` spec (every process holds the full copy, which is what the
    audit cares about)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        return None
    total = int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return {
            "spec": "host", "replicated": True, "total_bytes": total,
            "per_device_bytes": total, "devices": 1,
            "dtype": str(np.dtype(dtype)), "shape": tuple(shape),
        }
    if isinstance(sharding, NamedSharding):
        spec = str(sharding.spec)
    else:
        spec = type(sharding).__name__
    replicated = bool(getattr(sharding, "is_fully_replicated", True))
    per_device = total
    try:
        shard_shape = sharding.shard_shape(tuple(shape))
        per_device = (
            int(np.prod(shard_shape, dtype=np.int64)) * itemsize
            if shard_shape
            else itemsize
        )
    except Exception:
        pass
    try:
        devices = len(sharding.device_set)
    except Exception:
        devices = 1
    return {
        "spec": spec, "replicated": replicated, "total_bytes": total,
        "per_device_bytes": per_device, "devices": devices,
        "dtype": str(np.dtype(dtype)), "shape": tuple(shape),
    }


def materialize_replicated(tree):
    """Host-local numpy copy of a (possibly sharded) global-state pytree.

    Sharded leaves (ZeRO-1 moments, branch-parallel decoder banks) are
    re-replicated with a jitted identity first — fetching them directly
    would fail because they span non-addressable devices. COLLECTIVE on
    multi-host runs: every process must call it, in the same tree order.
    """

    def loc(x):
        if (
            isinstance(x, jax.Array)
            and hasattr(x, "sharding")
            and not x.sharding.is_fully_replicated
        ):
            # eager resharding device_put: no per-leaf trace/compile (a
            # jitted identity here would recompile for every leaf shape at
            # every checkpoint save)
            x = jax.device_put(x, NamedSharding(x.sharding.mesh, P()))
        return np.asarray(x)

    return jax.tree_util.tree_map(loc, tree)


def _scheduler_host_info() -> Tuple[int, int]:
    """(host_count, host_index) from scheduler envs only — safe before the
    XLA backend exists (the reference parses the same envs, SLURM/OMPI,
    distributed.py:86-103)."""
    # Cloud TPU pod VMs expose the slice topology in TPU_* envs. A
    # single-name value (e.g. "localhost" on one-host setups) carries no
    # multi-host information — fall through to the scheduler envs then.
    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    if len(hosts) > 1:
        return len(hosts), int(os.environ.get("TPU_WORKER_ID", 0))
    for count_key, rank_key in (
        ("SLURM_NTASKS", "SLURM_PROCID"),
        ("OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK"),
        ("WORLD_SIZE", "RANK"),
    ):
        if count_key in os.environ:
            return int(os.environ[count_key]), int(os.environ.get(rank_key, 0))
    return 1, 0


# set when setup_distributed had to skip rendezvous (backend already
# initialized): the scheduler envs then over-report the connected world
_rendezvous_skipped = False


def local_host_info() -> Tuple[int, int]:
    """(host_count, host_index) for data sharding across hosts: the live JAX
    distributed runtime when attached, scheduler envs otherwise. After a
    skipped rendezvous this reports (1, 0) — the process really is alone, so
    sharding by the scheduler's world size would silently train on a
    fraction of the data with no gradient sync."""
    if jax.process_count() > 1:
        return jax.process_count(), jax.process_index()
    if _rendezvous_skipped:
        return 1, 0
    return _scheduler_host_info()


def setup_distributed() -> None:
    """Initialize the multi-host JAX runtime when launched under a scheduler
    (the analog of setup_ddp's rendezvous, distributed.py:119-198). No-op for
    single-process runs.

    Rendezvous resolution order (cf. the reference's master-addr discovery
    for Summit/SLURM, distributed.py:143-159):
    1. explicit ``HYDRAGNN_COORDINATOR`` / ``JAX_COORDINATOR_ADDRESS`` plus
       the scheduler's world size/rank envs,
    2. bare ``jax.distributed.initialize()`` auto-detection — covers GCE TPU
       pods (metadata server) and SLURM/OpenMPI clusters JAX knows natively.

    Must run before anything touches the XLA backend — including
    ``jax.process_count()`` — so the already-initialized guard uses
    ``jax.distributed.is_initialized()``, which doesn't.
    """
    if jax.distributed.is_initialized():
        return
    coord = envflags.env_str("HYDRAGNN_COORDINATOR") or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    count, index = _scheduler_host_info()
    try:
        if coord and count > 1:
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=count, process_id=index
            )
        elif count > 1:
            jax.distributed.initialize()
    except RuntimeError as e:
        if "must be called before" not in str(e):
            # genuine rendezvous failure (unreachable coordinator, mismatch):
            # abort — N silently-independent "replicas" would clobber shared
            # checkpoints and fake the scaling result
            raise
        # the XLA backend was touched before run_training (interactive use,
        # tests): train single-host rather than crash, but say so
        global _rendezvous_skipped
        _rendezvous_skipped = True
        warnings.warn(f"multi-host rendezvous skipped: {e}")


def gather_across_hosts(values):
    """Concatenate per-host arrays across every process: dict of
    [n_local, ...] -> dict of [n_global, ...], ragged-safe (each host may
    hold a different sample count — pad to the max, then slice per the
    gathered counts). The analog of the reference's padded all-gather of
    test predictions (gather_tensor_ranks,
    hydragnn/train/train_validate_test.py:410-448). Identity on one host.
    """

    if jax.process_count() == 1:
        return values
    from jax.experimental import multihost_utils

    out = {}
    for k, v in values.items():
        v = np.asarray(v)
        counts = np.asarray(
            multihost_utils.process_allgather(
                np.asarray([v.shape[0]], np.int64)
            )
        ).reshape(-1)
        max_n = int(counts.max())
        pad = np.zeros((max_n - v.shape[0],) + v.shape[1:], v.dtype)
        stacked = np.asarray(
            multihost_utils.process_allgather(np.concatenate([v, pad]))
        )
        out[k] = np.concatenate(
            [stacked[p, : int(counts[p])] for p in range(stacked.shape[0])]
        )
    return out
