"""Epoch/step training loop — the hot path.

TPU re-design of the reference's ``train_validate_test``/``train``/``validate``
/``test`` (hydragnn/train/train_validate_test.py:52-748):

- the whole optimizer step is one jitted, donated function — forward, loss,
  backward, and update fuse into a single XLA program; gradient all-reduce is
  inserted by the compiler when the batch is sharded over a mesh (no DDP wrap);
- head-index bookkeeping (get_head_indices, :316-379) does not exist: targets
  arrive per-head from the loader with static shapes;
- H2D transfer of the next batch overlaps with device compute because JAX
  dispatch is async.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..data.graph import GraphBatch
from ..models.base import HydraModel
from ..utils import envflags
from ..utils import tracer as tr
from .loss import compute_loss
from .optimizer import ReduceLROnPlateau
from .state import TrainState


# float batch fields cast to bfloat16 under mixed precision (targets and
# masks stay f32/bool so the loss accumulates in f32 via promotion)
_MP_INPUT_FIELDS = ("x", "pos", "edge_attr", "edge_shifts", "pe", "rel_pe")


def cast_batch_bf16(batch: GraphBatch, keep_pos: bool = False) -> GraphBatch:
    """Cast the model-input channels of a batch to bfloat16. ``keep_pos``
    preserves f32 positions for the autograd-force objective, where forces
    come from d(energy)/d(pos) and bf16 positions would quantize them."""
    upd = {}
    for f in _MP_INPUT_FIELDS:
        if keep_pos and f == "pos":
            continue
        v = getattr(batch, f)
        if v is not None and jnp.issubdtype(v.dtype, jnp.floating):
            upd[f] = v.astype(jnp.bfloat16)
    return batch.replace(**upd)


def cast_floats(tree, dtype, keep=None):
    """Every floating leaf cast to ``dtype``; ``keep(leaf name)`` true leaves
    one as it is (a model's ``float32_leaves``, see ``mp_keep``)."""
    def cast(path, p):
        if not (isinstance(p, jnp.ndarray) and jnp.issubdtype(p.dtype, jnp.floating)):
            return p
        if keep is not None and path and keep(str(getattr(path[-1], "key", ""))):
            return p
        return p.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, tree)


def mp_keep(model):
    """The model's own statement of which parameter leaves the mixed-precision
    cast leaves in float32 (``float32_leaves(leaf name) -> bool``), or None."""
    return getattr(model, "float32_leaves", None)


def mp_cast(params, batch, compute_grad_energy: bool, keep=None):
    """The mixed-precision input cast, shared by the single-device and mesh
    step builders so their numerics stay byte-identical: bf16 params + bf16
    input channels (f32 positions under the autograd-force objective)."""
    with tr.scope(tr.HG_CAST):
        return (
            cast_floats(params, jnp.bfloat16, keep),
            cast_batch_bf16(batch, keep_pos=compute_grad_energy),
        )


def mp_restore_stats(mutated: dict) -> dict:
    """Persist batch-norm running statistics in f32 after a bf16 forward."""
    if "batch_stats" in mutated:
        with tr.scope(tr.HG_CAST):
            mutated = dict(
                mutated,
                batch_stats=cast_floats(mutated["batch_stats"], jnp.float32),
            )
    return mutated


def mp_cast_eval(variables, batch, compute_grad_energy: bool, keep=None):
    """Eval-side cast: bf16 params AND running stats (eval normalizes with
    the running statistics, unlike training)."""
    variables = {
        "params": cast_floats(variables["params"], jnp.bfloat16, keep),
        "batch_stats": cast_floats(
            variables.get("batch_stats", {}), jnp.bfloat16
        ),
    }
    return variables, cast_batch_bf16(batch, keep_pos=compute_grad_energy)


def make_train_step(
    model: HydraModel,
    tx: optax.GradientTransformation,
    compute_grad_energy: bool = False,
    mixed_precision: bool = False,
    guard: Optional[bool] = None,
    numerics: Optional[bool] = None,
):
    """Build the jitted SGD step: (state, batch, rng) -> (state, loss, tasks).

    ``compute_grad_energy=True`` switches to the energy+force objective
    (reference: train_validate_test.py:517-520 -> Base.energy_force_loss).

    ``mixed_precision=True`` runs the forward/backward in bfloat16 (MXU
    native) against f32 master weights: params and input channels are cast
    to bf16 inside the differentiated function, so gradients flow back
    through the cast and land in f32 for the optimizer; running batch-norm
    statistics are re-cast to f32 before being stored. Targets stay f32, so
    residuals and the loss accumulate in f32 by dtype promotion. The rule:
    features run in the compute dtype; coordinates and the loss accumulation
    stay float32. A geometric edge term (an EGNN layer's edge length) is cast
    to the feature stream's dtype where it joins it (models/layers.py
    ``pair_message_factored``), so f32 coordinates promote no ``[E, C]`` array.

    ``guard`` (default: on, env HYDRAGNN_STEP_GUARD=0 disables): in-graph
    non-finite step guard — loss/global-grad-norm finiteness is computed in
    the same program and a bad step's optimizer update is gated to identity
    (per-leaf select), advancing the state's skip counters (train/guard.py).
    A good step commits the EXACT unguarded update values.

    ``numerics`` (default: off, env HYDRAGNN_NUMERICS=1 enables; wired from
    ``Telemetry.numerics``): in-graph per-layer activation + per-param-group
    gradient statistics (obs/numerics.py) ride the step as a FOURTH output
    ``{"ok", "act", "grad"}`` — the step then returns a 4-tuple, and the
    returned callable carries ``_numerics_meta`` (tensor name tables,
    written at trace time) and ``_nan_diagnose`` (the provenance
    drill-down) attributes. Off, the step and its outputs are byte-
    identical to the historical 3-tuple."""
    cfg = model.cfg
    from ..obs import numerics as obs_numerics
    from ..utils import faultinject
    from .guard import guard_enabled, guarded_update, step_ok

    use_guard = guard_enabled(guard)
    use_numerics = obs_numerics.numerics_enabled(numerics)
    meta = {"act_names": None, "grad_names": None}

    def loss_fn(params, batch_stats, batch, rng):
        if mixed_precision:
            params, batch = mp_cast(params, batch, compute_grad_energy, mp_keep(model))
        variables = {"params": params, "batch_stats": batch_stats}
        (tot, tasks, mutated, _), acts = obs_numerics.run_probed(
            use_numerics, meta,
            lambda: compute_loss(
                model, variables, batch, cfg, True, rng, compute_grad_energy
            ),
        )
        if mixed_precision:
            mutated = mp_restore_stats(mutated)
        return tot.astype(jnp.float32), (tasks, mutated, acts)

    if cfg.conv_checkpointing:
        # rematerialize the forward during backward (reference: per-conv torch
        # checkpoint, Base.py:459-465), with the save rule picked by
        # Training.remat_policy (ops/remat.py — 'names' keeps the Pallas
        # kernel outputs instead of re-running the kernels in the backward)
        from ..ops.remat import loss_remat

        loss_fn = loss_remat(loss_fn, cfg.remat_policy)

    from .compile_plane import note_trace

    @partial(jax.jit, donate_argnums=0)
    def train_step(state: TrainState, batch: GraphBatch, rng):
        # retrace sentinel: the body runs once per jit trace, so this call
        # IS the trace census (train/compile_plane.py)
        note_trace("train_step", (state, batch, rng))
        with tr.scope(tr.HG_LOSS):
            (tot, (tasks, mutated, acts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state.batch_stats, batch, rng)
        # chaos-test hook: exact no-op unless a fault is armed (trace-time)
        grads = faultinject.poison_grads(
            grads, state.step, faultinject.lr_of(state.opt_state)
        )
        new_stats = mutated.get("batch_stats", state.batch_stats)
        numer = None
        if use_numerics:
            # gradient stats AFTER the fault hook, so injected NaNs show up
            # in the same census the provenance drill-down reads
            gnames, gstats = obs_numerics.grad_group_stats(grads)
            meta["grad_names"] = gnames
            numer = {"ok": step_ok(tot, grads), "act": acts, "grad": gstats}
        if use_guard:

            def do_update():
                with tr.scope(tr.HG_OPTIMIZER):
                    updates, opt_state = tx.update(
                        grads, state.opt_state, state.params
                    )
                    return (
                        optax.apply_updates(state.params, updates),
                        opt_state,
                    )

            new_state = guarded_update(
                state,
                numer["ok"] if numer is not None else step_ok(tot, grads),
                do_update,
                new_stats,
            )
        else:
            with tr.scope(tr.HG_OPTIMIZER):
                updates, opt_state = tx.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
            new_state = state.replace(
                params=params,
                opt_state=opt_state,
                batch_stats=new_stats,
                step=state.step + 1,
            )
        if use_numerics:
            return new_state, tot, tasks, numer
        return new_state, tot, tasks

    if not use_numerics:
        return train_step
    # the numerics build returns a wrapper so the jit object stays AOT-
    # reachable (compile plane) and the host-side name tables + NaN
    # drill-down travel with the step function (obs/numerics.py)
    return obs_numerics.numerics_step_wrapper(
        train_step, meta, model, compute_grad_energy, mixed_precision
    )


def make_eval_step(
    model: HydraModel,
    compute_grad_energy: bool = False,
    mixed_precision: bool = False,
):
    cfg = model.cfg
    from .compile_plane import note_trace

    @jax.jit
    def eval_step(state: TrainState, batch: GraphBatch):
        note_trace("eval_step", (state, batch))
        variables = state.variables()
        if mixed_precision:
            variables, batch = mp_cast_eval(
                variables, batch, compute_grad_energy, mp_keep(model)
            )
        tot, tasks, _, outputs = compute_loss(
            model, variables, batch, cfg, False, None, compute_grad_energy
        )
        return tot, tasks, outputs

    return eval_step


def _weighted_avg(entries: List[Tuple[float, Dict[str, float], int]]):
    total_n = sum(n for _, _, n in entries) or 1
    tot = sum(l * n for l, _, n in entries) / total_n
    task_names = entries[0][1].keys() if entries else []
    tasks = {
        k: sum(t[k] * n for _, t, n in entries) / total_n for k in task_names
    }
    return tot, tasks


def device_prefetch(iterator, depth: int = 2, device=None, epoch: int = 0,
                    start_batch: int = 0):
    """Double-buffered device staging: a background thread ``device_put``s
    upcoming batches so the H2D copy overlaps the current step's compute.
    The reference pays this cost inline every step (``data.to(device)``,
    train_validate_test.py:514); async dispatch hides *compute* but the
    transfer itself still serializes with the dispatching thread — staging
    from a second thread takes it off the critical path entirely.

    Single-device only at the call sites (sharded stacked batches are placed
    by the parallel step's own sharding logic). ``epoch`` and ``start_batch``
    only label the staging thread's ``h2d_stage`` regions."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=max(int(depth), 1))
    stop = threading.Event()
    _END, _ERR = object(), object()

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
            for k, batch in enumerate(iterator, start_batch):
                # the host cost of staging: device_put returns before the
                # copy has finished, and nothing here waits for it
                tr.start(tr.H2D_STAGE, batch=k, epoch=epoch)
                staged = jax.device_put(batch, device)
                tr.stop(tr.H2D_STAGE)
                if not put_or_stop(staged):
                    return
            put_or_stop(_END)
        except BaseException as e:  # surfaced in the consumer
            put_or_stop((_ERR, e))

    threading.Thread(target=producer, daemon=True).start()
    try:
        while True:
            item = q.get()  # graftlint: disable=threads -- producer is a daemon doing only device_put; it always posts _END/_ERR, and the loader-side stall watchdog (data/pipeline.py) owns stall detection
            if item is _END:
                return
            if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                raise item[1]
            yield item
    finally:
        stop.set()


def _maybe_device_prefetch(iterator, depth: Optional[int] = None,
                           epoch: int = 0, start_batch: int = 0):
    """Wrap with device_prefetch on single-device runs (multi-device batch
    placement belongs to the parallel step). ``depth`` comes from
    ``Training.double_buffer`` (true = 2, false = off, an int = that
    queue depth); the HYDRAGNN_DEVICE_PREFETCH env always wins (0
    disables), and None means "no config reached here" — the historical
    env-or-2 default, so direct callers keep their behavior."""
    if envflags.env_set("HYDRAGNN_DEVICE_PREFETCH"):
        depth = envflags.env_int("HYDRAGNN_DEVICE_PREFETCH", 2)
    elif depth is None:
        depth = 2
    active = (
        depth > 0
        and jax.local_device_count() == 1
        and jax.process_count() == 1
    )
    try:  # the telemetry smoke's A/B assertion reads this gauge
        from ..obs.registry import registry

        registry().gauge(
            "hydragnn_device_prefetch_depth",
            "Double-buffered device_put queue depth (0 = staging inline)",
        ).set(float(depth if active else 0))
    except Exception:
        pass
    if not active:
        return iterator
    return device_prefetch(
        iterator, depth=depth, epoch=epoch, start_batch=start_batch
    )


def train_epoch(loader, step_fn, state, rng, start_batch: int = 0,
                telemetry=None, tracer=None, prefetch_depth=None,
                nan_watch=None, guard_log=None):
    """One training epoch. Returns ``(state, tot, tasks, rng, cursor)``:
    ``cursor`` is None when the epoch completed, or the next-batch offset
    (loader-absolute) when a SIGTERM arrived between steps — the mid-epoch
    preemption stop (single-process only: the per-step flag check cannot be
    agreed across hosts without a per-step collective, so multi-host runs
    keep the epoch-boundary stop). ``start_batch`` fast-forwards a loader
    WITHOUT native resume support by consuming (not stepping) its first
    batches; loaders that implement ``resume()`` skip building them
    entirely and report their offset via ``start_batch`` attribute.
    ``telemetry`` (obs/telemetry.StepTelemetry, or None) receives every
    step's batch + host dispatch time — under async dispatch the queue
    throttles the host to the device rate, so the window means it
    publishes converge to device step time without per-step syncs.
    ``tracer`` (obs/trace.Tracer, or None) emits one span tree per
    every-Nth sampled step: a ``train/step`` root with retroactive
    ``train/host_batch_build`` (host batching + validation + H2D staging,
    the ``dataload`` region) and ``train/device_dispatch`` children —
    unsampled steps pay one ``is not None`` check.
    ``nan_watch`` (obs/numerics.NanWatch, or None) receives every step's
    ok flag + held batch for the deferred non-finite check and NaN
    provenance drill-down (requires a numerics-enabled ``step_fn``).
    ``guard_log`` (a dict, or None) is filled with this epoch's
    ``nonfinite`` step census — batch index, spec-ladder level, and (when
    the loader exposes ``batch_sources``) the mixture draw ids of every
    step whose loss came back non-finite — the batch provenance the
    epoch-boundary guard policy attaches to its ``guard_skip`` event."""
    from ..utils import faultinject, preemption

    # Device-side loss bookkeeping: the per-step (loss, tasks) scalars stay
    # on device and are read back ONCE at epoch end, so step i+1 dispatches
    # while step i is still executing (JAX async dispatch keeps the chip
    # saturated; a per-step float() would block the host on every step and
    # serialize the pipeline — the reference tolerates this because torch
    # .item() overlaps with DDP bucket comms, XLA does not).
    entries = []
    # the loader may already skip batches itself (GraphLoader.resume);
    # cursor values reported to checkpoints are absolute within the epoch
    offset = int(getattr(loader, "start_batch", 0) or 0)
    check_preempt = jax.process_count() == 1
    cursor = None
    consumed = 0
    # per-step provenance meta ((batch index, pad level, mixture sources)):
    # two ints and a small tuple per step, recorded only when a consumer
    # asked; MixturePlane exposes batch_sources, plain loaders don't
    step_meta = [] if (guard_log is not None or nan_watch is not None) else None
    src_fn = getattr(loader, "batch_sources", None)
    # the watch needs the failing step's state.step value (the fault-
    # injection hooks key on it); one host read of the incoming counter
    # per epoch, then pure python increments
    step0 = (
        int(jax.device_get(state.step)) if nan_watch is not None else 0
    )
    # epoch_restart: the loader and the staging thread start from cold, up
    # to the epoch's first batch in hand (encloses that first dataload)
    epoch = int(getattr(loader, "epoch", 0) or 0)
    tr.start(tr.EPOCH_RESTART, epoch=epoch)
    it = _maybe_device_prefetch(
        iter(loader), depth=prefetch_depth, epoch=epoch, start_batch=offset
    )
    for i in range(len(loader)):
        # dataload span covers host batching + H2D staging (the reference's
        # per-step data.to(device), train_validate_test.py:506-514; here the
        # jitted step overlaps with the next host batch via async dispatch)
        t_build = time.perf_counter()
        tr.start(tr.DATALOAD, batch=offset + consumed, epoch=epoch)
        try:
            batch = next(it)
        except StopIteration:
            tr.stop(tr.DATALOAD)
            break
        tr.stop(tr.DATALOAD)
        if i == 0:
            tr.stop(tr.EPOCH_RESTART)
        build_dt = time.perf_counter() - t_build
        consumed += 1
        if i < start_batch:
            continue  # fast-forward (mid-epoch resume on a generic loader)
        sp = None
        if tracer is not None and tracer.sample_step():
            sp = tracer.begin("train/step")
            sp.set_attribute("batch_index", offset + consumed - 1)
            tracer.emit_completed(
                "train/host_batch_build",
                time.time() - build_dt,
                build_dt,
                parent=sp,
            )
        # rng_split: two tiny programs a step; when the device's queue of
        # programs is full it is here that the host waits for the device
        idx = offset + consumed - 1
        tr.start(tr.RNG_SPLIT, batch=idx, epoch=epoch)
        rng, sub = jax.random.split(rng)
        tr.stop(tr.RNG_SPLIT)
        tr.start(tr.TRAIN_STEP, batch=idx, epoch=epoch)
        t_step = time.perf_counter()
        # fleet chaos hook: host-side sleep when HYDRAGNN_FAULT_STRAGGLE
        # is armed — the slow-host model the fleet watchdog must flag
        # (utils/faultinject.py; exact no-op unarmed, one dict lookup).
        # INSIDE the measured interval: the injected slowness must land
        # in the step time the telemetry window pushes as the fleet
        # heartbeat, or the drill would not model what the watchdog
        # measures
        faultinject.maybe_straggle(i)
        # host-loss drills (elastic_smoke): SIGKILL (dead host) or SIGTERM
        # (preemption with grace) this process before dispatching a step —
        # armed on the cumulative cross-epoch step count, not i
        faultinject.maybe_host_fault()
        tr.start(tr.DISPATCH, batch=idx, epoch=epoch)
        out = step_fn(state, batch, sub)
        tr.stop(tr.DISPATCH)
        # a numerics-enabled step rides its stat bundle as a 4th output
        # (obs/numerics.py); the historical 3-tuple is unchanged otherwise
        state, tot, tasks = out[0], out[1], out[2]
        numer = out[3] if len(out) > 3 else None
        # graph_mask is loader data (host numpy, or an already-transferred
        # leaf under device_prefetch) — reading it never waits on compute
        n = int(np.asarray(batch.graph_mask).sum())
        tr.stop(tr.TRAIN_STEP)
        entries.append((tot, tasks, n))
        if step_meta is not None:
            level = (
                f"{int(batch.node_mask.shape[-1])}n/"
                f"{int(batch.edge_mask.shape[-1])}e"
            )
            srcs = src_fn(idx) if src_fn is not None else None
            step_meta.append((idx, level, srcs))
            if nan_watch is not None:
                nan_watch.on_step(
                    state, batch, sub, step0 + len(entries) - 1, idx,
                    numer, level=level, sources=srcs,
                )
        if sp is not None:
            dispatch_dt = time.perf_counter() - t_step
            tracer.emit_completed(
                "train/device_dispatch",
                time.time() - dispatch_dt,
                dispatch_dt,
                parent=sp,
                attributes={"real_graphs": n},
            )
            sp.set_attribute("real_graphs", n)
            tracer.finish(sp)
        if telemetry is not None:
            telemetry.on_step(
                batch, time.perf_counter() - t_step, real_graphs=n,
                numerics=numer,
            )
        if check_preempt and preemption.preempted():
            # SIGTERM between steps: stop HERE and let the loop checkpoint
            # state + loader cursor, so resume replays exactly the batches
            # this epoch never stepped (docs/ROBUSTNESS.md "Data plane")
            cursor = offset + consumed
            break
        max_batches = envflags.env_int("HYDRAGNN_MAX_NUM_BATCH", 0)
        if max_batches > 0 and i + 1 >= max_batches:
            break
    tr.stop(tr.EPOCH_RESTART)  # an epoch without a batch; closed already otherwise
    # epoch_drain: the epoch's one host sync, a full drain of the device
    tr.start(tr.EPOCH_DRAIN, epoch=epoch)
    if nan_watch is not None:
        # drain the watch ring at the boundary the loop syncs on anyway
        nan_watch.end_epoch(state)
    # single host sync for the whole epoch
    entries = jax.device_get(entries)
    tr.stop(tr.EPOCH_DRAIN)
    entries = [
        (float(t), {k: float(v) for k, v in d.items()}, n)
        for t, d, n in entries
    ]
    # the steps' counters (tr.COUNTER_PREFIX entries) go to the tracer's
    # table, summed; they stay in the tasks too
    for _, d, _ in entries:
        for k, v in d.items():
            if k.startswith(tr.COUNTER_PREFIX):
                tr.count(k, v)
    if guard_log is not None and step_meta is not None:
        # non-finite loss census -> batch provenance for the guard-skip
        # event (grad-only NaNs keep a finite loss; the NaN watch covers
        # those precisely when Telemetry.numerics is on)
        guard_log["nonfinite"] = [
            {"batch": m[0], "level": m[1], "sources": m[2]}
            for e, m in zip(entries, step_meta)
            if not np.isfinite(e[0])
        ]
    # a guarded-and-skipped step reports its (non-finite) loss but applied
    # no update — excluding it keeps the epoch mean meaningful for the
    # plateau scheduler / early stopping. If EVERY step was non-finite
    # (unguarded collapse), keep them: a NaN epoch must not be masked.
    finite = [e for e in entries if np.isfinite(e[0])]
    if finite and len(finite) < len(entries):
        entries = finite
    tot, tasks = _weighted_avg(entries)
    return state, tot, tasks, rng, cursor


def evaluate(loader, eval_fn, state, prefetch_depth=None):
    entries = []
    for batch in _maybe_device_prefetch(iter(loader), depth=prefetch_depth):
        tot, tasks, _ = eval_fn(state, batch)
        n = int(np.asarray(batch.graph_mask).sum())
        entries.append((tot, tasks, n))
    entries = jax.device_get(entries)
    entries = [
        (float(t), {k: float(v) for k, v in d.items()}, n)
        for t, d, n in entries
    ]
    return _weighted_avg(entries)


class EarlyStopping:
    """(reference: hydragnn/utils/model/model.py:305-320)"""

    def __init__(self, patience: int = 10, min_delta: float = 0.0):
        self.patience = patience
        self.min_delta = min_delta
        self.best = float("inf")
        self.count = 0

    def __call__(self, val_loss: float) -> bool:
        if val_loss < self.best - self.min_delta:
            self.best = val_loss
            self.count = 0
            return False
        self.count += 1
        return self.count > self.patience


class BestCheckpoint:
    """Best-validation checkpointing with warmup
    (reference: Checkpoint, hydragnn/utils/model/model.py:323-363)."""

    def __init__(self, save_fn: Callable[..., None], warmup: int = 0):
        self.save_fn = save_fn
        self.warmup = warmup
        self.best = float("inf")

    def __call__(self, state: TrainState, val_loss: float, epoch: int) -> bool:
        if epoch < self.warmup or val_loss >= self.best:
            return False
        self.best = val_loss
        self.save_fn(state, epoch)
        return True


def train_validate_test(
    model: HydraModel,
    state: TrainState,
    tx: optax.GradientTransformation,
    train_loader,
    val_loader,
    test_loader,
    config: Dict[str, Any],
    log_name: str = "run",
    verbosity: int = 0,
    seed: int = 0,
    save_fn: Optional[Callable[..., None]] = None,
    log_fn: Optional[Callable[[int, Dict[str, float]], None]] = None,
    step_fn: Optional[Callable] = None,
    eval_fn: Optional[Callable] = None,
    restore_fn: Optional[Callable[[TrainState], TrainState]] = None,
    loader_state_fn: Optional[Callable[[Dict[str, int]], None]] = None,
    writer=None,
) -> Tuple[TrainState, Dict[str, List[float]]]:
    """Outer epoch loop (reference: train_validate_test.py:52-264).

    Returns the final state and the loss history. ``HYDRAGNN_VALTEST=0``
    skips val/test epochs (reference :179); ``HYDRAGNN_MAX_NUM_BATCH`` caps
    timed batches (reference :46-47). ``step_fn``/``eval_fn`` override the
    default single-host jitted steps (used by the multi-host mesh path,
    api.py). ``restore_fn`` (template_state -> restored state) is the
    rollback path of ``Training.non_finite_policy: rollback`` — api.py
    wires it to the verified-checkpoint restore with mesh re-placement.
    ``loader_state_fn`` persists the loader cursor dict of a MID-epoch
    preemption stop (api.py wires it to ``save_loader_state``); without it
    a mid-epoch SIGTERM still checkpoints, at epoch-replay granularity.
    ``writer`` (utils.MetricsWriter) additionally receives the run's
    already-counted health signals — guard skip totals, data-plane skip
    tallies, retrace violations, compile-cache hits/misses — so they land
    in ``scalars.jsonl``/TensorBoard instead of stdout-only report lines;
    it is also the TB mirror of the per-step telemetry layer when the
    ``Telemetry`` config section enables one (obs/telemetry.py).
    """
    training = config["NeuralNetwork"]["Training"]
    num_epoch = training["num_epoch"]
    do_valtest = envflags.env_flag("HYDRAGNN_VALTEST") is not False

    compute_grad_energy = training.get("compute_grad_energy", False)
    # bf16 compute against f32 master weights (MXU-native; make_train_step)
    mixed_precision = training.get("mixed_precision", False)
    # resolved BEFORE the step builders: Telemetry.numerics changes the
    # step program (in-graph probes ride the outputs — obs/numerics.py)
    from ..obs.telemetry import StepTelemetry, resolve_telemetry

    obs_settings = resolve_telemetry(config)
    if step_fn is None:
        step_fn = make_train_step(
            model, tx, compute_grad_energy, mixed_precision,
            numerics=obs_settings["numerics"],
        )
    if eval_fn is None:
        eval_fn = make_eval_step(model, compute_grad_energy, mixed_precision)
    # a numerics-enabled builder (here or api.py's mesh builders) carries
    # its name tables + NaN drill-down as attributes; capture them before
    # the compile plane wraps the callable below
    numerics_meta = getattr(step_fn, "_numerics_meta", None)
    nan_diagnose = getattr(step_fn, "_nan_diagnose", None)
    scheduler = ReduceLROnPlateau()
    stopper = (
        EarlyStopping(patience=training.get("patience", 10))
        if training.get("EarlyStopping", False)
        else None
    )
    checkpointer = (
        BestCheckpoint(save_fn, warmup=training.get("checkpoint_warmup", 0))
        if training.get("Checkpoint", False) and save_fn is not None
        else None
    )

    from ..utils import preemption
    from ..utils.profile import Profiler
    from ..utils.walltime import should_stop
    from .guard import NonFinitePolicy

    # Training.non_finite_policy: what a guard-skipped step means at the
    # epoch boundary (the only place the loop syncs the host anyway)
    nf_policy = NonFinitePolicy(
        policy=str(training.get("non_finite_policy", "warn_skip")),
        rollback_after=int(training.get("non_finite_rollback_after", 3)),
        lr_backoff=float(training.get("non_finite_lr_backoff", 0.5)),
        max_rollbacks=int(training.get("non_finite_max_rollbacks", 3)),
        restore_fn=restore_fn,
        log_name=log_name,
    )

    profiler = Profiler(
        # documented location first (docs/CONFIG.md "NeuralNetwork.Profile");
        # the historical top-level section keeps working
        config["NeuralNetwork"].get("Profile")
        or config.get("Profile"),  # graftlint: disable=config_keys -- legacy top-level Profile accepted for pre-r15 configs; NeuralNetwork.Profile is the documented home
        log_dir=f"./logs/{log_name}/profile",
    )
    check_remaining = training.get("CheckRemainingTime", False)
    preemption.install()
    tr.enable()

    # per-step telemetry layer (obs/telemetry.py): opt-in via the top-level
    # ``Telemetry`` config section (HYDRAGNN_TELEMETRY overrides) — step
    # time, goodput, padding waste, MFU estimate, memory gauges, the
    # versioned metrics.jsonl stream, an optional /metrics endpoint, and
    # the on-demand profiling trigger. None when disabled: the loop then
    # pays one `is not None` check per step and nothing else.
    # (obs_settings was resolved above, before the step builders.)
    telemetry = (
        StepTelemetry(obs_settings, log_name, writer=writer)
        if obs_settings["enabled"]
        else None
    )
    if telemetry is not None and numerics_meta is not None:
        telemetry.attach_numerics(numerics_meta)
    elif numerics_meta is not None:
        import warnings as _warnings

        # the probes are computed in-graph either way, but their window
        # gauges/records ride the enabled sinks — say so instead of
        # silently publishing nothing (the runbook's per-window history
        # would be missing; provenance events + flight dumps still work)
        _warnings.warn(
            "Telemetry.numerics is on but Telemetry.enabled is off: the "
            "hydragnn_numerics_* gauges and metrics.jsonl 'numerics' "
            "records are published by the enabled per-step layer and will "
            "not appear — NaN provenance events and flight-recorder dumps "
            "still fire. Set Telemetry.enabled: true for the full "
            "observatory.",
            RuntimeWarning,
            stacklevel=2,
        )
    # NaN provenance watch (obs/numerics.py): deferred per-step ok checks +
    # the drill-down on a guarded skip; exists exactly when the step rides
    # a numerics bundle
    nan_watch = None
    if numerics_meta is not None:
        from ..obs.numerics import NanWatch

        nan_watch = NanWatch(diagnose=nan_diagnose, log_name=log_name)
    run_dir = os.path.join("./logs", log_name)
    # tracing plane (obs/trace.py; docs/OBSERVABILITY.md "Tracing"): spans
    # for every trace_interval_steps-th step to logs/<run>/trace.jsonl,
    # with the region timers (dataload/train_step/...) folded in as child
    # spans of whatever sampled span is open
    tracer = None
    if obs_settings["trace"]:
        from ..obs import trace as obs_trace

        # fleet mode: every host writes its own span stream (host 0 keeps
        # the plain trace.jsonl name) — two processes appending one JSONL
        # on a shared filesystem interleave mid-line; obs/fleet.py
        # merge_traces stitches the streams into the run-level view
        trace_kw = {}
        if obs_settings.get("fleet"):
            from ..obs.fleet import host_identity

            host_i, _ = host_identity()
            if host_i > 0:
                trace_kw = {
                    "filename": f"trace-h{host_i}.jsonl", "rank0": True,
                }
        tracer = obs_trace.Tracer(
            run_dir,
            sample=float(obs_settings["trace_sample"]),
            every_n_steps=int(obs_settings["trace_interval_steps"]),
            **trace_kw,
        )
        obs_trace.install(tracer)
    # crash flight recorder (obs/flightrec.py): armed whenever the plane is
    # on — unhandled exception / SIGUSR2 / fatal guard policy dump the last
    # events + spans + a registry snapshot to logs/<run>/flightrec/
    flight = None
    if obs_settings["flight_recorder"] and (
        obs_settings["enabled"] or obs_settings["trace"]
        or obs_settings["numerics"]
    ):
        from ..obs.flightrec import FlightRecorder

        flight = FlightRecorder(run_dir, tracer=tracer).install()
    # persistent incident stream (obs/events.py): whenever the plane is
    # on, every typed event also lands in logs/<run>/events.jsonl so a
    # COMPLETED run's incidents are readable post-hoc — the run doctor's
    # (obs/doctor.py) primary event source; the in-memory ring alone only
    # survives inside flight dumps
    events_armed = False
    if (
        obs_settings["enabled"] or obs_settings["trace"]
        or obs_settings["numerics"]
    ):
        # submodule import: the package __init__ re-exports the events()
        # accessor under the submodule's name (the flightrec.py lesson)
        from ..obs.events import attach_stream as _attach_events

        events_armed = _attach_events(run_dir) is not None

    # compile plane (train/compile_plane.py): AOT warm-up of every
    # (train, eval) x pad-bucket specialization against the persistent
    # compilation cache, plus the retrace sentinel. Degrades to off when no
    # cache directory is active (api.run_training wires one by default;
    # direct callers opt in via setup_compile_cache).
    from .compile_plane import CompilePlane

    plane = CompilePlane(
        mode=str(training.get("precompile", "background")),
        retrace_policy=str(training.get("retrace_policy", "warn")),
        log_name=log_name,
        remat_policy=str(training.get("remat_policy", "full")),
    )
    step_fn = plane.launch(
        step_fn,
        eval_fn,
        state,
        train_loader,
        val_loader,
        test_loader,
        rng=jax.random.PRNGKey(seed),
        skip_eval=not do_valtest,
    )
    if telemetry is not None:
        # MFU source: the AOT warm-up's cost_analysis table — background
        # mode fills it while epoch 0 runs, so early windows may publish
        # no MFU and later ones do (the flush handles None)
        telemetry.attach_flops(plane.train_flops_for)
        # comm-accounting source (same fill discipline): per-spec
        # collective bytes + the compute-vs-comm decomposition ride the
        # step_window records and the fleet heartbeat
        telemetry.attach_comm(plane.train_comm_for)
        if telemetry.want_mfu:
            # precompile: off never populates flops_by_spec — harvest the
            # first organic executable instead (or warn once naming the
            # cause) so the MFU gauge is not silently zeroed
            plane.enable_flops_fallback()

    rng = jax.random.PRNGKey(seed)
    hist: Dict[str, List[float]] = {"train": [], "val": [], "test": [], "lr": []}
    # Early stopping / best-val checkpointing RETURN THE BEST STATE, not
    # whatever the run degraded to during the patience window (the whole
    # point of patience; e.g. a tiny decoder can ReLU-die epochs after its
    # best epoch and the final state would evaluate at the constant-
    # prediction floor). The copy is host-materialized so step donation
    # can't invalidate it. SINGLE-PROCESS ONLY: the copy materializes
    # sharded leaves (a collective on multi-host) but the improvement
    # decision uses va_loss, which is weighted by each host's LOCAL
    # real-graph count on ragged tails — hosts could disagree at a
    # near-tie and deadlock in the gather. Multi-host runs keep the
    # final state; their best-val weights live in the BestCheckpoint
    # file (Training.Checkpoint).
    return_best = (
        training.get(
            "return_best", bool(stopper is not None or checkpointer is not None)
        )
        and do_valtest
        and jax.process_count() == 1
    )
    best_val = float("inf")
    best_state = None
    # Training.warmup_epochs: linear LR ramp over the first W epochs. Tiny
    # ReLU decoders can be killed outright by the first full-LR updates
    # (alive at init, dead by epoch 2 — the constant-prediction floor);
    # ramping bounds the early step sizes without changing the recipe's
    # steady state. The plateau scheduler only engages after the ramp.
    warmup_epochs = int(training.get("warmup_epochs", 0))
    base_lr = float(state.learning_rate)
    # Training.double_buffer -> device-staging queue depth (ROADMAP #3 H2D
    # overlap): true = depth 2, false = inline device_put, int = depth.
    # HYDRAGNN_DEVICE_PREFETCH still wins inside _maybe_device_prefetch.
    db = training.get("double_buffer", True)
    prefetch_depth = 0 if not db else (2 if db is True else int(db))
    # data-plane skip tally dedup: log at the epoch boundary only when the
    # run-level count changed (ingest skips report once, at epoch 0)
    reported_skips = 0
    # guard-skip EVENT accounting for the telemetry counter: a rollback
    # restores an older state whose skipped_steps total is LOWER, so
    # absorbing the raw total (max-merge) would swallow every post-rollback
    # skip until the old high-water mark is passed — accumulate positive
    # deltas instead, resyncing the reference on any decrease. Seeded from
    # the INCOMING state's counter: a Training.continue resume carries the
    # previous run's total, which are not THIS process's events
    guard_seen = (
        int(jax.device_get(state.skipped_steps))
        if writer is not None or telemetry is not None
        else 0
    )
    guard_events = 0
    try:
        for epoch in range(num_epoch):
            t0 = time.time()
            if warmup_epochs and epoch < warmup_epochs:
                # ramp ends AT base_lr on the last warmup epoch; the
                # plateau scheduler only engages afterwards
                state = state.with_learning_rate(
                    base_lr * (epoch + 1) / warmup_epochs
                )
            profiler.epoch_begin(epoch)
            train_loader.set_epoch(epoch)
            guard_log: Dict[str, Any] = {}
            with tr.timer("train"):
                state, tr_loss, tr_tasks, rng, cursor = train_epoch(
                    train_loader, step_fn, state, rng, telemetry=telemetry,
                    tracer=tracer, prefetch_depth=prefetch_depth,
                    nan_watch=nan_watch, guard_log=guard_log,
                )
            hist["train"].append(tr_loss)
            # mixture plane (mix/plane.py): per-source draw/skip tallies +
            # the per-branch loss drift monitor, at the epoch boundary the
            # loop already syncs on
            mix_hook = getattr(train_loader, "mixture_epoch_hook", None)
            if mix_hook is not None:
                mix_hook(
                    epoch, tr_tasks, writer=writer, verbosity=verbosity,
                    log_name=log_name,
                )
            # data-plane skip tally (data/validate.py): whenever the run's
            # validator has dropped samples, say so at the epoch boundary —
            # silent data loss is not an option (docs/ROBUSTNESS.md)
            sval = getattr(train_loader, "validator", None)
            if sval is not None and sval.skipped_total != reported_skips:
                reported_skips = sval.skipped_total
                print(
                    f"[{log_name}] epoch {epoch}: data-plane skips: "
                    f"{sval.tally()}",
                    file=sys.stderr,
                )
            # route the run's already-counted health signals into the
            # metric stream (scalars.jsonl + TensorBoard + the registry) —
            # machine-readable, not stdout-only: guard skips, data-plane
            # skip tally, retrace violations, this run's cache hits/misses
            if writer is not None or telemetry is not None:
                skipped_total = int(jax.device_get(state.skipped_steps))
                guard_events += max(skipped_total - guard_seen, 0)
                guard_seen = skipped_total
                plane_rep = plane.report()
                health = {
                    "guard/skipped_steps": skipped_total,
                    "data/skipped_samples": (
                        sval.skipped_total if sval is not None else 0
                    ),
                    "compile/retrace_violations": plane_rep["violations"],
                    "compile/cache_hits": plane_rep["cache_hits"],
                    "compile/cache_misses": plane_rep["cache_misses"],
                }
                if writer is not None:
                    writer.add_scalars(health, epoch)
                if telemetry is not None:
                    from .compile_plane import compile_metrics

                    telemetry.absorb_counters(
                        guard_skipped=guard_events,
                        data_skipped=(
                            dict(sval.counts) if sval is not None else None
                        ),
                        retrace_violations=plane_rep["violations"],
                        compile_metrics=compile_metrics(),
                    )
            if cursor is not None:
                # SIGTERM between steps: checkpoint state + loader cursor
                # NOW (the grace window is ticking — no val/test, no policy
                # pass) and stop; Training.continue replays the remaining
                # batches of THIS epoch in the same order (api.py wires
                # loader_state_fn -> save_loader_state). hist stays
                # rectangular by CARRYING the last real val/test values —
                # copying the partial epoch's train loss in (the pre-r7
                # behavior) corrupted HPO early-stopping comparisons, which
                # minimize over hist["val"] (hpo.py): a lucky partial-epoch
                # train loss would masquerade as a validation improvement.
                # A first-epoch preemption has no real value to carry, so
                # the train loss stands in there (the HYDRAGNN_VALTEST=0
                # degenerate case); either way the emitted stream marks the
                # row as filler so consumers can skip it.
                last_val = hist["val"][-1] if hist["val"] else tr_loss
                last_test = hist["test"][-1] if hist["test"] else tr_loss
                hist["val"].append(last_val)
                hist["test"].append(last_test)
                hist["lr"].append(state.learning_rate)
                filler_row = {
                    "train": tr_loss,
                    "val": last_val,
                    "test": last_test,
                    "lr": state.learning_rate,
                }
                if log_fn is not None:
                    # the filler row flows through the SAME epoch-logging
                    # hook as every measured epoch (api.py owns the tag
                    # schema there), keeping every sink rectangular like
                    # hist itself
                    log_fn(epoch, filler_row)
                if writer is not None:
                    # marks this epoch's val/test as carried, not measured
                    # (the scalars.jsonl/TB analog of the filler flag in
                    # metrics.jsonl)
                    writer.add_scalar("loss/filler", 1.0, epoch)
                if telemetry is not None:
                    telemetry.on_epoch(epoch, filler_row, filler=True)
                preemption.note_global_stop()
                if save_fn is not None:
                    save_fn(state, epoch)
                    if loader_state_fn is not None:
                        # GraphLoader owns the record shape (state_dict);
                        # generic loaders fall back to the same four fields
                        if hasattr(train_loader, "state_dict"):
                            sd = train_loader.state_dict(int(cursor))
                        else:
                            sd = {
                                "epoch": int(
                                    getattr(train_loader, "epoch", epoch)
                                ),
                                "next_batch": int(cursor),
                                "seed": int(
                                    getattr(train_loader, "seed", 0) or 0
                                ),
                                "num_batches": int(len(train_loader)),
                            }
                        loader_state_fn(sd)
                if verbosity > 0:
                    print(
                        f"[{log_name}] SIGTERM: checkpointed mid-epoch "
                        f"{epoch} at batch {cursor}, stopping"
                    )
                break
            # non-finite-step policy: warn/raise/rollback BEFORE val/test so
            # a rollback epoch evaluates the restored state, not a stale one.
            # Skip provenance for the guard_skip event: the NaN watch's
            # located records when numerics is on (covers grad-only NaNs +
            # layer attribution), else the epoch's non-finite loss census
            provenance = (
                nan_watch.take() if nan_watch is not None
                else guard_log.get("nonfinite")
            )
            rollbacks_before = nf_policy.rollbacks_done
            if tracer is not None:
                # every epoch's guard verdict is traced (epochs are rare;
                # the guard's skip/rollback/fatal events attach to this
                # span's trace_id, so a rollback post-mortem has its anchor)
                with tracer.span("train/guard_verdict", epoch=epoch):
                    state = nf_policy.after_epoch(
                        state, epoch, provenance=provenance
                    )
            else:
                state = nf_policy.after_epoch(
                    state, epoch, provenance=provenance
                )
            if nf_policy.rollbacks_done > rollbacks_before:
                # the warmup ramp below recomputes the LR from base_lr every
                # warmup epoch — scale the base too, or the next ramp line
                # would silently erase the backoff the rollback just applied
                base_lr *= nf_policy.lr_backoff ** (
                    nf_policy.rollbacks_done - rollbacks_before
                )

            if do_valtest:
                with tr.timer("validate"):
                    va_loss, _ = evaluate(
                        val_loader, eval_fn, state,
                        prefetch_depth=prefetch_depth,
                    )
                with tr.timer("test"):
                    te_loss, _ = evaluate(
                        test_loader, eval_fn, state,
                        prefetch_depth=prefetch_depth,
                    )
            else:
                va_loss = te_loss = tr_loss
            hist["val"].append(va_loss)
            hist["test"].append(te_loss)
            profiler.epoch_end(epoch)

            if epoch >= warmup_epochs:
                new_lr = scheduler.step(va_loss, state.learning_rate)
                if new_lr != state.learning_rate:
                    state = state.with_learning_rate(new_lr)
            hist["lr"].append(state.learning_rate)

            if log_fn is not None:
                log_fn(
                    epoch,
                    {"train": tr_loss, "val": va_loss, "test": te_loss, "lr": state.learning_rate},
                )
            if telemetry is not None:
                telemetry.on_epoch(
                    epoch,
                    {
                        "train": tr_loss,
                        "val": va_loss,
                        "test": te_loss,
                        "lr": state.learning_rate,
                    },
                )
            if verbosity > 0:
                print(
                    f"[{log_name}] epoch {epoch}: train {tr_loss:.5f} val {va_loss:.5f} "
                    f"test {te_loss:.5f} lr {state.learning_rate:.2e} ({time.time()-t0:.1f}s)"
                )

            if return_best and va_loss < best_val:
                best_val = va_loss
                from ..parallel.mesh import materialize_replicated

                best_state = materialize_replicated(state)
            if checkpointer is not None:
                checkpointer(state, va_loss, epoch)
            if stopper is not None and stopper(va_loss):
                break
            # SLURM walltime-aware stop (reference: train_validate_test.py:257-264)
            if check_remaining and should_stop(time.time() - t0):
                break
            # TPU-pod preemption (SIGTERM): checkpoint and stop cleanly so
            # Training.continue resumes with <= 1 epoch lost; the decision
            # is agreed across hosts so nobody blocks in a collective
            if preemption.preempted_global():
                preemption.note_global_stop()
                if save_fn is not None:
                    save_fn(state, epoch)
                if verbosity > 0:
                    print(f"[{log_name}] SIGTERM: checkpointed at epoch {epoch}, stopping")
                break
    except BaseException as e:
        # capture the crash while the black box is still armed: the
        # teardown below uninstalls the excepthook before the exception
        # could reach it (KeyboardInterrupt is a shutdown, not a crash)
        if flight is not None and not isinstance(e, KeyboardInterrupt):
            try:
                flight.dump("train_exception", exc=e)
            except Exception:  # noqa: BLE001 — never mask the real error
                pass
        raise
    finally:
        profiler.close()
        preemption.uninstall()
        # join the warm-up worker, disarm the sentinel, and (verbosity > 0)
        # print the one-line compile report the smokes parse
        rep = plane.finish(verbosity)
        if telemetry is not None:
            # final absorption AFTER plane.finish: the warm-up worker has
            # joined, so the flops table is complete and the run-level
            # compile tallies are final. The whole teardown is exception-
            # guarded: a telemetry failure here must neither mask the real
            # training exception nor discard a completed run's result.
            try:
                from .compile_plane import compile_metrics

                try:
                    guard_total = int(jax.device_get(state.skipped_steps))
                    guard_events += max(guard_total - guard_seen, 0)
                except Exception:  # state donated-dead on an error path
                    pass
                telemetry.absorb_counters(
                    guard_skipped=guard_events,
                    data_skipped=(
                        dict(train_loader.validator.counts)
                        if getattr(train_loader, "validator", None)
                        is not None
                        else None
                    ),
                    retrace_violations=rep["violations"],
                    compile_metrics=compile_metrics(),
                )
                # verdict hook (obs/doctor.py): the FULL compile-plane
                # report — HBM/comm tables, cache tallies, retrace
                # violations, device capacity — lands in metrics.jsonl as
                # a typed compile_report record, so the doctor's rules
                # read it instead of scraping the stderr line
                telemetry.compile_record(rep)
                telemetry.run_record(
                    {
                        "log_name": log_name,
                        "epochs": len(hist["train"]),
                        "global_step": telemetry.global_step,
                        "endpoint_port": telemetry.endpoint_port,
                        "compile": {
                            k: rep[k]
                            for k in (
                                "precompiled",
                                "specializations",
                                "cache_hits",
                                "cache_misses",
                                "violations",
                                "time_to_first_step",
                            )
                        },
                    }
                )
            except Exception as e:  # noqa: BLE001
                import warnings as _warnings

                _warnings.warn(
                    f"telemetry teardown failed ({type(e).__name__}: {e}); "
                    "the run result is unaffected",
                    RuntimeWarning,
                    stacklevel=2,
                )
            finally:
                try:
                    telemetry.close()
                except Exception:  # noqa: BLE001 — same contract
                    pass
        # tracing-plane teardown LAST: the flight recorder must still be
        # armed while the telemetry teardown above could raise, and the
        # tracer's close flushes the span tail (abnormal exits are covered
        # by its atexit hook + the recorder's excepthook)
        if flight is not None:
            try:
                flight.uninstall()
            except Exception:  # noqa: BLE001 — observability teardown
                pass
        if tracer is not None:
            from ..obs import trace as obs_trace

            try:
                obs_trace.uninstall(tracer)
                tracer.close()
            except Exception:  # noqa: BLE001 — same contract
                pass
        if events_armed:
            from ..obs.events import detach_stream as _detach_events

            try:
                _detach_events()
            except Exception:  # noqa: BLE001 — same contract
                pass
        # run-verdict hook: HYDRAGNN_DOCTOR=1 runs the diagnosis engine
        # over the run dir the moment the streams are closed, writing
        # logs/<run>/doctor.json and one grep-able verdict line — the
        # post-run analog of `python -m hydragnn_tpu.obs.doctor <run>`
        from ..obs.telemetry import env_flag as _env_flag

        if _env_flag("HYDRAGNN_DOCTOR"):
            try:
                import json as _json

                from ..obs import doctor as _doctor

                streams = _doctor.RunStreams.from_run_dir(run_dir)
                findings, d_report = _doctor.diagnose(streams)
                with open(os.path.join(run_dir, "doctor.json"), "w") as fh:
                    _json.dump(
                        {
                            "v": _doctor.DOCTOR_SCHEMA_VERSION,
                            "mode": "diagnose",
                            "target": run_dir,
                            "findings": [f.to_dict() for f in findings],
                            "report": d_report,
                            # was the binary under diagnosis built from a
                            # clean tree? (graftlint verdict — the static
                            # analog of the runtime evidence above)
                            "static_findings":
                                _doctor.static_findings_record(),
                        },
                        fh, indent=2, default=str,
                    )
                print(
                    f"[{log_name}] run doctor: {len(findings)} finding(s)"
                    + (
                        ": " + ",".join(f.kind for f in findings)
                        if findings else ""
                    ),
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — diagnosis must never
                print(                # take the diagnosed run down
                    f"[{log_name}] run doctor failed: "
                    f"{type(e).__name__}: {e}",
                    file=sys.stderr,
                )
    if best_state is not None:
        state = best_state
    return state, hist


def test_model(
    model: HydraModel,
    state: TrainState,
    loader,
    compute_grad_energy: bool = False,
    mixed_precision: bool = False,
) -> Tuple[float, Dict[str, float], Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Full-dataset evaluation returning flattened real predictions/targets
    per head (reference: test(), train_validate_test.py:620-748).
    ``mixed_precision`` must match training so the reported test loss uses
    the same numerics that drove checkpoint selection."""
    eval_fn = make_eval_step(model, compute_grad_energy, mixed_precision)
    cfg = model.cfg
    if compute_grad_energy:
        # energy is reported graph-level, forces node-level, regardless of the
        # (node) head type (reference: test(), train_validate_test.py:655-698)
        names_types = [(cfg.output_names[0], "graph"), ("forces", "node")]
    else:
        names_types = list(zip(cfg.output_names, cfg.output_type))
    entries = []
    preds: Dict[str, List[np.ndarray]] = {n: [] for n, _ in names_types}
    trues: Dict[str, List[np.ndarray]] = {n: [] for n, _ in names_types}
    for batch in loader:
        tot, tasks, outputs = eval_fn(state, batch)
        n = int(np.asarray(batch.graph_mask).sum())
        entries.append((float(tot), {k: float(v) for k, v in tasks.items()}, n))
        for name, t in names_types:
            if t == "graph":
                mask = np.asarray(batch.graph_mask)
                if compute_grad_energy:
                    target = np.asarray(batch.graph_targets["energy"]).reshape(
                        -1, 1
                    )
                else:
                    target = np.asarray(batch.graph_targets[name])
            else:
                mask = np.asarray(batch.node_mask)
                target = np.asarray(batch.node_targets[name])
            preds[name].append(np.asarray(outputs[name]).reshape(target.shape)[mask])
            trues[name].append(target[mask])
    tot, tasks = _weighted_avg(entries)
    preds_flat = {k: np.concatenate(v) for k, v in preds.items()}
    trues_flat = {k: np.concatenate(v) for k, v in trues.items()}
    # per-rank pickle dump of the collected test samples (reference:
    # HYDRAGNN_DUMP_TESTDATA, train_validate_test.py:642-652). "0"/"false"
    # disable (matching HYDRAGNN_VALTEST semantics); "1"/"true" use the
    # default directory; anything else is the output directory.
    dump = envflags.env_str("HYDRAGNN_DUMP_TESTDATA", "")
    if dump and dump.lower() not in ("0", "false"):
        import pickle

        path = (
            dump
            if dump.lower() not in ("1", "true")
            else os.path.join("logs", "testdata")
        )
        os.makedirs(path, exist_ok=True)
        fname = os.path.join(path, f"testdata_rank{jax.process_index()}.pkl")
        with open(fname, "wb") as f:
            pickle.dump({"preds": preds_flat, "trues": trues_flat}, f)
    return (tot, tasks, preds_flat, trues_flat)
