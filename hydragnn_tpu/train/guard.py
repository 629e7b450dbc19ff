"""Non-finite step guard: in-graph skip of bad optimizer steps + the
host-side policy that decides what a skipped step means.

The failure mode this closes: long bf16 runs (mixed_precision puts bf16 on
every hot path) occasionally produce a non-finite loss or gradient — one such
step without a guard writes NaN into the parameters and the run is dead from
that point on, usually discovered hours later from a flatlined loss curve.
``Optimizer.clip_grad_norm`` bounds finite outliers but passes NaN/inf
through (0 * inf = NaN inside the clip scale).

In-graph side (used by every train-step builder — single-device, mesh DP,
branch-parallel): compute loss/global-grad-norm finiteness and gate the
optimizer update to identity on a bad step (per-leaf selects — see
``guarded_update`` for why not ``lax.cond``). The state carries
``skipped_steps`` (total) and ``consecutive_skips`` (reset by any good step)
counters, advanced in-graph, so the check costs no extra host sync — the
loop reads them once per epoch where it already syncs. On the mesh steps the
decision is computed AFTER the gradient pmean, so every device/host agrees
by construction.

Host side (train/loop.py): ``Training.non_finite_policy`` —
``error`` (raise at the epoch boundary), ``warn_skip`` (log and keep going;
the default), ``rollback`` (after K consecutive skips, restore the last
verified checkpoint with an LR backoff — agreed across hosts the same way
``preemption.preempted_global()`` agrees its stop).
"""

from __future__ import annotations

import sys
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from ..utils import envflags
from ..utils import tracer as tr


def guard_enabled(guard: Optional[bool] = None) -> bool:
    """Resolve a step builder's ``guard`` argument: explicit True/False wins,
    None falls back to ``HYDRAGNN_STEP_GUARD`` (default on — the guard is
    numerically identical on finite steps, see tests/test_faults.py, and its
    cost is one global-norm pass bounded by the BENCH_GUARD A/B cell)."""
    if guard is not None:
        return bool(guard)
    return envflags.env_force("HYDRAGNN_STEP_GUARD") is not False


def step_ok(tot, grads):
    """In-graph finiteness decision: the loss and the global gradient norm
    (one reduction over all leaves — a single NaN/inf anywhere poisons the
    norm, so one scalar check covers the whole tree)."""
    with tr.scope(tr.HG_GUARD):
        return jnp.isfinite(tot) & jnp.isfinite(optax.global_norm(grads))


def guarded_update(
    state,
    ok,
    do_update: Callable[[], Tuple],
    new_stats,
):
    """Gate the optimizer update to identity on a bad step and advance the
    skip counters in-graph.

    ``do_update`` returns ``(params, opt_state)`` — the caller's full update
    arithmetic (tx.update + apply_updates + any ZeRO sharding constraints),
    so on a good step the committed values are EXACTLY the unguarded ones.
    ``new_stats`` are the batch statistics a good step would persist; a bad
    step keeps the previous ones (a NaN forward can poison running means).

    The merge is an elementwise ``select(ok, new, old)`` per leaf rather
    than a ``lax.cond``: a cond around the whole update forms an XLA
    conditional over every params/opt-state buffer, which blocks fusion
    with the surrounding program and (measured on the CPU backend) made the
    step ~30x slower end-to-end; selects fuse into the update arithmetic
    and cost one predicated copy per leaf. The update is computed
    unconditionally — its NaN outputs on a bad step are discarded by the
    select, never multiplied in. Donation-safe: old and new buffers share
    shape/dtype/sharding."""
    params_new, opt_new = do_update()

    def merge(new, old):
        new = jnp.asarray(new)
        return jnp.where(ok, new, jnp.asarray(old, new.dtype))

    with tr.scope(tr.HG_GUARD):
        params, opt_state, stats = jax.tree_util.tree_map(
            merge,
            (params_new, opt_new, new_stats),
            (state.params, state.opt_state, state.batch_stats),
        )
    # counter arithmetic must PRESERVE the leaves' (weak) dtype: the fresh
    # state carries python-int counters (weak int32 under jit, like `step`),
    # and an explicit int32 cast here would flip the output aval to strong
    # int32 — recompiling the ENTIRE step on its second call (measured: one
    # full extra XLA compile per train-step specialization suite-wide)
    return state.replace(
        params=params,
        opt_state=opt_state,
        batch_stats=stats,
        step=state.step + 1,
        skipped_steps=state.skipped_steps + jnp.where(ok, 0, 1),
        consecutive_skips=jnp.where(ok, 0, state.consecutive_skips + 1),
    )


def agreed_any(flag: bool) -> bool:
    """Cross-host agreement on a local boolean — ANY process's True wins,
    the same contract as ``preemption.preempted_global()``: the rollback
    decision must be unanimous or hosts diverge on which state they train
    (the counters are computed from pmean'd values and already agree; the
    allgather makes the host-side decision robust to any residual skew)."""
    if jax.process_count() == 1:
        return bool(flag)
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.asarray([bool(flag)], np.int32))
    return bool(np.asarray(flags).any())


class NonFinitePolicy:
    """Epoch-boundary driver of ``Training.non_finite_policy``.

    Owned by the training loop: call ``after_epoch(state, epoch)`` once per
    epoch; it reads the in-graph counters (the loop is already host-synced
    there), warns/raises per policy, and for ``rollback`` returns a restored
    + LR-backed-off state after K agreed consecutive skips."""

    POLICIES = ("error", "warn_skip", "rollback")

    def __init__(
        self,
        policy: str = "warn_skip",
        rollback_after: int = 3,
        lr_backoff: float = 0.5,
        max_rollbacks: int = 3,
        restore_fn: Optional[Callable] = None,
        log_name: str = "run",
    ):
        if policy not in self.POLICIES:
            raise ValueError(
                f"Training.non_finite_policy {policy!r} must be one of "
                f"{self.POLICIES}"
            )
        self.policy = policy
        self.rollback_after = int(rollback_after)
        self.lr_backoff = float(lr_backoff)
        self.max_rollbacks = int(max_rollbacks)
        self.restore_fn = restore_fn
        self.log_name = log_name
        self._prev_skipped = 0
        self.rollbacks_done = 0

    def after_epoch(self, state, epoch: int, provenance=None):
        """Apply the policy; returns the (possibly restored) state.

        ``provenance`` (optional) is the epoch's per-skip batch attribution
        — a list of dicts with ``batch`` / ``level`` (spec-ladder pad
        level) / ``sources`` (mixture draw ids) / ``layer`` (when the
        numerics drill-down located the tensor) — attached to the
        ``guard_skip`` event so a poisoned source or a recurring pad level
        is identifiable from the event stream alone (train/loop.py fills it
        from the NaN watch when ``Telemetry.numerics`` is on, else from the
        epoch's non-finite loss census)."""
        skipped = int(jax.device_get(state.skipped_steps))
        consec = int(jax.device_get(state.consecutive_skips))
        new_skips = skipped - self._prev_skipped
        self._prev_skipped = skipped
        if new_skips <= 0:
            return state
        msg = (
            f"[{self.log_name}] epoch {epoch}: {new_skips} non-finite "
            f"step(s) skipped by the train-step guard "
            f"(total {skipped}, {consec} consecutive at epoch end)"
        )
        # structured incident record (obs/events.py): the epoch's skip tally
        # with the active trace context attached (the loop opens a
        # train/guard_verdict span around this call when tracing is on)
        from ..obs.events import EV_GUARD_FATAL, EV_GUARD_SKIP
        from ..obs.events import emit as _emit_event

        extra = {}
        if provenance:
            levels = sorted({str(p["level"]) for p in provenance
                             if p.get("level")})
            sources = sorted({int(s) for p in provenance
                              for s in (p.get("sources") or [])})
            batches = [int(p["batch"]) for p in provenance
                       if p.get("batch") is not None]
            layers = sorted({str(p["layer"]) for p in provenance
                             if p.get("layer")})
            if levels:
                extra["levels"] = ",".join(levels)
            if sources:
                extra["sources"] = ",".join(str(s) for s in sources)
            if batches:  # bounded: a diverged epoch skips every step
                extra["batches"] = ",".join(str(b) for b in batches[:16])
            if layers:
                extra["layers"] = ",".join(layers[:8])
        _emit_event(
            EV_GUARD_SKIP,
            severity="warn",
            epoch=epoch,
            new_skips=new_skips,
            total=skipped,
            consecutive=consec,
            policy=self.policy,
            **extra,
        )
        if self.policy == "error":
            err = RuntimeError(
                msg + "; Training.non_finite_policy is 'error'. Inspect the "
                "data/LR, or set 'warn_skip'/'rollback' to ride through."
            )
            # black-box dump BEFORE raising: the fatal guard verdict is one
            # of the flight recorder's trigger points — the dump carries
            # this epoch's guard_skip/guard_fatal events + registry snapshot
            _emit_event(
                EV_GUARD_FATAL, severity="fatal", epoch=epoch, total=skipped
            )
            from ..obs import flightrec as _flightrec

            _flightrec.trigger("fatal_guard", exc=err)
            raise err
        print(msg, file=sys.stderr)
        if self.policy != "rollback":
            return state
        if not agreed_any(consec >= self.rollback_after):
            return state
        # agreed rollback: restore the last VERIFIED checkpoint and back
        # off the LR — the recovery for sustained divergence (K consecutive
        # bad steps means the current trajectory is lost, not one cosmic ray)
        self.rollbacks_done += 1
        if self.rollbacks_done > self.max_rollbacks:
            raise RuntimeError(
                f"[{self.log_name}] non_finite_policy=rollback exceeded "
                f"Training.non_finite_max_rollbacks={self.max_rollbacks}: "
                "the run keeps diverging after restore+LR-backoff. Lower "
                "the learning rate or inspect the data."
            )
        if self.restore_fn is None:
            raise RuntimeError(
                f"[{self.log_name}] non_finite_policy=rollback triggered "
                f"({consec} consecutive skips) but no checkpoint restore "
                "path is wired. Enable Training.Checkpoint so a verified "
                "checkpoint exists to roll back to."
            )
        state = self.restore_fn(state)
        from ..obs.events import EV_GUARD_ROLLBACK
        from ..obs.events import emit as _emit_rollback

        _emit_rollback(
            EV_GUARD_ROLLBACK,
            severity="error",
            epoch=epoch,
            rollback=self.rollbacks_done,
            max_rollbacks=self.max_rollbacks,
        )
        # COMPOUND the backoff across rollbacks: sustained divergence keeps
        # restoring the SAME checkpoint (BestCheckpoint only writes on val
        # improvement), so a flat factor would retry the identical LR until
        # max_rollbacks — rollback k runs at backoff^k of the restored LR
        # (matching the loop's per-rollback base_lr scaling for the ramp)
        lr = float(state.learning_rate) * self.lr_backoff**self.rollbacks_done
        state = state.with_learning_rate(lr)
        # the restored checkpoint carries its own (older) counters; re-sync
        # so the next epoch's delta is computed against the restored total
        self._prev_skipped = int(jax.device_get(state.skipped_steps))
        print(
            f"[{self.log_name}] rollback {self.rollbacks_done}/"
            f"{self.max_rollbacks}: restored last verified checkpoint, "
            f"learning rate backed off to {lr:.3e}",
            file=sys.stderr,
        )
        return state
