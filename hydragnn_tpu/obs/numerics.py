"""In-graph numerics observatory: per-layer activation and per-param-group
gradient statistics computed inside the jitted step, plus the NaN provenance
drill-down (docs/OBSERVABILITY.md "Numerics").

The blind spot this closes: the step guard (train/guard.py) reports only
*that* a loss or gradient went non-finite — never which layer or channel.
On a long bf16 run the distance between "guard skipped 40 steps last epoch"
and "the PNAPlus gate head underflows bf16 at LR 3e-3" used to be a manual
bisection. Three pieces close it:

1. **Probe taps** (``probe(name, x, mask)``): one-line call sites in
   ``models/base.py`` / ``models/layers.py`` naming intermediates. A tap is
   a no-op unless a collection context is active *at trace time* — enabled
   runs pay a handful of fused reductions per tensor, disabled runs compile
   the identical program as before (the tap never appears in the jaxpr).
   Stats are collected as RAW moments (max-abs, sum-of-squares, element
   count, non-finite count, bf16-underflow count) so they reduce correctly
   across the window (max/sum) and across mesh devices (pmax/psum); hosts
   finalize rms / fractions at flush time.

2. **Step ride-along**: the train-step builders (train/loop.py,
   parallel/dp.py, parallel/branch.py) bundle the probe stack, per-param-
   group gradient stats, and the guard's ok flag into a 4th step output
   when ``Telemetry.numerics`` is on. The outputs are fresh (non-donated)
   device arrays; nothing syncs the host — the telemetry layer reads them
   back at its flush cadence, by which point the producing steps have long
   retired (obs/telemetry.py).

3. **NaN provenance** (``NanWatch``): the loop feeds every step's ok flag
   (plus the batch, rng, and ladder/source provenance) into a small ring;
   entries are checked once they are ``lag`` steps old — old enough that
   reading the flag never stalls the async dispatch pipeline. A failed step
   re-runs its HELD batch through a probe-instrumented diagnostic program
   (``make_nan_diagnostic``) that localizes the FIRST non-finite tensor in
   forward order (activations, then gradient groups), emits a typed
   ``numerics_provenance`` event, and triggers one flight-recorder dump per
   run. NOTE the diagnostic runs against the CURRENT params (the failing
   step's params were donated ``lag`` steps ago); data-driven and LR-driven
   divergence — the cases worth drilling into — reproduce, a one-off
   cosmic-ray flip does not (the event then reports ``layer:
   <unreproduced>`` and still carries the batch provenance).
"""

from __future__ import annotations

import threading
import warnings
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# raw stat vector layout, per probed tensor / gradient group:
#   [max_abs, sum_sq, count, nonfinite, bf16_underflow]
# max-abs merges by MAX (window steps, mesh devices), the rest by SUM;
# finalize_stats turns the raw moments into {max_abs, rms, nonfinite,
# bf16_underflow} on the host.
STAT_FIELDS = ("max_abs", "sum_sq", "count", "nonfinite", "bf16_underflow")
STAT_WIDTH = len(STAT_FIELDS)

# smallest positive NORMAL bfloat16/float32 magnitude (bf16 shares f32's
# 8-bit exponent): a nonzero value below this is subnormal in bf16 — the
# gradient-underflow precursor the mixed-precision guard wants to see
# coming before it flushes to zero
BF16_TINY = 1.1754944e-38


def numerics_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve a step builder's ``numerics`` argument: explicit True/False
    wins, None means OFF. Deliberately NOT an env fallback: numerics
    changes the step's return arity (3- to 4-tuple), and the
    ``HYDRAGNN_NUMERICS`` override must not break every direct builder
    caller that unpacks three values (bench.py, examples). The env is
    honored where the 4-tuple consumer lives — ``resolve_telemetry``
    (obs/telemetry.py ``env_flag``), which the loop and api.py feed into
    the builders' explicit ``numerics=`` argument."""
    return bool(flag)


# ---------------------------------------------------------------------------
# probe taps + collection context
# ---------------------------------------------------------------------------


class ProbeRecord:
    """One trace's ordered probe collection. ``add`` appends raw (possibly
    vmap-batched) stat components; ``stack`` reduces each probe to a [5]
    vector and stacks them [P, 5] in FORWARD order — the order the NaN
    drill-down walks to find the *first* non-finite tensor."""

    def __init__(self):
        self.entries: List[Tuple[str, Tuple]] = []

    def add(self, name: str, comps: Tuple) -> None:
        # repeated module calls keep distinct rows (suffix #k) so the
        # forward-order walk stays unambiguous
        seen = sum(1 for n, _ in self.entries if n == name or n.startswith(f"{name}#"))
        if seen:
            name = f"{name}#{seen}"
        self.entries.append((name, comps))

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.entries)

    def stack(self):
        """(names, [P, 5] f32 array) — P == 0 yields an empty stack (a
        model with no taps still produces a structurally valid bundle)."""
        import jax.numpy as jnp

        if not self.entries:
            return (), jnp.zeros((0, STAT_WIDTH), jnp.float32)
        rows = []
        for _, (maxabs, sumsq, cnt, nonfin, under) in self.entries:
            # components may carry leading vmap axes (branch banks): the
            # final reduction here collapses them with the right semantics
            rows.append(
                jnp.stack(
                    [
                        jnp.max(maxabs),
                        jnp.sum(sumsq),
                        jnp.sum(cnt),
                        jnp.sum(nonfin),
                        jnp.sum(under),
                    ]
                )
            )
        return self.names, jnp.stack(rows).astype(jnp.float32)


class _TapStack(threading.local):
    def __init__(self):
        self.stack: List[ProbeRecord] = []


_TAPS = _TapStack()


@contextmanager
def collecting(record: ProbeRecord):
    """Activate probe collection on this thread for the duration of a
    traced function body. Thread-local: the compile plane's background
    warm-up worker traces concurrently with epoch 0 without cross-talk."""
    _TAPS.stack.append(record)
    try:
        yield record
    finally:
        _TAPS.stack.pop()


def collection_active() -> bool:
    """Whether a collection context is open on this thread — call sites
    with non-trivial name construction guard on it so disabled runs pay
    only this list check at trace time."""
    return bool(_TAPS.stack)


def probe(name: str, x, mask=None) -> None:
    """Tap a named intermediate. No-op (one thread-local list check, at
    trace time only) unless a ``collecting`` context is active. ``mask``
    restricts the statistics to real rows — padding rows carry garbage by
    contract (models/base.py), and counting their NaNs would fire false
    provenance."""
    if not _TAPS.stack:
        return
    _TAPS.stack[-1].add(name, _stat_components(x, mask))


def _stat_components(x, mask=None) -> Tuple:
    """Raw stat components of one tensor: (max_abs, sum_sq, count,
    nonfinite, bf16_underflow), each a fully-reduced scalar at the trace
    site (vmap lifts them to per-branch vectors; ProbeRecord.stack
    re-reduces). Stats compute in f32 so a bf16 forward's sums don't
    themselves overflow/quantize.

    Op-lean by design (the probes ride EVERY step — the telemetry smoke's
    numerics A/B holds the bill at <= 2%): masked-out rows are zeroed ONCE
    (``where`` never propagates the unselected branch's NaNs), after which
    zero is finite and zero-magnitude — so the non-finite and underflow
    censuses need no further mask arithmetic; the element count comes from
    the (much smaller) mask array times the static row width; and all four
    tensor statistics come out of ONE variadic ``lax.reduce`` — a single
    fused traversal of the probed tensor (measured ~4.5x cheaper than four
    separate jnp reductions on the CPU backend), with the elementwise
    inputs fused into the reduction loop by XLA."""
    import jax.numpy as jnp

    x = jnp.asarray(x).astype(jnp.float32)
    if mask is not None:
        m = jnp.asarray(mask)
        m = m.reshape(m.shape + (1,) * (x.ndim - m.ndim))
        x = jnp.where(m, x, 0.0)
        cnt = jnp.sum(m.astype(jnp.float32)) * float(
            x.size // max(m.size, 1)
        )
    else:
        cnt = jnp.asarray(float(x.size), jnp.float32)
    maxabs, sumsq, nonfin, under = _fused_reduce()(x)
    return maxabs, sumsq, cnt, nonfin, under


_FUSED_REDUCE = None


def _fused_reduce():
    """The one-pass variadic stat reduction, built lazily (module import
    stays jax-free) and wrapped in a ``custom_jvp`` with zero tangents:
    the stats are observability outputs that must never be differentiated,
    and ``lax.reduce`` has no AD rule for the symbolic-zero tangents that
    linearizing the surrounding loss would otherwise push through it."""
    global _FUSED_REDUCE
    if _FUSED_REDUCE is not None:
        return _FUSED_REDUCE
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.custom_jvp
    def fused(x):
        ax = jnp.abs(x)
        sq = x * x
        nonfin_e = (~jnp.isfinite(x)).astype(jnp.float32)
        under_e = ((ax > 0.0) & (ax < BF16_TINY)).astype(jnp.float32)

        def _comb(a, b):
            # jnp.maximum propagates NaN -> a NaN'd tensor reports nan
            return (jnp.maximum(a[0], b[0]), a[1] + b[1], a[2] + b[2],
                    a[3] + b[3])

        return lax.reduce(
            (ax, sq, nonfin_e, under_e),
            (jnp.float32(0), jnp.float32(0), jnp.float32(0),
             jnp.float32(0)),
            _comb,
            tuple(range(x.ndim)),
        )

    @fused.defjvp
    def _fused_jvp(primals, tangents):
        out = fused(*primals)
        return out, tuple(jnp.zeros_like(o) for o in out)

    _FUSED_REDUCE = fused
    return fused


def run_probed(enabled: bool, meta: Dict[str, Any], thunk: Callable):
    """The step builders' shared collection wrapper: run ``thunk`` (the
    loss computation) under probe collection when ``enabled``, recording
    the forward-ordered tap names into the builder's mutable ``meta`` cell
    at trace time. Returns ``(thunk result, acts stack | None)`` — one
    spelling for train/loop.py, parallel/dp.py, and parallel/branch.py, so
    the collection protocol cannot desynchronize across builders."""
    if not enabled:
        return thunk(), None
    rec = ProbeRecord()
    with collecting(rec):
        out = thunk()
    names, acts = rec.stack()
    meta["act_names"] = names
    return out, acts


def numerics_step_wrapper(jitted, meta: Dict[str, Any], model,
                          compute_grad_energy: bool = False,
                          mixed_precision: bool = False):
    """The step builders' shared numerics epilogue: wrap the jit object so
    it stays AOT-reachable for the compile plane, and attach the host-side
    contract — ``_jitted`` (the true jit, for api.py's attach_lower_fn),
    ``_numerics_meta`` (tensor name tables), ``_nan_diagnose`` (the
    provenance drill-down)."""
    from ..train.compile_plane import attach_lower_fn

    wrapper = attach_lower_fn(lambda s, b, r: jitted(s, b, r), jitted)
    wrapper._jitted = jitted
    wrapper._numerics_meta = meta
    wrapper._nan_diagnose = make_nan_diagnostic(
        model, compute_grad_energy, mixed_precision
    )
    return wrapper


# ---------------------------------------------------------------------------
# gradient groups + reductions
# ---------------------------------------------------------------------------


def grad_group_stats(grads):
    """(names, [G, 5]) over the top-level param groups of a gradient tree
    (flax params dicts: one group per module — ``graph_convs_0``,
    ``heads_NN_0``, ...; non-dict trees collapse to one ``params`` group).
    Sorted-key order: deterministic across traces and processes."""
    import jax
    import jax.numpy as jnp

    if isinstance(grads, dict) and grads:
        groups = [(k, grads[k]) for k in sorted(grads)]
    else:
        groups = [("params", grads)]
    names = []
    rows = []
    for name, sub in groups:
        leaves = [l for l in jax.tree_util.tree_leaves(sub)
                  if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)]
        if not leaves:
            continue
        # per-LEAF fused reductions, combined per group. Deliberately NOT a
        # ravel+concatenate of the group: on the branch-parallel and ZeRO
        # paths the gradient leaves are mesh-SHARDED, and a concat under
        # the outer jit would force GSPMD to all-gather the full bank per
        # step just to compute five scalars — per-leaf reductions partial-
        # reduce in place and only the scalars travel.
        comps = [_stat_components(l) for l in leaves]
        names.append(name)
        rows.append(
            jnp.stack(
                [
                    (comps[0][0] if len(comps) == 1
                     else jnp.max(jnp.stack([c[0] for c in comps]))),
                    sum(c[1] for c in comps),
                    sum(c[2] for c in comps),
                    sum(c[3] for c in comps),
                    sum(c[4] for c in comps),
                ]
            )
        )
    if not rows:
        return (), jnp.zeros((0, STAT_WIDTH), jnp.float32)
    return tuple(names), jnp.stack(rows).astype(jnp.float32)


def cross_device_reduce(stacked, axis_names):
    """Reduce a [P, 5] stat stack across mesh devices inside ``shard_map``:
    max-abs merges by ``pmax``, the summed moments by ``psum`` — the same
    merge semantics the host applies across window steps."""
    import jax
    import jax.numpy as jnp

    if stacked.shape[0] == 0:
        return stacked
    return jnp.concatenate(
        [
            jax.lax.pmax(stacked[:, :1], axis_names),
            jax.lax.psum(stacked[:, 1:], axis_names),
        ],
        axis=1,
    )


def finalize_stats(raw) -> Dict[str, float]:
    """Host-side finalization of one raw [5] vector."""
    import numpy as np

    maxabs, sumsq, cnt, nonfin, under = (float(v) for v in np.asarray(raw))
    denom = max(cnt, 1.0)
    rms = float(np.sqrt(max(sumsq, 0.0) / denom)) if np.isfinite(sumsq) else sumsq
    return {
        "max_abs": maxabs,
        "rms": rms,
        "nonfinite": nonfin,
        "bf16_underflow": under / denom,
    }


def _is_bad(row) -> bool:
    import numpy as np

    r = np.asarray(row)
    return bool(r[3] > 0 or not np.isfinite(r[0]) or not np.isfinite(r[1]))


def locate_first_nonfinite(act_names, acts, grad_names, gstats) -> Optional[Dict[str, Any]]:
    """First non-finite tensor in forward order: activations (probe order),
    then gradient groups. Returns {layer, kind, stats} or None."""
    import numpy as np

    acts = np.asarray(acts) if acts is not None else np.zeros((0, STAT_WIDTH))
    for p in range(acts.shape[0]):
        if _is_bad(acts[p]):
            name = act_names[p] if act_names and p < len(act_names) else f"probe{p}"
            return {"layer": name, "kind": "activation",
                    "stats": finalize_stats(acts[p])}
    gstats = np.asarray(gstats) if gstats is not None else np.zeros((0, STAT_WIDTH))
    for g in range(gstats.shape[0]):
        if _is_bad(gstats[g]):
            name = grad_names[g] if grad_names and g < len(grad_names) else f"group{g}"
            return {"layer": name, "kind": "gradient",
                    "stats": finalize_stats(gstats[g])}
    return None


# ---------------------------------------------------------------------------
# NaN provenance: diagnostic step + deferred watch
# ---------------------------------------------------------------------------


def make_nan_diagnostic(model, compute_grad_energy: bool = False,
                        mixed_precision: bool = False) -> Callable:
    """Build the host-callable drill-down ``diagnose(state, batch, rng,
    step) -> finding | None`` for one model/objective.

    The diagnostic is its own jit program (built lazily — compiled only on
    the first guarded skip, never on clean runs) running the replicated
    single-device objective with every probe active, full per-group
    gradient stats, and the SAME fault-injection hooks as the live step
    (``faultinject.poison_grads`` with the failing step's index, so an
    injected fault reproduces under diagnosis). Stacked mesh batches are
    diagnosed row by row; all-padding filler rows are skipped. It never
    traces a sentinel'd builder name, so an armed retrace sentinel ignores
    it."""
    holder: Dict[str, Any] = {"jit": None, "act_names": None, "grad_names": None}

    def _build():
        import jax
        import jax.numpy as jnp

        from ..train.loss import compute_loss
        from ..utils import faultinject

        cfg = model.cfg

        def loss_probe(params, batch_stats, batch, rng):
            if mixed_precision:
                from ..train.loop import mp_cast, mp_keep

                params, batch = mp_cast(params, batch, compute_grad_energy, mp_keep(model))
            rec = ProbeRecord()
            with collecting(rec):
                tot, _, _, _ = compute_loss(
                    model,
                    {"params": params, "batch_stats": batch_stats},
                    batch, cfg, True, rng, compute_grad_energy,
                )
            names, acts = rec.stack()
            holder["act_names"] = names
            return tot.astype(jnp.float32), acts

        @jax.jit
        def diag(params, batch_stats, batch, rng, step, lr):
            (tot, acts), grads = jax.value_and_grad(loss_probe, has_aux=True)(
                params, batch_stats, batch, rng
            )
            grads = faultinject.poison_grads(grads, step, lr)
            gnames, gstats = grad_group_stats(grads)
            holder["grad_names"] = gnames
            return tot, acts, gstats

        return diag

    def diagnose(state, batch, rng, step: int) -> Optional[Dict[str, Any]]:
        import jax
        import numpy as np

        from ..utils import faultinject

        if holder["jit"] is None:
            holder["jit"] = _build()
        diag = holder["jit"]
        lr = faultinject.lr_of(state.opt_state)
        if batch.graph_mask.ndim == 2:  # stacked [D, ...] mesh batch
            rows = [
                jax.tree_util.tree_map(lambda x, _r=r: x[_r], batch)
                for r in range(int(batch.graph_mask.shape[0]))
            ]
        else:
            rows = [batch]
        for r, row in enumerate(rows):
            if not bool(np.asarray(row.graph_mask).any()):
                continue  # all-padding filler row (BranchRoutedLoader)
            tot, acts, gstats = jax.device_get(
                diag(state.params, state.batch_stats, row, rng,
                     jnp_int(step), lr)
            )
            finding = locate_first_nonfinite(
                holder["act_names"], acts, holder["grad_names"], gstats
            )
            if finding is not None:
                if len(rows) > 1:
                    finding["shard"] = r
                finding["loss"] = float(tot)
                return finding
        return None

    return diagnose


def jnp_int(v: int):
    import jax.numpy as jnp

    return jnp.asarray(int(v), jnp.int32)


class NanWatch:
    """Deferred per-step non-finite watch + provenance driver.

    The loop feeds every step (``on_step``); entries are checked ``lag``
    steps later, when their ok flag has certainly retired — reading it then
    costs a host copy of one ready scalar, never a pipeline stall. A failed
    entry is drilled down via the diagnostic, emitted as a typed
    ``numerics_provenance`` event (layer, stat vector, batch spec, source
    draw ids), and — once per run — dumped to the flight recorder.
    ``take()`` hands the accumulated skip provenance to the epoch-boundary
    guard policy so ``guard_skip`` events carry it too.

    Bounded by design: a persistently diverged ``warn_skip`` run fails
    EVERY remaining step — after ``max_diagnoses`` drill-downs the watch
    stops re-running the (forward+backward) diagnostic and stops emitting
    per-skip events (which would evict the incident context out of the
    event ring), while the cheap skip bookkeeping (batch/level/sources for
    the epoch's ``guard_skip`` tally) continues. The same reasoning that
    caps flight-recorder dumps at one per run.

    Memory: the ring pins ``lag`` held batches — device-resident ones
    under ``Training.double_buffer`` staging, so numerics-on costs up to
    ``lag x batch`` extra HBM (a few hundred MB at the OC20 shape; budget
    it against ``hydragnn_hbm_peak_bytes``). ``lag`` defaults to 4: far
    past any async-dispatch queue depth (the flag is retired when read),
    half the residency of the first cut. Once the diagnostic budget is
    spent the batch references are dropped on insert — a long diverged
    run's ring holds no batches at all."""

    def __init__(self, diagnose: Optional[Callable] = None, lag: int = 4,
                 log_name: str = "run", max_diagnoses: int = 16):
        self.diagnose = diagnose
        self.lag = max(int(lag), 1)
        self.log_name = log_name
        self.max_diagnoses = max(int(max_diagnoses), 1)
        self._ring: deque = deque()
        self.skips: List[Dict[str, Any]] = []
        self.located = 0
        self.suppressed = 0
        self._attempts = 0
        self._dumped = False

    def on_step(self, state, batch, rng, step: int, batch_index: int,
                numerics, level: Optional[str] = None,
                sources: Optional[Sequence[int]] = None) -> None:
        if numerics is None:
            return
        if self._attempts >= self.max_diagnoses:
            batch = None  # budget spent: never pin another batch in HBM
        self._ring.append(
            (numerics.get("ok"), batch, rng, step, batch_index, level, sources)
        )
        while len(self._ring) > self.lag:
            self._check(state, self._ring.popleft())

    def end_epoch(self, state) -> None:
        """Drain the ring at the epoch boundary (the loop host-syncs there
        anyway, so the remaining flags are ready)."""
        while self._ring:
            self._check(state, self._ring.popleft())

    def take(self) -> List[Dict[str, Any]]:
        out, self.skips = self.skips, []
        return out

    def _check(self, state, entry) -> None:
        import numpy as np

        ok, batch, rng, step, batch_index, level, sources = entry
        try:
            if ok is None or bool(np.asarray(ok)):
                return
        except Exception:
            return  # a dead/donated flag is unreadable, not an incident
        prov: Dict[str, Any] = {"batch": int(batch_index), "step": int(step)}
        if level:
            prov["level"] = level
        if sources:
            prov["sources"] = [int(s) for s in sources]
        if self._attempts >= self.max_diagnoses:
            # diagnostic budget spent (sustained divergence): keep the
            # cheap bookkeeping for the epoch's guard_skip tally, skip the
            # drill-down re-run and the per-skip event — announced once
            self.suppressed += 1
            prov["layer"] = "<diagnostic_budget_spent>"
            prov["kind"] = "unknown"
            self.skips.append(prov)
            if self.suppressed == 1:
                try:
                    from .events import EV_NUMERICS_PROVENANCE
                    from .events import emit as _emit

                    _emit(
                        EV_NUMERICS_PROVENANCE,
                        severity="warn",
                        layer="<diagnostic_budget_spent>",
                        tensor_kind="unknown",
                        max_diagnoses=self.max_diagnoses,
                        note="sustained divergence: further skips are "
                             "tallied without per-skip drill-down",
                    )
                except Exception:
                    pass
            return
        self._attempts += 1
        finding = None
        if self.diagnose is not None:
            try:
                finding = self.diagnose(state, batch, rng, step)
            except Exception as e:  # diagnosis must never take training down
                warnings.warn(
                    f"NaN provenance diagnostic failed "
                    f"({type(e).__name__}: {e}); the guard skip is still "
                    "recorded without layer attribution",
                    RuntimeWarning,
                    stacklevel=2,
                )
        if finding is not None:
            import numpy as _np

            self.located += 1
            prov.update(
                {
                    "layer": finding["layer"],
                    "kind": finding["kind"],
                    # non-finite stats ARE the signal here; stringify them
                    # so the event ring stays strict-JSON serializable
                    # (flight-recorder events.json)
                    **{
                        f"stat_{k}": (
                            float(v) if _np.isfinite(v) else str(v)
                        )
                        for k, v in finding["stats"].items()
                    },
                }
            )
            if "shard" in finding:
                prov["shard"] = finding["shard"]
        else:
            # current-params re-run stayed finite (one-off flip, or the
            # trajectory moved on): still a typed record with provenance
            prov["layer"] = "<unreproduced>"
            prov["kind"] = "unknown"
        self.skips.append(prov)
        try:
            from .events import EV_NUMERICS_PROVENANCE
            from .events import emit as _emit

            attrs = dict(prov)
            # "kind" is the event's own discriminator — the tensor kind
            # (activation/gradient) travels as tensor_kind
            attrs["tensor_kind"] = attrs.pop("kind", "unknown")
            if "sources" in attrs:
                attrs["sources"] = ",".join(str(s) for s in attrs["sources"])
            _emit(EV_NUMERICS_PROVENANCE, severity="warn", **attrs)
        except Exception:
            pass
        if not self._dumped:
            # ONE flight-record dump per run: a diverging run skips every
            # remaining step — per-skip dumps would burn the whole dump
            # budget on copies of the same incident
            self._dumped = True
            try:
                from . import flightrec as _flightrec

                _flightrec.trigger("numerics_provenance")
            except Exception:
                pass
