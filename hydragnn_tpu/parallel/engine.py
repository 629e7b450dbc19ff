"""The one mesh-step builder: ``(objective, rule_table, mesh)`` -> steps.

ROADMAP item 1. The dp / ZeRO-1/2/3 / branch-parallel trio
(parallel/dp.py, parallel/branch.py, the constraint paths in
parallel/mesh.py) collapses into this module, driven by a declarative
rule table (parallel/rules.py):

- ``place_state``        — between-steps placement: every params /
  opt_state / batch_stats leaf device_put by its first-matching rule;
  non-scalar leaves NO rule matches are placed replicated and audited
  (obs/sharding.py ``record_unmatched``).
- ``make_mesh_train_step`` / ``make_mesh_eval_step`` — the train/eval
  steps every caller uses. The guard (train/guard.py), numerics probes
  (obs/numerics.py), retrace sentinel (``note_trace``), fault-injection
  hook, and donate/jit plumbing are threaded through ONCE here instead
  of per-builder.

Two step families remain — selected by ``table.routed``, not by caller:

- **unrouted** (dp, zero1/2/3): params consumed replicated inside the
  shard_map (ZeRO-3's between-steps ``P(data)`` storage all-gathers at
  the program boundary), gradients pmean over the whole mesh; the
  table's ``grads``-scope rules become in-step ``with_sharding_
  constraint`` pins between the pmean and the optimizer update (the
  reduce-scatter lowering, ex-``zero2_grad_constraint``), its
  ``params``-scope rules the step-output constraint
  (ex-``zero3_param_constraint``).
- **routed** (branch / mp): decoder-bank leaves (the table's model-axis
  rules) shard over the model axis, batches arrive branch-routed
  (parallel/routing.py BranchRoutedLoader), decoder gradients pmean
  over ``data`` only — the reference's ``MultiTaskModelMP`` per-branch
  DDP subgroup semantics, ported verbatim from the retired branch.py.

The math in both families is a line-for-line port of the retired
builders (bit-identical train loss on the same mesh is asserted in
tests/test_sharding_rules.py), with the mesh axis names resolved from
the table's logical ``data``/``model`` axes so the engine runs on both
the legacy ``(branch, data)`` mesh (deprecation shims) and the 2D
``(data, model)`` mesh (``make_mesh2d``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.base import HydraModel
from ..train.loss import compute_loss
from ..train.state import TrainState
from ..utils import tracer as tr
from . import rules as R
from jax import shard_map

from .mesh import DATA_AXIS, batch_axes


@dataclasses.dataclass
class Objective:
    """What to optimize, independent of placement: the model + optimizer
    and the step-level switches every retired builder accepted. One
    objective builds steps under any rule table."""

    model: HydraModel
    tx: Any = None
    compute_grad_energy: bool = False
    mixed_precision: bool = False
    guard: Optional[bool] = None
    numerics: Optional[bool] = None


def ensure_stacked(batch):
    """Guarantee the leading device axis the shard_map steps expect.

    ``GraphLoader(num_shards=1)`` emits unstacked batches (the plain-jit
    contract); a 1-device mesh still wants ``[1, ...]``. Keeping the shim
    here keeps the [D, ...] contract in one place for every consumer.
    """
    if batch.graph_mask.ndim == 1:
        return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], batch)
    return batch


# ---------------------------------------------------------------------------
# table -> concrete mesh resolution
# ---------------------------------------------------------------------------


def _resolved(table: R.RuleTable, mesh: Mesh):
    """(axis_map, logical axis sizes, concrete model axis name or None)."""
    amap = R.resolve_axes(mesh)
    shape = dict(mesh.shape)
    sizes = {tok: int(shape[ax]) for tok, ax in amap.items()}
    return amap, sizes, amap.get(R.MODEL)


def _section_specs(tree, table: R.RuleTable, scope: str, amap, sizes):
    return R.spec_tree(tree, table, scope, amap, sizes)


def place_state(
    state: TrainState, table: R.RuleTable, mesh: Mesh
) -> TrainState:
    """Place a TrainState per the rule table: replicate everything (step
    counter included), then device_put each params / opt_state /
    batch_stats leaf at its matched spec. Optimizer moments are PLACED,
    not re-initialized, so ``Training.continue`` resumes with its
    restored Adam state. Unmatched non-scalar leaves land replicated and
    are reported to the sharding audit."""
    from ..obs import sharding as obs_sharding
    from .mesh import replicate_state

    amap, sizes, _ = _resolved(table, mesh)
    state = replicate_state(state, mesh)
    unmatched: List[str] = []

    def put(tree, scope):
        specs, miss = _section_specs(tree, table, scope, amap, sizes)
        unmatched.extend(miss)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree,
            specs,
        )

    state = state.replace(
        params=put(state.params, "params"),
        batch_stats=put(state.batch_stats, "batch_stats"),
        opt_state=put(state.opt_state, "opt_state"),
    )
    obs_sharding.record_unmatched(table.name, unmatched)
    return state


def _constrain(tree, table, scope, mesh, amap, sizes, default_explicit):
    """In-jit counterpart of ``place_state`` for one scope: matched
    leaves pinned to their rule's spec with ``with_sharding_constraint``.
    ``default_explicit=True`` pins unmatched/replicated leaves to an
    explicit ``P()`` too (the params/ZeRO-3 output contract: GSPMD must
    not be free to leave merged params sharded); ``False`` leaves them
    untouched (the grads/ZeRO-2 contract)."""

    def pin(path, leaf):
        p = R.path_str(path)
        _, axes = R.match_rule(table, p, leaf, scope, sizes)
        if axes:
            spec = P(*[amap[a] if a is not None else None for a in axes])
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec)
            )
        if default_explicit:
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, P())
            )
        return leaf

    return jax.tree_util.tree_map_with_path(pin, tree)


def _routed_model(model, table: R.RuleTable, mesh: Mesh):
    """(local model slice, b_local, model axis name) for a routed table.

    The model is rebuilt for the device-local branch slice: identical
    module tree, bank leaves sliced by the shard_map specs. Branch-loss
    balancing is stripped from the LOCAL cfg — the global weight vector
    does not slice with the remapped local dataset ids, so the step
    applies balancing to the decoder gradient scales instead (the
    per-branch effective-LR equivalent)."""
    _, _, model_ax = _resolved(table, mesh)
    if model_ax is None:
        raise R.RuleError(
            f"rule table {table.name!r} is routed but mesh axes "
            f"{tuple(mesh.axis_names)} carry no model/branch axis "
            "(parallel/mesh.py make_mesh2d(model_size=...))"
        )
    cfg = model.cfg
    msize = int(dict(mesh.shape)[model_ax])
    assert cfg.num_branches % msize == 0, (
        f"num_branches {cfg.num_branches} not divisible by model axis "
        f"{msize}"
    )
    b_local = cfg.num_branches // msize
    lcfg = dataclasses.replace(
        cfg, num_branches=b_local,
        branch_loss_weights=None, branch_loss_metrics=False,
    )
    return type(model)(cfg=lcfg), b_local, model_ax


def _routed_top_keys(tree, table, scope, amap, sizes, model_ax):
    """Top-level collection keys whose subtree carries model-axis-sharded
    leaves — the decoder banks. Drives the mixed (per-branch vs global)
    gradient reduction; derived from the TABLE so reduction and placement
    can never disagree."""
    keys = set()
    if not isinstance(tree, dict):
        return keys
    specs, _ = _section_specs(tree, table, scope, amap, sizes)
    for k, sub in specs.items():
        for spec in jax.tree_util.tree_leaves(
            sub, is_leaf=lambda x: isinstance(x, P)
        ):
            if isinstance(spec, P) and model_ax in tuple(spec):
                keys.add(k)
                break
    return keys


# ---------------------------------------------------------------------------
# the one train-step builder
# ---------------------------------------------------------------------------


def make_mesh_train_step(
    objective: Objective, table: R.RuleTable, mesh: Mesh
):
    """Jitted (state, stacked_batch, rng) -> (state, loss, tasks) under
    ``table``'s placement on ``mesh``. The only train-step builder —
    dp/zero/branch are rule presets, not code paths."""
    R.validate_table(table)
    model, tx = objective.model, objective.tx
    compute_grad_energy = objective.compute_grad_energy
    mixed_precision = objective.mixed_precision
    cfg = model.cfg
    from ..obs import numerics as obs_numerics
    from ..obs import sharding as obs_sharding
    from ..train.compile_plane import note_trace
    from ..train.guard import guard_enabled, guarded_update, step_ok
    from ..utils import faultinject

    amap, sizes, model_ax = _resolved(table, mesh)
    routed = table.routed
    # ZeRO staging read off the table, not caller flags: any non-replicated
    # grads-scope rule arms the in-step grad pin (stage 2), any params-scope
    # rule the step-output param constraint (stage 3)
    pin_grads = table.shards("grads")
    pin_params = table.shards("params") and not routed
    if routed:
        local, b_local, model_ax = _routed_model(model, table, mesh)
        lcfg = local.cfg
        sentinel, builder = "branch_train_step", "branch_parallel_train_step"
        obs_sharding.note_builder(
            builder, dict(mesh.shape),
            rules=table.name, branches=int(cfg.num_branches),
        )
    else:
        local, lcfg = model, cfg
        sentinel, builder = "parallel_train_step", "parallel_train_step"
        obs_sharding.note_builder(
            builder, dict(mesh.shape),
            rules=table.name, zero2=pin_grads, zero3=pin_params,
        )
    # resolve at BUILD time like every step builder (loop.py): the env
    # default freezes when the step is constructed, not at first trace
    use_guard = guard_enabled(objective.guard)
    use_numerics = obs_numerics.numerics_enabled(objective.numerics)
    meta = {"act_names": None, "grad_names": None}
    _both = batch_axes(mesh)  # model/branch-major — legacy reduce order

    def per_device_loss(params, batch_stats, batch, rng):
        if mixed_precision:
            from ..train.loop import mp_cast, mp_keep, mp_restore_stats

            params, batch = mp_cast(params, batch, compute_grad_energy, mp_keep(model))
        variables = {"params": params, "batch_stats": batch_stats}
        (tot, tasks, mutated, _), acts = obs_numerics.run_probed(
            use_numerics, meta,
            lambda: compute_loss(
                local, variables, batch, lcfg, True, rng, compute_grad_energy
            ),
        )
        if mixed_precision:
            mutated = mp_restore_stats(mutated)
        return tot.astype(jnp.float32), (tasks, mutated, acts)

    if cfg.conv_checkpointing:
        from ..ops.remat import loss_remat

        per_device_loss = loss_remat(per_device_loss, cfg.remat_policy)

    # -- routed reduction: decoder subtrees pmean over data only ------------

    def _mixed_pmean(tree, scale_enc, scale_dec_vec, dec_keys):
        """pmean with decoder subtrees reduced over data only (per-BRANCH
        weighted mean — ``scale_dec_vec`` is a [b_local] vector applied
        along the leading bank axis), encoder subtrees over the whole
        mesh (global mean)."""
        out = {}
        for k, v in tree.items():
            if k in dec_keys:

                def dec_scale(g):
                    s = scale_dec_vec.reshape(
                        (b_local,) + (1,) * (g.ndim - 1)
                    )
                    return g * s

                out[k] = jax.lax.pmean(
                    jax.tree_util.tree_map(dec_scale, v), DATA_AXIS
                )
            else:
                out[k] = jax.lax.pmean(
                    jax.tree_util.tree_map(lambda g: g * scale_enc, v),
                    _both,
                )
        return out

    def routed_grads(dec_params, dec_stats):
        def sharded_grads(params, batch_stats, batch, rng):
            batch = jax.tree_util.tree_map(lambda x: x[0], batch)
            # graphs arrive with GLOBAL dataset ids; remap to this
            # device's local branch-slice index (padding rows clip
            # harmlessly — their loss terms are masked out)
            br = jax.lax.axis_index(model_ax)
            local_ds = jnp.clip(
                batch.dataset_id.astype(jnp.int32) - br * b_local,
                0,
                b_local - 1,
            )
            batch = batch.replace(dataset_id=local_ds)
            with tr.scope(tr.HG_LOSS):
                (tot, (tasks, mutated, acts)), grads = jax.value_and_grad(
                    per_device_loss, has_aux=True
                )(params, batch_stats, batch, rng)
            gm = batch.graph_mask.astype(jnp.float32)
            n = jnp.sum(gm)
            # encoder: weighted mean over every shard (DDP analog)
            n_tot = jax.lax.psum(n, _both)
            scale_enc = n * mesh.size / jnp.maximum(n_tot, 1.0)
            # decoder: weighted mean over each BRANCH's graphs (the
            # reference's per-branch DDP subgroup). The per-device loss
            # averages over its shard, so slice j's raw gradient carries
            # a factor n_j_shard/n_shard; rescaling by n_shard * D /
            # n_j_total before the data-axis pmean yields exactly the
            # per-branch weighted mean — also correct when several
            # branches share a device block (b_local > 1), where a single
            # block-mass scale would train each branch at ~1/b_local
            # effective LR.
            branch_mass = jax.ops.segment_sum(
                gm, batch.dataset_id, num_segments=b_local
            )
            branch_tot = jax.lax.psum(branch_mass, DATA_AXIS)
            scale_dec_vec = (
                n * sizes[R.DATA] / jnp.maximum(branch_tot, 1.0)
            )
            if cfg.branch_loss_weights:
                # static per-branch loss balancing: scale each branch's
                # decoder gradient by its weight — this device's
                # b_local-slice of the global vector
                w_all = jnp.asarray(cfg.branch_loss_weights, jnp.float32)
                w_local = jax.lax.dynamic_slice(
                    w_all, (br * b_local,), (b_local,)
                )
                scale_dec_vec = scale_dec_vec * w_local
            grads = _mixed_pmean(
                grads, scale_enc, scale_dec_vec, dec_params
            )
            tot = jax.lax.pmean(tot * scale_enc, _both)
            tasks = jax.lax.pmean(
                jax.tree_util.tree_map(lambda t: t * scale_enc, tasks),
                _both,
            )
            stats = mutated.get("batch_stats", batch_stats)
            new_stats = _mixed_pmean(
                stats, scale_enc, scale_dec_vec, dec_stats
            )
            if use_numerics:
                acts = obs_numerics.cross_device_reduce(acts, _both)
                return grads, tot, tasks, new_stats, acts
            return grads, tot, tasks, new_stats

        return sharded_grads

    def unrouted_grads(params, batch_stats, batch, rng):
        # batch leaves arrive with leading axis [D_local=1, ...] inside
        # the shard; drop it to recover the per-device batch.
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        with tr.scope(tr.HG_LOSS):
            (tot, (tasks, mutated, acts)), grads = jax.value_and_grad(
                per_device_loss, has_aux=True
            )(params, batch_stats, batch, rng)
        # weight each shard by its real-graph count so empty/remainder
        # shards neither dilute gradients nor corrupt running batch-norm
        # statistics
        n = jnp.sum(batch.graph_mask.astype(jnp.float32))
        n_tot = jax.lax.psum(n, _both)
        scale = n * mesh.size / jnp.maximum(n_tot, 1.0)
        # gradient all-reduce over the whole mesh (DDP analog)
        grads = jax.lax.pmean(
            jax.tree_util.tree_map(lambda g: g * scale, grads), _both
        )
        tot = jax.lax.pmean(tot * scale, _both)
        tasks = jax.lax.pmean(
            jax.tree_util.tree_map(lambda t: t * scale, tasks), _both
        )
        stats = mutated.get("batch_stats", batch_stats)
        new_stats = jax.lax.pmean(
            jax.tree_util.tree_map(lambda s: s * scale, stats), _both
        )
        if use_numerics:
            acts = obs_numerics.cross_device_reduce(acts, _both)
            return grads, tot, tasks, new_stats, acts
        return grads, tot, tasks, new_stats

    rep = P()
    if not routed:
        # params consumed replicated: under ZeRO-3 storage XLA inserts the
        # transient all-gather at the program boundary (gather-at-use)
        grad_map = shard_map(
            unrouted_grads,
            mesh=mesh,
            in_specs=(rep, rep, P(_both), rep),
            out_specs=(rep, rep, rep, rep)
            + ((rep,) if use_numerics else ()),
            check_vma=False,
        )

    def _pin_out_params(params):
        """The step-output param contract: ZeRO-3 re-shards updated
        params (transient full copies); ZeRO-2 pins them replicated so
        the sharded updates all-gather HERE instead of falling back to
        full-grad replication upstream. No-op for dp/routed tables."""
        if pin_params:
            return _constrain(
                params, table, "params", mesh, amap, sizes,
                default_explicit=True,
            )
        if pin_grads:
            return jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P())
                ),
                params,
            )
        return params

    def step(state: TrainState, batch, rng):
        # retrace sentinel: one execution per jit trace (compile_plane.py)
        note_trace(sentinel, (state, batch, rng))
        if routed:
            # specs depend on the state's tree structure -> built per trace
            pspecs, _ = _section_specs(
                state.params, table, "params", amap, sizes
            )
            sspecs, _ = _section_specs(
                state.batch_stats, table, "batch_stats", amap, sizes
            )
            dec_p = _routed_top_keys(
                state.params, table, "params", amap, sizes, model_ax
            )
            dec_s = _routed_top_keys(
                state.batch_stats, table, "batch_stats", amap, sizes,
                model_ax,
            )
            gmap = shard_map(
                routed_grads(dec_p, dec_s),
                mesh=mesh,
                in_specs=(pspecs, sspecs, P(_both), rep),
                out_specs=(pspecs, rep, rep, sspecs)
                + ((rep,) if use_numerics else ()),
                check_vma=False,
            )
        else:
            gmap = grad_map
        acts = None
        if use_numerics:
            grads, tot, tasks, new_stats, acts = gmap(
                state.params, state.batch_stats, batch, rng
            )
        else:
            grads, tot, tasks, new_stats = gmap(
                state.params, state.batch_stats, batch, rng
            )
        # chaos-test hook: exact no-op unless a fault is armed. AFTER the
        # pmean, so the poison (like the real failure it models) is
        # identical on every device and the guard decision agrees.
        grads = faultinject.poison_grads(
            grads, state.step, faultinject.lr_of(state.opt_state)
        )
        numer = None
        if use_numerics:
            # gradient stats on the reduced (and possibly poisoned) grads:
            # replicated values, so the census agrees across the mesh
            gnames, gstats = obs_numerics.grad_group_stats(grads)
            meta["grad_names"] = gnames
            numer = {"ok": step_ok(tot, grads), "act": acts, "grad": gstats}

        # The optimizer update runs OUTSIDE the shard_map, under the outer
        # jit: with replicated state this is byte-identical to an in-map
        # update; with ZeRO-1 moments (P(data) placed) XLA partitions the
        # elementwise update by the moments' sharding; with routed tables
        # decoder grads/moments stay model-sharded by propagation.
        def do_update():
            g = grads
            if pin_grads:
                # ZeRO-2 site: pinned between the pmean and the update,
                # XLA lowers the reduce+constraint pair to reduce-scatter
                g = _constrain(
                    g, table, "grads", mesh, amap, sizes,
                    default_explicit=False,
                )
            with tr.scope(tr.HG_OPTIMIZER):
                updates, opt_state = tx.update(
                    g, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
            return _pin_out_params(params), opt_state

        if use_guard:
            # ok is computed from the reduced loss/grads — replicated
            # values, so the guard's select agrees across the whole mesh
            new_state = guarded_update(
                state,
                numer["ok"] if numer is not None else step_ok(tot, grads),
                do_update,
                new_stats,
            )
            # the guard's per-leaf select merges old and new params, which
            # does not preserve do_update's output constraint — re-apply
            # the output contract on the merged params or GSPMD is free to
            # leave them sharded
            if pin_params or pin_grads:
                new_state = new_state.replace(
                    params=_pin_out_params(new_state.params)
                )
        else:
            params, opt_state = do_update()
            new_state = state.replace(
                params=params,
                opt_state=opt_state,
                batch_stats=new_stats,
                step=state.step + 1,
            )
        if use_numerics:
            return new_state, tot, tasks, numer
        return new_state, tot, tasks

    # donate the incoming state so params/opt-state update in place in HBM
    jitted = jax.jit(step, donate_argnums=0)
    if not use_numerics:
        return jitted
    # numerics build: keep the jit AOT-reachable and carry the host-side
    # name tables + NaN drill-down (the diagnostic runs the replicated
    # single-device GLOBAL objective per shard row — obs/numerics.py; in
    # routed mode branch ids stay global there, so no local remap)
    return obs_numerics.numerics_step_wrapper(
        jitted, meta, model, compute_grad_energy, mixed_precision
    )


# ---------------------------------------------------------------------------
# the one eval-step builder
# ---------------------------------------------------------------------------


def make_mesh_eval_step(objective: Objective, table: R.RuleTable, mesh: Mesh):
    """Jitted (state, stacked_batch) -> (loss, tasks) under the table's
    placement — the eval twin of ``make_mesh_train_step``."""
    R.validate_table(table)
    model = objective.model
    compute_grad_energy = objective.compute_grad_energy
    mixed_precision = objective.mixed_precision
    cfg = model.cfg
    from ..train.compile_plane import note_trace

    amap, sizes, model_ax = _resolved(table, mesh)
    _both = batch_axes(mesh)
    rep = P()

    if table.routed:
        local, b_local, model_ax = _routed_model(model, table, mesh)
        lcfg = local.cfg

        def sharded_eval(params, batch_stats, batch):
            batch = jax.tree_util.tree_map(lambda x: x[0], batch)
            br = jax.lax.axis_index(model_ax)
            local_ds = jnp.clip(
                batch.dataset_id.astype(jnp.int32) - br * b_local,
                0,
                b_local - 1,
            )
            batch = batch.replace(dataset_id=local_ds)
            variables = {"params": params, "batch_stats": batch_stats}
            if mixed_precision:
                from ..train.loop import mp_cast_eval

                variables, batch = mp_cast_eval(
                    variables, batch, compute_grad_energy
                )
            tot, tasks, _, _ = compute_loss(
                local, variables, batch, lcfg, False, None,
                compute_grad_energy,
            )
            n = jnp.sum(batch.graph_mask.astype(jnp.float32))
            n_tot = jax.lax.psum(n, _both)
            scale = n * mesh.size / jnp.maximum(n_tot, 1.0)
            tot = jax.lax.pmean(tot * scale, _both)
            tasks = jax.lax.pmean(
                jax.tree_util.tree_map(lambda t: t * scale, tasks), _both
            )
            return tot, tasks

        def eval_step(state: TrainState, batch):
            note_trace("branch_eval_step", (state, batch))
            pspecs, _ = _section_specs(
                state.params, table, "params", amap, sizes
            )
            sspecs, _ = _section_specs(
                state.batch_stats, table, "batch_stats", amap, sizes
            )
            mapped = shard_map(
                sharded_eval,
                mesh=mesh,
                in_specs=(pspecs, sspecs, P(_both)),
                out_specs=(rep, rep),
                check_vma=False,
            )
            return mapped(state.params, state.batch_stats, batch)

        return jax.jit(eval_step)

    def sharded_eval(state: TrainState, batch):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        variables = state.variables()
        if mixed_precision:
            # keep eval numerics identical to the single-host eval step
            from ..train.loop import mp_cast_eval, mp_keep

            variables, batch = mp_cast_eval(
                variables, batch, compute_grad_energy, mp_keep(model)
            )
        tot, tasks, _, _ = compute_loss(
            model, variables, batch, cfg, False, None, compute_grad_energy
        )
        # weight by real graphs so padded shards don't skew the mean
        n = jnp.sum(batch.graph_mask.astype(jnp.float32))
        n_tot = jax.lax.psum(n, _both)
        scale = n * mesh.size / jnp.maximum(n_tot, 1.0)
        tot = jax.lax.pmean(tot * scale, _both)
        tasks = jax.lax.pmean(
            jax.tree_util.tree_map(lambda t: t * scale, tasks), _both
        )
        return tot, tasks

    mapped = shard_map(
        sharded_eval,
        mesh=mesh,
        in_specs=(rep, P(_both)),
        out_specs=(rep, rep),
        check_vma=False,
    )

    def eval_step(state: TrainState, batch):
        note_trace("parallel_eval_step", (state, batch))
        return mapped(state, batch)

    return jax.jit(eval_step)
