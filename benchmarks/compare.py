"""The comparison that decides ``correct`` for a training cell.

The program's readings come from the timed path's own object (the compiled
step with its state) on its first three steps; the reference's from
``reference/<mpnn_type>.py`` on the same graphs, with weights drawn from the
same seed by ``reference/common.py``. Compared, each with a limit of its own
from the traffic file's ``limits``:

- ``loss1..3``: |loss - reference| / |reference| at each of three steps;
- ``grad_gap``: the first gradient as the optimizer got it (mu after one
  step / (1 - b1)), by the worst leaf: | |g| - |g_ref| | over the larger of
  the leaf's and the median leaf's reference norm;
- ``dparam_gap``: the parameters' change after the three steps, the same way,
  over leaves whose reference gradient is at least a thousandth of the median
  leaf's (a leaf whose gradient is nought to rounding moves under Adam by
  round-off alone).
"""

from __future__ import annotations

import functools
import importlib
import json
from typing import Dict, List, Sequence

import numpy as np


# the nearest precision below the one a configuration states: the control
CONTROL_MODE = {"float32": "bf16", "bfloat16": "fp8"}


def flat_norms(tree) -> Dict[str, float]:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in leaves:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        out[name] = float(leaf)
    return out


@functools.lru_cache(maxsize=None)
def leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree, scale):
        return jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))) * scale, tree)

    @jax.jit
    def delta_norms(new, old):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))),
            new, old)

    return norms, delta_norms


def match_records(captured: Sequence[Sequence[dict]], records: List[dict]) -> List[List[List[dict]]]:
    """Which raw records each shard of each captured batch of the program
    holds, in its order, found by the bytes of each real graph's positions;
    checks that the loader's masks count exactly those graphs' nodes and
    edges. -> [step][shard][record]; a shard of padding alone is left out."""
    index = {r["pos"].tobytes(): i for i, r in enumerate(records)}
    out = []
    for shards in captured:
        step = []
        for cap in shards:
            pos, node_graph = cap["pos"], cap["node_graph"]
            real_nodes = np.flatnonzero(cap["node_mask"])
            if real_nodes.size == 0:
                continue
            order = np.argsort(node_graph[real_nodes], kind="stable")
            gids, starts = np.unique(node_graph[real_nodes][order], return_index=True)
            bounds = np.r_[starts, real_nodes.size]
            chosen = []
            for k in range(gids.size):
                rows = real_nodes[order[bounds[k]:bounds[k + 1]]]
                key = np.ascontiguousarray(pos[rows], np.float32).tobytes()
                if key not in index:
                    raise AssertionError("a graph of the program's batch is not a record of the traffic")
                chosen.append(records[index[key]])
            n_graphs = int(np.sum(cap["graph_mask"]))
            n_edges = sum(r["senders"].shape[0] for r in chosen)
            if n_graphs != len(chosen) or n_edges != int(cap["real_edges"]):
                raise AssertionError(
                    f"loader masks disagree with the records: graphs {n_graphs}/{len(chosen)}, "
                    f"edges {int(cap['real_edges'])}/{n_edges}")
            step.append(chosen)
        out.append(step)
    return out


@functools.lru_cache(maxsize=None)
def _reference_step(mpnn_type: str, arch_json: str, mode: str, lr: float):
    """One jitted AdamW step of the reference per (model, precision)."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc

    ref = importlib.import_module(f"reference.{mpnn_type.lower()}")
    arch = json.loads(arch_json)

    def loss_of(p, shards):
        # as the mesh step combines its devices: each shard's own loss (own
        # batch statistics), weighted by its share of the real graphs
        counts = [jnp.sum(b["graph_w"]) for b in shards]
        total = sum(counts)
        return sum(ref.loss_fn(p, b, arch, mode) * (n / total) for b, n in zip(shards, counts))

    @jax.jit
    def step(p, opt, shards):
        loss, grads = jax.value_and_grad(loss_of)(p, shards)
        new, opt = rc.adamw_update(p, grads, opt, lr)
        return new, opt, loss, grads

    return step


def reference_readings(mpnn_type: str, arch: dict, input_dim: int, seed: int,
                       step_records: List[List[List[dict]]], lr: float, mode: str = "f32",
                       drop_half: bool = False) -> Dict[str, Dict[str, float]]:
    """Three AdamW steps of the plain reference; ``mode`` below f32 is the
    control. ``drop_half`` plants the half-batch fault in the reference."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc

    ref = importlib.import_module(f"reference.{mpnn_type.lower()}")
    params = rc.make_weights(ref.weight_spec(arch, input_dim), seed)["params"]
    if drop_half:
        step_records = [[recs[: max(len(recs) // 2, 1)] for recs in shards] for shards in step_records]
    every = [recs for shards in step_records for recs in shards]
    n_pad = rc.pad_to(max(sum(r["x"].shape[0] for r in recs) for recs in every) + 1, 128)
    e_pad = rc.pad_to(max(sum(r["senders"].shape[0] for r in recs) for recs in every), 128)
    g_pad = max(len(recs) for recs in every) + 1
    norms, delta_norms = leaf_norms_fn()
    step = _reference_step(mpnn_type, json.dumps(arch, sort_keys=True), mode, float(lr))

    opt = {"mu": jax.tree_util.tree_map(jnp.zeros_like, params),
           "nu": jax.tree_util.tree_map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.float32)}
    p0, p, losses, g1 = params, params, [], None
    for i, shards in enumerate(step_records):
        batches = [{k: jnp.asarray(v) for k, v in rc.batch_records(recs, n_pad, e_pad, g_pad).items()}
                   for recs in shards]
        p, opt, loss, grads = step(p, opt, batches)
        losses.append(float(loss))
        if i == 0:
            g1 = flat_norms(norms(grads, 1.0))
        del grads
    dp = flat_norms(delta_norms(p, p0))
    return {"loss": losses, "grad": g1, "dparam": dp}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> Dict[str, float]:
    """Per leaf: | |prog| - |ref| | over the larger of the leaf's and the
    median leaf's reference norm."""
    if set(prog) != set(ref):
        raise AssertionError(f"parameter leaves differ: {sorted(set(prog) ^ set(ref))[:6]}")
    names = [n for n in ref if keep is None or keep(n)]
    med = float(np.median([ref[n] for n in names]))
    out = {}
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        out[n] = gap if np.isfinite(gap) else float("inf")
    return out


def _worst_and_median(gaps: Dict[str, float]):
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def compare(prog: Dict, ref: Dict, limits: Dict[str, float]):
    """-> (correct, compared rows {name: {value, limit}}, notes)."""
    values = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        values[f"loss{i + 1}"] = abs(a - b) / max(abs(b), 1e-30)
    gaps_g = leaf_gaps(prog["grad"], ref["grad"])
    values["grad_gap"], where_g, values["grad_gap_median"] = _worst_and_median(gaps_g)
    med_g = float(np.median(list(ref["grad"].values())))
    moved = lambda n: ref["grad"][n] >= 1e-3 * med_g
    gaps_d = leaf_gaps(prog["dparam"], ref["dparam"], keep=moved)
    values["dparam_gap"], where_d, values["dparam_gap_median"] = _worst_and_median(gaps_d)
    compared, ok = {}, True
    for name, value in values.items():
        limit = limits.get(name)
        value = float(value) if np.isfinite(value) else 1e30
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:
            ok = False
    top = lambda gaps: [[n, float("%.3g" % gaps[n])] for n in sorted(gaps, key=gaps.get, reverse=True)[:6]]
    # the worst leaves' reference gradient over the median leaf's: a leaf whose
    # gradient is small moves under Adam by the rounding of its step
    rel = lambda n: float("%.3g" % (ref["grad"][n] / max(med_g, 1e-30)))
    notes = {"grad_gap_leaf": where_g, "dparam_gap_leaf": where_d,
             "grad_top": top(gaps_g), "dparam_top": top(gaps_d),
             "grad_gap_leaf_grad_rel": rel(where_g), "dparam_gap_leaf_grad_rel": rel(where_d),
             "leaves_left_out": sorted(n for n in ref["grad"] if not moved(n))}
    return ok, compared, notes
