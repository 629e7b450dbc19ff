"""Driver of the cells whose traffic file says ``"kind": "train"``.

The system under test is the program's own loader (``prepare_data``), jitted
step (``make_train_step``) and epoch loop (``train_epoch``). Set-up builds one
object, the compiled step with its state, drives it through its first steps
from the seed (read for ``correct``), and hands that same object to the
window. Shapes belong to the traffic file: the dataset, the split, the batch
order and the packing plan do not see ``--seed``, which draws the weights.

A rate is whole steps over their own drained time: after warm-up the device
is drained, the clock read, epochs run through ``train_epoch``; the loader
stops handing out batches at ``--seconds``, the steps already fed finish, the
device is drained and the clock read again.
"""

from __future__ import annotations

import copy
import gc
import importlib
import os
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

import common
import compare
import datagen
import tracing


class Feed:
    """The window's view of the program's loader: same batches, same order;
    stops handing them out at ``deadline`` (or after ``limit`` batches). It
    reads nothing back from the device while it feeds: it keeps each batch's
    masks and counts them in ``totals()``, once the window has closed.
    ``keep`` holds host copies of the first batches for the comparison."""

    def __init__(self, loader, limit: Optional[int] = None, deadline: Optional[float] = None,
                 keep: Optional[list] = None):
        self.loader, self.limit, self.deadline, self.keep = loader, limit, deadline, keep
        self.batches = 0
        self._masks: list = []

    def __len__(self):
        return len(self.loader)

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def __iter__(self):
        for batch in self.loader:
            if self.limit is not None and self.batches >= self.limit:
                return
            if self.deadline is not None and time.perf_counter() >= self.deadline:
                return
            self.batches += 1
            self._masks.append((batch.graph_mask, batch.node_mask, batch.edge_mask))
            if self.keep is not None:
                pos, ng = np.asarray(batch.pos, np.float32), np.asarray(batch.node_graph)
                gm, nm, em = (np.asarray(m) for m in self._masks[-1])
                stacked = pos.ndim == 3  # [shards, ...] batches of the mesh step
                self.keep.append([
                    {"pos": p, "node_graph": g_, "node_mask": n_, "graph_mask": m_, "real_edges": int(e_.sum())}
                    for p, g_, n_, m_, e_ in (zip(pos, ng, nm, gm, em) if stacked else [(pos, ng, nm, gm, em)])
                ])
            yield batch

    def totals(self) -> Dict[str, int]:
        out = {"batches": self.batches, "graphs": 0, "nodes": 0, "edges": 0, "node_slots": 0, "edge_slots": 0}
        for masks in self._masks:
            gm, nm, em = (np.asarray(m) for m in masks)
            out["graphs"] += int(gm.sum())
            out["nodes"] += int(nm.sum())
            out["edges"] += int(em.sum())
            out["node_slots"] += nm.size
            out["edge_slots"] += em.size
        self._masks = []
        return out


class Step:
    """The compiled step, counted. While ``reading`` it keeps what the
    comparison needs of the first three steps: each loss, the leaf norms of
    the first gradient as the optimizer got it (mu / (1 - b1) of the state
    after one step) and of the parameters' change after the third."""

    def __init__(self, step: Callable):
        self.step, self.calls, self.reading = step, 0, False
        self.losses, self.grad, self.dparam, self._p0 = [], None, None, None
        self.first_step_at = None
        self._norms, self._delta = compare.leaf_norms_fn()

    def __call__(self, state, batch, rng):
        import jax

        if self.reading and self.calls == 0:
            self._p0 = jax.tree_util.tree_map(lambda a: a.copy(), state.params)
        out = self.step(state, batch, rng)
        self.calls += 1
        if self.reading and self.calls <= 3:
            self.losses.append(out[1])
            if self.calls == 1:
                jax.block_until_ready(out[1])
                self.first_step_at = time.perf_counter()
                mu = next(s.mu for s in out[0].opt_state.inner_state if hasattr(s, "mu"))
                self.grad = self._norms(mu, 1.0 / (1.0 - 0.9))
            if self.calls == 3:
                self.dparam = self._delta(out[0].params, self._p0)
                self._p0 = None
        return out

    def readings(self) -> Dict[str, Any]:
        import jax

        return {
            "loss": [float(x) for x in jax.device_get(self.losses)],
            "grad": compare.flat_norms(jax.device_get(self.grad)),
            "dparam": compare.flat_norms(jax.device_get(self.dparam)),
        }


def to_graphs(records, Graph):
    return [
        Graph(x=r["x"], pos=r["pos"], senders=r["senders"], receivers=r["receivers"],
              graph_targets={"energy": r["energy"]}, node_targets={"forces": r["forces"]}, z=r["z"])
        for r in records
    ]


def split(n: int, perc_train: float, seed: int):
    idx = np.random.default_rng(seed).permutation(n)
    n_train = int(n * perc_train)
    n_val = (n - n_train) // 2
    return idx[:n_train], idx[n_train:n_train + n_val], idx[n_train + n_val:]


def load_datasets(traffic: Dict[str, Any], dirs: Dict[str, str], scale: float):
    """The traffic file's records, and its (train, val, test) split of them as
    the program's ``Graph`` objects: a function of the traffic file alone."""
    from hydragnn_tpu.data.graph import Graph

    records = datagen.dataset(traffic, dirs["data"], scale)
    parts = split(len(records), float(traffic["split"]["perc_train"]), int(traffic["split"]["split_seed"]))
    graphs = to_graphs(records, Graph)
    return records, graphs, tuple([graphs[i] for i in part] for part in parts)


def program_config(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cfg = copy.deepcopy(ctx["config"]["program_config"])
    cfg["NeuralNetwork"]["Training"].update(ctx["traffic"].get("training_overrides", {}))
    if int(ctx["cell"]["chips"]) > 1:
        cfg["Parallel"] = {"rules": "dp"}
        t = cfg["NeuralNetwork"]["Training"]
        t["batch_size"] = int(t["batch_size"]) * int(ctx["cell"]["chips"])
    return cfg


class Bench:
    """What a run builds once whatever the seed: data, loader, model, the
    jitted step."""


def setup(ctx: Dict[str, Any], dirs: Dict[str, str], scale: float = 1.0) -> Bench:
    from hydragnn_tpu.api import prepare_data
    from hydragnn_tpu.models import create_model
    from hydragnn_tpu.train import make_optimizer, make_train_step
    from hydragnn_tpu.train.compile_plane import install_metrics_listeners, setup_compile_cache
    from hydragnn_tpu.utils import tracer as tr

    env = Bench()
    env.chips = int(ctx["cell"]["chips"])
    env.traffic = traffic = ctx["traffic"]
    env.stages = {}
    # ---- data: a function of the traffic file alone
    t = time.perf_counter()
    env.records, _, datasets = load_datasets(traffic, dirs, scale)
    env.stages["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    env.config, (env.loader, _, _), _ = prepare_data(program_config(ctx), datasets)
    env.stages["prepare_data_s"] = time.perf_counter() - t
    env.training = training = env.config["NeuralNetwork"]["Training"]
    env.arch = env.config["NeuralNetwork"]["Architecture"]
    install_metrics_listeners()
    setup_compile_cache(training)
    env.ref = importlib.import_module(f"reference.{env.arch['mpnn_type'].lower()}")
    env.spec = env.ref.weight_spec(env.arch, int(env.arch["input_dim"]))
    env.model = create_model(env.config)
    env.tx = make_optimizer(training["Optimizer"])
    cge, mp = bool(training.get("compute_grad_energy", False)), bool(training.get("mixed_precision", False))
    env.place = lambda state: state
    if env.chips > 1:
        # all local devices through Parallel.rules "dp": the program's one mesh step
        import jax

        from hydragnn_tpu.api import resolve_parallel
        from hydragnn_tpu.parallel import (
            Objective, make_mesh2d, make_mesh_train_step, place_state, promote_batch)

        if jax.local_device_count() != env.chips:
            raise SystemExit(f"the cell asks for {env.chips} chips, this host has {jax.local_device_count()}")
        table, mesh = resolve_parallel(env.config), make_mesh2d(model_size=1)
        mesh_step = make_mesh_train_step(
            Objective(model=env.model, tx=env.tx, compute_grad_energy=cge, mixed_precision=mp,
                      numerics=False), table, mesh)
        env.raw_step = lambda s, b, r: mesh_step(s, promote_batch(b, mesh), r)
        env.place = lambda state: place_state(state, table, mesh)
    else:
        env.raw_step = make_train_step(env.model, env.tx, cge, mp)
    db = training.get("double_buffer", True)
    env.depth = 0 if not db else (2 if db is True else int(db))
    tr.reset()
    tr.enable()
    return env


def first_steps(env: Bench, seed: int, break_step: Optional[Callable] = None):
    """The one object (compiled step + state, weights from ``seed``) driven
    through its first three steps by the window's own call and feed, read
    for `correct`: -> (state, step, captured batches, rng)."""
    import jax

    from hydragnn_tpu.train import TrainState
    from hydragnn_tpu.train.loop import train_epoch
    from reference import common as rc

    variables = rc.make_weights(env.spec, seed)
    state = env.place(TrainState.create(variables, env.tx))
    step = Step(break_step(env.raw_step) if break_step is not None else env.raw_step)
    rng = jax.random.PRNGKey(seed % (2**31))
    captured: list = []
    step.reading = True
    env.loader.set_epoch(0)
    state, _, _, rng, _ = train_epoch(
        Feed(env.loader, limit=3, keep=captured), step, state, rng, prefetch_depth=env.depth)
    step.reading = False
    if step.calls != 3:
        raise RuntimeError(f"an epoch of the traffic holds {step.calls} steps; `correct` reads three")
    return state, step, captured, rng


def drive(ctx: Dict[str, Any], seed: int, seconds: float, trace: bool, t_process: float,
          devices, dirs: Dict[str, str], scale: float = 1.0,
          break_step: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of a training cell. ``scale`` and ``break_step`` are for the
    rehearsal and the fault tests under ``tests/``; a benchmark run passes
    neither."""
    import jax

    from hydragnn_tpu.train.compile_plane import compile_metrics
    from hydragnn_tpu.train.loop import train_epoch
    from hydragnn_tpu.utils import tracer as tr

    t_entry = time.perf_counter()
    env = setup(ctx, dirs, scale)
    chips, traffic, stages, records = env.chips, env.traffic, env.stages, env.records
    arch, training, train_loader, depth = env.arch, env.training, env.loader, env.depth

    t = time.perf_counter()
    state, step, captured, rng = first_steps(env, seed, break_step)
    time_to_first_step = step.first_step_at - t_entry
    # ---- warm-up: the loop's every program fetched, queues filled
    train_loader.set_epoch(1)
    state, _, _, rng, _ = train_epoch(
        Feed(train_loader, limit=int(traffic.get("warmup_steps", 8))), step, state, rng,
        prefetch_depth=depth)
    jax.block_until_ready(state)
    stages["compile_and_warmup_s"] = time.perf_counter() - t
    skipped0 = int(jax.device_get(state.skipped_steps))

    # ---- the window
    m0 = compile_metrics()
    regions0 = tr.get_regions()
    calls0 = step.calls
    span = tracing.TraceSpan(os.path.join(dirs["trace"], ctx["cell"]["name"]), seconds) if trace else None
    feeds = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    if span:
        span.start()
    epoch = 2
    while time.perf_counter() < deadline:
        train_loader.set_epoch(epoch)
        feed = Feed(train_loader, deadline=deadline)
        feeds.append(feed)
        state, _, _, rng, _ = train_epoch(feed, step, state, rng, prefetch_depth=depth)
        epoch += 1
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    reduced = span.finish("jit_train_step", chips, ("dataload", "train_step")) if span else None
    m1 = compile_metrics()
    regions1 = tr.get_regions()
    window_s = t1 - t0
    totals = [f.totals() for f in feeds]
    win = {k: sum(t[k] for t in totals) for k in totals[0]}
    if step.calls - calls0 != win["batches"]:
        raise RuntimeError(f"{step.calls - calls0} steps ran on {win['batches']} batches fed")
    skipped = int(jax.device_get(state.skipped_steps)) - skipped0
    device = common.device_stamp(devices, chips)

    # ---- correctness, once the window has closed and the state is freed
    prog = step.readings()
    del state, step, train_loader
    env.loader = env.raw_step = env.model = None
    gc.collect()
    t = time.perf_counter()
    step_records = compare.match_records(captured, records)
    ref_read = compare.reference_readings(
        arch["mpnn_type"], arch, int(arch["input_dim"]), seed, step_records,
        float(training["Optimizer"]["learning_rate"]))
    correct, compared, notes = compare.compare(prog, ref_read, traffic.get("limits", {}))
    stages["reference_s"] = time.perf_counter() - t

    # ---- metrics
    setup_s = t0 - t_process
    ctx = dict(ctx, window={**win, "seconds": window_s, "epochs": len(feeds)}, chips=chips,
               arch=arch, training=training,
               counters={
                   "compile": {k: m1[k] - m0[k] for k in m1},
                   "compile_total": m1,
                   "regions": {k: regions1[k]["total"] - regions0.get(k, {"total": 0.0})["total"]
                               for k in regions1},
                   "time_to_first_step_s": time_to_first_step,
               },
               peaks=common.peaks_for(device["kind"]) if device["platform"] == "tpu" else None,
               trace=reduced)
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(win["batches"]), "failed": int(skipped),
    }
    common.fill_result(result, ctx, device, trace, {
        "train_graphs_per_s_per_chip": win["graphs"] / window_s / chips, "setup_s": setup_s})
    result["info"] = {
        "seed": seed, "window_s": window_s, "steps": win["batches"], "epochs": len(feeds),
        "graphs": win["graphs"], "setup_s": setup_s, "stages": stages,
        "compile_in_window": ctx["counters"]["compile"], "compile_total": m1,
        "notes": notes, "memory_stats": devices[0].memory_stats(),
    }
    result["compared"] = compared
    return result
