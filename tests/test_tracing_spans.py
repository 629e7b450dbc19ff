"""The span recorder across the training iteration's three threads, and the
stable names inside the compiled step (utils/tracer.py is the vocabulary;
docs/OBSERVABILITY.md "Regions, scopes and kernel names").

(a) two threads on the same region names; (b) a tiny ``train_epoch`` with the
staging thread on: one ``batch_build`` / ``h2d_stage`` / ``dispatch`` /
``train_step`` a step, one ``epoch_restart`` / ``epoch_drain`` an epoch, and
the main thread's regions cover the epoch's wall time; (c) every Pallas entry
point holds a ``pallas_call`` named ``hg_<kernel>`` and both step builders
lower with ``hg_loss`` / ``hg_optimizer`` / ``hg_guard`` in their debug info;
(d) the scopes change no bit of the step's result.
"""

import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.data import (
    GraphLoader,
    MinMax,
    VariablesOfInterest,
    deterministic_graph_dataset,
    extract_variables,
    split_dataset,
)
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.train import TrainState, make_optimizer
from hydragnn_tpu.train import loop
from hydragnn_tpu.utils import tracer as tr


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps, of every
    annotation that was exited, the threads that entered and exited it and
    when."""

    spans = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.entered_on, self.t0 = threading.get_ident(), time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.exited_on, self.t1 = threading.get_ident(), time.perf_counter()
        _Annotation.spans.append(self)


@pytest.fixture
def recorder(monkeypatch):
    tr.reset()
    tr.enable()
    _Annotation.spans = []
    monkeypatch.setattr(tr, "_annotation", _Annotation)
    yield tr
    tr.reset()


def _two_threads(work, n=2):
    """Run ``work(k)`` on ``n`` threads that start together."""
    gate, errors = threading.Barrier(n), []

    def run(k):
        try:
            gate.wait()
            work(k)
        except BaseException as e:  # surfaced in the test's thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


@pytest.mark.parametrize("case", ["totals", "annotations", "reset"])
def pytest_recorder_two_threads(recorder, case):
    rounds = 200
    if case == "totals":
        # same names, interleaved: every stop lands in the shared totals
        def work(k):
            for i in range(rounds):
                recorder.start("outer", batch=i, epoch=k)
                recorder.start("inner")
                recorder.stop("inner")
                recorder.stop("outer")

        _two_threads(work)
        regions = recorder.get_regions()
        assert regions["outer"]["count"] == regions["inner"]["count"] == 2 * rounds
        assert regions["outer"]["total"] >= regions["inner"]["total"] > 0
    elif case == "annotations":
        # an out-of-order stop on one thread unwinds that thread's stack
        # alone: no annotation is ever exited by another thread
        def work(k):
            for i in range(rounds):
                recorder.start("a", batch=i)
                recorder.start("b")
                time.sleep(0)
                recorder.stop("a")  # closes b's annotation, then a's
                recorder.stop("b")

        _two_threads(work)
        spans = _Annotation.spans
        assert len(spans) == 2 * 2 * rounds
        assert all(a.entered_on == a.exited_on for a in spans)
        assert {a.attrs["batch"] for a in spans if a.name == "a"} == set(range(rounds))
        assert recorder._state.anns == []
    else:
        # reset() on this thread while the other holds a span open: the
        # other's span stays open and still records when it closes
        opened, go = threading.Event(), threading.Event()

        def other():
            recorder.start("held")
            opened.set()
            go.wait(5)
            recorder.stop("held")

        t = threading.Thread(target=other)
        t.start()
        opened.wait(5)
        recorder.start("mine")
        recorder.reset()
        assert recorder.get_regions() == {}
        assert recorder._state.open == {}
        recorder.stop("mine")  # closed by reset: a no-op now
        go.set()
        t.join()
        regions = recorder.get_regions()
        assert set(regions) == {"held"} and regions["held"]["count"] == 1
        held = [a for a in _Annotation.spans if a.name == "held"]
        assert len(held) == 1 and held[0].entered_on == held[0].exited_on == t.ident


def _setup(mixed_precision=False, prefetch=0, graphs=64):
    raw = deterministic_graph_dataset(graphs, seed=97)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest([0], ["t"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in raw]
    train, va, te = split_dataset(ready, 0.8, seed=0)
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN",
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 8,
                        "num_headlayers": 2,
                        "dim_headlayers": [8, 8],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["t"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "batch_size": 8,
                "num_epoch": 1,
                "Optimizer": {"type": "AdamW", "learning_rate": 5e-3},
            },
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]}, "graph_features": {"dim": [1]}},
    }
    config = update_config(config, train, va, te)
    loader = GraphLoader(train, 8, seed=0, drop_last=True, prefetch=prefetch)
    model = create_model(config)
    variables = init_model(model, next(iter(loader)), seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    return config, model, tx, variables, loader


def pytest_train_epoch_regions_cover_the_iteration(recorder, monkeypatch):
    _, model, tx, variables, loader = _setup(prefetch=2, graphs=320)
    step = loop.make_train_step(model, tx)
    # the suite runs on 8 virtual devices, where train_epoch stages inline:
    # hand it device_prefetch itself, as a one-chip run gets it
    monkeypatch.setattr(
        loop, "_maybe_device_prefetch",
        lambda it, depth=None, **labels: loop.device_prefetch(it, depth=2, **labels),
    )
    state, rng = TrainState.create(variables, tx), jax.random.PRNGKey(0)
    loader.set_epoch(0)
    state, _, _, rng, _ = loop.train_epoch(loader, step, state, rng)  # compiles
    recorder.reset()
    _Annotation.spans = []
    loader.set_epoch(1)
    t0 = time.perf_counter()
    loop.train_epoch(loader, step, state, rng)
    t1 = time.perf_counter()
    regions = recorder.get_regions()
    steps = len(loader)
    assert steps >= 30
    for name in (tr.BATCH_BUILD, tr.H2D_STAGE, tr.DISPATCH, tr.TRAIN_STEP,
                 tr.RNG_SPLIT, tr.DATALOAD):
        assert regions[name]["count"] == steps, (name, regions[name])
    assert regions[tr.EPOCH_RESTART]["count"] == regions[tr.EPOCH_DRAIN]["count"] == 1
    assert regions[tr.DISPATCH]["total"] <= regions[tr.TRAIN_STEP]["total"]
    # three threads, each with its own regions, labelled batch by batch
    main = threading.get_ident()
    by_thread = {}
    for a in _Annotation.spans:
        by_thread.setdefault(a.entered_on, set()).add(a.name)
    assert sorted(map(sorted, by_thread.values())) == sorted(map(sorted, [
        {tr.BATCH_BUILD}, {tr.H2D_STAGE},
        {tr.EPOCH_RESTART, tr.DATALOAD, tr.RNG_SPLIT, tr.TRAIN_STEP, tr.DISPATCH, tr.EPOCH_DRAIN},
    ]))
    for name in (tr.BATCH_BUILD, tr.H2D_STAGE, tr.DISPATCH):
        batches = [a.attrs["batch"] for a in _Annotation.spans if a.name == name and a.attrs["epoch"] == 1]
        assert batches[:steps] == list(range(steps)), name
    # the main thread's regions cover the epoch: union of their intervals
    covered, end = 0.0, t0
    for a in sorted((a for a in _Annotation.spans if a.entered_on == main), key=lambda a: a.t0):
        covered += max(a.t1, end) - max(a.t0, end)
        end = max(a.t1, end)
    assert 0.95 * (t1 - t0) <= covered <= t1 - t0, (covered, t1 - t0, regions)


def _pallas_names(jaxpr):
    """Names of every ``pallas_call`` in a jaxpr, nested ones included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


def _kernel_call(kernel):
    from hydragnn_tpu.ops.pallas_flash_attention import (
        flash_block_summary,
        flash_self_attention,
    )
    from hydragnn_tpu.ops.pallas_fused_edge import fused_edge_message_sum
    from hydragnn_tpu.ops.pallas_multi_agg import fused_multi_agg
    from hydragnn_tpu.ops.pallas_segment import sorted_segment_sum

    n, e, c = 16, 64, 8
    recv = jnp.sort(jnp.arange(e, dtype=jnp.int32) % n)
    nodes, edges = jnp.ones((n, c)), jnp.ones((e, c))
    qkv = jnp.ones((n, 2, 8))
    return {
        "fused_edge": (tr.HG_FUSED_EDGE, lambda nr, ei: fused_edge_message_sum(
            nr, ei, jnp.ones((c, c)), jnp.zeros((c,)), recv, n, 8, interpret=True), (nodes, edges)),
        "sorted_segment": (tr.HG_SORTED_SEGMENT, lambda m: sorted_segment_sum(
            m, recv, n, 8, interpret=True), (edges,)),
        "multi_agg": (tr.HG_MULTI_AGG, lambda nr, ei: fused_multi_agg(
            nr, ei, None, recv, n, 8, interpret=True)[0], (nodes, edges)),
        "flash_attention": (tr.HG_FLASH_ATTENTION, lambda q: flash_self_attention(
            q, q, q, jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool), 1, n, interpret=True), (qkv,)),
        "flash_block_summary": (tr.HG_FLASH_ATTENTION, lambda q: flash_block_summary(
            q, q, q, jnp.ones((n,), bool), interpret=True)[2], (qkv,)),
    }[kernel]


@pytest.mark.parametrize(
    "kernel",
    ["fused_edge", "sorted_segment", "multi_agg", "flash_attention", "flash_block_summary"],
)
def pytest_kernel_entry_points_are_named(kernel):
    name, fn, args = _kernel_call(kernel)
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]
    # differentiated: the kernel keeps its name and the tangent rule's ops
    # carry the <name>_tangent scope in the lowered program's debug info
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) ** 2)))
    assert _pallas_names(jax.make_jaxpr(grad)(*args).jaxpr) == [name]
    text = grad.lower(*args).as_text(debug_info=True)
    assert f"{name}{tr.TANGENT}" in text and f"{name}/" in text


def _lowered_step(builder):
    if builder == "single":
        _, model, tx, variables, loader = _setup(mixed_precision=True)
        step = loop.make_train_step(model, tx, mixed_precision=True)
        state, batch = TrainState.create(variables, tx), next(iter(loader))
    else:
        from hydragnn_tpu.parallel import (
            Objective, make_mesh2d, make_mesh_train_step, place_state, preset)

        _, model, tx, variables, loader = _setup(mixed_precision=True)
        stacked = GraphLoader(loader.graphs, 8, seed=0, drop_last=True,
                              num_shards=jax.device_count())
        mesh, table = make_mesh2d(), preset("dp")
        step = make_mesh_train_step(
            Objective(model=model, tx=tx, mixed_precision=True), table, mesh)
        state = place_state(TrainState.create(variables, tx), table, mesh)
        batch = next(iter(stacked))
    return step.lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)


@pytest.mark.parametrize("builder", ["single", "mesh"])
def pytest_step_phases_are_named(builder):
    text = _lowered_step(builder)
    for name in (tr.HG_LOSS, tr.HG_OPTIMIZER, tr.HG_GUARD, tr.HG_CAST):
        assert name in text, name
    # JAX's own wrappers split forward from backward under hg_loss
    assert f"{tr.HG_LOSS}/jvp(" in text and f"{tr.HG_LOSS}/transpose(" in text


def pytest_scopes_change_no_bit(monkeypatch):
    _, model, tx, variables, loader = _setup(mixed_precision=True)
    batch, rng = next(iter(loader)), jax.random.PRNGKey(3)

    def one_step():
        step = loop.make_train_step(model, tx, mixed_precision=True)
        host = jax.tree_util.tree_map(np.array, variables)
        state, tot, _ = step(TrainState.create(host, tx), batch, rng)
        return jax.device_get((tot, state.params, state.opt_state))

    named = one_step()
    monkeypatch.setattr(tr, "scope", lambda name: contextlib.nullcontext())
    bare = one_step()
    leaves_named, leaves_bare = (jax.tree_util.tree_leaves(t) for t in (named, bare))
    assert len(leaves_named) == len(leaves_bare) > 4
    for a, b in zip(leaves_named, leaves_bare):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
