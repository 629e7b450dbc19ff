"""Compile plane (train/compile_plane.py): persistent compilation cache,
AOT warm-up of the SpecLadder, retrace sentinel, LapPE disk cache.

The ladder-contract tests drive the REAL builders (make_train_step /
make_eval_step) over a multi-level ladder and assert warm-up covers exactly
the loader's spec shapes — no over-compilation (levels nothing can select
are skipped), no under-compilation (a full epoch + eval pass adds zero
traces) — and that the sentinel catches a deliberately injected weak-type
flip (the PR 3 int32 incident as a caught regression).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.config.lint import lint_config
from hydragnn_tpu.data import (
    GraphLoader,
    MinMax,
    VariablesOfInterest,
    deterministic_graph_dataset,
    extract_variables,
    split_dataset,
)
from hydragnn_tpu.data.graph import SpecLadder
from hydragnn_tpu.models import create_model, init_model
from hydragnn_tpu.train import (
    TrainState,
    make_eval_step,
    make_optimizer,
    make_train_step,
    train_validate_test,
)
from hydragnn_tpu.train import compile_plane as cp


@pytest.fixture(autouse=True)
def _plane_isolation():
    """Scrub sentinel + cache-dir global state around every test (an armed
    sentinel or a stale cache dir must not leak across tests)."""
    yield
    cp.sentinel().reset()
    cp.set_cache_dir(None)


def _base_config(num_buckets=3, extra_training=None):
    cfg = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN",
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 8,
                        "num_headlayers": 1,
                        "dim_headlayers": [8],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["sum_x_x2_x3"],
                "output_index": [0],
                "type": ["graph"],
            },
            "Training": {
                "batch_size": 8,
                "num_epoch": 1,
                "num_pad_buckets": num_buckets,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
                **(extra_training or {}),
            },
        },
        "Dataset": {"node_features": {"dim": [1, 1, 1]}, "graph_features": {"dim": [1]}},
    }
    return cfg


def _tiny_setup(num_buckets=3, batch_size=8, extra_training=None):
    raw = deterministic_graph_dataset(64, seed=97)
    raw = MinMax.fit(raw).apply(raw)
    voi = VariablesOfInterest(
        [0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1]
    )
    ready = [extract_variables(g, voi) for g in raw]
    tr, va, te = split_dataset(ready, 0.7, seed=0)
    config = update_config(_base_config(num_buckets, extra_training), tr, va, te)
    # ONE ladder over all splits (the api.prepare_data contract) so eval
    # reuses the train specs
    spec = SpecLadder.for_dataset(tr + va + te, batch_size, num_buckets=num_buckets)
    loaders = tuple(
        GraphLoader(ds, batch_size, shuffle=sh, seed=0, spec=spec)
        for ds, sh in ((tr, True), (va, False), (te, False))
    )
    model = create_model(config)
    batch = next(iter(loaders[0]))
    variables = init_model(model, batch, seed=0)
    tx = make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)
    return config, model, state, tx, loaders, spec


# ---------------------------------------------------------------------------
# config completion + lint
# ---------------------------------------------------------------------------


def pytest_config_completion_defaults():
    raw = deterministic_graph_dataset(8, seed=97)
    voi = VariablesOfInterest([0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in MinMax.fit(raw).apply(raw)]
    cfg = update_config(_base_config(), ready, ready, ready)
    training = cfg["NeuralNetwork"]["Training"]
    assert training["precompile"] == "background"
    assert training["retrace_policy"] == "warn"
    assert training["compile_cache_dir"] is None
    assert cfg["Dataset"]["lappe_cache"] is True


@pytest.mark.parametrize(
    "key,val",
    [("precompile", "sometimes"), ("retrace_policy", "ignore")],
)
def pytest_config_completion_rejects_bad_values(key, val):
    raw = deterministic_graph_dataset(8, seed=97)
    voi = VariablesOfInterest([0], ["sum_x_x2_x3"], ["graph"], [0], [1, 1, 1], [1])
    ready = [extract_variables(g, voi) for g in MinMax.fit(raw).apply(raw)]
    cfg = _base_config(extra_training={key: val})
    with pytest.raises(ValueError, match=key):
        update_config(cfg, ready, ready, ready)


def pytest_lint_handles_compile_plane_keys():
    cfg = {
        "Dataset": {"lappe_cache": True},
        "NeuralNetwork": {
            "Training": {
                "compile_cache_dir": False,
                "precompile": "background",
                "retrace_policy": "warn",
            }
        },
    }
    statuses = {f.path: f.status for f in lint_config(cfg)}
    for path in (
        "Dataset.lappe_cache",
        "NeuralNetwork.Training.compile_cache_dir",
        "NeuralNetwork.Training.precompile",
        "NeuralNetwork.Training.retrace_policy",
    ):
        assert statuses[path] == "handled", (path, statuses)


# ---------------------------------------------------------------------------
# cache-dir resolution
# ---------------------------------------------------------------------------


def _repo_cache_dir():
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "logs", "xla_cache",
    )


def _tree(path):
    return sorted(
        os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
    )


def pytest_compile_cache_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache is there and nowhere else —
    no jax_compilation_cache_dir update is issued, nothing lands under
    <checkout>/logs."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "1")
    # what jax does with the variable at import time
    jax.config.update("jax_compilation_cache_dir", placed)
    cp._reset_jax_cache_object()
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda key, val: (updates.append(key), real_update(key, val))[1],
    )
    before = _tree(_repo_cache_dir())
    monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE_MIN_SECS", "0")
    try:
        assert cp.compile_cache_dir() == placed
        assert cp.setup_compile_cache({}) == placed
        assert "jax_compilation_cache_dir" not in updates
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)))
        assert _tree(placed), "nothing was cached where the variable points"
        assert _tree(_repo_cache_dir()) == before
    finally:
        monkeypatch.undo()
        cp.set_cache_dir(None)


def pytest_compile_cache_default_is_anchored_on_the_checkout(
        tmp_path, monkeypatch):
    """Unset: <checkout>/logs/xla_cache whatever the working directory; the
    run name cannot move it because the rule never sees it."""
    import inspect

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE", raising=False)
    assert list(inspect.signature(cp.setup_compile_cache).parameters) == [
        "training"
    ]
    try:
        for cwd in (tmp_path, tmp_path / "elsewhere"):
            cwd.mkdir(exist_ok=True)
            monkeypatch.chdir(cwd)
            assert cp.compile_cache_dir() == _repo_cache_dir()
            assert cp.setup_compile_cache({}) == _repo_cache_dir()
            assert cp.cache_dir_active() == _repo_cache_dir()
            assert not os.path.exists(cwd / "logs")
    finally:
        cp.set_cache_dir(None)


def pytest_compile_cache_switches(monkeypatch):
    """The on/off values stay; a path in either channel is an error, not a
    silently ignored placement."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE", raising=False)
    try:
        # config false disables AND deactivates an earlier run's dir
        assert cp.setup_compile_cache({}) == _repo_cache_dir()
        assert cp.setup_compile_cache({"compile_cache_dir": False}) is None
        assert cp.cache_dir_active() is None
        # env "1" forces it back on over the config
        monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "1")
        assert (
            cp.setup_compile_cache({"compile_cache_dir": False})
            == _repo_cache_dir()
        )
        # env off wins over everything
        for off in ("0", "off", "none", "false", ""):
            monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", off)
            assert cp.setup_compile_cache({}) is None
            assert cp.cache_dir_active() is None
        monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "/tmp/some/dir")
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            cp.setup_compile_cache({})
        monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE")
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            cp.setup_compile_cache({"compile_cache_dir": "/tmp/x"})
    finally:
        cp.set_cache_dir(None)


def pytest_plane_degrades_to_off_without_cache_dir():
    cp.set_cache_dir(None)
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=1)
    step = make_train_step(model, tx)
    ev = make_eval_step(model)
    plane = cp.CompilePlane(mode="background", retrace_policy="error")
    plane.launch(step, ev, state, loaders[0], loaders[1], loaders[2])
    rep = plane.finish()
    assert rep["mode"] == "off"
    assert rep["specializations"] == 0
    assert not cp.sentinel().armed


# ---------------------------------------------------------------------------
# ladder contract: warm-up covers exactly the loader's spec shapes, and the
# sentinel catches an injected weak-type flip
# ---------------------------------------------------------------------------


def pytest_ladder_warmup_exact_coverage_and_weak_type_sentinel(tmp_path):
    cp.set_cache_dir(str(tmp_path / "xla_cache"), min_compile_secs=0)
    cp.sentinel().reset()
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=3)
    train_loader, val_loader, test_loader = loaders
    n_levels = len(spec.specs)
    assert n_levels > 1, "test needs a multi-level ladder"
    # the loaders expose one template per selectable level
    assert [s for s, _ in train_loader.spec_template_batches()] == list(spec.specs)

    step = make_train_step(model, tx)
    ev = make_eval_step(model)
    plane = cp.CompilePlane(mode="blocking", retrace_policy="error")
    wrapped = plane.launch(step, ev, state, train_loader, val_loader, test_loader)

    # exact coverage: train levels + deduped eval levels, nothing more
    assert len(plane.jobs) == 2 * n_levels
    assert len(plane.compiled) == 2 * n_levels
    assert plane.errors == []
    counts = cp.sentinel().counts()
    assert counts["train_step"] == n_levels
    assert counts["eval_step"] == n_levels
    assert cp.sentinel().armed

    # a full epoch + eval passes add ZERO traces (no under-compilation):
    # with retrace_policy=error any miss would raise right here
    rng = jax.random.PRNGKey(0)
    for batch in train_loader:
        rng, sub = jax.random.split(rng)
        state, tot, _ = wrapped(state, batch, sub)
    for loader in (val_loader, test_loader):
        for batch in loader:
            ev(state, batch)
    jax.block_until_ready(tot)
    assert cp.sentinel().counts() == counts
    assert cp.sentinel().violations() == []

    # the PR 3 incident as a caught regression: a strong-typed step counter
    # (the weak-type flip) is a NEW specialization — the sentinel raises
    # with the aval diff against the nearest known signature
    flipped = state.replace(step=jnp.int32(0))
    with pytest.raises(cp.RetraceError) as exc:
        wrapped(flipped, next(iter(train_loader)), jax.random.PRNGKey(1))
    assert "weak" in str(exc.value)
    assert ".step" in str(exc.value)
    rep = plane.finish()
    assert rep["violations"] == 1
    assert rep["time_to_first_step"] is not None


def pytest_sentinel_warn_policy_warns_instead_of_raising(tmp_path):
    cp.set_cache_dir(str(tmp_path / "xla_cache"), min_compile_secs=0)
    cp.sentinel().reset()
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=1)
    step = make_train_step(model, tx)
    plane = cp.CompilePlane(mode="blocking", retrace_policy="warn")
    wrapped = plane.launch(step, None, state, loaders[0])
    assert cp.sentinel().armed
    flipped = state.replace(step=jnp.int32(0))
    with pytest.warns(RuntimeWarning, match="retrace sentinel"):
        new_state, tot, _ = wrapped(
            flipped, next(iter(loaders[0])), jax.random.PRNGKey(0)
        )
    assert np.isfinite(float(tot))  # warn policy: training continues
    assert plane.report()["violations"] == 1
    plane.finish()
    # a SECOND plane in the same process baselines the process-global
    # sentinel: the earlier run's violation is not attributed to it
    plane2 = cp.CompilePlane(mode="off", retrace_policy="warn")
    plane2.launch(wrapped, None, state, loaders[0])
    assert plane2.report()["violations"] == 0
    plane2.finish()


def pytest_background_mode_precompiles_and_arms(tmp_path):
    cp.set_cache_dir(str(tmp_path / "xla_cache"), min_compile_secs=0)
    cp.sentinel().reset()
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=1)
    step = make_train_step(model, tx)
    ev = make_eval_step(model)
    plane = cp.CompilePlane(mode="background", retrace_policy="warn")
    plane.launch(step, ev, state, loaders[0], loaders[1], loaders[2])
    assert plane._worker is not None
    plane._worker.join(timeout=120)
    assert not plane._worker.is_alive(), "warm-up worker wedged"
    rep = plane.finish()
    assert rep["precompiled"] == rep["specializations"] == 2
    assert cp.sentinel().counts() == {"train_step": 1, "eval_step": 1}
    # the AOT executables landed in the persistent cache on disk
    assert any(
        f.endswith("-cache") for f in os.listdir(tmp_path / "xla_cache")
    )


def pytest_cache_hits_across_fresh_builders(tmp_path):
    """The restart mechanism in-process: a FRESH step builder (new jit
    object → full retrace) compiled against a warm cache must be served
    from disk (cache_hits delta > 0) instead of recompiling."""
    cp.set_cache_dir(str(tmp_path / "xla_cache"), min_compile_secs=0)
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=1)
    batch = next(iter(loaders[0]))
    step_a = make_train_step(model, tx)
    state, tot, _ = step_a(state, batch, jax.random.PRNGKey(0))
    jax.block_until_ready(tot)
    m0 = cp.compile_metrics()
    # rebuild everything the way a restarted process would
    variables = init_model(model, batch, seed=0)
    state_b = TrainState.create(variables, tx)
    step_b = make_train_step(model, tx)
    state_b, tot, _ = step_b(state_b, batch, jax.random.PRNGKey(0))
    jax.block_until_ready(tot)
    delta = {k: v - m0[k] for k, v in cp.compile_metrics().items()}
    assert delta["cache_hits"] > 0, delta


def pytest_train_validate_test_wires_the_plane(tmp_path, capsys):
    """End-to-end through the loop: background precompile + error-mode
    sentinel over two epochs with val/test — zero violations, report line
    printed (the smokes parse it)."""
    cp.set_cache_dir(str(tmp_path / "xla_cache"), min_compile_secs=0)
    cp.sentinel().reset()
    config, model, state, tx, loaders, spec = _tiny_setup(
        num_buckets=2,
        extra_training={
            "num_epoch": 2,
            "precompile": "background",
            "retrace_policy": "error",
        },
    )
    state, hist = train_validate_test(
        model, state, tx, *loaders, config, verbosity=1
    )
    assert len(hist["train"]) == 2
    err = capsys.readouterr().err
    assert "compile plane: mode=background" in err
    assert "violations=0" in err
    assert not cp.sentinel().armed  # finish() disarmed


# ---------------------------------------------------------------------------
# where a start goes: trace / lower / compile-or-fetch seconds by program
# ---------------------------------------------------------------------------

_PHASE_KEYS = (("trace_s", "trace_s"), ("lower_s", "lower_s"), ("backend_compile_s", "backend_s"))


@pytest.fixture
def phases():
    """Listeners on, tracer enabled and clean; ``phases()`` is the pair of
    differences (counters, programs) since the test began."""
    from hydragnn_tpu.utils import tracer as tr

    cp.install_metrics_listeners()
    tr.reset()
    tr.enable()
    m0, p0 = cp.compile_metrics(), cp.compile_programs()
    yield lambda: (cp._metrics_delta(m0), cp._programs_delta(p0))
    tr.disable()
    tr.reset()


def pytest_compile_metrics_count_the_three_phases_and_stay_flat(phases):
    def fresh_phase_fn(x):
        return jnp.tanh(x) * 3.0

    jax.jit(fresh_phase_fn)(np.ones((3, 5), np.float32))
    delta, _ = phases()
    assert delta["trace_s"] > 0 and delta["lower_s"] > 0 and delta["backend_compile_s"] > 0
    assert delta["programs"] == 1
    # benchmarks/drive_train.py takes m1[k] - m0[k] over EVERY key
    assert all(type(v) in (int, float) for v in cp.compile_metrics().values())


def pytest_compile_programs_rows_by_name_sum_to_the_totals(phases):
    def fresh_by_name_fn(x):
        return jnp.cos(x) + 1.0

    f = jax.jit(fresh_by_name_fn)
    f(np.ones((4,), np.float32))
    _, programs = phases()
    # one key for `fresh_by_name_fn` (trace) and `jit(fresh_by_name_fn)` (lower, backend)
    assert "jit(fresh_by_name_fn)" not in programs
    row = programs["fresh_by_name_fn"]
    assert row["n"] == 1 and min(row["trace_s"], row["lower_s"], row["backend_s"]) > 0
    f(np.ones((6,), np.float32))  # a second shape: the same program once more
    delta, programs = phases()
    assert programs["fresh_by_name_fn"]["n"] == 2
    assert cp.compile_programs()["fresh_by_name_fn"]["t_first"] > 0
    for total, field in _PHASE_KEYS:
        assert sum(r[field] for r in programs.values()) == pytest.approx(delta[total], rel=1e-9)
    assert sum(r["n"] for r in programs.values()) == delta["programs"]


def pytest_nested_traces_count_once_under_the_outer_program(phases):
    """A trace encloses the traces of the jitted functions it calls, each
    with its own entry and duration: only the outermost adds to
    ``trace_s``, to a row and to the region."""
    from hydragnn_tpu.utils import tracer as tr

    inner_a = jax.jit(lambda x: jnp.sin(x) * 2.0)
    inner_b = jax.jit(lambda x, y: x @ y)

    def fresh_outer_fn(x):
        return inner_b(inner_a(x), x)

    jax.jit(fresh_outer_fn)(np.ones((4, 4), np.float32))
    delta, programs = phases()
    assert list(programs) == ["fresh_outer_fn"], programs
    row = programs["fresh_outer_fn"]
    assert row["n"] == 1 and delta["programs"] == 1
    assert row["trace_s"] == pytest.approx(delta["trace_s"], rel=1e-9)
    regions = tr.get_regions()
    assert regions[tr.COMPILE_TRACE]["count"] == 1
    # the region lies inside jax's own span of the outer trace
    assert 0 < regions[tr.COMPILE_TRACE]["total"] <= row["trace_s"] + 1e-3


def pytest_a_program_compiled_inside_a_trace_keeps_its_own_seconds(phases):
    """An eager op inside a trace is a program of its own; its seconds are
    taken out of the enclosing trace's, so no second is counted twice."""
    from hydragnn_tpu.utils import tracer as tr

    def fresh_eager_inside_fn(x):
        with jax.ensure_compile_time_eval():
            c = jnp.arange(7.0) * 2.5  # compiled and run while tracing
        return x + c.sum()

    jax.jit(fresh_eager_inside_fn)(np.ones((7,), np.float32))
    delta, programs = phases()
    outer = programs["fresh_eager_inside_fn"]
    assert len(programs) > 1 and delta["programs"] == len(programs)
    inner_s = sum(r["lower_s"] + r["backend_s"] for k, r in programs.items() if k != "fresh_eager_inside_fn")
    span = tr.get_regions()[tr.COMPILE_TRACE]
    assert span["count"] == 1  # the inner programs' traces are inside it
    assert inner_s > 0 and outer["trace_s"] < span["total"] - 0.5 * inner_s


def pytest_phase_regions_nest_inside_an_open_region(phases):
    from hydragnn_tpu.utils import tracer as tr

    tr.start("outer_of_compile")
    jax.jit(lambda x: x * 5.0 - 1.0)(np.ones((9,), np.float32))
    assert list(tr._state.open) == ["outer_of_compile"]  # still open, nothing else left open
    tr.stop("outer_of_compile")
    regions = tr.get_regions()
    for name in (tr.COMPILE_TRACE, tr.COMPILE_LOWER, tr.COMPILE_BACKEND):
        assert regions[name]["count"] == 1 and regions[name]["total"] > 0, name
    inside = sum(regions[n]["total"] for n in (tr.COMPILE_TRACE, tr.COMPILE_LOWER, tr.COMPILE_BACKEND))
    assert inside <= regions["outer_of_compile"]["total"]


def pytest_phase_annotations_carry_the_program_name(phases, monkeypatch):
    from hydragnn_tpu.utils import tracer as tr

    seen = []

    class Annotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            seen.append((self.name, self.attrs))

        def __exit__(self, *exc):
            seen.append((self.name, "exit"))

    monkeypatch.setattr(tr, "_annotation", Annotation)

    def fresh_annotated_fn(x):
        return x - 2.0

    jax.jit(fresh_annotated_fn)(np.ones((2,), np.float32))
    assert seen == [
        (region, what)
        for region in (tr.COMPILE_TRACE, tr.COMPILE_LOWER, tr.COMPILE_BACKEND)
        for what in ({"fun_name": "fresh_annotated_fn"}, "exit")
    ]


def pytest_tracer_disabled_counters_count_and_no_region_opens(phases):
    from hydragnn_tpu.utils import tracer as tr

    tr.disable()
    jax.jit(lambda x: x / 7.0)(np.ones((11,), np.float32))
    delta, programs = phases()
    assert delta["programs"] == 1 and delta["trace_s"] > 0 and len(programs) == 1
    assert tr.get_regions() == {}


def pytest_compile_on_a_second_thread_is_recorded(phases):
    import threading

    from hydragnn_tpu.utils import tracer as tr

    def fresh_worker_fn(x):
        return x * x + 4.0

    worker = threading.Thread(target=lambda: jax.jit(fresh_worker_fn)(np.ones((13,), np.float32)))
    tr.start("main_thread_region")  # the worker's phases are on its own stack
    worker.start()
    worker.join()
    assert list(tr._state.open) == ["main_thread_region"]
    tr.stop("main_thread_region")
    _, programs = phases()
    assert programs["fresh_worker_fn"]["n"] == 1
    assert tr.get_regions()[tr.COMPILE_BACKEND]["count"] == 1


@pytest.mark.parametrize("broken", ["_close_phase", "_program_key"])
def pytest_listener_that_raises_does_not_fail_the_jit_call(phases, monkeypatch, broken):
    def boom(*a, **k):
        raise RuntimeError("listener bug")

    monkeypatch.setattr(cp, broken, boom)
    out = jax.jit(lambda x: x + 17.0)(np.ones((3,), np.float32))
    np.testing.assert_allclose(np.asarray(out), 18.0)
    monkeypatch.undo()
    jax.jit(lambda x: x + 19.0)(np.ones((3,), np.float32))  # and the next compile is counted
    assert phases()[0]["programs"] >= 1


def _smoke_regex(script):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "run-scripts", script)
    spec = importlib.util.spec_from_file_location(script[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._PLANE_RE


@pytest.mark.parametrize("script", ["compile_smoke.py", "chaos_smoke.py"])
def pytest_report_first_line_still_matches_the_smokes(phases, script):
    """The smokes parse the report's first line; the start's breakdown is a
    line of its own after it."""
    jax.jit(lambda x: x * 23.0)(np.ones((3,), np.float32))
    plane = cp.CompilePlane(mode="off")
    plane._m0, plane._p0 = cp.compile_metrics(), cp.compile_programs()

    def fresh_reported_fn(x):
        return jnp.exp(x) - 29.0

    jax.jit(fresh_reported_fn)(np.ones((3,), np.float32))
    rep = plane.report()
    assert rep["programs"] == 1 and rep["trace_s"] >= 0 and rep["lower_s"] >= 0
    assert [p["name"] for p in rep["top_programs"]] == ["fresh_reported_fn"]
    assert rep["top_programs"][0]["n"] == 1
    first, second = cp.format_report(rep).splitlines()
    matches = list(_smoke_regex(script).finditer(cp.format_report(rep)))
    assert len(matches) == 1 and matches[0].group(0) in first
    assert second.startswith("compile plane start: programs=1 trace_s=")
    assert "top=fresh_reported_fn*1:" in second


def pytest_report_lists_the_five_costliest_programs():
    rows = {f"p{i}": {"n": i, "trace_s": float(i), "lower_s": 0.5, "backend_s": 0.25} for i in range(8)}
    top = cp.top_programs(rows, 5)
    assert [r["name"] for r in top] == ["p7", "p6", "p5", "p4", "p3"]
    assert top[0] == {"name": "p7", "n": 7, "trace_s": 7.0, "lower_s": 0.5, "backend_s": 0.25}


# ---------------------------------------------------------------------------
# stacked-loader template
# ---------------------------------------------------------------------------


def pytest_stacked_loader_template_matches_emitted_batches():
    config, model, state, tx, loaders, spec = _tiny_setup(num_buckets=1)
    tr = loaders[0].graphs
    stacked = GraphLoader(tr, 8, shuffle=False, num_shards=2, spec=spec)
    (tspec, tmpl), = stacked.spec_template_batches()
    real = next(iter(stacked))
    t_shapes = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), tmpl)
    r_shapes = jax.tree_util.tree_map(lambda x: (np.shape(x), str(np.asarray(x).dtype)), real)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(lambda a, b: a == b, t_shapes, r_shapes)
    )


# ---------------------------------------------------------------------------
# LapPE disk cache
# ---------------------------------------------------------------------------


def pytest_lappe_cache_roundtrip(tmp_path, monkeypatch):
    from hydragnn_tpu.data import lappe

    raw = deterministic_graph_dataset(6, seed=3)
    d = str(tmp_path / "lappe")
    first = lappe.add_dataset_pe(raw, 2, cache=d)
    # entries are sharded into <key[:2]>/ subdirectories (flat million-file
    # dirs degrade on common filesystems)
    files = [
        os.path.join(sub, f)
        for sub in os.listdir(d)
        for f in os.listdir(os.path.join(d, sub))
    ]
    assert files and all(f.endswith(".npy") for f in files)
    assert all(os.path.basename(f).startswith(os.path.dirname(f)) for f in files)

    # second pass must be served from disk: eigh is forbidden
    def _boom(*a, **k):
        raise AssertionError("np.linalg.eigh called despite a warm cache")

    monkeypatch.setattr(np.linalg, "eigh", _boom)
    second = lappe.add_dataset_pe(raw, 2, cache=d)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.pe, b.pe)
        np.testing.assert_array_equal(a.rel_pe, b.rel_pe)
    monkeypatch.undo()

    # corrupt entry: silently recomputed, then identical
    victim = os.path.join(d, files[0])
    with open(victim, "wb") as f:
        f.write(b"not an npy")
    third = lappe.add_dataset_pe(raw, 2, cache=d)
    for a, b in zip(first, third):
        np.testing.assert_array_equal(a.pe, b.pe)


def pytest_lappe_cache_key_separates_k_and_topology(tmp_path):
    from hydragnn_tpu.data import lappe

    raw = deterministic_graph_dataset(2, seed=5)
    d = str(tmp_path / "lappe")
    a = lappe.add_dataset_pe(raw, 2, cache=d)
    b = lappe.add_dataset_pe(raw, 3, cache=d)  # different k: new entries
    assert a[0].pe.shape[1] == 2 and b[0].pe.shape[1] == 3


def pytest_lappe_cache_env_knob(tmp_path, monkeypatch):
    from hydragnn_tpu.data import lappe

    monkeypatch.setenv("HYDRAGNN_LAPPE_CACHE", "0")
    assert lappe.resolve_cache_dir(True) is None
    monkeypatch.setenv("HYDRAGNN_LAPPE_CACHE", str(tmp_path / "x"))
    assert lappe.resolve_cache_dir(False) == str(tmp_path / "x")
    monkeypatch.delenv("HYDRAGNN_LAPPE_CACHE")
    assert lappe.resolve_cache_dir(False) is None
    assert lappe.resolve_cache_dir(str(tmp_path / "y")) == str(tmp_path / "y")
    assert lappe.resolve_cache_dir(True) == os.path.join("logs", "lappe_cache")
