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
