"""Unified telemetry plane (docs/OBSERVABILITY.md): registry instrument
semantics, Prometheus text exposition, the /metrics//healthz//readyz HTTP
endpoint, per-step StepTelemetry windows (goodput / padding waste / MFU),
the versioned metrics.jsonl schema, the on-demand profiling trigger, the
GraphServer endpoint contract, and the mid-epoch-preemption filler fix."""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from hydragnn_tpu.obs import (
    MetricsRegistry,
    StepTelemetry,
    TelemetryHTTPServer,
    mfu_estimate,
    peak_flops,
    registry,
    render_text,
    resolve_telemetry,
)
from hydragnn_tpu.obs.telemetry import MetricsStream, ProfileTrigger


# ---------------------------------------------------------------------------
# registry


def pytest_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", labelnames=("k",))
    c.inc(k="a")
    c.inc(2.5, k="a")
    c.inc(k="b")
    assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, k="a")
    # set_total is a max-merge: absorbing an external monotonic total twice
    # (or absorbing an older snapshot) never double counts or regresses
    c.set_total(10, k="a")
    c.set_total(7, k="a")
    assert c.value(k="a") == 10.0

    g = reg.gauge("g")
    g.set(1.5)
    g.set(-2.0)
    assert g.value() == -2.0

    h = reg.histogram("h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(5.55)
    assert snap["0.1"] == 1 and snap["1.0"] == 2 and snap["+Inf"] == 3

    # get-or-create returns the same instrument; a shape mismatch is loud
    assert reg.counter("c_total", labelnames=("k",)) is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("c_total", labelnames=("other",))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad-name")
    with pytest.raises(ValueError, match="do not match"):
        c.inc(k="a", extra="x")
    # bucket bounds are part of a histogram's shape: silently inheriting an
    # earlier declaration's buckets would skew scrape-side percentiles
    assert reg.histogram("h", buckets=(0.1, 1.0)) is h
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h", buckets=(0.5,))


def pytest_render_text_exposition_format():
    reg = MetricsRegistry()
    reg.counter("t_total", "counts things", labelnames=("k",)).inc(
        3, k='va"l\nue'
    )
    reg.gauge("t_gauge").set(0.25)
    reg.histogram("t_lat", buckets=(0.5,)).observe(0.1)
    text = render_text(reg)
    assert "# TYPE t_total counter\n" in text
    assert "# HELP t_total counts things\n" in text
    # label values escaped per the exposition grammar
    assert 't_total{k="va\\"l\\nue"} 3\n' in text
    assert "t_gauge 0.25\n" in text
    assert 't_lat_bucket{le="0.5"} 1\n' in text
    assert 't_lat_bucket{le="+Inf"} 1\n' in text
    assert "t_lat_sum 0.1" in text and "t_lat_count 1" in text


# ---------------------------------------------------------------------------
# endpoint


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def pytest_prometheus_concurrent_scrape_under_mutation():
    """The RLock contract: /metrics scrapes racing registry mutation (new
    instruments registered, counters inc'd, histograms observed, from
    several threads) must all succeed with well-formed exposition text —
    no torn lines, no exceptions."""
    import threading

    reg = MetricsRegistry()
    reg.gauge("scrape_up").set(1)
    srv = TelemetryHTTPServer(reg=reg, port=0)
    stop = threading.Event()
    errors = []

    def mutate(tid):
        i = 0
        while not stop.is_set():
            i += 1
            try:
                reg.counter("scrape_c_total", labelnames=("t",)).inc(t=tid)
                reg.histogram("scrape_lat", buckets=(0.1, 1.0)).observe(
                    0.01 * (i % 7)
                )
                reg.gauge(f"scrape_g_{tid}_{i % 5}").set(i)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                return

    writers = [
        threading.Thread(target=mutate, args=(t,), daemon=True)
        for t in range(3)
    ]
    for w in writers:
        w.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        bodies = []

        def scrape():
            for _ in range(25):
                code, text = _get(base + "/metrics")
                if code != 200:
                    errors.append(AssertionError(f"scrape got {code}"))
                    return
                bodies.append(text)

        scrapers = [
            threading.Thread(target=scrape, daemon=True) for _ in range(4)
        ]
        for s in scrapers:
            s.start()
        for s in scrapers:
            s.join(timeout=30)
        assert not errors, errors
        assert bodies
        for text in bodies:
            assert "scrape_up 1" in text
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                # every sample line is "name[{labels}] value" — a torn
                # write under concurrent mutation would break this shape
                assert len(line.rsplit(" ", 1)) == 2, line
                float(line.rsplit(" ", 1)[1].replace("+Inf", "inf"))
    finally:
        stop.set()
        for w in writers:
            w.join(timeout=5)
        srv.close()
    assert not errors, errors


def pytest_http_endpoint_metrics_health_ready():
    reg = MetricsRegistry()
    reg.gauge("up").set(1)
    ready = {"ok": False}
    healthy = {"ok": True}
    srv = TelemetryHTTPServer(
        reg=reg,
        port=0,
        ready_fn=lambda: ready["ok"],
        health_fn=lambda: (healthy["ok"], "detail-text"),
    )
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, text = _get(base + "/metrics")
        assert code == 200 and "up 1" in text
        # readiness follows the callback — the warm-up flip contract
        assert _get(base + "/readyz")[0] == 503
        ready["ok"] = True
        assert _get(base + "/readyz")[0] == 200
        code, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        healthy["ok"] = False
        code, body = _get(base + "/healthz")
        assert code == 503 and json.loads(body)["detail"] == "detail-text"
        assert _get(base + "/nope")[0] == 404
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# per-step telemetry


def _batches():
    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset

    graphs = deterministic_graph_dataset(24, seed=7)
    loader = GraphLoader(graphs, 6, seed=0, prefetch=0)
    return list(loader)


def pytest_step_telemetry_windows_and_stream(tmp_path):
    settings = resolve_telemetry(
        {"Telemetry": {"enabled": True, "interval_steps": 2,
                       "profile_trigger": False}}
    )
    telem = StepTelemetry(settings, "obs_run", log_path=str(tmp_path))
    telem.attach_flops(lambda key: 1e9)  # 1 GFLOP per step, every spec
    batches = _batches()

    def mfu_samples():
        return [
            l for l in render_text().splitlines()
            if l.startswith("hydragnn_mfu_estimate")
        ]

    # first window on the suite's real device, the CPU: it has no listed
    # peak, so no MFU is computed and no gauge sample is published
    for b in batches[:2]:
        telem.on_step(b, 0.01, real_graphs=int(np.asarray(b.graph_mask).sum()))
    assert mfu_samples() == []
    # second window as a listed TPU generation
    telem._device_kind = "TPU v5 lite"
    for b in batches[2:4]:
        telem.on_step(b, 0.01, real_graphs=int(np.asarray(b.graph_mask).sum()))
    assert len(mfu_samples()) == 1
    telem.on_epoch(0, {"train": 0.5, "val": 0.4, "test": 0.3, "lr": 0.01})
    telem.close()

    records = [
        json.loads(l)
        for l in open(tmp_path / "obs_run" / "metrics.jsonl")
    ]
    assert all(r["v"] == 1 and "ts" in r for r in records)
    windows = [r for r in records if r["kind"] == "step_window"]
    assert len(windows) == 2  # 4 steps / interval 2
    for w, pair in zip(windows, (batches[0:2], batches[2:4])):
        real = sum(int(np.asarray(b.node_mask).sum()) for b in pair)
        padded = sum(b.num_nodes for b in pair)
        assert w["padding_waste"] == pytest.approx(1 - real / padded, abs=1e-4)
        assert w["step_time_ms"] == pytest.approx(10.0, rel=0.01)
        real_g = sum(int(np.asarray(b.graph_mask).sum()) for b in pair)
        assert w["graphs_per_sec"] == pytest.approx(real_g / 0.02, rel=0.01)
    assert windows[0]["mfu_est"] is None
    # 2 steps x 1 GFLOP / 0.02 s / peak — the attach_flops contract
    assert windows[1]["mfu_est"] == pytest.approx(
        mfu_estimate(2e9, 0.02, "TPU v5 lite"), rel=0.01
    )
    epochs = [r for r in records if r["kind"] == "epoch"]
    assert epochs == [
        {**epochs[0]}
    ] and epochs[0]["filler"] is False and epochs[0]["val"] == 0.4

    # the registry carries the same window (process-global registry)
    text = render_text()
    assert "hydragnn_padding_waste_fraction" in text
    assert "hydragnn_mfu_estimate" in text
    assert 'hydragnn_goodput_per_second{axis="graphs"}' in text


def pytest_step_telemetry_absorbs_counters(tmp_path):
    settings = resolve_telemetry({"Telemetry": {"enabled": True,
                                                "profile_trigger": False}})
    telem = StepTelemetry(settings, "obs_absorb", log_path=str(tmp_path))
    telem.absorb_counters(
        guard_skipped=3,
        data_skipped={"nonfinite_features": 2},
        retrace_violations=1,
        compile_metrics={"cache_hits": 5, "cache_misses": 7},
    )
    # idempotent: re-absorbing the same totals must not double count
    telem.absorb_counters(guard_skipped=3, compile_metrics={
        "cache_hits": 5, "cache_misses": 7})
    reg = registry()
    assert reg.get("hydragnn_guard_skipped_steps_total").value() == 3
    assert (
        reg.get("hydragnn_data_skipped_samples_total").value(
            reason="nonfinite_features"
        )
        == 2
    )
    assert reg.get("hydragnn_compile_cache_hits_total").value() == 5
    telem.close()


def pytest_resolve_telemetry_validation():
    assert resolve_telemetry({})["enabled"] is False
    assert resolve_telemetry({"Telemetry": {"enabled": True}})["enabled"]
    with pytest.warns(UserWarning, match="not consumed"):
        out = resolve_telemetry({"Telemetry": {"enabled": True, "typo": 1}})
    assert "typo" not in out
    with pytest.raises(ValueError, match="interval_steps"):
        resolve_telemetry({"Telemetry": {"interval_steps": 0}})
    with pytest.raises(ValueError, match="http_port"):
        resolve_telemetry({"Telemetry": {"http_port": -2}})
    # env override wins in both directions
    os.environ["HYDRAGNN_TELEMETRY"] = "1"
    try:
        assert resolve_telemetry({})["enabled"] is True
        os.environ["HYDRAGNN_TELEMETRY"] = "0"
        assert (
            resolve_telemetry({"Telemetry": {"enabled": True}})["enabled"]
            is False
        )
    finally:
        del os.environ["HYDRAGNN_TELEMETRY"]


def pytest_metrics_stream_rank_gating(tmp_path):
    s = MetricsStream(str(tmp_path / "r0"), rank0=True)
    s.write("epoch", {"epoch": 0})
    s.close()
    assert os.path.exists(tmp_path / "r0" / "metrics.jsonl")
    s1 = MetricsStream(str(tmp_path / "r1"), rank0=False)
    s1.write("epoch", {"epoch": 0})
    s1.close()
    assert not os.path.exists(tmp_path / "r1" / "metrics.jsonl")


def pytest_peak_flops_table():
    assert peak_flops("TPU v5p chip") == 459e12
    assert peak_flops("TPU v6e") == 918e12
    assert peak_flops("TPU v5 lite") == 197e12
    assert mfu_estimate(197e12, 1.0, "TPU v5 lite") == pytest.approx(1.0)
    assert mfu_estimate(1.0, 0.0, "TPU v5 lite") == 0.0
    # no default: a device the table does not list has no peak and no MFU
    for kind in ("cpu", "unknown", "Radeon"):
        assert peak_flops(kind) is None
        assert mfu_estimate(197e12, 1.0, kind) is None


def pytest_profile_trigger_touch_file(tmp_path, monkeypatch):
    """Touching the trigger file makes the next flush capture N steps of
    xprof trace into a step-stamped directory, consuming the file."""
    run_dir = tmp_path / "trig"
    os.makedirs(run_dir)
    trig = ProfileTrigger(str(run_dir), steps=2, install_signal=False)
    trig._polled_at = -10.0  # bypass the 1 Hz poll limiter for the test
    open(run_dir / "profile_trigger", "w").close()
    import jax.numpy as jnp

    trig.poll(global_step=5)
    assert trig.active
    assert not os.path.exists(run_dir / "profile_trigger"), "not consumed"
    _ = (jnp.ones((16, 16)) @ jnp.ones((16, 16))).block_until_ready()
    trig.step(6)
    assert trig.active  # window is 2 steps
    trig.step(7)
    assert not trig.active and trig.captures == 1
    out = run_dir / "profile_on_demand" / "step5"
    found = [f for _, _, fs in os.walk(out) for f in fs]
    assert found, "no trace written by the on-demand capture"
    trig.close()


# ---------------------------------------------------------------------------
# serve endpoint contract (the unit-level twin of telemetry_smoke leg 2)


def pytest_graphserver_endpoint_ready_flip(tmp_path, monkeypatch):
    from hydragnn_tpu.config import update_config, voi_from_config
    from hydragnn_tpu.data import deterministic_graph_dataset, split_dataset
    from hydragnn_tpu.data.graph import SpecLadder
    from hydragnn_tpu.data.pipeline import (
        extract_variables,
        spec_template_batches,
    )
    from hydragnn_tpu.models.create import create_model, init_model
    from hydragnn_tpu.serve import GraphServer, ServeConfig
    from hydragnn_tpu.train.state import InferenceState

    monkeypatch.chdir(tmp_path)
    raw = deterministic_graph_dataset(40, seed=7)
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "obs_serve",
            "format": "synthetic",
            "synthetic": {"number_configurations": 40},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["s"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": {"num_sharedlayers": 1,
                                            "dim_sharedlayers": 8,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [8, 8]}},
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["s"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 1, "batch_size": 8,
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
    }
    tr, va, te = split_dataset(raw, 0.7, seed=0)
    cfg = update_config(cfg, tr, va, te)
    ready = [extract_variables(g, voi_from_config(cfg)) for g in raw]
    ladder = SpecLadder.for_dataset(ready, 8, num_buckets=2)
    model = create_model(cfg)
    tmpl = spec_template_batches(ready, ladder)[0][1]
    state = InferenceState.create(init_model(model, tmpl, seed=0))

    server = GraphServer(
        model, state, ladder, ServeConfig(http_port=0),
        template_graphs=ready,
    ).start()
    try:
        assert server.http_port is not None
        base = f"http://127.0.0.1:{server.http_port}"
        assert server.wait_ready(300), server.failed
        assert _get(base + "/readyz")[0] == 200
        assert _get(base + "/healthz")[0] == 200
        (out,) = server.predict([ready[0]], timeout=60)
        assert isinstance(out, dict)
        code, text = _get(base + "/metrics")
        assert code == 200
        assert 'hydragnn_serve_events_total{event="completed"}' in text
        assert "hydragnn_serve_queue_depth" in text
        assert "hydragnn_serve_batch_latency_seconds_count" in text
        assert "hydragnn_serve_request_latency_seconds_count" in text
        # a draining server must report not-ready (LB removal contract),
        # and /metrics must keep answering THROUGH the drain — operators
        # watch the drain complete on the scrape surface
        import threading

        scrape_results = []

        def scrape_through_drain():
            for _ in range(10):
                scrape_results.append(_get(base + "/metrics"))

        scraper = threading.Thread(target=scrape_through_drain, daemon=True)
        server.initiate_drain()
        scraper.start()
        assert _get(base + "/readyz")[0] == 503
        assert server.drain(timeout=30)
        scraper.join(timeout=30)
        assert len(scrape_results) == 10
        for code, text in scrape_results:
            assert code == 200
            assert "hydragnn_serve_ready 0" in text
        assert server.stats()["http_port"] == server.http_port
    finally:
        server.close()

    # endpoint opt-out for embedded/test servers
    server2 = GraphServer(
        model, state, ladder, ServeConfig(http_port=-1),
        template_graphs=ready,
    ).start()
    try:
        assert server2.http_port is None
    finally:
        server2.close()


# ---------------------------------------------------------------------------
# mid-epoch preemption: history carry-forward + filler marking


def pytest_preemption_filler_carries_last_real_valtest(tmp_path, monkeypatch):
    """A mid-epoch SIGTERM stop used to copy the partial epoch's TRAIN loss
    into hist["val"]/hist["test"], corrupting HPO early-stopping
    comparisons (hpo.py minimizes hist["val"]). The row must carry the
    last REAL val/test values instead, and the emitted stream must mark it
    as filler."""
    from hydragnn_tpu.api import prepare_data
    from hydragnn_tpu.models.create import create_model, init_model
    from hydragnn_tpu.train import (
        TrainState,
        make_optimizer,
        train_validate_test,
    )
    from hydragnn_tpu.utils import preemption

    monkeypatch.chdir(tmp_path)
    cfg = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "filler",
            "format": "synthetic",
            "synthetic": {"number_configurations": 48},
            "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1]},
            "graph_features": {"name": ["s"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "GIN", "radius": 2.0, "max_neighbours": 100,
                "hidden_dim": 8, "num_conv_layers": 2, "task_weights": [1.0],
                "output_heads": {"graph": {"num_sharedlayers": 1,
                                            "dim_sharedlayers": 8,
                                            "num_headlayers": 2,
                                            "dim_headlayers": [8, 8]}},
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["s"], "output_index": [0],
                "type": ["graph"], "denormalize_output": False,
            },
            "Training": {"num_epoch": 4, "batch_size": 8,
                          "precompile": "off",
                          "Optimizer": {"type": "AdamW",
                                         "learning_rate": 0.01}},
        },
        "Telemetry": {"enabled": True, "interval_steps": 100,
                      "profile_trigger": False},
    }
    cfg, (tr_l, va_l, te_l), _ = prepare_data(cfg)
    model = create_model(cfg)
    variables = init_model(model, next(iter(tr_l)), seed=0)
    tx = make_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    state = TrainState.create(variables, tx)

    # "SIGTERM" arrives mid-epoch 1: epoch 0 completes (real val/test),
    # the first step check of epoch 1 then sees the flag
    calls = {"n": 0}
    n_batches = len(tr_l)

    def fake_preempted():
        calls["n"] += 1
        return calls["n"] > n_batches

    monkeypatch.setattr(preemption, "preempted", fake_preempted)
    state, hist = train_validate_test(
        model, state, tx, tr_l, va_l, te_l, cfg, log_name="filler_run"
    )
    assert len(hist["train"]) == 2, hist  # epoch 0 full + epoch 1 partial
    # the filler row CARRIES epoch 0's measured values
    assert hist["val"][1] == hist["val"][0]
    assert hist["test"][1] == hist["test"][0]
    # and the stream marks exactly the preempted row as filler
    records = [
        json.loads(l)
        for l in open(tmp_path / "logs" / "filler_run" / "metrics.jsonl")
    ]
    epochs = {r["epoch"]: r for r in records if r["kind"] == "epoch"}
    assert epochs[0]["filler"] is False
    assert epochs[1]["filler"] is True
    assert epochs[1]["val"] == pytest.approx(hist["val"][0])
