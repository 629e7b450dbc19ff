"""Fleet observability plane (docs/OBSERVABILITY.md "Fleet"): host
identity, registry-snapshot push + collector merge semantics (counter
max-merge vs gauge last-write, stale hosts), the straggler/desync
watchdog with coordinated command broadcast, per-host trace stitching,
the communication-accounting HLO census, the sharding-layout inspector,
and the host-disambiguation satellites (flight dumps, build info,
metrics.jsonl, bench-gate topology guard)."""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from hydragnn_tpu.obs import fleet as obs_fleet
from hydragnn_tpu.obs import sharding as obs_sharding
from hydragnn_tpu.obs import trace as obs_trace
from hydragnn_tpu.obs.events import (
    EV_FLEET_DESYNC,
    EV_FLEET_HOST_STALE,
    EV_FLEET_STRAGGLER,
    events,
)
from hydragnn_tpu.obs.registry import MetricsRegistry, registry
from hydragnn_tpu.obs.telemetry import resolve_telemetry
from hydragnn_tpu.train import compile_plane as cp
from hydragnn_tpu.utils import faultinject


def _push(host, step, step_time_s=None, samples=(), ack=0, comm=None):
    return {
        "v": 1, "host": host, "step": step, "step_time_s": step_time_s,
        "comm_fraction_est": comm, "ack": ack, "samples": list(samples),
    }


def _sample(name, kind, value, labels=()):
    return {"n": name, "k": kind, "l": [list(kv) for kv in labels],
            "v": value}


# ---------------------------------------------------------------------------
# host identity


def pytest_host_identity_env_override(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", "3")
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_COUNT", "8")
    assert obs_fleet.host_identity() == (3, 8)
    monkeypatch.delenv("HYDRAGNN_FLEET_HOST_INDEX")
    monkeypatch.delenv("HYDRAGNN_FLEET_HOST_COUNT")
    idx, count = obs_fleet.host_identity()
    assert idx == jax.process_index() and count == jax.process_count()


def pytest_series_key_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c_total", "h", labelnames=("k",)).inc(2, k="a")
    reg.gauge("g", "h").set(1.5)
    reg.histogram("h_seconds", "h").observe(0.1)
    reg.gauge("hydragnn_fleet_min", "h", labelnames=("series",)).set(
        9.0, series="x"
    )
    snap = obs_fleet.registry_snapshot(reg)
    names = {s["n"] for s in snap}
    # counters/gauges verbatim, histograms as _sum/_count, no buckets,
    # and the fleet's own output gauges are excluded (no feedback loop)
    assert {"c_total", "g", "h_seconds_sum", "h_seconds_count"} <= names
    assert not any(n.endswith("_bucket") for n in names)
    assert not any(n.startswith("hydragnn_fleet_") for n in names)
    assert obs_fleet.series_key("c_total", [("k", "a")]) == 'c_total{k="a"}'
    assert obs_fleet.series_key("g", []) == "g"


# ---------------------------------------------------------------------------
# collector merge semantics (satellite: snapshot merge test coverage)


def pytest_collector_counter_max_merge_vs_gauge_last_write():
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(stale_after_s=100.0, reg=reg)
    col.absorb(
        _push(0, 10, samples=[_sample("c_total", "counter", 5.0),
                              _sample("g", "gauge", 1.0)]),
        now=0.0,
    )
    col.absorb(
        _push(1, 9, samples=[_sample("c_total", "counter", 3.0),
                             _sample("g", "gauge", 3.0)]),
        now=1.0,
    )
    g_min = reg.get("hydragnn_fleet_min")
    g_max = reg.get("hydragnn_fleet_max")
    g_mean = reg.get("hydragnn_fleet_mean")
    assert (g_min.value(series="c_total"),
            g_max.value(series="c_total")) == (3.0, 5.0)
    assert g_mean.value(series="g") == 2.0
    # counter max-merge: a lower (replayed/reordered) total cannot move a
    # host's monotonic series down
    col.absorb(
        _push(1, 11, samples=[_sample("c_total", "counter", 2.0)]), now=2.0
    )
    assert g_min.value(series="c_total") == 3.0
    # gauge last-write-wins: the same host's newer sample replaces
    col.absorb(_push(1, 12, samples=[_sample("g", "gauge", 0.5)]), now=3.0)
    assert g_min.value(series="g") == 0.5
    # per-host step + lag gauges
    assert reg.get("hydragnn_fleet_host_step").value(host="1") == 12.0
    assert reg.get("hydragnn_fleet_step_lag").value(host="1") == 0.0
    assert reg.get("hydragnn_fleet_step_lag").value(host="0") == 2.0


def pytest_collector_disappearing_host_goes_stale_not_frozen():
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(stale_after_s=10.0, reg=reg)
    col.absorb(_push(0, 5, samples=[_sample("g", "gauge", 1.0)]), now=0.0)
    col.absorb(_push(1, 5, samples=[_sample("g", "gauge", 9.0)]), now=0.0)
    assert reg.get("hydragnn_fleet_max").value(series="g") == 9.0
    assert reg.get("hydragnn_fleet_hosts").value() == 2.0
    events().clear()
    # host 1 disappears; host 0 keeps pushing new values past the timeout:
    # the aggregate must track host 0, not freeze at host 1's last sample
    col.absorb(_push(0, 8, samples=[_sample("g", "gauge", 2.0)]), now=20.0)
    assert reg.get("hydragnn_fleet_max").value(series="g") == 2.0
    assert reg.get("hydragnn_fleet_min").value(series="g") == 2.0
    assert reg.get("hydragnn_fleet_hosts").value() == 1.0
    assert reg.get("hydragnn_fleet_host_stale").value(host="1") == 1.0
    assert any(
        e["kind"] == EV_FLEET_HOST_STALE and e["host"] == 1
        for e in events().snapshot()
    )
    # a returning host rejoins the aggregates
    col.absorb(_push(1, 9, samples=[_sample("g", "gauge", 9.0),
                                    _sample("only_h1", "gauge", 5.0)]),
               now=21.0)
    assert reg.get("hydragnn_fleet_max").value(series="g") == 9.0
    assert reg.get("hydragnn_fleet_host_stale").value(host="1") == 0.0
    # a series whose ONLY contributor goes stale is retired from the
    # aggregates entirely — a frozen last value scraping forever would be
    # indistinguishable from a live reading
    assert reg.get("hydragnn_fleet_max").value(series="only_h1") == 5.0
    col.absorb(_push(0, 10, samples=[_sample("g", "gauge", 1.0)]), now=40.0)
    import math

    assert math.isnan(reg.get("hydragnn_fleet_max").value(series="only_h1"))
    assert reg.get("hydragnn_fleet_max").value(series="g") == 1.0


# ---------------------------------------------------------------------------
# straggler / desync watchdog


def pytest_watchdog_straggler_and_desync_commands():
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(
        straggler_factor=1.5, max_step_lag=5, stale_after_s=100.0, reg=reg
    )
    col.absorb(_push(0, 10, step_time_s=0.01), now=0.0)
    r = col.absorb(_push(1, 10, step_time_s=0.1), now=0.1)
    cmds = r["commands"]
    assert any(
        c["kind"] == EV_FLEET_STRAGGLER and c["host"] == 1
        and c["cause"] == "step_time" for c in cmds
    ), cmds
    # the firing condition does not re-queue while it persists...
    n_cmds = len(col.pending_commands())
    col.absorb(_push(1, 11, step_time_s=0.1), now=0.2)
    assert len(col.pending_commands()) == n_cmds
    # ...but re-arms once cleared
    col.absorb(_push(1, 12, step_time_s=0.01), now=0.3)
    col.absorb(_push(1, 13, step_time_s=0.1), now=0.4)
    assert len(col.pending_commands()) == n_cmds + 1
    # desync: step progress skewed past the bound flags the laggard
    col.absorb(_push(0, 30, step_time_s=0.01), now=0.5)
    cmds = col.pending_commands()
    assert any(
        c["kind"] == EV_FLEET_DESYNC and c["host"] == 1 for c in cmds
    ), cmds
    # ack filtering: a pusher that acked command N only receives > N
    last = max(c["id"] for c in cmds)
    r = col.absorb(_push(0, 31, step_time_s=0.01, ack=last), now=0.6)
    assert r["commands"] == []
    # restart protection: a command is delivered to each host at most
    # once — a restarted pusher (fresh ack=0) must NOT replay the ring
    # (each stale replay would burn a flight dump)
    r = col.absorb(_push(0, 32, step_time_s=0.01, ack=0), now=0.7)
    assert r["commands"] == []


def pytest_watchdog_two_host_default_factor_detects():
    """The straggler baseline excludes the candidate host — at the
    DEFAULT factor 2.0 a 2-host fleet must still detect (a fleet-median
    baseline reduces the 2-host condition to 0 > fast: never fires)."""
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(stale_after_s=100.0, reg=reg)  # 2.0
    col.absorb(_push(0, 10, step_time_s=0.02), now=0.0)
    r = col.absorb(_push(1, 10, step_time_s=0.2), now=0.1)
    assert any(
        c["kind"] == EV_FLEET_STRAGGLER and c["host"] == 1
        for c in r["commands"]
    ), r["commands"]


def pytest_stale_threshold_scales_with_push_cadence():
    """A host legitimately pushing slower than fleet_stale_after_s (big
    steps, wide flush windows) must not flap stale/rejoined — the
    threshold stretches to ~3x the host's own observed cadence."""
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(stale_after_s=30.0, reg=reg)
    for i, t in enumerate((0.0, 40.0, 80.0, 120.0)):
        col.absorb(_push(1, i, step_time_s=4.0), now=t)
        col.absorb(_push(0, i, step_time_s=4.0), now=t + 1.0)
    # host 1 silent 100 s on a ~40 s cadence: under 3x, not stale
    col.sweep(now=220.0)
    assert reg.get("hydragnn_fleet_host_stale").value(host="1") != 1.0
    # silent well past 3x its cadence: genuinely stale
    col.sweep(now=450.0)
    assert reg.get("hydragnn_fleet_host_stale").value(host="1") == 1.0


def pytest_fleet_plane_rejects_malformed_env_collector(monkeypatch):
    """HYDRAGNN_FLEET_COLLECTOR gets the same host:port grammar check as
    the config key — a malformed value degrades loudly instead of
    binding an unrelated port and pushing at port 80."""
    monkeypatch.setenv("HYDRAGNN_FLEET_COLLECTOR", "rank0host")
    settings = resolve_telemetry({"Telemetry": {"fleet": True}})
    with pytest.warns(RuntimeWarning, match="not 'host:port'"):
        plane = obs_fleet.FleetPlane.from_settings(settings)
    try:
        # degraded to the no-address resolution: loopback ephemeral
        assert plane.endpoint is not None
        assert plane.pusher is not None
        assert "127.0.0.1" in plane.pusher.url
    finally:
        plane.close()


def pytest_host_identity_malformed_env_does_not_raise(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", "$SLURM_PROCID")
    with pytest.warns(RuntimeWarning, match="malformed"):
        idx, count = obs_fleet.host_identity()
    assert (idx, count) == (jax.process_index(), jax.process_count())


def pytest_watchdog_collective_budget():
    reg = MetricsRegistry()
    col = obs_fleet.FleetCollector(
        collective_budget=0.3, stale_after_s=100.0, reg=reg
    )
    col.absorb(_push(0, 5, step_time_s=0.01, comm=0.1), now=0.0)
    r = col.absorb(_push(1, 5, step_time_s=0.01, comm=0.6), now=0.1)
    assert any(
        c["kind"] == EV_FLEET_STRAGGLER and c["host"] == 1
        and c["cause"] == "collective_budget" for c in r["commands"]
    ), r["commands"]
    # a later window with no fresh fraction (None) CLEARS the stored
    # sample — the condition must un-fire rather than evaluate a stale
    # reading forever — and a fresh breach re-fires as a new command
    n = len(col.pending_commands())
    col.absorb(_push(1, 6, step_time_s=0.01, comm=None), now=0.2)
    assert len(col.pending_commands()) == n
    col.absorb(_push(1, 7, step_time_s=0.01, comm=0.7), now=0.3)
    assert len(col.pending_commands()) == n + 1


def pytest_pusher_applies_commands_once_with_event_and_dump(tmp_path):
    from hydragnn_tpu.obs.flightrec import FlightRecorder

    events().clear()
    rec = FlightRecorder(str(tmp_path)).install(signal_hook=False)
    try:
        pusher = obs_fleet.FleetPusher("http://invalid.example/unused", 1, 2)
        try:
            cmd = {"id": 1, "kind": EV_FLEET_STRAGGLER, "host": 1,
                   "step": 40, "cause": "step_time"}
            pusher._apply_commands([cmd])
            pusher._apply_commands([cmd])  # replay must be a no-op
        finally:
            pusher.close()
        evs = [e for e in events().snapshot()
               if e["kind"] == EV_FLEET_STRAGGLER]
        assert len(evs) == 1 and evs[0]["step"] == 40
        dumps = os.listdir(os.path.join(str(tmp_path), "flightrec"))
        # coordinated dump keyed by the fleet step, host-disambiguated
        assert any("fleet_straggler_step40" in d and d.endswith("-h0")
                   for d in dumps), dumps
    finally:
        rec.uninstall()


# ---------------------------------------------------------------------------
# end-to-end: HTTP push round trip (the single-host degenerate case)


def pytest_fleet_plane_loopback_round_trip():
    settings = resolve_telemetry(
        {"Telemetry": {"enabled": True, "fleet": True}}
    )
    plane = obs_fleet.FleetPlane.from_settings(settings)
    assert plane is not None
    try:
        assert plane.collector is not None and plane.pusher is not None
        registry().gauge("fleet_rt_gauge").set(42.0)
        assert plane.pusher.push_now(7, step_time_s=0.01)
        assert plane.collector.hosts()[0]["step"] == 7
        assert (
            registry().get("hydragnn_fleet_max").value(series="fleet_rt_gauge")
            == 42.0
        )
    finally:
        plane.close()


def pytest_fleet_plane_binds_for_offhost_collector_address(monkeypatch):
    """An explicit (non-loopback) collector address implies off-host
    pushers — rank 0 must not bind loopback-only, or every push is
    refused; an explicit loopback address keeps the loopback bind."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    settings = resolve_telemetry(
        {"Telemetry": {"fleet": True,
                       "fleet_collector": f"10.11.12.13:{port}"}}
    )
    plane = obs_fleet.FleetPlane.from_settings(settings)
    try:
        assert plane.endpoint is not None
        assert plane.endpoint._httpd.server_address[0] == "0.0.0.0"
    finally:
        plane.close()
    settings = resolve_telemetry(
        {"Telemetry": {"fleet": True,
                       "fleet_collector": f"127.0.0.1:{port}"}}
    )
    plane = obs_fleet.FleetPlane.from_settings(settings)
    try:
        assert plane.endpoint._httpd.server_address[0] == "127.0.0.1"
    finally:
        plane.close()


def pytest_comm_fraction_unknown_not_diluted(tmp_path):
    """A visited spec with bytes but no FLOPs-backed decomposition must
    yield comm_fraction_est None for the window, not a zero-diluted
    average (a collective-budget breach could hide behind the dilution)."""
    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset
    from hydragnn_tpu.obs.telemetry import StepTelemetry

    settings = resolve_telemetry(
        {"Telemetry": {"enabled": True, "interval_steps": 2,
                       "profile_trigger": False}}
    )
    telem = StepTelemetry(settings, "comm_frac", log_path=str(tmp_path))
    telem.attach_comm(
        lambda key: {"bytes_total": 100.0, "comm_fraction_est": None}
    )
    loader = GraphLoader(
        deterministic_graph_dataset(12, seed=7), 6, seed=0, prefetch=0
    )
    for b in list(loader)[:2]:
        telem.on_step(b, 0.01, real_graphs=1)
    telem.close()
    recs = [
        json.loads(l)
        for l in open(tmp_path / "comm_frac" / "metrics.jsonl")
    ]
    w = [r for r in recs if r["kind"] == "step_window"]
    assert w and w[0]["comm_bytes_per_step"] == 100.0
    assert w[0]["comm_fraction_est"] is None


def pytest_fleet_plane_off_is_none():
    settings = resolve_telemetry({"Telemetry": {"enabled": True}})
    assert settings["fleet"] is False
    assert obs_fleet.FleetPlane.from_settings(settings) is None


def pytest_resolve_telemetry_fleet_validation():
    out = resolve_telemetry({"Telemetry": {"fleet": True}})
    assert out["fleet"] is True and out["fleet_straggler_factor"] == 2.0
    with pytest.raises(ValueError, match="fleet_straggler_factor"):
        resolve_telemetry({"Telemetry": {"fleet_straggler_factor": 0.5}})
    with pytest.raises(ValueError, match="fleet_max_step_lag"):
        resolve_telemetry({"Telemetry": {"fleet_max_step_lag": 0}})
    with pytest.raises(ValueError, match="fleet_collective_budget"):
        resolve_telemetry({"Telemetry": {"fleet_collective_budget": 1.5}})
    with pytest.raises(ValueError, match="fleet_collector"):
        resolve_telemetry({"Telemetry": {"fleet_collector": "no-port"}})
    os.environ["HYDRAGNN_FLEET"] = "1"
    try:
        assert resolve_telemetry({})["fleet"] is True
    finally:
        del os.environ["HYDRAGNN_FLEET"]


# ---------------------------------------------------------------------------
# trace stitching + host-stamped spans


def pytest_trace_host_stamp_and_merge(tmp_path, monkeypatch):
    paths = []
    for host in (0, 1):
        monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", str(host))
        monkeypatch.setenv("HYDRAGNN_FLEET_HOST_COUNT", "2")
        fname = "trace.jsonl" if host == 0 else f"trace-h{host}.jsonl"
        t = obs_trace.Tracer(str(tmp_path), rank0=True, filename=fname)
        t.emit_completed(f"host{host}/step", 100.0 + host, 0.01)
        t.emit_completed(f"host{host}/late", 200.0 - host, 0.01)
        t.close()
        paths.append(os.path.join(str(tmp_path), fname))
    monkeypatch.delenv("HYDRAGNN_FLEET_HOST_INDEX")
    monkeypatch.delenv("HYDRAGNN_FLEET_HOST_COUNT")
    out = os.path.join(str(tmp_path), "merged.jsonl")
    summary = obs_fleet.merge_traces(paths, out)
    assert summary["spans"] == 4 and summary["hosts"] == [0, 1]
    recs = [json.loads(l) for l in open(out)]
    # every span self-identifies, and the stitch is time-ordered
    assert {r["host"] for r in recs} == {0, 1}
    starts = [int(r["startTimeUnixNano"]) for r in recs]
    assert starts == sorted(starts)
    # the CLI wrapper stitches the same way
    out2 = os.path.join(str(tmp_path), "merged2.jsonl")
    assert obs_fleet.main([out2] + paths) == 0
    assert open(out2).read() == open(out).read()


# ---------------------------------------------------------------------------
# communication accounting (compile plane HLO census)


def pytest_collective_census_text_parse():
    hlo = """
  %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %x), replica_groups={}
  %ard = f32[4]{0} all-reduce-done(f32[4]{0} %s)
  %ag = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %y)
  %rs = bf16[1024]{0} reduce-scatter(bf16[2048]{0} %z)
  %cp = u8[16]{0} collective-permute(u8[16]{0} %w)
"""
    c = cp.collective_census(hlo)
    # async start/done pairs count once (the -done carries no new
    # motion), and a -start's (operand, destination) tuple counts only
    # its largest component — the operand entries alias buffers the sync
    # form would not count
    assert c["all-reduce"] == {"count": 1, "bytes": 8 * 16 * 4}
    assert c["all-gather"] == {"count": 1, "bytes": 8 * 4}
    assert c["reduce-scatter"] == {"count": 1, "bytes": 1024 * 2}
    assert c["collective-permute"] == {"count": 1, "bytes": 16}
    s = cp.summarize_comm(c, flops=1e9, device_kind="TPU v5 lite")
    assert s["bytes_total"] == sum(e["bytes"] for e in c.values())
    assert s["ops_total"] == 4
    assert 0.0 < s["comm_fraction_est"] < 1.0
    # no flops -> decomposition unknown, bytes still real
    s2 = cp.summarize_comm(c, flops=None, device_kind="TPU v5 lite")
    assert s2["comm_fraction_est"] is None
    # a device with no listed peak has no compute-time estimate either
    s3 = cp.summarize_comm(c, flops=1e9, device_kind="cpu")
    assert s3["compute_time_est_s"] is None
    assert s3["comm_fraction_est"] is None


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a multi-device mesh")
def pytest_collective_census_real_mesh_program():
    from hydragnn_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()

    def f(x):
        return jax.lax.psum(x, ("branch", "data"))

    sm = jax.shard_map(
        f, mesh=mesh, in_specs=(P(("branch", "data")),), out_specs=P(),
        check_vma=False,
    )
    compiled = jax.jit(sm).lower(
        jnp.zeros((jax.device_count(), 64), jnp.float32)
    ).compile()
    census = cp.collective_census(compiled.as_text())
    assert census.get("all-reduce", {}).get("count", 0) >= 1, census
    assert census["all-reduce"]["bytes"] > 0


def pytest_precompile_analysis_mode_harvests_without_cache(monkeypatch):
    """``precompile: analysis`` runs the (blocking) AOT warm-up with NO
    persistent cache active — the harvests (FLOPs/HBM/comm) are the
    point; blocking/background still degrade to off."""
    monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "off")

    class _Spec:
        n_nodes, n_edges = 8, 16

    class _Loader:
        @staticmethod
        def spec_template_batches():
            return [(_Spec(), jnp.zeros((8, 4)))]

    fn = jax.jit(lambda s, b, r: (s, jnp.sum(b * s), None))
    from hydragnn_tpu.train.compile_plane import setup_compile_cache

    setup_compile_cache({})
    degraded = cp.CompilePlane(mode="background", log_name="analysis_test")
    degraded.launch(fn, None, jnp.float32(2.0), _Loader(),
                    rng=jax.random.PRNGKey(0), skip_eval=True)
    assert degraded.mode == "off" and degraded.jobs == []
    plane = cp.CompilePlane(mode="analysis", log_name="analysis_test")
    plane.launch(fn, None, jnp.float32(2.0), _Loader(),
                 rng=jax.random.PRNGKey(0), skip_eval=True)
    assert plane.mode == "analysis"
    assert plane.compiled and not plane.errors
    assert plane.train_flops_for((8, 16)) is not None
    plane.finish()
    with pytest.raises(ValueError, match="precompile mode"):
        cp.CompilePlane(mode="bogus")


def pytest_ici_bandwidth_table():
    assert cp.ici_bytes_per_s("TPU v5p chip") == 600e9
    assert cp.ici_bytes_per_s("TPU v5e") == 200e9
    assert cp.ici_bytes_per_s("cpu") == 50e9  # conservative fallback


# ---------------------------------------------------------------------------
# sharding-layout inspector


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a multi-device mesh")
def pytest_sharding_inspector_zero_placements():
    from hydragnn_tpu.parallel.mesh import (
        make_mesh,
        shard_optimizer_state,
        shard_params_zero3,
    )

    mesh = make_mesh()
    data_n = mesh.shape["data"]
    big = 64 * data_n

    class _State:
        params = shard_params_zero3(
            {"enc": {"w": jnp.zeros((big, 32))}, "b": jnp.zeros((3,))},
            mesh, min_size=128,
        )
        opt_state = shard_optimizer_state(
            {"mu": jnp.zeros((big, 32)), "nu": jnp.zeros((big, 32))},
            mesh, min_size=128,
        )
        batch_stats = None

    obs_sharding.note_builder(
        "parallel_train_step", dict(mesh.shape), zero3=True
    )
    report = obs_sharding.inspect_state(
        _State(), threshold_bytes=1 << 30, label="zero3", mesh=mesh
    )
    by_path = {e["path"]: e for e in report["sections"]["params"]}
    opt = {e["path"]: e for e in report["sections"]["opt_state"]}
    # zero3: the large param leaf is stored sharded, optimizer moments too
    assert not by_path["params['enc']['w']"]["replicated"]
    assert by_path["params['enc']['w']"]["per_device_bytes"] * data_n == (
        by_path["params['enc']['w']"]["total_bytes"]
    )
    assert by_path["params['b']"]["replicated"]  # under min_size
    assert all(not e["replicated"] for e in opt.values())
    assert report["builder"]["name"] == "parallel_train_step"
    assert report["mesh"]["data"] == data_n
    assert report["audit"] == []  # huge threshold: nothing flagged
    # inject an over-replicated leaf: re-inspect with a tiny threshold —
    # the (small, replicated) bias is now a finding, the sharded leaves
    # are not
    report2 = obs_sharding.inspect_state(
        _State(), threshold_bytes=4, label="zero3_audit", mesh=mesh
    )
    flagged = {f["path"] for f in report2["audit"]}
    assert "params['b']" in flagged
    assert "params['enc']['w']" not in flagged
    # grep-able rendering + event emission via record()
    events().clear()
    obs_sharding.record(report2)
    text = obs_sharding.format_report(report2)
    assert "sharding[zero3_audit]" in text and "AUDIT" in text
    assert "SHARDED" in text and "REPLICATED" in text
    assert any(
        e["kind"] == "sharding_audit" for e in events().snapshot()
    )
    assert "zero3_audit" in obs_sharding.snapshot()
    assert (
        registry().get("hydragnn_sharding_audit_warnings").value(
            label="zero3_audit"
        )
        >= 1
    )


def pytest_sharding_inspector_host_arrays():
    table = obs_sharding.sharding_table(
        {"w": np.zeros((16, 16), np.float32)}, section="params"
    )
    assert table[0]["replicated"] and table[0]["total_bytes"] == 1024
    findings = obs_sharding.audit_table(table, threshold_bytes=1024)
    assert len(findings) == 1 and "params['w']" in findings[0]["path"]
    assert obs_sharding.audit_table(table, threshold_bytes=2048) == []


# ---------------------------------------------------------------------------
# host-disambiguation satellites


def pytest_flight_dumps_from_two_hosts_do_not_collide(tmp_path, monkeypatch):
    """Concurrent-dump coverage: two hosts dumping the SAME reason at the
    same second onto one shared run dir must land side-by-side."""
    from hydragnn_tpu.obs.flightrec import FlightRecorder

    dirs = []
    for host in (0, 1):
        monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", str(host))
        monkeypatch.setenv("HYDRAGNN_FLEET_HOST_COUNT", "2")
        rec = FlightRecorder(str(tmp_path))
        out = rec.dump("fleet_desync_step12")
        assert out is not None
        dirs.append(os.path.basename(out))
    assert len(set(dirs)) == 2
    assert dirs[0].endswith("-h0") and dirs[1].endswith("-h1")
    metas = [
        json.load(open(os.path.join(str(tmp_path), "flightrec", d,
                                    "meta.json")))
        for d in dirs
    ]
    assert [m["host"] for m in metas] == [0, 1]


def pytest_build_info_carries_fleet_identity(monkeypatch):
    from hydragnn_tpu.obs.telemetry import publish_build_info

    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", "2")
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_COUNT", "4")
    # drop only this gauge (publish_build_info is idempotent by registry
    # state; a full reset() would orphan other modules' bound instruments)
    registry()._metrics.pop("hydragnn_build_info", None)
    try:
        publish_build_info()
        bi = registry().get("hydragnn_build_info")
        assert bi is not None
        (_, labels, value) = bi.samples()[0]
        lab = dict(labels)
        assert value == 1.0
        assert lab["process_index"] == "2" and lab["process_count"] == "4"
    finally:
        registry()._metrics.pop("hydragnn_build_info", None)


def pytest_metrics_stream_host_field_and_suffix(tmp_path, monkeypatch):
    from hydragnn_tpu.obs.telemetry import MetricsStream

    s = MetricsStream(str(tmp_path / "h0"), rank0=True)
    s.write("epoch", {"epoch": 0})
    s.close()
    rec = json.loads(open(tmp_path / "h0" / "metrics.jsonl").readline())
    assert rec["host"] == 0
    # a non-zero fleet host writes its own stream file (shared-FS safety)
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_INDEX", "1")
    monkeypatch.setenv("HYDRAGNN_FLEET_HOST_COUNT", "2")
    s1 = MetricsStream(str(tmp_path / "h1"), rank0=True)
    s1.write("epoch", {"epoch": 0})
    s1.close()
    assert not os.path.exists(tmp_path / "h1" / "metrics.jsonl")
    rec1 = json.loads(
        open(tmp_path / "h1" / "metrics-h1.jsonl").readline()
    )
    assert rec1["host"] == 1
    # REAL multi-host fleet: a non-zero JAX rank (rank0=False) still
    # writes its suffixed stream when the fleet plane is on — the
    # per-host stream IS the plane's contract, overriding the historical
    # rank-0 gate; without the fleet flag the gate stands
    s2 = MetricsStream(str(tmp_path / "h2"), rank0=False, fleet=True)
    s2.write("epoch", {"epoch": 0})
    s2.close()
    assert os.path.exists(tmp_path / "h2" / "metrics-h1.jsonl")
    s3 = MetricsStream(str(tmp_path / "h3"), rank0=False, fleet=False)
    s3.write("epoch", {"epoch": 0})
    s3.close()
    assert not os.path.exists(tmp_path / "h3")  # gate held: nothing written


def _bench_gate():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "run-scripts", "bench_gate.py",
    )
    spec = importlib.util.spec_from_file_location("bench_gate_fleet", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pytest_bench_gate_trace_topology_guard(tmp_path):
    bg = _bench_gate()
    t = obs_trace.Tracer(str(tmp_path), rank0=True)
    for dur in (0.010, 0.020):
        t.emit_completed("train/step", 100.0, dur)
    t.close()
    stats = bg.trace_stage_stats(os.path.join(str(tmp_path), "trace.jsonl"))
    assert stats["_meta"]["host_count"] == 1
    # same topology: a blown-up stage fails
    baseline = {
        "train/step": {"p50_ms": 0.1, "p99_ms": 0.1, "count": 2},
        "_meta": {"host_count": 1},
    }
    failures, _ = bg.gate_trace(stats, baseline, threshold=0.5)
    assert failures
    # changed topology: explicit skip note, no failures
    baseline["_meta"] = {"host_count": 2}
    failures, report = bg.gate_trace(stats, baseline, threshold=0.5)
    assert failures == []
    assert any("topology changed" in line for line in report)
    # a legacy baseline without _meta compares as host_count 1
    del baseline["_meta"]
    failures, _ = bg.gate_trace(stats, baseline, threshold=0.5)
    assert failures


# ---------------------------------------------------------------------------
# straggle fault injection


def pytest_maybe_straggle_parses_specs(monkeypatch):
    calls = []
    monkeypatch.setattr(
        "time.sleep", lambda s: calls.append(round(float(s), 3))
    )
    faultinject.maybe_straggle(3)  # unarmed: no-op
    monkeypatch.setenv("HYDRAGNN_FAULT_STRAGGLE", "2:0.01")
    faultinject.maybe_straggle(1)
    faultinject.maybe_straggle(2)
    assert calls == [0.01]
    monkeypatch.setenv("HYDRAGNN_FAULT_STRAGGLE", "4+:0.02")
    faultinject.maybe_straggle(3)
    faultinject.maybe_straggle(4)
    faultinject.maybe_straggle(9)
    assert calls == [0.01, 0.02, 0.02]
    monkeypatch.setenv("HYDRAGNN_FAULT_STRAGGLE", "1+")
    faultinject.maybe_straggle(2)  # bare spec: default 0.05s
    assert calls[-1] == 0.05
    # comma lists work like every sibling indexed fault point (one
    # grammar: utils/faultinject.py _index_armed)
    monkeypatch.setenv("HYDRAGNN_FAULT_STRAGGLE", "1,5+:0.03")
    n = len(calls)
    faultinject.maybe_straggle(1)
    faultinject.maybe_straggle(3)
    faultinject.maybe_straggle(7)
    assert calls[n:] == [0.03, 0.03]


# ---------------------------------------------------------------------------
# telemetry window -> fleet heartbeat integration


def pytest_step_telemetry_window_pushes_heartbeat(tmp_path):
    from hydragnn_tpu.data import GraphLoader, deterministic_graph_dataset
    from hydragnn_tpu.obs.telemetry import StepTelemetry

    settings = resolve_telemetry(
        {"Telemetry": {"enabled": True, "interval_steps": 2,
                       "fleet": True, "jsonl": False,
                       "profile_trigger": False}}
    )
    telem = StepTelemetry(settings, "fleet_hb", log_path=str(tmp_path))
    assert telem.fleet is not None and telem.fleet.collector is not None
    try:
        loader = GraphLoader(
            deterministic_graph_dataset(12, seed=7), 6, seed=0, prefetch=0
        )
        for b in list(loader)[:2] * 2:
            telem.on_step(b, 0.01, real_graphs=1)
    finally:
        # close() runs the final synchronous push (terminal step) before
        # tearing the plane down
        telem.close()
    assert registry().get("hydragnn_fleet_host_step") is not None
    assert registry().get("hydragnn_fleet_host_step").value(host="0") >= 4
