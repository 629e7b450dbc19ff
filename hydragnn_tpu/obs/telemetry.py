"""Per-step train telemetry: goodput, padding waste, MFU estimate, memory,
the versioned ``metrics.jsonl`` stream, and the on-demand profiling trigger.

This is the measurement substrate of the next MFU round (ROADMAP item 3 —
you cannot close a padding-waste or H2D-stall gap you never measure) and of
the HPO fleet (item 5 — the scheduler consumes the stream instead of
scraping stdout). Opt-in for training via the top-level ``Telemetry``
config section (docs/CONFIG.md; ``HYDRAGNN_TELEMETRY=1/0`` overrides);
publishing is rank-0-gated like ``MetricsWriter``.

What ``StepTelemetry`` measures, per flush window of ``interval_steps``:

- **step time** (host dispatch-to-dispatch wall time per optimizer step;
  under JAX async dispatch the queue throttles the host to the device
  rate, so the steady-state mean converges to the device step time without
  forcing a per-step sync — the same reasoning the epoch loop uses for its
  loss bookkeeping),
- **goodput**: real (mask-counted) graphs / nodes / edges per second,
- **padding-waste fraction** per axis (graphs / nodes / edges): 1 − real
  slots / padded slots, overall and per pad-bucket label,
- **MFU estimate**: XLA-counted FLOPs of each visited specialization (the
  flops-audit recipe, run-scripts/flops_audit.py — cost analysis of the
  compiled executable, cached by the compile plane's AOT warm-up) divided
  by elapsed time and the chip's peak (``peak_flops``),
- **memory**: per-device peak bytes in use + host RSS.

Sinks: (a) ``logs/<run>/metrics.jsonl`` — one JSON record per window /
epoch / run, every record stamped ``{"v": 1, "ts": ...}``; (b) the
existing ``MetricsWriter`` (TensorBoard + scalars.jsonl); (c) the
process-wide registry (obs/registry.py), scrapeable when an endpoint is
mounted (``Telemetry.http_port`` / ``Serving.http_port``).

On-demand profiling: touching ``logs/<run>/profile_trigger`` (or sending
``SIGUSR1``) makes the next flush start an xprof capture of the following
``profile_steps`` steps into ``logs/<run>/profile_on_demand/`` — the
live-run analog of the epoch-scoped ``Profile`` config section
(utils/profile.py), for when the slowdown is happening *now*.
"""

from __future__ import annotations

import json
import math
import os
import signal
import threading
import time
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..utils import envflags
from .registry import registry

# record shapes + version live in obs/schema.py (one source of truth the
# producers stamp and the consumers — doctor, smokes — validate against)
from .schema import METRICS_SCHEMA_VERSION as SCHEMA_VERSION

# memory gauges are the one flush component with a real price (device
# memory_stats + /proc reads, ~300us) — refresh at most this often rather
# than every window, keeping the per-step telemetry bill in microseconds
_MEMORY_REFRESH_S = 1.0

TELEMETRY_DEFAULTS: Dict[str, Any] = {
    "enabled": False,
    "interval_steps": 10,
    "http_port": None,  # None = no training-side endpoint; 0 = ephemeral
    "http_host": "127.0.0.1",  # bind interface; "0.0.0.0" for off-host
    "mfu": True,
    "jsonl": True,
    "profile_trigger": True,
    "profile_steps": 5,
    # tracing plane (obs/trace.py; docs/OBSERVABILITY.md "Tracing"):
    # spans to logs/<run>/trace.jsonl under head-based sampling —
    # trace_sample is the per-request probability on the serving side,
    # trace_interval_steps the every-Nth-step cadence on the training side
    "trace": False,
    "trace_sample": 0.01,
    "trace_interval_steps": 50,
    # crash flight recorder (obs/flightrec.py): events + spans + registry
    # snapshot dumped on unhandled exception / SIGUSR2 / fatal guard /
    # serve wedge; armed whenever the plane is on (enabled, trace, or
    # numerics)
    "flight_recorder": True,
    # in-graph numerics probes + NaN provenance drill-down (obs/numerics.py;
    # docs/OBSERVABILITY.md "Numerics"): per-layer activation and per-param-
    # group gradient statistics ride the step outputs, and a guarded skip
    # re-runs its held batch through a probe-instrumented diagnostic that
    # names the first non-finite tensor. HYDRAGNN_NUMERICS=1/0 overrides.
    "numerics": False,
    # fleet plane (obs/fleet.py; docs/OBSERVABILITY.md "Fleet"): per-host
    # registry snapshots push to a rank-0 collector each flush window,
    # which publishes across-host hydragnn_fleet_* aggregates and runs the
    # straggler/desync watchdog. HYDRAGNN_FLEET=1/0 overrides "fleet";
    # HYDRAGNN_FLEET_COLLECTOR overrides the collector address.
    "fleet": False,
    "fleet_collector": None,        # "host:port" push target / rank-0 bind port
    "fleet_collector_port": 0,      # rank-0 bind port when no address is given
    "fleet_collector_host": "127.0.0.1",  # rank-0 bind interface
    "fleet_straggler_factor": 2.0,  # step time vs fleet median before flagging
    "fleet_max_step_lag": 200,      # steps of progress skew before fleet_desync
    "fleet_stale_after_s": 30.0,    # heartbeat silence before a host goes stale
    "fleet_collective_budget": None,  # est. collective fraction bound (None=off)
    "fleet_sharding_audit_bytes": 1 << 20,  # replicated-leaf audit threshold
}

# peak dense bf16 FLOP/s by TPU generation (public figures; bench.py
# delegates here so the bench cells and the live MFU gauge share one table)
PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5": 197e12,  # v5e / "TPU v5 lite"
    "v4": 275e12,
}


# the tri-state on/off env parse moved to the shared boundary module in
# r15 (utils/envflags.py, enforced by analysis/env_census.py); re-exported
# here because every plane historically imported it from telemetry
env_flag = envflags.env_flag


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s of ``device_kind``, or None for a device the
    table does not list ("cpu" included): a device without a known peak
    has no utilization, so no MFU is computed or published for it."""
    kind = str(device_kind).lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    return None


def mfu_estimate(
    flops: float, seconds: float, device_kind: str
) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over the chip peak; None
    when the device has no listed peak."""
    peak = peak_flops(device_kind)
    if peak is None:
        return None
    if seconds <= 0:
        return 0.0
    return (float(flops) / float(seconds)) / peak


def resolve_telemetry(config: Dict[str, Any]) -> Dict[str, Any]:
    """Resolve the top-level ``Telemetry`` section to a complete, validated
    settings dict. Unknown keys warn (matching config completion's
    ignore-unknown behavior); ``HYDRAGNN_TELEMETRY`` env overrides
    ``enabled`` (``0``/``off`` forces off, ``1`` forces on)."""
    section = dict((config or {}).get("Telemetry", {}) or {})
    unknown = sorted(set(section) - set(TELEMETRY_DEFAULTS))
    if unknown:
        warnings.warn(
            f"Telemetry config keys {unknown} are not consumed (known keys: "
            f"{sorted(TELEMETRY_DEFAULTS)}); check docs/OBSERVABILITY.md",
            stacklevel=2,
        )
        for k in unknown:
            section.pop(k)
    out = dict(TELEMETRY_DEFAULTS)
    out.update(section)
    env = env_flag("HYDRAGNN_TELEMETRY")
    if env is not None:
        out["enabled"] = env
    env_num = env_flag("HYDRAGNN_NUMERICS")
    if env_num is not None:
        out["numerics"] = env_num
    if not isinstance(out["numerics"], bool):
        raise ValueError(
            f"Telemetry.numerics must be true/false, got {out['numerics']!r}"
        )
    if int(out["interval_steps"]) < 1:
        raise ValueError(
            f"Telemetry.interval_steps must be >= 1, got "
            f"{out['interval_steps']!r}"
        )
    if int(out["profile_steps"]) < 1:
        raise ValueError(
            f"Telemetry.profile_steps must be >= 1, got "
            f"{out['profile_steps']!r}"
        )
    if out["http_port"] is not None and not (
        0 <= int(out["http_port"]) <= 65535
    ):
        raise ValueError(
            "Telemetry.http_port must be null (off), 0 (ephemeral), or a "
            f"port number <= 65535, got {out['http_port']!r}"
        )
    if not isinstance(out["http_host"], str) or not out["http_host"]:
        raise ValueError(
            "Telemetry.http_host must be a non-empty bind address, got "
            f"{out['http_host']!r}"
        )
    if not (0.0 <= float(out["trace_sample"]) <= 1.0):
        raise ValueError(
            "Telemetry.trace_sample must be a probability in [0, 1], got "
            f"{out['trace_sample']!r}"
        )
    if int(out["trace_interval_steps"]) < 1:
        raise ValueError(
            "Telemetry.trace_interval_steps must be >= 1, got "
            f"{out['trace_interval_steps']!r}"
        )
    env_fleet = env_flag("HYDRAGNN_FLEET")
    if env_fleet is not None:
        out["fleet"] = env_fleet
    if not isinstance(out["fleet"], bool):
        raise ValueError(
            f"Telemetry.fleet must be true/false, got {out['fleet']!r}"
        )
    if float(out["fleet_straggler_factor"]) <= 1.0:
        raise ValueError(
            "Telemetry.fleet_straggler_factor must be > 1 (it multiplies "
            f"the fleet median step time), got "
            f"{out['fleet_straggler_factor']!r}"
        )
    if int(out["fleet_max_step_lag"]) < 1:
        raise ValueError(
            "Telemetry.fleet_max_step_lag must be >= 1, got "
            f"{out['fleet_max_step_lag']!r}"
        )
    if float(out["fleet_stale_after_s"]) <= 0:
        raise ValueError(
            "Telemetry.fleet_stale_after_s must be > 0, got "
            f"{out['fleet_stale_after_s']!r}"
        )
    if out["fleet_collective_budget"] is not None and not (
        0.0 < float(out["fleet_collective_budget"]) <= 1.0
    ):
        raise ValueError(
            "Telemetry.fleet_collective_budget must be null (off) or a "
            f"fraction in (0, 1], got {out['fleet_collective_budget']!r}"
        )
    if int(out["fleet_sharding_audit_bytes"]) < 0:
        raise ValueError(
            "Telemetry.fleet_sharding_audit_bytes must be >= 0, got "
            f"{out['fleet_sharding_audit_bytes']!r}"
        )
    if out["fleet_collector"] is not None:
        from .fleet import _valid_collector_addr

        # ONE grammar with the HYDRAGNN_FLEET_COLLECTOR env path
        # (obs/fleet.py applies the same helper, warn-and-degrade there)
        if not _valid_collector_addr(str(out["fleet_collector"])):
            raise ValueError(
                "Telemetry.fleet_collector must be a 'host:port' address, "
                f"got {out['fleet_collector']!r}"
            )
    return out


_GIT_DESCRIBE: Optional[str] = None


def _git_describe() -> str:
    """``git describe --always --dirty`` of the repo this package runs
    from, cached; "unknown" outside a checkout (wheels, containers)."""
    global _GIT_DESCRIBE
    if _GIT_DESCRIBE is not None:
        return _GIT_DESCRIBE
    try:
        import subprocess

        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        # only trust git if the discovered repo IS this package's root: an
        # installed (non-checkout) copy nested under some other project's
        # checkout would otherwise stamp build-info with that repo's
        # describe — a confidently wrong process identity
        top = subprocess.run(
            ["git", "-C", root, "rev-parse", "--show-toplevel"],
            capture_output=True, text=True, timeout=5,
        )
        if top.returncode != 0 or os.path.realpath(
            top.stdout.strip()
        ) != os.path.realpath(root):
            _GIT_DESCRIBE = "unknown"
            return _GIT_DESCRIBE
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            cwd=root, capture_output=True, text=True, timeout=5,
        )
        _GIT_DESCRIBE = (
            out.stdout.strip() if out.returncode == 0 and out.stdout.strip()
            else "unknown"
        )
    except Exception:
        _GIT_DESCRIBE = "unknown"
    return _GIT_DESCRIBE


def publish_build_info() -> None:
    """Publish the ``hydragnn_build_info`` info-gauge (value 1; the facts
    ride the labels, Prometheus *_info convention): jax/jaxlib versions,
    backend, device count, git describe. Idempotent by REGISTRY state, not
    a module flag — a ``registry().reset()`` (second in-process run, tests)
    must not leave later scrapes/dumps permanently without the series (the
    per-process-baseline lesson the PR 5 sentinel report recorded). Every
    scrape and flight-recorder snapshot self-describes once any publisher
    (StepTelemetry, the endpoint, the recorder) has run."""
    have = registry().get("hydragnn_build_info")
    if have is not None and have.samples():
        return
    jax_v = jaxlib_v = backend = "unknown"
    devices = 0
    try:
        import jax

        jax_v = jax.__version__
        backend = jax.default_backend()
        devices = jax.device_count()
    except Exception:
        pass
    try:
        import jaxlib

        jaxlib_v = jaxlib.__version__
    except Exception:
        pass
    try:
        from .fleet import host_identity

        host_i, host_n = host_identity()
        registry().gauge(
            "hydragnn_build_info",
            "Build/runtime identity of this process (value is always 1; "
            "the facts are the labels)",
            labelnames=(
                "jax", "jaxlib", "backend", "devices", "git",
                "process_index", "process_count",
            ),
        ).set(
            1.0,
            jax=jax_v,
            jaxlib=jaxlib_v,
            backend=backend,
            devices=str(devices),
            git=_git_describe(),
            # fleet identity: every scrape self-identifies which host of
            # how many produced it (obs/fleet.py host_identity)
            process_index=str(host_i),
            process_count=str(host_n),
        )
    except Exception:
        pass


def host_memory_bytes() -> float:
    """Resident-set size of this process in bytes (stdlib-only: /proc on
    Linux, ru_maxrss as the portable fallback)."""
    try:
        with open("/proc/self/statm") as fh:
            rss_pages = int(fh.read().split()[1])
        return float(rss_pages * os.sysconf("SC_PAGE_SIZE"))
    except Exception:
        try:
            import resource

            rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            return float(rss_kb) * 1024.0
        except Exception:
            return 0.0


class MetricsStream:
    """The versioned ``metrics.jsonl`` sink: one JSON object per line, every
    record stamped with the schema version and a wall-clock timestamp.
    Rank-0-gated like ``MetricsWriter`` — exactly one stream per run."""

    def __init__(self, run_dir: str, rank0: Optional[bool] = None,
                 fleet: bool = False):
        if rank0 is None:
            try:
                import jax

                rank0 = jax.process_index() == 0
            except Exception:
                rank0 = True
        # fleet identity: every record self-identifies its host, and a
        # non-zero host writing onto a shared filesystem gets its own
        # stream file (two processes appending one JSONL interleave
        # mid-line) — obs/fleet.py host_identity. With the fleet plane ON
        # the per-host stream overrides the historical rank-0 gate: the
        # whole point of the plane is per-host records, and the suffixed
        # filename makes the multi-writer case safe (the Tracer gets the
        # same override in train/loop.py)
        from .fleet import host_identity

        self._host, _ = host_identity()
        fname = (
            "metrics.jsonl" if self._host == 0
            else f"metrics-h{self._host}.jsonl"
        )
        if fleet and self._host > 0:
            rank0 = True
        self.path = os.path.join(run_dir, fname)
        self._fh = None
        self._flushed_at = 0.0
        # HPO trial labeling (hpo.py run_hpo exports HYDRAGNN_TRIAL_ID per
        # trial): every record of a worker's stream carries its trial id,
        # so a parent study can attribute per-trial signals after the fact
        trial = envflags.env_str("HYDRAGNN_TRIAL_ID")
        self._trial: Optional[Any] = None
        if trial is not None:
            try:
                self._trial = int(trial)
            except ValueError:
                self._trial = trial
        if rank0:
            os.makedirs(run_dir, exist_ok=True)
            self._fh = open(self.path, "a")
            # abnormal-exit guarantee: an unhandled exception (or a signal
            # handler exiting via sys.exit) still flushes the buffered tail
            # of the stream — without this a crash truncates the final
            # telemetry window (the 1 Hz flush limiter keeps it in memory)
            import atexit

            atexit.register(self._atexit_flush)

    def _atexit_flush(self) -> None:
        try:
            if self._fh is not None:
                self._fh.flush()
        except Exception:
            pass

    def write(self, kind: str, record: Dict[str, Any]) -> None:
        if self._fh is None:
            return
        line = {"v": SCHEMA_VERSION, "ts": round(time.time(), 3),
                "kind": kind, "host": self._host, **record}
        if self._trial is not None:
            line["trial"] = self._trial
        try:
            self._fh.write(json.dumps(line) + "\n")
            # flush ~1/s, not per record: the file flush is one of the two
            # syscalls that dominate the per-step telemetry bill (the <=2%
            # overhead budget of run-scripts/telemetry_smoke.py);
            # non-window records (epoch/run) are rare and tailed live
            now = time.monotonic()
            if kind != "step_window" or now - self._flushed_at >= 1.0:
                self._fh.flush()
                self._flushed_at = now
        except (OSError, ValueError) as e:
            # a full disk / vanished run dir must not kill the training run
            # (the plane's contract: observability never takes the owner
            # down) — drop the stream and keep going
            self._fh = None
            warnings.warn(
                f"metrics.jsonl stream failed ({e}); telemetry records are "
                "dropped for the rest of this run",
                RuntimeWarning,
                stacklevel=2,
            )

    def close(self) -> None:
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None
        try:
            import atexit

            atexit.unregister(self._atexit_flush)
        except Exception:
            pass


class ProfileTrigger:
    """On-demand xprof capture: arm via a touch file or ``SIGUSR1``; the
    next flush starts ``jax.profiler`` for the following ``steps`` steps.

    The touch file (``<run_dir>/profile_trigger``) is polled at most once
    a second (a ``stat`` costs ~100us on network filesystems — per-window
    polling alone would blow the <=2% overhead budget) and consumed
    (unlinked) when the capture starts; the signal flag is checked every
    step (one attribute read, so SIGUSR1 reacts within a window). Captures
    land in step-stamped subdirectories of ``<run_dir>/profile_on_demand``
    so repeated triggers never clobber."""

    def __init__(self, run_dir: str, steps: int = 5,
                 install_signal: bool = True):
        self.trigger_path = os.path.join(run_dir, "profile_trigger")
        self.out_dir = os.path.join(run_dir, "profile_on_demand")
        self.steps = max(int(steps), 1)
        self.captures = 0
        self._signaled = False
        self._polled_at = 0.0
        self._active_until: Optional[int] = None
        self._prev_handler = None
        if install_signal:
            try:
                self._prev_handler = signal.signal(
                    signal.SIGUSR1, self._on_signal
                )
            except ValueError:
                pass  # not the main thread: touch-file trigger only

    def _on_signal(self, signum, frame) -> None:
        self._signaled = True  # async-signal-safe: only a flag

    def _consume_trigger(self) -> bool:
        if self._signaled:
            self._signaled = False
            return True
        now = time.monotonic()
        if now - self._polled_at < 1.0:
            return False
        self._polled_at = now
        if os.path.exists(self.trigger_path):
            try:
                os.unlink(self.trigger_path)
            except OSError:
                pass
            return True
        return False

    @property
    def active(self) -> bool:
        return self._active_until is not None

    def poll(self, global_step: int) -> None:
        """Flush-cadence check: start a capture if armed."""
        if self.active or not self._consume_trigger():
            return
        try:
            import jax

            out = os.path.join(self.out_dir, f"step{global_step}")
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out, create_perfetto_trace=True)
        except Exception as e:  # an epoch-profile may already be tracing
            warnings.warn(
                f"on-demand profile trigger could not start a capture: {e}",
                RuntimeWarning,
                stacklevel=2,
            )
            return
        self._active_until = int(global_step) + self.steps

    def step(self, global_step: int) -> None:
        """Per-step check: stop the capture once its window is done."""
        if self._active_until is not None and global_step >= self._active_until:
            self._stop()

    def _stop(self) -> None:
        self._active_until = None
        try:
            import jax

            jax.effects_barrier()
            jax.profiler.stop_trace()
            self.captures += 1
        except Exception:
            pass

    def close(self) -> None:
        if self.active:
            self._stop()
        if self._prev_handler is not None:
            try:
                signal.signal(signal.SIGUSR1, self._prev_handler)
            except ValueError:
                pass
            self._prev_handler = None


# whether mask readback should batch both masks into one device_get round
# trip: True on accelerator backends (each readback is a device sync, so
# one round trip beats two) and False on the CPU backend (np.asarray is a
# ~1us zero-copy view there, device_get ~7x slower).
# Resolved once, at the first non-numpy batch.
_BATCH_MASK_READBACK: Optional[bool] = None


def _mask_arrays(nm, em):
    global _BATCH_MASK_READBACK
    if isinstance(nm, np.ndarray):
        return nm, np.asarray(em)
    if _BATCH_MASK_READBACK is None:
        import jax

        _BATCH_MASK_READBACK = jax.default_backend() != "cpu"
    if _BATCH_MASK_READBACK:
        import jax

        return jax.device_get((nm, em))
    return np.asarray(nm), np.asarray(em)


def _batch_census(batch, real_graphs: Optional[int] = None):
    """(real, padded) counts per axis for a (possibly device-stacked)
    ``GraphBatch``. Masks are loader-produced leaves, so reading them never
    waits on device compute (the same contract the epoch loop relies on),
    and the per-shard pad spec is recovered from the trailing axes of a
    stacked batch. Device-resident masks read back per ``_mask_arrays``
    (one batched round trip on accelerators); the graph mask is only
    materialized when the loop did not already pass its count —
    padded counts and stacking come from shapes, which are free."""
    gshape = tuple(batch.graph_mask.shape)
    nm, em = _mask_arrays(batch.node_mask, batch.edge_mask)
    real = {
        "graphs": (
            int(np.asarray(batch.graph_mask).sum())
            if real_graphs is None
            else int(real_graphs)
        ),
        "nodes": int(nm.sum()),
        "edges": int(em.sum()),
    }
    padded = {"graphs": int(np.prod(gshape)), "nodes": int(nm.size),
              "edges": int(em.size)}
    if len(gshape) == 2:  # stacked [num_shards, ...]
        spec_key = (int(nm.shape[1]), int(em.shape[1]))
    else:
        spec_key = (int(nm.size), int(em.size))
    return real, padded, spec_key


class StepTelemetry:
    """Per-step instrumentation layer of the training loop.

    Construct via ``from_config`` (returns None when the ``Telemetry``
    section is absent/disabled — the loop then skips every call site);
    drive with ``on_step(batch, dt, real_graphs)`` from the epoch loop,
    ``on_epoch`` at epoch boundaries, ``absorb_counters`` wherever the
    run-level totals are already host-synced, and ``close`` in the run's
    ``finally``."""

    @staticmethod
    def from_config(
        config: Dict[str, Any],
        log_name: str,
        writer=None,
        log_path: str = "./logs",
    ) -> Optional["StepTelemetry"]:
        settings = resolve_telemetry(config)
        if not settings["enabled"]:
            return None
        return StepTelemetry(settings, log_name, writer=writer,
                             log_path=log_path)

    def __init__(self, settings: Dict[str, Any], log_name: str, writer=None,
                 log_path: str = "./logs"):
        self.settings = settings
        self.log_name = log_name
        self.run_dir = os.path.join(log_path, log_name)
        self.writer = writer
        self.interval = int(settings["interval_steps"])
        self.want_mfu = bool(settings["mfu"])
        self.global_step = 0
        self._flops_for: Optional[Callable[[Tuple[int, int]], Optional[float]]] = None
        self._flops_cache: Dict[Tuple[int, int], Optional[float]] = {}
        # comm accounting source (train/compile_plane.py comm_by_spec):
        # (per-shard padded nodes, edges) -> per-spec collective table
        self._comm_for: Optional[
            Callable[[Tuple[int, int]], Optional[Dict[str, Any]]]
        ] = None
        self._device_kind: Optional[str] = None
        self._mem_refreshed_at = 0.0
        self._numerics_meta: Optional[Dict[str, Any]] = None
        self._g_num: Dict[str, Any] = {}
        self._reset_window()
        publish_build_info()

        # -- sinks / registry ------------------------------------------------
        self.stream = (
            MetricsStream(self.run_dir, fleet=bool(settings.get("fleet")))
            if settings["jsonl"]
            else None
        )
        self.trigger = (
            ProfileTrigger(self.run_dir, steps=int(settings["profile_steps"]))
            if settings["profile_trigger"]
            else None
        )
        self.http = None
        if settings["http_port"] is not None:
            from .prometheus import start_endpoint

            self.http = start_endpoint(
                int(settings["http_port"]),
                ready_fn=lambda: True,
                health_fn=lambda: (True, "training"),
                label=f"telemetry[{log_name}]",
                host=str(settings["http_host"]),
            )
        # fleet plane (obs/fleet.py): rank-0 collector + per-host pusher;
        # None when Telemetry.fleet is off — every call site then pays one
        # `is not None` check, nothing else
        self.fleet = None
        if settings.get("fleet"):
            from .fleet import FleetPlane

            self.fleet = FleetPlane.from_settings(settings, self.run_dir)
        reg = registry()
        self._h_step = reg.histogram(
            "hydragnn_step_time_seconds",
            "Optimizer-step wall time (host dispatch-to-dispatch)",
            labelnames=("phase",),
        )
        self._g_rate = reg.gauge(
            "hydragnn_goodput_per_second",
            "Real (mask-counted) items processed per second over the last "
            "telemetry window",
            labelnames=("axis",),
        )
        self._g_waste = reg.gauge(
            "hydragnn_padding_waste_fraction",
            "1 - real/padded slots over the last telemetry window",
            labelnames=("axis",),
        )
        self._g_waste_bucket = reg.gauge(
            "hydragnn_padding_waste_bucket_fraction",
            "Node-slot padding waste per pad-bucket specialization",
            labelnames=("bucket",),
        )
        self._g_mfu = reg.gauge(
            "hydragnn_mfu_estimate",
            "XLA-counted FLOPs / elapsed / chip peak over the last window",
        )
        self._g_devmem = reg.gauge(
            "hydragnn_device_memory_peak_bytes",
            "Per-device peak bytes in use",
            labelnames=("device",),
        )
        self._g_hostmem = reg.gauge(
            "hydragnn_host_memory_rss_bytes", "Host process resident set size"
        )
        self._g_epoch = reg.gauge(
            "hydragnn_epoch", "Last completed training epoch"
        )
        self._g_loss = reg.gauge(
            "hydragnn_loss", "Per-epoch loss", labelnames=("split",)
        )
        self._g_lr = reg.gauge(
            "hydragnn_learning_rate", "Current injected learning rate"
        )
        self._c_guard = reg.counter(
            "hydragnn_guard_skipped_steps_total",
            "Non-finite steps skipped by the in-graph guard",
        )
        self._c_data_skip = reg.counter(
            "hydragnn_data_skipped_samples_total",
            "Samples dropped by the data-plane validator",
            labelnames=("reason",),
        )
        self._c_retrace = reg.counter(
            "hydragnn_retrace_violations_total",
            "Trace-sentinel violations (silent recompiles) this process",
        )
        self._c_cache_hits = reg.counter(
            "hydragnn_compile_cache_hits_total",
            "Persistent compilation cache hits this process",
        )
        self._c_cache_misses = reg.counter(
            "hydragnn_compile_cache_misses_total",
            "Persistent compilation cache misses this process",
        )
        # materialize the always-expected series so a scrape is schema-
        # complete from the first window (counters appear at 0, not never)
        self._c_guard.set_total(0)
        self._c_retrace.set_total(0)
        self._c_cache_hits.set_total(0)
        self._c_cache_misses.set_total(0)

    def _reset_window(self) -> None:
        self._w_steps = 0
        self._w_dt = 0.0
        self._w_real = {"graphs": 0, "nodes": 0, "edges": 0}
        self._w_padded = {"graphs": 0, "nodes": 0, "edges": 0}
        self._w_buckets: Dict[Tuple[int, int], Dict[str, float]] = {}
        # device-resident numerics stacks ([P,5] act, [G,5] grad per step):
        # held un-synced until flush — by then the producing steps have
        # retired, so the readback copies ready buffers instead of stalling
        # the async dispatch pipeline
        self._w_numerics: List[Tuple[Any, Any]] = []

    # -- wiring --------------------------------------------------------------

    def attach_flops(
        self, flops_for: Callable[[Tuple[int, int]], Optional[float]]
    ) -> None:
        """Install the FLOPs source: (per-shard padded nodes, edges) ->
        XLA-counted FLOPs of that train-step specialization, or None while
        unknown (the compile plane fills its table as warm-up progresses)."""
        self._flops_for = flops_for

    def attach_numerics(self, meta: Dict[str, Any]) -> None:
        """Install the numerics name tables (the step builder's mutable
        meta cell — act_names/grad_names are written at trace time, so they
        are populated by the time the first window flushes)."""
        self._numerics_meta = meta

    def attach_comm(
        self,
        comm_for: Callable[[Tuple[int, int]], Optional[Dict[str, Any]]],
    ) -> None:
        """Install the comm-accounting source: (per-shard padded nodes,
        edges) -> that train-step specialization's collective table
        (train/compile_plane.py ``train_comm_for``), or None while warm-up
        has not walked its HLO yet. The flush windows then carry the
        per-step collective bytes + compute-vs-comm decomposition."""
        self._comm_for = comm_for

    def _flops_of(self, key: Tuple[int, int]) -> Optional[float]:
        got = self._flops_cache.get(key)
        if got is None and self._flops_for is not None:
            got = self._flops_for(key)
            if got is not None:
                self._flops_cache[key] = float(got)
        return got

    # -- per-step path -------------------------------------------------------

    def on_step(self, batch, dt: float, real_graphs: Optional[int] = None,
                numerics: Optional[Dict[str, Any]] = None) -> None:
        """Record one optimizer step: ``dt`` is the host wall time of the
        dispatch (see module docstring for why that converges to device
        step time), ``real_graphs`` the already-computed mask count the
        loop has anyway, ``numerics`` the step's in-graph stat bundle
        (obs/numerics.py) when ``Telemetry.numerics`` is on — held as
        device arrays until flush."""
        self.global_step += 1
        if numerics is not None:
            self._w_numerics.append(
                (numerics.get("act"), numerics.get("grad"))
            )
        self._h_step.observe(dt, phase="train")
        real, padded, key = _batch_census(batch, real_graphs)
        self._w_steps += 1
        self._w_dt += float(dt)
        for axis in ("graphs", "nodes", "edges"):
            self._w_real[axis] += real[axis]
            self._w_padded[axis] += padded[axis]
        b = self._w_buckets.setdefault(
            key, {"steps": 0, "real_nodes": 0, "padded_nodes": 0, "dt": 0.0}
        )
        b["steps"] += 1
        b["real_nodes"] += real["nodes"]
        b["padded_nodes"] += padded["nodes"]
        b["dt"] += float(dt)
        if self.trigger is not None:
            self.trigger.step(self.global_step)
        if self._w_steps >= self.interval:
            self.flush()

    def flush(self) -> None:
        """Close the current window: compute rates/waste/MFU, update the
        registry, emit one ``step_window`` record, poll the profile
        trigger, refresh the memory gauges."""
        if self._w_steps == 0:
            if self.trigger is not None:
                self.trigger.poll(self.global_step)
            return
        dt = max(self._w_dt, 1e-9)
        rates = {a: self._w_real[a] / dt for a in ("graphs", "nodes", "edges")}
        waste = {
            a: 1.0 - self._w_real[a] / max(self._w_padded[a], 1)
            for a in ("graphs", "nodes", "edges")
        }
        for a in ("graphs", "nodes", "edges"):
            self._g_rate.set(rates[a], axis=a)
            self._g_waste.set(waste[a], axis=a)
        buckets = {}
        flops = 0.0
        flops_known = self.want_mfu and self._flops_for is not None
        for key, b in self._w_buckets.items():
            label = f"{key[0]}n/{key[1]}e"
            bucket_waste = 1.0 - b["real_nodes"] / max(b["padded_nodes"], 1)
            self._g_waste_bucket.set(bucket_waste, bucket=label)
            buckets[label] = {
                "steps": b["steps"],
                "padding_waste": round(bucket_waste, 4),
            }
            if flops_known:
                f = self._flops_of(key)
                if f is None:
                    flops_known = False
                else:
                    flops += f * b["steps"]
        mfu = None
        if flops_known and flops > 0:
            mfu = mfu_estimate(flops, dt, self._device_kind_cached())
            if mfu is not None:  # None: no listed peak for this device
                self._g_mfu.set(mfu)
        # comm accounting (compile-plane HLO walk): window-weighted
        # collective bytes per step + the compute-vs-comm decomposition —
        # None until every visited spec's table is harvested
        comm_bytes = comm_frac = None
        if self._comm_for is not None:
            total_bytes = 0.0
            frac_weighted = 0.0
            steps_seen = 0
            known = frac_known = True
            for key, b in self._w_buckets.items():
                c = self._comm_for(key)
                if c is None:
                    known = False
                    break
                total_bytes += float(c.get("bytes_total", 0.0)) * b["steps"]
                frac = c.get("comm_fraction_est")
                if frac is None:
                    # a spec whose FLOPs never harvested has bytes but no
                    # decomposition — publishing a fraction diluted by
                    # zeros would underestimate (and could mask a
                    # fleet_collective_budget breach), so the whole
                    # window's fraction stays unknown instead
                    frac_known = False
                else:
                    frac_weighted += float(frac) * b["steps"]
                steps_seen += b["steps"]
            if known and steps_seen:
                comm_bytes = total_bytes / steps_seen
                if frac_known:
                    comm_frac = frac_weighted / steps_seen
        num_rec = None
        if self._w_numerics and self._numerics_meta is not None:
            try:  # observability never takes the owner down
                num_rec = self._flush_numerics()
            except Exception as e:
                warnings.warn(
                    f"numerics window flush failed ({type(e).__name__}: "
                    f"{e}); this window's layer statistics are dropped",
                    RuntimeWarning,
                    stacklevel=2,
                )
        self._update_memory_gauges()
        if self.stream is not None:
            self.stream.write(
                "step_window",
                {
                    "step": self.global_step,
                    "steps": self._w_steps,
                    "step_time_ms": round(dt / self._w_steps * 1e3, 3),
                    "graphs_per_sec": round(rates["graphs"], 2),
                    "nodes_per_sec": round(rates["nodes"], 1),
                    "edges_per_sec": round(rates["edges"], 1),
                    "padding_waste": round(waste["nodes"], 4),
                    "padding_waste_graphs": round(waste["graphs"], 4),
                    "padding_waste_edges": round(waste["edges"], 4),
                    # 9 decimals: a CPU-backend MFU is ~1e-7 against the
                    # TPU peak table and must not round to a dead 0.0
                    "mfu_est": round(mfu, 9) if mfu is not None else None,
                    # per-device collective bytes each step moves + the
                    # estimated fraction of step time inside collectives
                    # (compile-plane comm accounting; None until harvested)
                    "comm_bytes_per_step": (
                        round(comm_bytes, 1) if comm_bytes is not None
                        else None
                    ),
                    "comm_fraction_est": (
                        round(comm_frac, 6) if comm_frac is not None
                        else None
                    ),
                    "buckets": buckets,
                },
            )
            if num_rec is not None:
                self.stream.write(
                    "numerics", {"step": self.global_step, **num_rec}
                )
        if self.writer is not None:
            self.writer.add_scalars(
                {
                    "telemetry/step_time_ms": dt / self._w_steps * 1e3,
                    "telemetry/graphs_per_sec": rates["graphs"],
                    "telemetry/padding_waste": waste["nodes"],
                    **(
                        {"telemetry/mfu_est": mfu} if mfu is not None else {}
                    ),
                },
                self.global_step,
            )
        if self.trigger is not None:
            self.trigger.poll(self.global_step)
        if self.fleet is not None:
            # the flush IS the heartbeat: registry snapshot + step index +
            # window step time (+ collective fraction) push to the rank-0
            # collector on the fleet plane's background thread
            self.fleet.on_window(
                self.global_step,
                step_time_s=dt / max(self._w_steps, 1),
                comm_fraction_est=comm_frac,
            )
        self._reset_window()

    def _numerics_gauges(self):
        if not self._g_num:
            reg = registry()
            self._g_num = {
                "max_abs": reg.gauge(
                    "hydragnn_numerics_max_abs",
                    "Per-tensor max |x| over the last telemetry window "
                    "(obs/numerics.py probes)",
                    labelnames=("kind", "tensor"),
                ),
                "rms": reg.gauge(
                    "hydragnn_numerics_rms",
                    "Per-tensor rms over the last telemetry window",
                    labelnames=("kind", "tensor"),
                ),
                "underflow": reg.gauge(
                    "hydragnn_numerics_bf16_underflow_fraction",
                    "Fraction of (real) elements below the smallest normal "
                    "bf16 magnitude over the last window",
                    labelnames=("kind", "tensor"),
                ),
                "nonfinite": reg.counter(
                    "hydragnn_numerics_nonfinite_total",
                    "Non-finite elements seen per tensor (windows "
                    "accumulate)",
                    labelnames=("kind", "tensor"),
                ),
            }
        return self._g_num

    @staticmethod
    def _combine_numerics(stacks):
        """Merge per-step [P,5] stacks over the window: max-abs by max,
        the summed moments by sum. Returns a host [P,5] array or None."""
        arrs = [np.asarray(s) for s in stacks if s is not None and s.size]
        if not arrs:
            return None
        stacked = np.stack(arrs)  # [W, P, 5]
        out = np.empty(stacked.shape[1:], np.float64)
        out[:, 0] = stacked[:, :, 0].max(axis=0)
        out[:, 1:] = stacked[:, :, 1:].sum(axis=0)
        return out

    @staticmethod
    def _json_stat(v: float):
        # metrics.jsonl stays strict-JSON parseable: non-finite stats are
        # the SIGNAL here, so encode them as strings instead of bare NaN
        return float(v) if np.isfinite(v) else str(v)

    def _flush_numerics(self) -> Optional[Dict[str, Any]]:
        """Aggregate the window's numerics stacks, publish the per-tensor
        gauges, and return the metrics.jsonl ``numerics`` record body."""
        from .numerics import finalize_stats

        stacks, self._w_numerics = self._w_numerics, []
        acts = self._combine_numerics([a for a, _ in stacks])
        grads = self._combine_numerics([g for _, g in stacks])
        meta = self._numerics_meta or {}
        gauges = self._numerics_gauges()
        record: Dict[str, Any] = {}
        for kind, names, table in (
            ("activation", meta.get("act_names"), acts),
            ("gradient", meta.get("grad_names"), grads),
        ):
            if table is None:
                continue
            section: Dict[str, Any] = {}
            for i in range(table.shape[0]):
                name = (
                    names[i] if names and i < len(names) else f"{kind}{i}"
                )
                st = finalize_stats(table[i])
                gauges["max_abs"].set(st["max_abs"], kind=kind, tensor=name)
                gauges["rms"].set(st["rms"], kind=kind, tensor=name)
                gauges["underflow"].set(
                    st["bf16_underflow"], kind=kind, tensor=name
                )
                if st["nonfinite"] > 0:
                    gauges["nonfinite"].inc(
                        st["nonfinite"], kind=kind, tensor=name
                    )
                section[name] = {
                    "max_abs": self._json_stat(st["max_abs"]),
                    "rms": self._json_stat(st["rms"]),
                    "nonfinite": int(st["nonfinite"]),
                    "bf16_underflow": round(st["bf16_underflow"], 6),
                }
            record["activations" if kind == "activation" else "gradients"] = (
                section
            )
        return record or None

    def _device_kind_cached(self) -> str:
        if self._device_kind is None:
            try:
                import jax

                self._device_kind = jax.devices()[0].device_kind
            except Exception:
                self._device_kind = "unknown"
        return self._device_kind

    def _update_memory_gauges(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._mem_refreshed_at < _MEMORY_REFRESH_S:
            return
        self._mem_refreshed_at = now
        try:
            from ..utils.profile import peak_memory_stats

            for dev, peak in peak_memory_stats().items():
                self._g_devmem.set(peak, device=dev)
        except Exception:
            pass
        self._g_hostmem.set(host_memory_bytes())

    # -- epoch / run path ----------------------------------------------------

    def on_epoch(self, epoch: int, scalars: Dict[str, float],
                 filler: bool = False) -> None:
        """Epoch-boundary record. ``filler=True`` marks rows whose val/test
        entries are carried forward (mid-epoch preemption stop) rather than
        measured — consumers comparing validation curves (HPO early
        stopping) must skip them."""
        self.flush()
        self._g_epoch.set(int(epoch))
        for split, v in scalars.items():
            if split == "lr":
                self._g_lr.set(float(v))
            else:
                self._g_loss.set(float(v), split=split)
        if self.stream is not None:
            self.stream.write(
                "epoch",
                {
                    "epoch": int(epoch),
                    **{k: float(v) for k, v in scalars.items()},
                    "filler": bool(filler),
                },
            )

    def absorb_counters(
        self,
        guard_skipped: Optional[int] = None,
        data_skipped: Optional[Dict[str, int]] = None,
        retrace_violations: Optional[int] = None,
        compile_metrics: Optional[Dict[str, float]] = None,
    ) -> None:
        """Absorb externally maintained monotonic totals (idempotent:
        counters max-merge). Call wherever the owning subsystem is already
        host-synced — the epoch boundary, run end. ``guard_skipped`` must
        be a monotonic EVENT count: the raw TrainState counter can go DOWN
        on a rollback restore, so the loop accumulates positive deltas
        before absorbing (train/loop.py guard_events)."""
        if guard_skipped is not None:
            self._c_guard.set_total(int(guard_skipped))
        for reason, count in (data_skipped or {}).items():
            self._c_data_skip.set_total(int(count), reason=reason)
        if retrace_violations is not None:
            self._c_retrace.set_total(int(retrace_violations))
        if compile_metrics:
            self._c_cache_hits.set_total(int(compile_metrics["cache_hits"]))
            self._c_cache_misses.set_total(
                int(compile_metrics["cache_misses"])
            )

    def run_record(self, info: Dict[str, Any]) -> None:
        if self.stream is not None:
            self.stream.write("run", dict(info))

    def compile_record(self, rep: Dict[str, Any]) -> None:
        """Persist the compile plane's end-of-run report as a
        ``compile_report`` record (obs/schema.py) — the run doctor's
        source for HBM/comm/cache/retrace verdicts; until r14 this
        report was a stderr line only."""
        if self.stream is None:
            return
        body = {
            "mode": str(rep.get("mode", "off")),
            "precompiled": int(rep.get("precompiled") or 0),
            "specializations": int(rep.get("specializations") or 0),
            "cache_hits": int(rep.get("cache_hits") or 0),
            "cache_misses": int(rep.get("cache_misses") or 0),
            "violations": int(rep.get("violations") or 0),
            "time_to_first_step": rep.get("time_to_first_step"),
            "hbm_by_spec": dict(rep.get("hbm_by_spec") or {}),
            "hbm_peak_bytes": rep.get("hbm_peak_bytes"),
            "comm_by_spec": dict(rep.get("comm_by_spec") or {}),
            "comm_bytes_peak": rep.get("comm_bytes_peak"),
            "device_bytes_limit": rep.get("device_bytes_limit"),
            # JSON-safe extras (warmup errors may be exception objects)
            "warmup_errors": [str(e) for e in rep.get("warmup_errors") or []],
            "remat_policy": rep.get("remat_policy"),
        }
        self.stream.write("compile_report", body)

    @property
    def endpoint_port(self) -> Optional[int]:
        return self.http.port if self.http is not None else None

    def close(self) -> None:
        self.flush()
        if self.fleet is not None:
            # final synchronous push: the collector sees this host's
            # terminal step, and this host applies any last broadcast
            self.fleet.close(final_step=self.global_step)
            self.fleet = None
        if self.trigger is not None:
            self.trigger.close()
        if self.http is not None:
            self.http.close()
            self.http = None
        if self.stream is not None:
            self.stream.close()
