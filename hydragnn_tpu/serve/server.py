"""Fault-tolerant micro-batched graph inference server.

The serving plane the ROADMAP's "millions of users" north star needs, built
on the robustness substrate of the training side (docs/SERVING.md is the
operator doc):

- **admission + validation gate**: a bounded request queue with per-request
  deadlines; every request passes ``data/validate.validate_graph`` plus a
  channel-signature check at the door, so one malformed/NaN request gets a
  typed per-request error (serve/errors.py) instead of poisoning the
  co-batched requests beside it;
- **micro-batcher**: admitted graphs are packed into the run's existing
  ``SpecLadder`` pad buckets (``select_for`` picks the smallest warmed
  level), so the device only ever sees shapes that were AOT-warmed at
  startup — zero-retrace *and* latency-bounded by construction. Readiness
  flips only after warm-up covers the whole ladder; the retrace sentinel
  (train/compile_plane.py) then runs in ``error`` mode as the
  serving-correctness guard;
- **overload behavior**: load shedding with a typed ``SheddedError`` when
  the projected queue wait exceeds the configured p99 SLO, and a
  device-step watchdog that fails a wedged batch's requests with a bounded
  ``WedgedStepError`` and recycles the step runner instead of hanging the
  server;
- **hot checkpoint reload** (serve/reload.py): the run dir's ``latest``
  pointer is watched; candidates restore through the digest-verified
  walk-back chain into a standby state and swap in atomically between
  batches — a corrupt candidate is rejected and the current weights keep
  serving;
- **graceful drain**: ``initiate_drain`` (wired to SIGTERM by
  ``install_sigterm``) stops admissions with a typed ``ServerDrainingError``
  while every in-flight request still completes;
- **observability** (obs/; docs/OBSERVABILITY.md): every lifecycle counter,
  queue depth, readiness, and batch/request latency histograms publish into
  the process metrics registry, scraped at the server's mandatory
  ``/metrics`` + ``/healthz``/``/readyz`` endpoint (``Serving.http_port``,
  default ephemeral loopback) — ``/readyz`` IS the warm-up flip, and goes
  not-ready again the instant a drain starts.

Chaos hooks (exact no-ops unarmed) live in utils/faultinject.py:
``HYDRAGNN_FAULT_SERVE_REQ_NAN`` / ``HYDRAGNN_FAULT_SERVE_WEDGE`` /
``HYDRAGNN_FAULT_SERVE_SLOW_CLIENT``; tests/test_serve.py and
run-scripts/serve_chaos_smoke.py drive every path.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..data.graph import Graph, SpecLadder, batch_graphs
from ..data.validate import R_CHANNELS, describe_reason, validate_graph
from ..obs.events import (
    EV_DEADLINE,
    EV_DRAIN,
    EV_QUEUE_FULL,
    EV_SHED,
    EV_WEDGE,
)
from ..obs.events import emit as _emit_event
from ..obs.trace import STATUS_ERROR, STATUS_OK
from ..utils import faultinject
from .config import ServeConfig
from .errors import (
    DeadlineExceededError,
    InvalidRequestError,
    QueueFullError,
    RequestError,
    ServerClosedError,
    ServerDrainingError,
    SheddedError,
    WedgedStepError,
)

# consumer/waiter wake-up cadence (module-level so tests can pin it)
_TICK_S = 0.02
_JOIN_TIMEOUT_S = 5.0


def _emit_serve_event(kind, severity=None, trace_id=None, **attrs):
    """Typed incident record (obs/events.py), exception-proof: an event
    emission must never fail the request path it describes. ``severity``
    defaults through the per-kind DEFAULT_SEVERITY table (shed/queue-full
    rank warn, wedge error, drain info) so doctor rules and the flight
    recorder's census rank serve incidents without kind-name heuristics."""
    try:
        _emit_event(kind, severity=severity, trace_id=trace_id, **attrs)
    except Exception:
        pass


class PredictionHandle:
    """Client-side handle for one submitted request. ``result()`` blocks for
    the outcome and re-raises the request's typed error; ``error()`` returns
    it as a value instead (the response-object style the chaos smoke and
    ``GraphServer.predict`` use)."""

    __slots__ = (
        "request_id", "deadline", "submitted_at", "done_at", "_event",
        "_result", "_error", "trace",
    )

    def __init__(self, request_id: int, deadline: float):
        self.request_id = request_id
        self.deadline = deadline
        # monotonic admission/completion stamps (perf_counter): done_at is
        # set with the outcome so latency harnesses (BENCH_SERVE) and the
        # serve latency histogram compute per-request latency without a
        # waiter thread per request
        self.submitted_at: float = time.perf_counter()
        self.done_at: Optional[float] = None
        self._event = threading.Event()
        self._result: Optional[Dict[str, np.ndarray]] = None
        self._error: Optional[RequestError] = None
        # head-sampled tracing (obs/trace.py): the open serve/request root
        # span of this request's trace, or None (unsampled/no tracer)
        self.trace = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def error(self, timeout: Optional[float] = None) -> Optional[RequestError]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} has no outcome after {timeout}s"
            )
        return self._error

    def result(self, timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
        err = self.error(timeout)
        if err is not None:
            raise err
        return self._result

    # -- server side --------------------------------------------------------
    def _resolve(self, result: Dict[str, np.ndarray]) -> None:
        self._result = result
        self.done_at = time.perf_counter()
        self._event.set()

    def _fail(self, err: RequestError) -> None:
        err.request_id = self.request_id
        self._error = err
        self.done_at = time.perf_counter()
        self._event.set()


@dataclasses.dataclass
class _Request:
    graph: Graph
    handle: PredictionHandle


def _strip_targets(g: Graph) -> Graph:
    """Serving inputs carry no supervision: drop target tables (and the raw
    graph feature table) so request batches share one pytree structure with
    the warmed templates regardless of where the client got the graph."""
    if g.graph_targets is None and g.node_targets is None and g.graph_y is None:
        return g
    return dataclasses.replace(
        g, graph_targets=None, node_targets=None, graph_y=None
    )


def _channel_signature(g: Graph) -> Tuple[Tuple[str, int], ...]:
    """(field, width) census of the channels that shape a batch pytree. Two
    graphs with equal signatures batch into abstractly identical arrays; a
    mismatch would force a new jit specialization (or crash batching), so it
    is rejected at admission instead."""
    sig: List[Tuple[str, int]] = []
    for name in ("x", "pos", "edge_attr", "edge_shifts", "pe", "rel_pe", "z"):
        v = getattr(g, name)
        if v is None:
            continue
        arr = np.asarray(v)
        sig.append((name, int(arr.shape[1]) if arr.ndim > 1 else 1))
    return tuple(sig)


class _StepTimeout(Exception):
    """Internal: the step runner exceeded its watchdog budget."""


class _StepRunner:
    """One daemon worker executing device steps, replaceable on a wedge: a
    step that blows ``step_timeout_s`` leaves its thread abandoned (daemon —
    it cannot block process exit) and a fresh runner takes over, so the
    serve loop never queues behind a hung XLA program."""

    def __init__(self, name: str = "serve-step"):
        self._in: "queue.Queue" = queue.Queue(maxsize=1)
        self._out: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._main, daemon=True, name=name)
        self._thread.start()

    def _main(self) -> None:
        while True:
            thunk = self._in.get()  # graftlint: disable=threads -- daemon runner's idle loop: blocking for the next thunk IS the design; the wedge watchdog bounds run() on the consumer side and recycles the runner
            if thunk is None:
                return
            try:
                self._out.put(("ok", thunk()))
            except BaseException as e:  # surfaced in run()
                self._out.put(("err", e))

    def run(self, thunk, timeout: float):
        self._in.put(thunk)
        try:
            kind, val = self._out.get(timeout=timeout if timeout > 0 else None)
        except queue.Empty:
            raise _StepTimeout() from None
        if kind == "err":
            raise val
        return val

    def stop(self) -> None:
        try:
            self._in.put_nowait(None)
        except queue.Full:
            pass  # wedged mid-step; the daemon thread is simply abandoned


class GraphServer:
    """Micro-batched ``run_prediction`` with a full request lifecycle.

    Construct directly from (model, state, ladder, template graphs) or via
    ``api.run_server`` (which restores the run's verified checkpoint and
    reuses the data pipeline's ladder). ``state`` only needs a
    ``variables()`` method — ``train.state.InferenceState`` is the intended
    (optimizer-free) carrier, a full ``TrainState`` also works.
    """

    def __init__(
        self,
        model,
        state,
        ladder: SpecLadder,
        serve_config: Optional[ServeConfig] = None,
        *,
        template_graphs: Sequence[Graph],
        mixed_precision: bool = False,
        sort_edges: bool = False,
        log_name: str = "serve",
        checkpoint_label: Optional[str] = None,
        checkpoint_dir: Optional[str] = None,
        restore_template=None,
        tracer=None,
        flight_recorder=None,
    ):
        self.model = model
        self.cfg = serve_config or ServeConfig()
        # tracing plane (obs/trace.py; docs/OBSERVABILITY.md "Tracing"):
        # sampled requests get a serve/request trace covering admit ->
        # queue_wait -> (linked serve/step) -> respond. The server OWNS a
        # tracer/flight recorder handed to it (api.run_server builds them
        # from Telemetry.trace*): close() tears them down.
        self._tracer = tracer
        self._flight = flight_recorder
        self.ladder = ladder
        self.log_name = log_name
        self.mixed_precision = mixed_precision
        self.sort_edges = sort_edges
        self.current_checkpoint = checkpoint_label
        templates = [_strip_targets(g) for g in template_graphs]
        clean = [g for g in templates if validate_graph(g) is None]
        if not clean:
            raise ValueError(
                "GraphServer needs at least one valid template graph to warm "
                "the pad-bucket ladder"
            )
        self._template_graphs = clean
        self._channel_sig = _channel_signature(clean[0])
        # int8 plane wiring (serve/quantize.py): checkpoint_dir locates the
        # pre-quantized snapshot artifacts beside the run's checkpoints;
        # restore_template keeps the PRE-cast state tree — hot reload
        # restores msgpack subtrees into it (a quantized state's structure
        # cannot template a checkpoint restore); _quant_report is the
        # accuracy-gate verdict stats() exposes.
        self._checkpoint_dir = checkpoint_dir
        self.restore_template = (
            restore_template if restore_template is not None else state
        )
        self._quant_report: Optional[Dict[str, Any]] = None
        # cast AFTER the template/ladder fields above: int8 quantization
        # calibrates and gates on the warmed ladder's template batches
        self._state = self._cast_weights(state, entry=checkpoint_label)
        self._worst = ladder.specs[-1]
        # real-graph slots are bounded by the worst spec too (n_graphs
        # includes the +1 dummy slot): a Serving.micro_batch_graphs above
        # the ladder's batch size would make every full batch overflow
        # batch_graphs, failing its co-batched requests
        self._batch_cap = min(
            int(self.cfg.micro_batch_graphs), self._worst.n_graphs - 1
        )

        self._queue: "queue.Queue[_Request]" = queue.Queue(
            maxsize=max(int(self.cfg.max_queue_requests), 0)
        )
        self._holdover: Optional[_Request] = None
        self._form_started: Optional[float] = None
        self._submit_seq = itertools.count()
        self._batch_seq = itertools.count()
        self._inflight_graphs = 0
        self._per_graph_s = float(self.cfg.expected_latency_per_graph_s)
        self._swap_lock = threading.Lock()
        self._pending_state: Optional[Tuple[Any, Optional[str]]] = None
        self._ready = threading.Event()
        self._draining = threading.Event()
        # admissions stay open until this monotonic stamp once _draining is
        # set (Serving.drain_grace_s): /readyz flips not-ready immediately,
        # so a load balancer stops routing BEFORE clients start eating
        # ServerDrainingError. 0.0 default = reject the instant drain
        # starts (grace 0 keeps pre-fleet behavior exactly).
        self._drain_admit_deadline = 0.0
        self._drained = threading.Event()
        self._stop = threading.Event()
        self._closed = False
        self._armed = False
        # stats() reports violations as a delta against this launch-time
        # baseline of the process-global sentinel — a warn-policy training
        # run earlier in the process must not bleed into this server's count
        from ..train.compile_plane import sentinel

        self._violations_at_launch = len(sentinel().violations())
        self.failed: Optional[Exception] = None
        self.warmup_compiled: List[Tuple[str, float]] = []
        self._stats_lock = threading.Lock()
        self._stats: Dict[str, int] = {
            "submitted": 0,
            "admitted": 0,
            "completed": 0,
            "rejected": 0,
            "shed": 0,
            "queue_full": 0,
            "deadline_expired": 0,
            "wedged_batches": 0,
            "failed_batches": 0,
            "batches": 0,
            "reloads": 0,
        }
        # telemetry plane (obs/): every counter _bump touches is mirrored
        # into the process registry, plus queue depth / readiness gauges and
        # batch / per-request latency histograms — the scrapeable SLO
        # surface behind /metrics (Serving.http_port). Series materialize
        # at 0 so a scrape is schema-complete before the first request.
        # Scope: these are PROCESS metrics (one serving instance per
        # process is the run_server deployment model) — counters span every
        # instance's lifetime, gauges are last-writer; construction uses
        # set_default so building a standby server never clobbers a live
        # one's readiness.
        from ..obs.registry import registry as _obs_registry

        _reg = _obs_registry()
        self._m_events = _reg.counter(
            "hydragnn_serve_events_total",
            "Serving request-lifecycle event counts (GraphServer.stats keys)",
            labelnames=("event",),
        )
        for key in self._stats:
            self._m_events.inc(0, event=key)
        self._m_queue = _reg.gauge(
            "hydragnn_serve_queue_depth",
            "Admitted requests waiting in the micro-batcher queue",
        )
        self._m_ready = _reg.gauge(
            "hydragnn_serve_ready",
            "1 once the full ladder is warmed and admissions are open",
        )
        self._m_batch_lat = _reg.histogram(
            "hydragnn_serve_batch_latency_seconds",
            "Device micro-batch service time (form -> outputs on host)",
        )
        self._m_req_lat = _reg.histogram(
            "hydragnn_serve_request_latency_seconds",
            "Per-request latency, admission to delivered outcome (outcome="
            "error covers deadline/wedge/batch failures — without it the "
            "p99 would be survivorship-biased exactly under overload)",
            labelnames=("outcome",),
        )
        self._m_queue.set_default(0)
        self._m_ready.set_default(0)
        self._predict_fn = self._build_predict_fn()
        self._runner: Optional[_StepRunner] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._warm_thread: Optional[threading.Thread] = None
        self._watcher = None  # serve/reload.CheckpointWatcher
        self._prev_sigterm = None
        self._http = None  # obs/prometheus.TelemetryHTTPServer

    # -- construction helpers ------------------------------------------------

    def _build_predict_fn(self):
        import jax

        from ..train.compile_plane import note_trace
        from ..train.loop import mp_cast_eval, mp_keep

        model = self.model
        quantized = self.cfg.weights_dtype == "int8"
        w8a8 = bool(
            quantized
            and self.cfg.quantization is not None
            and self.cfg.quantization.mode == "w8a8"
        )
        # int8 states define their own precision story: mp_cast_eval would
        # cast the fp32 dequant scales (and the quant collection) to bf16,
        # silently shifting exactly the values the accuracy gate certified
        mixed_precision = self.mixed_precision and not quantized
        if w8a8:
            from flax import linen as nn

            from .quantize import w8a8_interceptor

        @jax.jit
        def predict_step(state, batch):
            # retrace sentinel census: runs once per jit trace
            note_trace("serve_predict", (state, batch))
            variables = state.variables()
            if mixed_precision:
                variables, batch = mp_cast_eval(variables, batch, False, mp_keep(model))
            if w8a8:
                with nn.intercept_methods(w8a8_interceptor):
                    return model.apply(variables, batch, train=False)
            return model.apply(variables, batch, train=False)

        return predict_step

    # -- lifecycle -----------------------------------------------------------

    def start(self, install_sigterm: bool = False) -> "GraphServer":
        """Launch warm-up + the serve loop (and the checkpoint watcher when
        ``Serving.hot_reload`` and a run dir are configured by the caller via
        ``attach_watcher``). Admission opens immediately — requests queue
        while the ladder warms; readiness (``wait_ready``) flips only once
        every servable specialization is compiled and the sentinel is armed."""
        if self._closed:
            raise ServerClosedError("server is closed")
        if self._serve_thread is not None:
            return self
        if int(self.cfg.http_port) >= 0:
            # mandatory observability surface (docs/SERVING.md
            # "Endpoints"): /metrics + /healthz + /readyz. Readiness IS the
            # full-ladder warm-up flip that opens the serve loop — a load
            # balancer routing on /readyz only ever sends traffic to a
            # zero-retrace server that is accepting admissions. Best-effort
            # bind: an occupied port warns instead of failing the server.
            from ..obs.prometheus import start_endpoint

            self._http = start_endpoint(
                int(self.cfg.http_port),
                ready_fn=lambda: (
                    self._ready.is_set()
                    and self.failed is None
                    and not self._closed
                    and not self._draining.is_set()
                ),
                health_fn=lambda: (
                    (True, "serving")
                    if self.failed is None and not self._closed
                    else (
                        False,
                        "closed"
                        if self.failed is None
                        else f"warm-up failed: {self.failed}",
                    )
                ),
                label=f"serve[{self.log_name}]",
                host=self.cfg.http_host,
            )
        if install_sigterm:
            import signal

            def _on_sigterm(signum, frame):
                # async-signal-safe: only flags; the serve loop finishes
                # in-flight + queued work and then exits (graceful drain)
                self.initiate_drain()
                prev = self._prev_sigterm
                if callable(prev):
                    prev(signum, frame)

            try:
                self._prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
            except ValueError:
                pass  # not the main thread; the caller wires drain itself
        self._warm_thread = threading.Thread(
            target=self._warmup, daemon=True, name="serve-warmup"
        )
        self._warm_thread.start()
        self._runner = _StepRunner()
        self._serve_thread = threading.Thread(
            target=self._serve_loop, daemon=True, name="serve-loop"
        )
        self._serve_thread.start()
        return self

    def attach_watcher(self, watcher) -> None:
        """Register a started CheckpointWatcher so close() tears it down."""
        self._watcher = watcher

    def _warmup(self) -> None:
        from ..data.pipeline import spec_template_batches
        from ..train.compile_plane import serve_warmup

        try:
            templates = spec_template_batches(
                self._template_graphs, self.ladder, sort_edges=self.sort_edges
            )
            if not templates:
                raise ValueError(
                    "no template graph fits any ladder level — the ladder "
                    "does not describe the template dataset"
                )
            compiled, errors, exec_s = serve_warmup(
                self._predict_fn,
                self._state,
                templates,
                policy=self.cfg.retrace_policy,
                label="serve",
            )
            self.warmup_compiled = compiled
            if errors:
                raise RuntimeError(
                    f"serve warm-up failed for {len(errors)} specialization(s): "
                    f"{errors}"
                )
            if self._stop.is_set():
                # close() raced warm-up: it already evaluated (and skipped)
                # its _armed disarm, so the sentinel serve_warmup just armed
                # would leak error-mode into the rest of the process
                from ..train.compile_plane import sentinel

                sentinel().disarm()
                return
            self._armed = True
            if self._per_graph_s <= 0 and exec_s > 0:
                # seed the shed estimator with the measured worst-level
                # execution time (one real graph per template batch)
                self._per_graph_s = exec_s
        except Exception as e:  # noqa: BLE001 — the server must fail typed
            self.failed = e
            self._stop.set()
            self._drained.set()
            self._fail_queued(
                ServerClosedError(f"serve warm-up failed: {e}")
            )
            return
        self._ready.set()
        self._m_ready.set(1)

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    @property
    def http_port(self) -> Optional[int]:
        """Port of the /metrics//healthz//readyz endpoint, or None when
        disabled (``Serving.http_port`` < 0) or the bind failed."""
        return self._http.port if self._http is not None else None

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until warm-up completes (True) or fails/times out (False;
        ``self.failed`` carries the warm-up error)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._ready.is_set():
            if self.failed is not None:
                return False
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(_TICK_S)
        return True

    def initiate_drain(self) -> None:
        """Stop admitting (async-signal-safe: only sets a flag); in-flight
        and queued requests still complete. The SIGTERM hook. (The ready
        gauge/endpoint report not-ready from here on — a draining server
        must fall out of its load balancer; the gauge write is a plain
        dict store, still async-signal-safe. Only the instance that
        reported ready may zero the shared gauge — draining a never-ready
        standby must not clobber a live server's readiness.)

        Drain ordering (docs/SERVING.md "Fleet"): /readyz keys off
        ``_draining`` and flips 503 the moment it is set, but ``submit``
        keeps admitting for ``Serving.drain_grace_s`` more — the window in
        which a load balancer observes the not-ready flip and stops
        routing here, so well-behaved clients never see a
        ServerDrainingError. The stamp is arithmetic + a float store,
        still async-signal-safe."""
        self._drain_admit_deadline = time.monotonic() + float(
            self.cfg.drain_grace_s
        )
        self._draining.set()
        if self._ready.is_set():
            self._m_ready.set(0)
        # typed drain record (signal-safe like the gauge write: the event
        # log's RLock allows same-thread re-entry, and the emit is a deque
        # append + counter inc)
        _emit_serve_event(EV_DRAIN, severity="info", queued=self._queue.qsize())

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Initiate + wait for the drain to finish. Returns True when every
        admitted request was answered."""
        self.initiate_drain()
        if timeout is None:
            timeout = self.cfg.drain_timeout_s or None
        return self._drained.wait(timeout)

    def close(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Shut down: optionally drain, stop every thread, disarm the
        sentinel, and fail whatever is still queued with a typed error."""
        if self._closed:
            return
        if drain and self._serve_thread is not None and self.failed is None:
            self.drain(timeout)
        self._closed = True
        self._stop.set()
        # drop any staged reload the serve loop will never swap in — a
        # watcher poll that staged between drain and here must not leak
        # the standby state past the server's lifetime
        with self._swap_lock:
            self._pending_state = None
        if self._ready.is_set():
            # same standby guard as initiate_drain: only a server that
            # reported ready un-reports on close
            self._m_ready.set(0)
        if self._http is not None:
            self._http.close()
            self._http = None
        if self._watcher is not None:
            self._watcher.stop()
        if self._serve_thread is not None:
            self._serve_thread.join(timeout=_JOIN_TIMEOUT_S)
            if self._serve_thread.is_alive():
                warnings.warn(
                    "serve loop still alive at close(); leaking the daemon "
                    "thread",
                    RuntimeWarning,
                    stacklevel=2,
                )
        if self._runner is not None:
            self._runner.stop()
        self._fail_queued(ServerClosedError("server closed"))
        if self._armed:
            from ..train.compile_plane import sentinel

            sentinel().disarm()
        if self._prev_sigterm is not None:
            import signal

            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass
            self._prev_sigterm = None
        # tracing-plane teardown (the server owns what it was handed)
        if self._flight is not None:
            try:
                self._flight.uninstall()
            except Exception:
                pass
        if self._tracer is not None:
            from ..obs import trace as _obs_trace

            try:
                _obs_trace.uninstall(self._tracer)
                self._tracer.close()
            except Exception:
                pass
        self._drained.set()

    def __enter__(self) -> "GraphServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))

    # -- admission -----------------------------------------------------------

    def submit(
        self,
        graph: Graph,
        deadline_s: Optional[float] = None,
        ) -> PredictionHandle:
        """Admit one request. Admission-time rejections raise the typed
        error directly (invalid request, queue full, shed, draining/closed);
        an admitted request's later failures are delivered on the handle."""
        idx = next(self._submit_seq)
        t_admit_wall = time.time()
        self._bump("submitted")
        # chaos hook: a slow client holding the admission door (no-op unarmed)
        faultinject.maybe_slow_client(idx)
        if self._closed or self.failed is not None:
            self._bump("rejected")
            raise ServerClosedError(
                "server is closed"
                if self.failed is None
                else f"server failed at warm-up: {self.failed}",
                request_id=idx,
            )
        # grace window (initiate_drain): /readyz is already 503, but
        # admissions stay open until the stamped deadline so the LB can
        # stop routing before clients see the typed rejection
        if self._draining.is_set() and (
            time.monotonic() >= self._drain_admit_deadline
        ):
            self._bump("rejected")
            raise ServerDrainingError(
                "server is draining (SIGTERM or drain()); request not admitted",
                request_id=idx,
            )
        g = _strip_targets(graph)
        # chaos hook: corrupt-request injection by submission index
        g = faultinject.poison_request(g, idx)
        if _channel_signature(g) != self._channel_sig:
            self._bump("rejected")
            raise InvalidRequestError(
                f"request {idx} channel layout {_channel_signature(g)} does "
                f"not match the served model's {self._channel_sig} — "
                f"{describe_reason(R_CHANNELS)}",
                request_id=idx,
                reason=R_CHANNELS,
            )
        reason = validate_graph(
            g, max_nodes=self._worst.n_nodes - 1, max_edges=self._worst.n_edges
        )
        if reason is not None:
            self._bump("rejected")
            raise InvalidRequestError(
                f"request {idx} rejected: {reason} ({describe_reason(reason)})",
                request_id=idx,
                reason=reason,
            )
        # load shedding: admit only what can plausibly meet the p99 SLO
        if self.cfg.slo_p99_s > 0 and self._per_graph_s > 0:
            backlog = self._queue.qsize() + self._inflight_graphs + (
                1 if self._holdover is not None else 0
            )
            projected = backlog * self._per_graph_s
            if projected > self.cfg.slo_p99_s:
                self._bump("shed")
                _emit_serve_event(
                    EV_SHED,
                    request_id=idx,
                    projected_wait_s=round(projected, 6),
                    slo_s=self.cfg.slo_p99_s,
                )
                raise SheddedError(
                    f"request {idx} shed: projected queue wait "
                    f"{projected:.3f}s exceeds the p99 SLO "
                    f"{self.cfg.slo_p99_s:.3f}s",
                    request_id=idx,
                    projected_wait_s=projected,
                    slo_s=self.cfg.slo_p99_s,
                )
        if deadline_s is None:
            deadline_s = self.cfg.default_deadline_s
        deadline = (
            time.monotonic() + float(deadline_s) if deadline_s else float("inf")
        )
        handle = PredictionHandle(idx, deadline)
        # head-sampling decision at the trace root, BEFORE the enqueue: the
        # serve loop could dequeue (and look for the trace context) the
        # instant the request lands in the queue
        if self._tracer is not None and self._tracer.sample_request():
            # backdated to submit ENTRY: the root's duration is the full
            # admission-to-outcome latency, and the admit child nests
            # inside it temporally
            root = self._tracer.begin("serve/request", start_unix=t_admit_wall)
            root.set_attribute("request_id", idx)
            handle.trace = root
            self._tracer.emit_completed(
                "serve/admit",
                t_admit_wall,
                time.time() - t_admit_wall,
                parent=root,
            )
        try:
            self._queue.put_nowait(_Request(g, handle))
        except queue.Full:
            self._bump("queue_full")
            _emit_serve_event(
                EV_QUEUE_FULL,
                trace_id=(
                    handle.trace.trace_id if handle.trace is not None else None
                ),
                request_id=idx,
                bound=self.cfg.max_queue_requests,
            )
            self._end_request_trace(handle, error="queue_full")
            raise QueueFullError(
                f"request {idx} rejected: admission queue is at its bound "
                f"({self.cfg.max_queue_requests} requests)",
                request_id=idx,
            ) from None
        self._bump("admitted")
        self._m_queue.set(self._queue.qsize())
        return handle

    def predict(
        self,
        graphs: Sequence[Graph],
        deadline_s: Optional[float] = None,
        timeout: Optional[float] = None,
    ) -> List[Union[Dict[str, np.ndarray], RequestError]]:
        """Blocking convenience: one outcome per input graph — a per-head
        prediction dict, or the request's typed ``RequestError`` as a value
        (admission rejections included), so one bad request never hides the
        results of the good ones beside it."""
        handles: List[Union[PredictionHandle, RequestError]] = []
        for g in graphs:
            try:
                handles.append(self.submit(g, deadline_s=deadline_s))
            except RequestError as e:
                handles.append(e)
        out: List[Union[Dict[str, np.ndarray], RequestError]] = []
        for h in handles:
            if isinstance(h, RequestError):
                out.append(h)
                continue
            err = h.error(timeout)
            out.append(err if err is not None else h.result(0))
        return out

    # -- serve loop ----------------------------------------------------------

    def _take_request(self, timeout: float) -> Optional[_Request]:
        """Next admitted request, honoring the holdover slot and failing
        deadline-expired requests at dequeue (never wasting batch slots on
        answers nobody is waiting for)."""
        deadline = time.monotonic() + max(timeout, 0.0)
        while not self._stop.is_set():
            if self._holdover is not None:
                req, self._holdover = self._holdover, None
            else:
                remaining = deadline - time.monotonic()
                if remaining <= 0 and timeout > 0:
                    return None
                try:
                    req = self._queue.get(
                        timeout=min(max(remaining, 0.0), _TICK_S)
                        if timeout > 0
                        else _TICK_S
                    )
                except queue.Empty:
                    if timeout > 0:
                        continue
                    return None
            if time.monotonic() > req.handle.deadline:
                self._bump("deadline_expired")
                _emit_serve_event(
                    EV_DEADLINE,
                    trace_id=(
                        req.handle.trace.trace_id
                        if req.handle.trace is not None
                        else None
                    ),
                    request_id=req.handle.request_id,
                    waited_s=round(
                        time.perf_counter() - req.handle.submitted_at, 6
                    ),
                )
                self._fail_request(
                    req.handle,
                    DeadlineExceededError(
                        "deadline expired while queued (waited past the "
                        "request's budget)"
                    ),
                )
                continue
            if req.handle.trace is not None:
                # queue-wait span, retroactive at dequeue: THE latency
                # explainer under pressure (admission -> this dequeue)
                wait = time.perf_counter() - req.handle.submitted_at
                self._tracer.emit_completed(
                    "serve/queue_wait",
                    time.time() - wait,
                    wait,
                    parent=req.handle.trace,
                )
            return req
        return None

    def _collect_batch(self) -> Optional[List[_Request]]:
        """Form one micro-batch: wait for a first request, then fill from
        the queue until the graph-count cap, the worst-spec pad budget, or
        the batch window closes. A request that does not fit is held over to
        lead the next batch."""
        first = self._take_request(timeout=0.0)
        if first is None:
            return None
        # batch-formation clock starts at the leading request's dequeue
        # (the serve/batch_form span; idle waiting before it is queue time)
        self._form_started = time.perf_counter()
        reqs = [first]
        n = first.graph.num_nodes
        e = first.graph.num_edges
        window_ends = time.monotonic() + self.cfg.batch_window_s
        while len(reqs) < self._batch_cap:
            remaining = window_ends - time.monotonic()
            if remaining <= 0 and self._queue.qsize() == 0 and self._holdover is None:
                break
            req = self._take_request(timeout=max(remaining, _TICK_S / 10))
            if req is None:
                break
            gn, ge = req.graph.num_nodes, req.graph.num_edges
            if n + gn > self._worst.n_nodes - 1 or e + ge > self._worst.n_edges:
                self._holdover = req
                break
            reqs.append(req)
            n, e = n + gn, e + ge
        return reqs

    def _serve_loop(self) -> None:
        import jax

        # process nothing before the ladder is warm: the first organic batch
        # must already be a cache hit (readiness == zero-retrace)
        while not self._ready.is_set():
            if self._stop.is_set():
                return
            time.sleep(_TICK_S)
        while not self._stop.is_set():
            reqs = self._collect_batch()
            # hot-reload swap point: AFTER batch formation, before dispatch —
            # between batches, never mid-flight, and a state installed while
            # the loop was blocked waiting for requests is guaranteed to
            # serve the very next batch (not the one after)
            with self._swap_lock:
                if self._pending_state is not None:
                    self._state, self.current_checkpoint = self._pending_state
                    self._pending_state = None
                    self._bump("reloads")
            if reqs is None:
                # exit only once the admission grace window has also passed
                # — a request legitimately admitted during drain_grace_s
                # must not race a loop that already quit
                if self._draining.is_set() and self._queue.qsize() == 0 and (
                    self._holdover is None
                ) and time.monotonic() >= self._drain_admit_deadline:
                    break
                continue
            self._inflight_graphs = len(reqs)
            batch_index = next(self._batch_seq)
            state = self._state
            graphs = [r.graph for r in reqs]
            step_span = self._begin_step_span(reqs, batch_index)
            t0 = time.perf_counter()
            try:
                spec = self.ladder.select_for(graphs)
                if step_span is not None:
                    sel_dt = time.perf_counter() - t0
                    self._tracer.emit_completed(
                        "serve/bucket_select",
                        time.time() - sel_dt,
                        sel_dt,
                        parent=step_span,
                        attributes={
                            "level": f"{spec.n_nodes}n/{spec.n_edges}e"
                        },
                    )
                batch = batch_graphs(graphs, spec, sort_edges=self.sort_edges)

                def step(_state=state, _batch=batch, _bi=batch_index):
                    # chaos hook: a wedged device step (no-op unarmed)
                    faultinject.maybe_serve_wedge(_bi)
                    return jax.device_get(self._predict_fn(_state, _batch))

                t_dev = time.perf_counter()
                outputs = self._runner.run(step, self.cfg.step_timeout_s)
                if step_span is not None:
                    dev_dt = time.perf_counter() - t_dev
                    self._tracer.emit_completed(
                        "serve/device_step",
                        time.time() - dev_dt,
                        dev_dt,
                        parent=step_span,
                    )
            except _StepTimeout:
                self._bump("wedged_batches")
                _emit_serve_event(
                    EV_WEDGE,
                    severity="error",
                    trace_id=(
                        step_span.trace_id if step_span is not None else None
                    ),
                    batch_index=batch_index,
                    graphs=len(reqs),
                    step_timeout_s=self.cfg.step_timeout_s,
                )
                # the wedged runner thread is abandoned (daemon); recycle
                self._runner = _StepRunner()
                for r in reqs:
                    self._fail_request(
                        r.handle,
                        WedgedStepError(
                            f"device step for batch {batch_index} exceeded "
                            f"step_timeout_s={self.cfg.step_timeout_s}s; the "
                            "batch was abandoned and the step runner recycled"
                        )
                    )
                self._finish_step_span(step_span, error="wedged_step")
                # black-box dump: a wedged device step is a flight-recorder
                # trigger point — the dump carries the wedge event (with its
                # trace_id), the abandoned batch's spans, and the registry
                self._flight_dump("serve_wedge")
                self._inflight_graphs = 0
                continue
            except Exception as e:  # noqa: BLE001 — batch-level failure
                self._bump("failed_batches")
                for r in reqs:
                    self._fail_request(
                        r.handle,
                        RequestError(
                            f"batch {batch_index} failed: "
                            f"{type(e).__name__}: {e}"
                        ),
                    )
                self._finish_step_span(
                    step_span, error=f"{type(e).__name__}: {e}"
                )
                self._inflight_graphs = 0
                continue
            dt = time.perf_counter() - t0
            self._m_batch_lat.observe(dt)
            self._m_queue.set(self._queue.qsize())
            t_resp = time.perf_counter()
            self._deliver(reqs, batch, outputs)
            if step_span is not None:
                resp_dt = time.perf_counter() - t_resp
                self._tracer.emit_completed(
                    "serve/respond",
                    time.time() - resp_dt,
                    resp_dt,
                    parent=step_span,
                )
            self._finish_step_span(step_span)
            self._bump("batches")
            self._bump("completed", len(reqs))
            # EMA service-time estimate drives the shed projection
            per_graph = dt / len(reqs)
            self._per_graph_s = (
                per_graph
                if self._per_graph_s <= 0
                else 0.8 * self._per_graph_s + 0.2 * per_graph
            )
            self._inflight_graphs = 0
        self._drained.set()

    def _deliver(self, reqs: List[_Request], batch, outputs: Dict[str, Any]) -> None:
        """Slice the padded batch outputs back into per-request, per-head
        host arrays: graph-level heads by graph row, node-level heads by the
        request's node span."""
        node_offsets = np.cumsum([0] + [r.graph.num_nodes for r in reqs])
        n_graphs = batch.num_graphs
        n_nodes = batch.num_nodes
        for i, r in enumerate(reqs):
            result: Dict[str, np.ndarray] = {}
            for name, arr in outputs.items():
                a = np.asarray(arr)
                if a.ndim and a.shape[0] == n_graphs:
                    result[name] = a[i]
                elif a.ndim and a.shape[0] == n_nodes:
                    result[name] = a[node_offsets[i] : node_offsets[i + 1]]
                else:  # scalar/aux output: handed through as-is
                    result[name] = a
            r.handle._resolve(result)
            self._m_req_lat.observe(
                r.handle.done_at - r.handle.submitted_at, outcome="ok"
            )
            self._end_request_trace(r.handle)

    # -- tracing helpers -----------------------------------------------------

    def _begin_step_span(self, reqs: List[_Request], batch_index: int):
        """Open the shared device-step span for a batch holding sampled
        requests: the span lives in the LEAD sampled request's trace and is
        cross-linked with every other sampled request in the batch (OTLP
        links), so one trace explains the whole co-batched step. Includes
        the retroactive serve/batch_form child (lead dequeue -> now)."""
        if self._tracer is None:
            return None
        sampled = [r.handle.trace for r in reqs if r.handle.trace is not None]
        if not sampled:
            return None
        sp = self._tracer.begin("serve/step", parent=sampled[0])
        sp.set_attribute("batch_index", batch_index)
        sp.set_attribute("graphs", len(reqs))
        for other in sampled[1:]:
            sp.add_link(other.trace_id, other.span_id)
            other.add_link(sp.trace_id, sp.span_id)
        if self._form_started is not None:
            form_dt = time.perf_counter() - self._form_started
            self._tracer.emit_completed(
                "serve/batch_form",
                time.time() - form_dt,
                form_dt,
                parent=sp,
            )
        return sp

    def _finish_step_span(self, span, error: Optional[str] = None) -> None:
        if span is None:
            return
        try:
            span.set_status(
                STATUS_ERROR if error is not None else STATUS_OK,
                error or "",
            )
            self._tracer.finish(span)
        except Exception:
            pass  # tracing must never fail the serve loop

    def _end_request_trace(
        self, handle: PredictionHandle, error: Optional[str] = None
    ) -> None:
        """Close a sampled request's root span with its outcome; the span's
        duration IS the request's admission-to-outcome latency."""
        root = handle.trace
        if root is None:
            return
        handle.trace = None
        try:
            root.set_status(
                STATUS_ERROR if error is not None else STATUS_OK,
                error or "",
            )
            self._tracer.finish(root)
        except Exception:
            pass

    def _flight_dump(self, reason: str) -> None:
        """Dump the black box (the server's own recorder when it was handed
        one, else whatever recorder is process-active)."""
        try:
            if self._flight is not None:
                self._flight.dump(reason)
            else:
                from ..obs import flightrec as _flightrec

                _flightrec.trigger(reason)
        except Exception:
            pass

    # -- bookkeeping ---------------------------------------------------------

    def _fail_request(self, handle: PredictionHandle, err: RequestError) -> None:
        """Fail one admitted request AND observe its latency with the error
        outcome — failed requests (deadline, wedge, batch error, drain) are
        precisely the slow tail, so excluding them would make the scraped
        p99 improve as the server violates its SLO harder."""
        handle._fail(err)
        self._m_req_lat.observe(
            handle.done_at - handle.submitted_at, outcome="error"
        )
        self._end_request_trace(
            handle, error=getattr(err, "code", type(err).__name__)
        )

    def _fail_queued(self, err: RequestError) -> None:
        if self._holdover is not None:
            self._fail_request(self._holdover.handle, err)
            self._holdover = None
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            self._fail_request(req.handle, err)

    def _cast_weights(self, state, entry: Optional[str] = None):
        """Apply ``Serving.weights_dtype`` to an incoming state — the one
        precision gate for both the startup restore and every hot-reload
        swap, so a reloaded checkpoint cannot silently revert the server
        to f32 weights. ``int8`` routes through the quantization plane
        (calibration + the accuracy gate; ``entry`` names the checkpoint
        for snapshot lookup and drift attribution) and may raise
        :class:`~hydragnn_tpu.serve.quantize.QuantizationDriftError`."""
        if self.cfg.weights_dtype == "float32":
            return state
        if self.cfg.weights_dtype == "int8":
            return self._quantize_state(state, entry)
        from ..train.state import cast_inference_weights

        return cast_inference_weights(state, self.cfg.weights_dtype)

    def _quant_batches(self) -> list:
        """The calibration/gate batches: the warmed ladder's template
        batches (the exact shapes serving runs), capped at
        ``Serving.quantization.calibration_batches``."""
        from ..data.pipeline import spec_template_batches

        templates = spec_template_batches(
            self._template_graphs, self.ladder, sort_edges=self.sort_edges
        )
        batches = [b for _, b in templates]
        if not batches:
            raise ValueError(
                "int8 quantization needs at least one template batch to "
                "calibrate and gate on — the ladder does not describe the "
                "template dataset"
            )
        cap = int(self.cfg.quantization.calibration_batches)
        return batches[: max(1, cap)]

    def _quantize_state(self, state, entry: Optional[str]):
        """The int8 install pipeline: pre-quantized snapshot fast path
        (no re-quantization, no calibration — the artifact banked its
        gate report where it was produced), else quantize + calibrate +
        gate, then publish the snapshot beside the checkpoint for the
        rest of the fleet."""
        from ..utils import faultinject
        from . import quantize as qz

        spec = self.cfg.quantization
        if isinstance(state, qz.QuantizedInferenceState):
            # already-quantized state handed in directly (embedding
            # callers/tests): same trust story as the snapshot path
            self._quant_report = {
                "source": "prequantized", "mode": state.mode,
            }
            return state
        if entry and self._checkpoint_dir:
            loaded = qz.load_snapshot(
                self.log_name, entry, spec.mode, self._checkpoint_dir
            )
            if loaded is not None:
                qstate, report = loaded
                self._quant_report = dict(
                    report, source="snapshot", mode=qstate.mode,
                )
                return qstate
        batches = self._quant_batches()
        qstate = qz.quantize_state(
            self.model, state, batches, spec.mode, spec.exclude
        )
        factor = faultinject.maybe_quant_drift(entry)
        if factor:
            qstate = qz.apply_scale_drift(qstate, factor)
        report = qz.gate_or_raise(
            self.model, state, qstate, batches, spec.max_error,
            run=self.log_name, entry=entry,
        )
        self._quant_report = dict(report, source="calibrated")
        if entry and self._checkpoint_dir:
            try:
                qz.save_snapshot(
                    qstate, self._quant_report, self.log_name, entry,
                    self._checkpoint_dir,
                )
            except OSError:
                pass  # the artifact is an accelerator, not a dependency
        return qstate

    def _install_state(self, state, label: Optional[str]) -> bool:
        """Stage a reloaded state; the serve loop swaps it in at the next
        batch boundary (in-flight batches keep the weights they started
        with). Refused (returns False) on a draining/stopping/closed
        server: a CheckpointWatcher poll racing close() must neither swap
        a new state into a server that is winding down nor leak the
        standby state past close()'s pending-state clear.

        The precision cast runs BEFORE the lock: int8 quantization
        (eager calibration + the accuracy gate) takes seconds, and the
        serve loop checks this lock at every batch boundary — staging
        must never stall traffic. A gate refusal
        (QuantizationDriftError) propagates to the caller; nothing was
        staged."""
        prepared = self._cast_weights(state, entry=label)
        with self._swap_lock:
            if self._closed or self._stop.is_set() or self._draining.is_set():
                return False
            self._pending_state = (prepared, label)
            return True

    def _bump(self, key: str, by: int = 1) -> None:
        with self._stats_lock:
            self._stats[key] = self._stats.get(key, 0) + by
        self._m_events.inc(by, event=key)

    def stats(self) -> Dict[str, Any]:
        """Structured serving counters + the current policy/observability
        snapshot (the chaos smoke and BENCH_SERVE parse this)."""
        from ..train.compile_plane import sentinel

        with self._stats_lock:
            out: Dict[str, Any] = dict(self._stats)
        out.update(
            ready=self.ready,
            draining=self.draining,
            closed=self._closed,
            queued=self._queue.qsize(),
            per_graph_latency_s=round(self._per_graph_s, 6),
            ladder_levels=len(self.ladder.specs),
            warmed_specializations=len(self.warmup_compiled),
            retrace_violations=max(
                len(sentinel().violations()) - self._violations_at_launch, 0
            ),
            current_checkpoint=self.current_checkpoint,
            http_port=self.http_port,
            weights_dtype=self.cfg.weights_dtype,
        )
        if self._quant_report is not None:
            out["quantization"] = dict(self._quant_report)
        return out
