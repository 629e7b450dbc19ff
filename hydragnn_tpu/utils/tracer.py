"""Region tracer facade — the GPTL/Score-P analog
(reference: hydragnn/utils/profiling_and_tracing/tracer.py:35-167).

The reference fans ``tr.start/stop`` out to GPTL and Score-P C libraries with
optional ``torch.cuda.synchronize`` + MPI barrier per span. Here the backend
is (a) an in-process accumulator (count/total/min/max per region) and (b)
``jax.profiler.TraceAnnotation`` so regions appear in xprof/TensorBoard
device traces, on the device trace's own clock. ``sync=True`` drains the
async JAX dispatch queue before timestamping — the device-sync analog of the
reference's ``cudasync=True`` (tracer.py:106-127) — controlled globally by
``HYDRAGNN_TRACE_LEVEL`` exactly like the reference's train-loop spans
(train_validate_test.py:477-498).

The training iteration runs on three threads (the loader's producer, the
H2D staging thread, the main loop), so the open-region and annotation stacks
are per thread — a ``TraceAnnotation`` is entered and exited on the thread
that opened it, always — and the accumulator is shared under a lock.

This module also holds the one vocabulary of names that a trace of a
training run shows (docs/OBSERVABILITY.md "Regions, scopes and kernel
names"): the host regions below, the ``jax.named_scope`` names inside the
compiled step, and the Pallas kernel names.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, Optional
from . import envflags

# -- host regions (tr.start/stop), by the thread that opens them ------------
DATALOAD = "dataload"  # main: waiting for the next staged batch
RNG_SPLIT = "rng_split"  # main: the key split (a full device queue holds the host here)
TRAIN_STEP = "train_step"  # main: dispatch + the real-graph count
DISPATCH = "dispatch"  # main, child of train_step: step_fn(state, batch, sub) alone
EPOCH_RESTART = "epoch_restart"  # main: iter(loader) to the epoch's first batch in hand
EPOCH_DRAIN = "epoch_drain"  # main: the epoch-end read of the per-step losses
BATCH_BUILD = "batch_build"  # loader producer: building ONE batch
H2D_STAGE = "h2d_stage"  # staging thread: the jax.device_put call
# the three phases of every program jax builds, opened and closed by the compile plane's jax.monitoring
# listeners (train/compile_plane.py) on the thread that compiles, with fun_name= as the attribute
COMPILE_TRACE = "compile_trace"  # Python to jaxpr; the outermost trace only, which encloses those of the functions it calls
COMPILE_LOWER = "compile_lower"  # jaxpr to an MLIR module (the Mosaic kernels are lowered here)
COMPILE_BACKEND = "compile_backend"  # compile_or_get_cached: the XLA compile cold, the cache fetch warm

# -- jax.named_scope names inside the compiled step (metadata only) ---------
HG_CAST = "hg_cast"  # the bf16 casts of mixed precision
HG_LOSS = "hg_loss"  # value_and_grad: jvp( = forward, transpose( = backward
HG_OPTIMIZER = "hg_optimizer"  # tx.update + apply_updates
HG_GUARD = "hg_guard"  # step_ok + the guarded select
HG_CCA_CONV = "hg_cca_conv"  # ZAYA: the two causal convolutions + q-k mean of a CCA sublayer
HG_ROUTER = "hg_router"  # ZAYA, JOYAI, AFMOE: the float32 router, the choice and the expert layout
HG_MOE = "hg_moe"  # ZAYA: gather to expert rows, the grouped products, scatter back
HG_MLA_PROJ = "hg_mla_proj"  # JOYAI: the four latent projections of an MLA block, their norms and RoPE
HG_MOE_DISPATCH = "hg_moe_dispatch"  # JOYAI, AFMOE: the gather with repeats of tokens into expert rows
HG_MOE_COMBINE = "hg_moe_combine"  # JOYAI, AFMOE: the gate-weighted sum over a token's rows
HG_SHARED_EXPERT = "hg_shared_expert"  # JOYAI, AFMOE: the shared expert's MLP on every token
HG_ATTN_PROJ = "hg_attn_proj"  # AFMOE: the query, key and value projections of an attention sublayer, the head norms and the rotation
HG_ATTN_GATE = "hg_attn_gate"  # AFMOE: the gate's projection, its sigmoid and the product with the attention output
HG_DSA_PROJ = "hg_dsa_proj"  # KEYEVL2: the sparse-attention indexer's projections (queries, key, weights), its key's LayerNorm and RoPE
HG_MTP = "hg_mtp"  # JOYAI: the multi-token-prediction module (join, its own layer, its norm)
HG_LOOP_EXIT = "hg_loop_exit"  # OURO: a pass's exit, the final norm and the exit gate's logit
HG_TOKEN_LOSS = "hg_token_loss"  # the chunked next-node cross-entropy

# -- counters: per-step scalars a step carries among its per-task entries
#    under COUNTER_PREFIX, summed here at the epoch drain (train/loop.py) -----
COUNTER_PREFIX = "count:"
CT_TOKENS = "count:tokens"  # real nodes x expert layers (OURO, which has none: x layer applications)
CT_TOKENS_ROUTED_HERE = "count:tokens_routed_here"  # tokens whose expert is held, over layers
CT_EXPERT_LOAD_MAX = "count:expert_load_max"  # largest load of a held expert, summed over layers
CT_EXPERT_LOAD_MEAN = "count:expert_load_mean"  # mean load of the held experts, summed over layers
CT_CAUSAL_PAIRS = "count:causal_pairs"  # (query, key) pairs within graphs, one layer's
# the causal flash launch's schedule, one block's forward launch, one head
# (ops/pallas_flash_attention.py causal_schedule_steps)
CT_FLASH_TILES_VISITED = "count:flash_tiles_visited"  # tiles in the query blocks' key windows
CT_FLASH_STEPS_SCHEDULED = "count:flash_steps_scheduled"  # steps run for them: loop trips, or q_blocks x k_windows under the grid
CT_WINDOW_PAIRS = "count:window_pairs"  # AFMOE: (query, key) pairs within graphs AND within the sliding window, one sliding layer's
# a sliding layer's launches (flash_causal_attention(window=W)), as the two entries above
CT_FLASH_WINDOW_TILES_VISITED = "count:flash_window_tiles_visited"
CT_FLASH_WINDOW_STEPS_SCHEDULED = "count:flash_window_steps_scheduled"
# a decoder stack's attention blocks, and those whose causal flash launch's residuals (``o``, a row's
# ``lse``) the layer's remat keeps, so that the forward kernel runs once (models/decoder.py remat_in_training)
CT_FLASH_BLOCKS = "count:flash_blocks"
CT_FLASH_BLOCKS_SAVED = "count:flash_blocks_saved"
# the token head's row chunks a step over its passes, and those whose gradient its forward scan formed
# (train/loss.py chunked_cross_entropy: all of them in training, none in evaluation)
CT_HEAD_CHUNKS = "count:head_chunks"
CT_HEAD_CHUNKS_GRAD_IN_FORWARD = "count:head_chunks_grad_in_forward"
CT_EXPERT_ROWS_HERE = "count:expert_rows_here"  # JOYAI, AFMOE: rows computed on this chip (a token is 0..k), over layers
CT_EXPERT_ROWS_OVERRUN = "count:expert_rows_overrun"  # JOYAI, AFMOE: rows past the row budget (the step is poisoned)
CT_MTP_PAIRS = "count:mtp_pairs"  # JOYAI: nodes whose two successors lie in their document
CT_DSA_SELECTED_PAIRS = "count:dsa_selected_pairs"  # KEYEVL2: (query, key) pairs the indexer selects, one layer's: sum of min(n_t, topk)
# OURO: the layers held here, and their applications a step (each held layer runs once a pass)
CT_LOOP_LAYERS_HELD = "count:loop_layers_held"
CT_LOOP_LAYER_APPLICATIONS = "count:loop_layer_applications"

# -- Pallas kernels: pallas_call(name=...) inside a scope of the same name;
#    the custom-JVP tangent rule runs under <name> + TANGENT ----------------
HG_FUSED_EDGE = "hg_fused_edge"
HG_SORTED_SEGMENT = "hg_sorted_segment"
HG_MULTI_AGG = "hg_multi_agg"
HG_FLASH_ATTENTION = "hg_flash_attention"
# the causal launches of a SLIDING layer (flash_causal_attention(window=W)): the same kernels under a
# name of their own, so a trace tells a step's two kinds of launch apart
HG_FLASH_WINDOW = "hg_flash_window"
# the causal launches under a learned selection (flash_causal_attention(select=...)), and the indexer
# that makes the selection with the launch of its loss (ops/pallas_dsa_indexer.py; ``_bwd``: the loss)
HG_FLASH_SPARSE = "hg_flash_sparse"
HG_DSA_INDEXER = "hg_dsa_indexer"
HG_GROUPED_EXPERT = "hg_grouped_expert"
TANGENT = "_tangent"
# the transpose of ops/segment.py gather(sorted_ids=True): a scope AROUND the
# hg_sorted_segment scope and call, which tells a transposed sum from a forward one
HG_GATHER_TRANSPOSE = "hg_gather_transpose"
# an edge-sized feature gather ``[N, C] -> [E, C]`` of the message path, and
# the tangent sums whose transposes are such gathers: an op_name that holds
# it and ends in ``/gather`` is one of a step's row gathers
HG_ROW_GATHER = "hg_row_gather"
BWD = "_bwd"  # a kernel's own backward launches (custom-VJP kernels)

_enabled = False
# HYDRAGNN_TRACE_LEVEL > 0, read once at enable(): drain at every region edge
_sync_default = False
# jax.profiler.TraceAnnotation, resolved once at enable() (None: no profiler)
_annotation = None
_lock = threading.Lock()
_regions: Dict[str, Dict[str, float]] = {}
# span-plane bridge (obs/trace.py), resolved lazily once: a region closing
# while a sampled span is open on this thread is emitted as a child span,
# so the pre-existing region instrumentation lands in the trace tree
_obs_trace = None


class _ThreadState(threading.local):
    """One thread's open regions: per-name stacks of start times, so a
    re-entrant start(name) nests instead of overwriting, and one LIFO of
    (name, TraceAnnotation) — xprof annotations are scoped C++ objects and
    must exit in strict nesting order, on the thread that entered them."""

    def __init__(self):
        self.open: Dict[str, list] = {}
        self.anns: list = []


_state = _ThreadState()


def scope(name: str):
    """``jax.named_scope(name)``: names the ops traced under it in the
    lowered program's metadata (and so in a device trace); no arithmetic."""
    import jax

    return jax.named_scope(name)


def _sync_devices() -> None:
    """Wait for all previously enqueued device work: enqueue a trivial op on
    each local device's (FIFO) compute stream and block on it —
    ``jax.effects_barrier`` alone would skip pure computations."""
    try:
        import jax
        import jax.numpy as jnp

        for d in jax.local_devices():
            jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 1)
    except Exception:
        pass


def _exit_annotations(anns: list, until: Optional[str] = None) -> None:
    """Exit this thread's annotations innermost first, down to and
    including the newest one named ``until`` (all of them when None)."""
    while anns:
        top_name, ann = anns.pop()
        try:
            ann.__exit__(None, None, None)
        except Exception:
            pass
        if top_name == until:
            break


def reset() -> None:
    """Clear the accumulator and THIS thread's open regions; spans another
    thread holds open stay open and record when that thread closes them."""
    with _lock:
        _regions.clear()
    _state.open.clear()
    _exit_annotations(_state.anns)


def enable() -> None:
    global _enabled, _sync_default, _annotation
    _sync_default = envflags.env_int("HYDRAGNN_TRACE_LEVEL", 0) > 0
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        except Exception:
            pass
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def start(name: str, sync: Optional[bool] = None, **attrs) -> None:
    """Open a region on the calling thread (reference: tracer.py:106-116).
    ``attrs`` go to the ``TraceAnnotation`` (loop spans carry ``batch=`` and
    ``epoch=``, so one batch can be followed across threads in a trace)."""
    if not _enabled:
        return
    if _sync_default if sync is None else sync:
        _sync_devices()
    st = _state
    if _annotation is not None:
        try:
            ann = _annotation(name, **attrs)
            ann.__enter__()
            st.anns.append((name, ann))
        except Exception:
            pass
    st.open.setdefault(name, []).append(time.perf_counter())


def stop(name: str, sync: Optional[bool] = None, discard: bool = False) -> None:
    """Close the calling thread's newest open ``name`` and accumulate
    (reference: tracer.py:118-127). ``discard`` closes without counting (a
    region opened around something that turned out not to happen)."""
    if not _enabled:
        return
    st = _state
    starts = st.open.get(name)
    if not starts:
        return
    if _sync_default if sync is None else sync:
        _sync_devices()
    dt = time.perf_counter() - starts.pop()
    if not starts:
        del st.open[name]
    # unwind annotations in strict LIFO order: an out-of-nesting stop closes
    # the inner (still-open) annotations early rather than corrupting the
    # xprof span tree by exiting out of order
    if any(n == name for n, _ in st.anns):
        _exit_annotations(st.anns, until=name)
    if discard:
        return
    with _lock:
        rec = _regions.get(name)
        if rec is None:
            rec = _regions[name] = {
                "count": 0.0, "total": 0.0, "min": float("inf"), "max": 0.0
            }
        rec["count"] += 1
        rec["total"] += dt
        rec["min"] = min(rec["min"], dt)
        rec["max"] = max(rec["max"], dt)
    _note_span(name, dt)


def _note_span(name: str, dt: float) -> None:
    """Forward a closed region to the span plane (no-op without an active
    tracer + open span — one attribute read on the unsampled hot path)."""
    global _obs_trace
    if _obs_trace is None:
        try:
            from ..obs import trace as _t

            _obs_trace = _t
        except Exception:
            _obs_trace = False
            return
    if _obs_trace is False:
        return
    try:
        _obs_trace.note_region(name, dt)
    except Exception:
        pass  # tracing must never fail the timed code


@contextlib.contextmanager
def timer(name: str, sync: Optional[bool] = None, **attrs):
    """(reference: tracer.py:158-167)"""
    start(name, sync, **attrs)
    try:
        yield
    finally:
        stop(name, sync)


def profile(name: str):
    """Decorator opening a region around the call (reference: tracer.py:145-155)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with timer(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


def count(name: str, value: float) -> None:
    """Add ``value`` to counter ``name`` in the region table (``total`` is
    the running sum, ``count`` the number of additions)."""
    if not _enabled:
        return
    with _lock:
        rec = _regions.get(name)
        if rec is None:
            rec = _regions[name] = {
                "count": 0.0, "total": 0.0, "min": float("inf"), "max": 0.0
            }
        rec["count"] += 1
        rec["total"] += value
        rec["min"] = min(rec["min"], value)
        rec["max"] = max(rec["max"], value)


def get_regions() -> Dict[str, Dict[str, float]]:
    with _lock:
        return {k: dict(v) for k, v in _regions.items()}


def print_report(prefix: str = "") -> None:
    """Per-process region dump (the GPTL ``pr_file`` analog,
    reference: examples/multibranch/train.py:507-514)."""
    regions = get_regions()
    if not regions:
        return
    width = max(len(k) for k in regions)
    print(f"{prefix}{'region'.ljust(width)}  count     total(s)    avg(s)      max(s)")
    for name, r in sorted(regions.items()):
        avg = r["total"] / max(r["count"], 1)
        print(
            f"{prefix}{name.ljust(width)}  {int(r['count']):<8d}"
            f"  {r['total']:<10.4f}  {avg:<10.4f}  {r['max']:<10.4f}"
        )


def save_report(path: str) -> None:
    import json

    with open(path, "w") as f:
        json.dump(get_regions(), f, indent=2)
