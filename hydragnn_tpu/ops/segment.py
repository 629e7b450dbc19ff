"""Masked segment reductions — the TPU replacement for torch_scatter.

The reference's message passing relies on torch_scatter/PyG CUDA scatter
kernels (SURVEY §2.3 item 2). On TPU the idiomatic lowering is
``jax.ops.segment_sum`` over statically shaped arrays: XLA turns sorted
segment reductions into efficient one-pass kernels and fuses the surrounding
elementwise math. Padding edges/nodes are neutralized by masks rather than by
dynamic shapes.

All functions take ``num_segments`` statically so shapes stay fixed under jit.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_derivatives import linear_call

from ..utils import envflags, tracer as tr


def _pallas_route_enabled() -> bool:
    """Whether ``sorted_ids`` segment sums route to the Pallas MXU kernel.

    ``jax.default_backend()`` is evaluated at trace time, which is correct
    for the supported configurations (the framework jits for the default
    backend); ``HYDRAGNN_PALLAS_SEGMENT=0/1`` overrides for a jit that
    targets a non-default device.
    """
    pref = envflags.env_force("HYDRAGNN_PALLAS_SEGMENT")
    if pref is not None:
        return pref
    return jax.default_backend() == "tpu"


def _debug_check_sorted(segment_ids) -> None:
    """Opt-in (HYDRAGNN_DEBUG_SORTED=1) runtime check that segment_ids is
    non-decreasing — ``sorted_ids=True`` is otherwise an unchecked caller
    promise, and an unsorted batch (e.g. hand-built at inference, bypassing
    GraphLoader's sort_edges) would silently produce wrong sums."""

    def _host_assert(ids):
        import numpy as np

        ids = np.asarray(ids)
        if ids.size and (np.diff(ids) < 0).any():
            raise AssertionError(
                "segment_sum(sorted_ids=True) received unsorted segment_ids; "
                "build batches with GraphLoader(sort_edges=True) or disable "
                "use_sorted_aggregation"
            )

    jax.debug.callback(_host_assert, segment_ids)


def _mask_messages(messages: jnp.ndarray, mask: Optional[jnp.ndarray], fill: float = 0.0):
    if mask is None:
        return messages
    m = mask.reshape(mask.shape + (1,) * (messages.ndim - mask.ndim))
    return jnp.where(m, messages, fill)


def segment_sum(
    messages,
    segment_ids,
    num_segments,
    mask=None,
    sorted_ids: bool = False,
    max_degree: Optional[int] = None,
):
    """Scatter-add of edge messages.

    With ``sorted_ids=True`` (receiver-sorted edge arrays, built by
    ``GraphLoader(sort_edges=True)``) and a static in-degree bound
    ``max_degree`` (config ``max_in_degree``, measured over the dataset),
    the TPU backend routes through the Pallas MXU kernel
    (ops/pallas_segment.py) instead of XLA's serialized scatter. Any other
    backend, or 1-D messages, falls back to ``jax.ops.segment_sum``.
    """
    msg = _mask_messages(messages, mask)
    if sorted_ids and envflags.env_force("HYDRAGNN_DEBUG_SORTED"):
        _debug_check_sorted(segment_ids)
    if sorted_ids and max_degree and msg.ndim == 2 and _pallas_route_enabled():
        from .pallas_segment import sorted_segment_sum

        # the kernel runs with its own tiles (ops/pallas_segment.py).
        # Forcing the route on a non-TPU backend (HYDRAGNN_PALLAS_SEGMENT=1,
        # e.g. the CPU-mesh dryrun) runs the kernel in interpret mode —
        # same program, Python-evaluated blocks
        return sorted_segment_sum(
            msg, segment_ids, num_segments, max_degree,
            interpret=jax.default_backend() != "tpu",
        )
    return jax.ops.segment_sum(msg, segment_ids, num_segments=num_segments)


def fused_edge_message_sum(
    node_recv,
    edge_in,
    weights,
    bias,
    segment_ids,
    num_segments,
    max_degree: int,
):
    """Fused gather -> edge dense -> segment sum of the edge hot path:

        segment_sum(relu(relu(node_recv[ids] + edge_in) @ weights + bias))

    Routing mirrors ``segment_sum``: receiver-sorted ids + a static
    in-degree bound on a TPU jit target go through the VMEM-resident Pallas
    kernel (ops/pallas_fused_edge.py) — per-edge messages never touch HBM;
    ``HYDRAGNN_PALLAS_SEGMENT=1`` forces the route off-TPU in interpret
    mode (the CPU-mesh dryrun / CI smoke); any other backend falls back to
    the dense plain-jnp reference, which is the same function. Both routes
    differentiate to arbitrary order (the kernel's tangent rule is plain
    jnp), so energy-force training composes.
    """
    if envflags.env_force("HYDRAGNN_DEBUG_SORTED"):
        _debug_check_sorted(segment_ids)
    if max_degree and _pallas_route_enabled():
        from .pallas_fused_edge import fused_edge_message_sum as _pallas_fused

        return _pallas_fused(
            node_recv, edge_in, weights, bias, segment_ids, num_segments,
            max_degree, interpret=jax.default_backend() != "tpu",
        )
    from .pallas_fused_edge import reference_edge_message_sum

    return reference_edge_message_sum(
        node_recv, edge_in, weights, bias, segment_ids, num_segments
    )


def _multiagg_route_enabled() -> bool:
    """Whether ``multi_moment_agg`` routes to the fused multi-moment Pallas
    kernel. ``HYDRAGNN_PALLAS_MULTIAGG=0/1`` is the dedicated override;
    unset, the decision falls through to ``HYDRAGNN_PALLAS_SEGMENT`` /
    the TPU-backend default, so one env flip drives every sorted kernel
    in an A/B (the multichip dryrun relies on that)."""
    pref = envflags.env_force("HYDRAGNN_PALLAS_MULTIAGG")
    if pref is not None:
        return pref
    return _pallas_route_enabled()


def multi_moment_agg(
    edge_in,
    segment_ids,
    num_segments,
    node_recv=None,
    gate=None,
    mask=None,
    sorted_ids: bool = False,
    max_degree: int = 0,
):
    """Multi-moment aggregation of ``(node_recv[ids] + edge_in) * gate``:
    the five moments ``(sum, count, min, max, sumsq)`` every PNA-family
    aggregate-and-scale derives from, in ONE pass — f32 each,
    ``node_recv``/``gate`` optional (None).

    Routing mirrors ``segment_sum``: receiver-sorted ids + a static
    in-degree bound on a TPU jit target go through the multi-output
    Pallas kernel (ops/pallas_multi_agg.py) — the [E, C] messages never
    round-trip HBM; ``HYDRAGNN_PALLAS_MULTIAGG=1`` (or the shared
    ``HYDRAGNN_PALLAS_SEGMENT=1``) forces the route off-TPU in interpret
    mode; any other backend falls back to the dense plain-jnp reference,
    which is the same function. Both routes differentiate to arbitrary
    order (the kernel's tangent rule is plain jnp), so energy-force
    training composes. ``mask`` is honored only on the dense route — the
    sorted layout neutralizes padding edges by construction (they all
    land on the final dummy node, masked downstream)."""
    if sorted_ids and envflags.env_force("HYDRAGNN_DEBUG_SORTED"):
        _debug_check_sorted(segment_ids)
    from .pallas_multi_agg import fused_multi_agg, reference_multi_agg

    if (sorted_ids and max_degree and edge_in.ndim == 2
            and _multiagg_route_enabled()):
        return fused_multi_agg(
            node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
            interpret=jax.default_backend() != "tpu",
        )
    return reference_multi_agg(
        node_recv, edge_in, gate, segment_ids, num_segments, mask=mask
    )


def segment_count(segment_ids, num_segments, mask=None):
    ones = jnp.ones(segment_ids.shape[:1], jnp.float32)
    if mask is not None:
        ones = jnp.where(mask, ones, 0.0)
    return jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)


def segment_mean(
    messages,
    segment_ids,
    num_segments,
    mask=None,
    eps: float = 0.0,
    sorted_ids: bool = False,
    max_degree: Optional[int] = None,
):
    s = segment_sum(
        messages, segment_ids, num_segments, mask,
        sorted_ids=sorted_ids, max_degree=max_degree,
    )
    n = segment_count(segment_ids, num_segments, mask)
    n = jnp.maximum(n, 1.0) if eps == 0.0 else n + eps
    return s / n.reshape(n.shape + (1,) * (s.ndim - 1))


def segment_max(messages, segment_ids, num_segments, mask=None):
    neg = jnp.finfo(messages.dtype).min
    m = jax.ops.segment_max(
        _mask_messages(messages, mask, neg), segment_ids, num_segments=num_segments
    )
    # segments with no (real) incoming messages -> 0, like torch_scatter 'max'
    return jnp.where(m <= neg / 2, 0.0, m)


def segment_min(messages, segment_ids, num_segments, mask=None):
    pos = jnp.finfo(messages.dtype).max
    m = jax.ops.segment_min(
        _mask_messages(messages, mask, pos), segment_ids, num_segments=num_segments
    )
    return jnp.where(m >= pos / 2, 0.0, m)


def segment_std(messages, segment_ids, num_segments, mask=None, eps: float = 1e-5):
    """Population std per segment (PNA 'std' aggregator semantics).

    Guarded against catastrophic cancellation: the moments accumulate in
    f32 regardless of the message dtype, and the E[x²]−E[x]² variance is
    clamped at zero BEFORE the sqrt — a bf16 near-constant segment
    otherwise yields a small negative variance (E[x²] and E[x]² agree to
    ~8 bits and the subtraction is pure rounding noise) and a NaN std
    that poisons the whole PNA step."""
    m = messages.astype(jnp.float32)
    mean = segment_mean(m, segment_ids, num_segments, mask)
    mean_sq = segment_mean(m * m, segment_ids, num_segments, mask)
    var = jnp.maximum(mean_sq - mean**2, 0.0)
    return jnp.sqrt(var + eps).astype(messages.dtype)


def segment_softmax(logits, segment_ids, num_segments, mask=None):
    """Numerically stable softmax within each segment (GAT attention)."""
    neg = jnp.finfo(logits.dtype).min
    masked = _mask_messages(logits, mask, neg)
    seg_max = jax.ops.segment_max(masked, segment_ids, num_segments=num_segments)
    seg_max = jnp.where(seg_max <= neg / 2, 0.0, seg_max)
    shifted = masked - seg_max[segment_ids]
    exp = jnp.exp(shifted)
    if mask is not None:
        exp = _mask_messages(exp, mask, 0.0)
    denom = jax.ops.segment_sum(exp, segment_ids, num_segments=num_segments)
    return exp / jnp.maximum(denom[segment_ids], 1e-16)


def gather(
    values,
    index,
    sorted_ids: bool = False,
    max_degree: Optional[int] = None,
):
    """Row gather ``values[index]``, the read side of ``segment_sum``.

    JAX transposes a gather into a scatter-add, which XLA runs a row at a
    time. Over receiver-sorted ``index`` that transpose IS a sorted segment
    sum, so under the predicate ``segment_sum`` itself routes on
    (``sorted_ids`` + a static in-degree bound ``max_degree`` + 2-D values +
    the Pallas route) the gather becomes a linear call whose transpose is
    ``segment_sum(ct, index, rows, sorted_ids=True, max_degree=...)``: the
    ``hg_sorted_segment`` kernel under the scope ``hg_gather_transpose``.
    The forward is the same ``values[index]`` on every route, under the
    scope ``hg_row_gather``.

    ``jax.custom_derivatives.linear_call`` is the tool: it is transposable
    (a ``custom_vjp`` on a tangent path is not, and the fused-edge tangent
    rule pushes this gather through ``jax.jvp``), its JVP is itself on the
    tangent, and its transpose is again a ``linear_call`` with the roles
    swapped, so grad-of-grad (energy-force training), ``jax.checkpoint`` and
    ``shard_map`` compose. It has no batching rule: do not ``vmap`` it (the
    conv stacks never are).

    The transpose inherits the kernel's contract: a row gathered more than
    ``max_degree`` times gets an UNSPECIFIED cotangent. That is the dummy
    node, which receives every padding edge; its row is exact only while
    every padding edge's cotangent is zero, which holds where each consumer
    of the gathered rows masks with ``edge_mask`` or lands on the dummy
    node's masked output (tests/test_fused_edge.py holds every stack that
    passes its flags to it).
    """
    if sorted_ids and max_degree and values.ndim == 2 and _pallas_route_enabled():
        num_rows = values.shape[0]

        def take(ids, x):
            with tr.scope(tr.HG_ROW_GATHER):
                return x[ids]

        def take_transpose(ids, ct):
            with tr.scope(tr.HG_GATHER_TRANSPOSE):
                return segment_sum(
                    ct, ids, num_rows, sorted_ids=True, max_degree=max_degree
                )

        return linear_call(take, take_transpose, index, values)
    with tr.scope(tr.HG_ROW_GATHER):
        return values[index]


def masked_global_mean_pool(x, node_graph, num_graphs, node_mask):
    """Per-graph mean over real nodes (reference: global_mean_pool, Base.py:478)."""
    return segment_mean(x, node_graph, num_graphs, node_mask)


def masked_global_sum_pool(x, node_graph, num_graphs, node_mask):
    return segment_sum(x, node_graph, num_graphs, node_mask)
