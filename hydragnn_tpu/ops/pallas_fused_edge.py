"""Pallas TPU kernel: fused gather -> edge dense -> sorted-segment sum.

The EGNN edge hot path (models/egnn.py EGCL, via layers.hoisted_pair_dense)
is three HBM round-trips today even with the sorted-segment MXU kernel:

    pre  = Dense_r(x)[recv] + Dense_s(x)[send] + edge terms   # [E, C] write
    msg  = relu(Dense_2(relu(pre)))                           # [E, C] rw
    agg  = sorted_segment_sum(msg, recv)                      # [E, C] read

At the SC25 production shape ([E, 866] ~ 12.8 MB per intermediate at batch
32) the r5 trace shows ~78% of the step stalled on non-dot time around
exactly these arrays (docs/PERFORMANCE.md). This kernel keeps the whole
chain VMEM-resident: per-edge messages never touch HBM.

It extends the ``sorted_segment_sum`` grid/blocking scheme
(ops/pallas_segment.py — ``row_starts``/scalar-prefetch ``estart`` windows
over receiver-sorted edges) with a weights operand and in-kernel dots:

- grid ``(C_blocks, row_blocks j, K)``; for output row-block ``j`` the K
  inner steps stream the edge windows that can touch its rows (bounded by
  ``Nb * max_degree``), revisiting the output block as a reduction
  accumulator — unchanged from the segment-sum kernel;
- the *receiver gather runs in-kernel*: the same in-register one-hot
  ``mine = (ids == j*Nb + iota)`` that scatters messages also GATHERS the
  receiver-projected node rows, as ``mine @ node_recv_block`` on the MXU
  (one-hot rows copy exactly one node row per edge, exact in any dtype).
  Edges owned by other row blocks get a zero gather row — harmless, since
  the same one-hot zeroes their contribution on the way out;
- senders are NOT sorted, so the sender-side gather (plus the small
  edge-local projections: length, edge_attr) stays an XLA gather fused
  into ONE edge-aligned operand ``edge_in`` — XLA gathers are fast on TPU
  and this is the only [E, C] array the fused path ever materializes;
- per step: ``pre = mine @ nrecv + ein``; ``msg = relu(relu(pre) @ W + b)``
  ([Eb, Ci] x [Ci, Cb] on the MXU); ``acc += mine.T @ msg``. The edge
  dense is recomputed for every row block whose windows cover the edge
  block — a ``K*Eb/(Nb*avg_degree)`` redundancy factor (~1.3x at the
  production shape), paid in MXU FLOPs that were previously stalled on
  HBM anyway.

Differentiation: ``jax.custom_jvp`` whose tangent rule is PLAIN jnp (the
dense reference implementation pushed through ``jax.jvp``). Only the
primal ever runs the Pallas kernel, so reverse-mode falls out by
transposing jnp ops (segment-sum VJP is a gather; dense VJP is two
matmuls) and the op composes under ``jax.grad`` to ANY order — unlike
``jax.custom_vjp``, which is first-order only and forced the grad-energy
guard the r5 round shipped (config/config.py). Call sites should wrap the
op in ``jax.checkpoint`` (models/layers.py does) so the tangent-rule
residuals are recomputed in the backward instead of re-materialized in
the forward, keeping the training forward VMEM-resident too.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_derivatives import linear_call
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ..utils import tracer as tr

from .pallas_segment import _pad_to, mxu_precision
from .segment import gather


def reference_edge_message_sum(
    node_recv, edge_in, weights, bias, segment_ids, num_segments,
    max_degree: Optional[int] = None,
):
    """Dense (plain-jnp) statement of the fused computation — the off-TPU
    fallback, the tangent rule, and the identity oracle for tests:

        segment_sum(relu(relu(node_recv[ids] + edge_in) @ weights + bias))

    ``max_degree`` is the tangent rule's alone: it holds the kernel's own
    contract (ascending ids, the in-degree bound), so the transpose of its
    ``node_recv[ids]`` gather is a sorted segment sum (ops/segment.py
    ``gather``). The fallback and the oracle leave it out and get the plain
    gather, as every route does where the Pallas route is off.

    With it the closing sum is a ``linear_call`` too, for the ORDER of the
    backward: its transpose, the ``dout[ids]`` gather, bars ``dout`` behind
    the recomputed ``node_recv[ids]`` rows, so XLA relays ``dout`` directly
    before its gather and the gather reads it from VMEM. Left to itself the
    scheduler relays ``dout`` first, recomputes the rows in between, and the
    gather fetches ``dout`` from HBM a row at a time, six times slower
    (models/layers.py ``pair_message_factored`` has the forward's half;
    PERF.md section 6, PR 32). The rows are a residual of the sum, which is
    linear in ``msg`` alone, hence the ``stop_gradient``.
    """
    rows = gather(node_recv, segment_ids, True, max_degree)
    msg = jax.nn.relu(jnp.dot(jax.nn.relu(rows + edge_in), weights) + bias)
    if not max_degree:
        return jax.ops.segment_sum(msg, segment_ids, num_segments=num_segments)

    def total(res, m):
        with tr.scope(tr.HG_ROW_GATHER):
            return jax.ops.segment_sum(m, res[0], num_segments=num_segments)

    def total_transpose(res, ct):
        ids, after = res
        ct, _ = jax.lax.optimization_barrier((ct, after))
        with tr.scope(tr.HG_ROW_GATHER):
            return ct[ids]

    return linear_call(total, total_transpose,
                       (segment_ids, jax.lax.stop_gradient(rows)), msg)


def _kernel(estart_ref, ids_ref, nrecv_ref, ein_ref, w_ref, b_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    nb = out_ref.shape[0]
    dtype = ein_ref.dtype
    precision = mxu_precision(dtype)
    # in-register one-hot: edge e belongs to local row r iff its receiver id
    # equals j*Nb + r; padding edges carry id -1 and never match
    rows = j * nb + jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    mine = (ids_ref[:] == rows).astype(dtype)  # [Eb, Nb]
    # in-kernel receiver gather: each one-hot row copies exactly one row of
    # the receiver-projected node block (exact in any dtype — the f32
    # accumulation sums a single product 1.0 * x, at the precision
    # mxu_precision asks for). Unowned/padding edges get a zero row; their
    # messages are zeroed by the same one-hot below.
    pre = jax.lax.dot_general(
        mine,
        nrecv_ref[:],
        (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    ).astype(dtype) + ein_ref[:]
    h = jnp.maximum(pre, jnp.zeros((), dtype))
    lin = jax.lax.dot_general(
        h,
        w_ref[:],
        (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)
    # round the message to the streaming dtype before accumulating, matching
    # the dense route (flax Dense emits operand-dtype outputs; the segment
    # accumulation stays f32 via preferred_element_type)
    msg = jnp.maximum(lin, 0.0).astype(dtype)
    out_ref[:] += jax.lax.dot_general(
        mine,
        msg,
        (((0,), (0,)), ((), ())),  # contract over the edge axis
        precision=precision,
        preferred_element_type=jnp.float32,
    )


# the launch's tiles: output rows a block, edges a streamed window, output
# channels a block. Stated here and nowhere else; a change to them is an edit
# of this line, claimed in a benchmark cell
BLOCK_ROWS, BLOCK_EDGES, BLOCK_COLS = 128, 512, 512


def normalize_tiles(
    ci, co, dtype,
    block_rows=BLOCK_ROWS, block_edges=BLOCK_EDGES, block_cols=BLOCK_COLS,
):
    """Clamp requested tiles to what the launch runs for these widths and
    this stream dtype: the one clamp, applied by ``fused_edge_message_sum``
    before the tiles become ``custom_jvp`` non-differentiable arguments.

    Channel padding: input width streams whole (the dense contracts over
    it). Output width: ONE block when it fits a lane-aligned <=1024 tile
    (the production hidden 866 -> 896, no pad waste and no re-streaming
    of the edge operand per output block); otherwise block_cols-blocks.
    """
    nb, eb = block_rows, block_edges
    ci_pad = ci + (-ci) % 128
    co128 = co + (-co) % 128
    cb = co128 if co128 <= 1024 else min(block_cols, co128)

    # VMEM fit: shrink the edge window until the resident working set —
    # double-buffered streams, weights, f32 accumulator, and the dense
    # intermediates (pre/h/msg live in VMEM scratch) — fits comfortably.
    # Redundant-recompute cost is eb-invariant (K ~ Nb*max_degree/eb, so
    # K*Eb is ~constant), which makes shrinking eb nearly free.
    itemsize = jnp.dtype(dtype).itemsize

    def _vmem_estimate(eb_):
        return (
            2 * eb_ * ci_pad * itemsize      # edge_in stream
            + 2 * nb * ci_pad * itemsize     # node_recv block
            + 2 * ci_pad * cb * itemsize     # weights block
            + nb * cb * 4                    # f32 accumulator
            + eb_ * ci_pad * 4               # pre (f32 dot output)
            + eb_ * ci_pad * itemsize        # h
            + 2 * eb_ * cb * 4               # lin + msg
        )

    while eb > 128 and _vmem_estimate(eb) > 12 * 1024 * 1024:
        eb //= 2
    return nb, eb, cb


def _forward(
    node_recv, edge_in, weights, bias, segment_ids, num_segments, max_degree,
    nb, eb, cb, interpret,
):
    e, ci = edge_in.shape
    ci_w, co = weights.shape
    assert ci_w == ci, (ci_w, ci)
    assert node_recv.shape[1] == ci, (node_recv.shape, ci)
    dtype = edge_in.dtype
    ci_pad = ci + (-ci) % 128
    ids = segment_ids.astype(jnp.int32)
    ein = _pad_to(_pad_to(edge_in, eb, 0), 128, 1)
    nrecv = _pad_to(_pad_to(node_recv, nb, 0), 128, 1)
    w = _pad_to(_pad_to(weights, 128, 0), cb, 1)
    b = _pad_to(bias.reshape(1, -1), cb, 1)
    assert ein.shape[1] == ci_pad and w.shape[0] == ci_pad
    n_pad = nrecv.shape[0]
    co_pad = w.shape[1]

    # K inner windows cover the worst legal row block (degree-capped), +1
    # for edge-block misalignment; trailing zero blocks so estart[j] + k is
    # always in range (same scheme as pallas_segment._forward)
    k_windows = (nb * max_degree + eb - 1) // eb + 1
    k_windows = min(k_windows, ein.shape[0] // eb)
    k_windows = max(k_windows, 1)
    ein = jnp.pad(ein, ((0, k_windows * eb), (0, 0)))
    e_pad = ein.shape[0]

    ids_col = jnp.full((e_pad, 1), -1, jnp.int32).at[:e, 0].set(ids)

    j_blocks = n_pad // nb
    row_starts = jnp.searchsorted(
        ids, jnp.arange(j_blocks, dtype=jnp.int32) * nb, side="left"
    ).astype(jnp.int32)
    estart_block = row_starts // eb

    def ids_index(c_i, j, k, estart):
        return (estart[j] + k, 0)

    def nrecv_index(c_i, j, k, estart):
        return (j, 0)

    def ein_index(c_i, j, k, estart):
        return (estart[j] + k, 0)

    def w_index(c_i, j, k, estart):
        return (0, c_i)

    def b_index(c_i, j, k, estart):
        return (0, c_i)

    def out_index(c_i, j, k, estart):
        return (j, c_i)

    grid = (co_pad // cb, j_blocks, k_windows)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((eb, 1), ids_index),
                pl.BlockSpec((nb, nrecv.shape[1]), nrecv_index),
                pl.BlockSpec((eb, ein.shape[1]), ein_index),
                pl.BlockSpec((w.shape[0], cb), w_index),
                pl.BlockSpec((1, cb), b_index),
            ],
            out_specs=pl.BlockSpec((nb, cb), out_index),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, co_pad), jnp.float32),
        interpret=interpret,
        name=tr.HG_FUSED_EDGE,
    )(estart_block, ids_col, nrecv, ein, w, b)
    return out[:num_segments, :co].astype(dtype)


def fused_edge_message_sum(
    node_recv,
    edge_in,
    weights,
    bias,
    segment_ids,
    num_segments: int,
    max_degree: int = 32,
    block_rows: int = BLOCK_ROWS,
    block_edges: int = BLOCK_EDGES,
    block_cols: int = BLOCK_COLS,
    interpret: bool = False,
):
    """Fused ``segment_sum(relu(relu(node_recv[ids] + edge_in) @ W + b))``
    for receiver-sorted edges, VMEM-resident end to end.

    ``segment_ids`` MUST be ascending and ``node_recv`` must span exactly
    the ``num_segments`` nodes the ids index. Segments holding more than
    ``max_degree`` edges get an UNSPECIFIED value, exactly like
    ``sorted_segment_sum`` — and, same as there, the spill can also starve
    LATER segments inside the same ``block_rows`` row block (their edges
    get pushed past the K streamed windows; subsequent row blocks are
    unaffected, since each gets its own ``estart``). The framework's
    batches satisfy this by construction: real in-degrees are capped, and
    the only over-cap segment is the FINAL dummy node, with no rows after
    it (data/graph.py padding docs). NOTE the dummy node's row is garbage
    rather than zero here (padding-edge messages are relu(bias)-shaped,
    not maskable pre-kernel) — same "mask downstream" contract, asserted
    at the model level by tests/test_fused_edge.py.

    Returns ``[num_segments, co]`` in the operand dtype; accumulation is
    f32 throughout. Differentiable to arbitrary order (custom-JVP with a
    plain-jnp tangent), so energy-force (grad-of-grad) training composes.
    Tiles past the clamp (``normalize_tiles``) run, and compile, as the
    clamped ones.
    """
    tiles = normalize_tiles(
        edge_in.shape[1], weights.shape[1], edge_in.dtype,
        block_rows, block_edges, block_cols,
    )
    return _fused_edge_message_sum(
        node_recv, edge_in, weights, bias, segment_ids, num_segments,
        max_degree, *tiles, interpret,
    )


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _fused_edge_message_sum(
    node_recv, edge_in, weights, bias, segment_ids, num_segments, max_degree,
    block_rows, block_edges, block_cols, interpret,
):
    with tr.scope(tr.HG_FUSED_EDGE):
        return _forward(
            node_recv, edge_in, weights, bias, segment_ids, num_segments,
            max_degree, block_rows, block_edges, block_cols, interpret,
        )


@_fused_edge_message_sum.defjvp
def _fused_jvp(
    num_segments, max_degree, block_rows, block_edges, block_cols, interpret,
    primals, tangents,
):
    node_recv, edge_in, weights, bias, segment_ids = primals
    t_nr, t_ei, t_w, t_b, _ = tangents
    out = _fused_edge_message_sum(
        node_recv, edge_in, weights, bias, segment_ids, num_segments,
        max_degree, block_rows, block_edges, block_cols, interpret,
    )
    # tangent in PLAIN jnp: linear in the tangents, built from transposable
    # primitives, differentiable to any order — reverse mode transposes it
    # into the gather + two-matmul VJP, and grad-of-grad just differentiates
    # this rule again. The primal-dependent residuals (relu masks, pre) are
    # what jax.checkpoint at the call site pushes into the backward.
    fn = lambda nr, ei, w, b: reference_edge_message_sum(
        nr, ei, w, b, segment_ids, num_segments, max_degree
    )
    with tr.scope(tr.HG_FUSED_EDGE + tr.TANGENT):
        _, t_out = jax.jvp(
            fn, (node_recv, edge_in, weights, bias), (t_nr, t_ei, t_w, t_b)
        )
    return out, t_out
