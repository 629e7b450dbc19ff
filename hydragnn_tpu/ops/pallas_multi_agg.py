"""Pallas TPU kernel: fused gather -> edge message -> multi-moment reduction.

The PNA family's message path was the largest piece of MFU headroom left
behind by the r6 fused edge kernel: PNA/PNAPlus/PNAEq aggregate every edge
message FOUR ways (mean/min/max/std, models/pna.py pna_aggregate), and the
r6 decision record argued fusion was pointless because "min/max/std need
the full [E, C] message array in HBM regardless". That premise only holds
for single-output kernels. This kernel is multi-output: one launch over the
receiver-sorted edge windows emits per-node

    (sum, min, max, sum-of-squares)

in a single pass — the same online-statistics trick the flash-attention
kernel uses for its softmax (m, l) running stats, applied to the PNA
moments — so the per-edge messages never round-trip HBM at all. mean and
std derive in plain jnp outside (std via the zero-clamped E[x²]−E[x]²
form; the count is a [E]-read segment count, negligible traffic).

It extends the sorted-edge grid/``estart`` scheme of
``ops/pallas_fused_edge.py``:

- grid ``(C_blocks, row_blocks j, K)``; for output row-block ``j`` the K
  inner steps stream the edge windows that can touch its rows (bounded by
  ``Nb * max_degree``), revisiting all four output blocks as reduction
  accumulators (sum/sumsq init 0, min/max init +/-FLT_MAX at k==0);
- the *receiver gather runs in-kernel*: the one-hot
  ``mine = (ids == j*Nb + iota)`` that scatters the moments also GATHERS
  the receiver-projected node rows as ``mine @ node_recv_block`` on the
  MXU (PNA's pre-MLP is pre_layers=1, already distributed over the concat
  by ``hoisted_pair_dense`` — so the whole message is
  ``node_recv[recv] + edge_in`` (optionally ``* gate`` for PNAPlus's
  Hadamard rbf gate), no weights operand needed);
- senders are unsorted, so the sender projection plus edge-local terms
  stay ONE XLA-gathered edge-aligned operand ``edge_in`` — the only
  [E, C] array the fused path materializes (PNAPlus adds the [E, C]
  ``gate``; PNAEq passes its post-MLP message as ``edge_in`` directly and
  skips the in-kernel gather);
- sum and sumsq accumulate as ``mine.T @ msg`` MXU contractions
  (f32 accumulation); min/max have no matmul form, but the receivers are
  SORTED, so each node's edges are one contiguous run of the window: a
  segmented running min/max along the edge axis (log2(max_degree)
  ``pltpu.roll`` + compare + select steps on the [Eb, Cb] tile) leaves
  every run's extremum on its LAST edge, and the same one-hot contraction
  that scatters the sums then copies those run-end values onto their node
  rows. Everything stays 2-D: the first form of this kernel reduced a
  [chunk, Nb, Cb] masked ``where``, which Mosaic refuses to lay out (an
  ``i1`` [chunk, Nb] -> [chunk, Nb, 1] shape cast; TPU v5e, jax 0.9.0).

Differentiation: ``jax.custom_jvp`` whose tangent rule is the PLAIN-jnp
dense reference pushed through ``jax.jvp`` — the recompute schedule
ROADMAP item 4 asked for: the backward re-derives the edge messages from
the gathered inputs (a gather + elementwise + segment ops, all
XLA-native) instead of loading stored [E, C] residuals, and because no
Pallas call appears on a tangent path the op composes under ``jax.grad``
to ANY order (energy-force grad-of-grad included). Call sites wrap the op
per ``Training.remat_policy`` (ops/remat.py) so the tangent residuals are
recomputed in the backward rather than materialized in the forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ..utils import tracer as tr

from .pallas_segment import _pad_to, mxu_precision

# min/max accumulator sentinel: large enough that no real message reaches
# it, small enough that +/-_BIG survives an f32 round-trip exactly
_BIG = 3.0e38


def reference_multi_agg(node_recv, edge_in, gate, segment_ids, num_segments,
                        mask=None):
    """Dense (plain-jnp) statement of the fused computation — the off-TPU
    fallback, the custom-JVP tangent rule, and the identity oracle for
    tests. Per-edge message ``m = (node_recv[ids] + edge_in) * gate`` with
    ``node_recv``/``gate`` optional (None); returns the five f32 moments

        (sum, count, min, max, sumsq)

    each ``[num_segments, C]`` (count ``[num_segments]``), with empty
    segments fixed to 0 in min/max (the torch_scatter convention the
    dense ``segment_min``/``segment_max`` already follow). All moments
    accumulate in f32 regardless of the message dtype — bf16 sumsq would
    otherwise lose exactly the low bits the std's E[x²]−E[x]² subtraction
    needs (ops/segment.py segment_std carries the same guard)."""
    msg = edge_in if node_recv is None else node_recv[segment_ids] + edge_in
    if gate is not None:
        msg = msg * gate
    msg = msg.astype(jnp.float32)
    ones = jnp.ones(segment_ids.shape[:1], jnp.float32)
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (msg.ndim - mask.ndim))
        msg_0 = jnp.where(m, msg, 0.0)
        msg_lo = jnp.where(m, msg, _BIG)
        msg_hi = jnp.where(m, msg, -_BIG)
        ones = jnp.where(mask, ones, 0.0)
    else:
        msg_0 = msg_lo = msg_hi = msg
    s = jax.ops.segment_sum(msg_0, segment_ids, num_segments=num_segments)
    cnt = jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
    mn = jax.ops.segment_min(msg_lo, segment_ids, num_segments=num_segments)
    mx = jax.ops.segment_max(msg_hi, segment_ids, num_segments=num_segments)
    ssq = jax.ops.segment_sum(
        msg_0 * msg_0, segment_ids, num_segments=num_segments
    )
    nonempty = (cnt > 0.0)[:, None]
    mn = jnp.where(nonempty, mn, 0.0)
    mx = jnp.where(nonempty, mx, 0.0)
    return s, cnt, mn, mx, ssq


def _make_kernel(has_recv: bool, has_gate: bool, max_degree: int):
    def kernel(estart_ref, *refs):
        i = 1
        ids_ref = refs[0]
        nrecv_ref = refs[i] if has_recv else None
        i += int(has_recv)
        ein_ref = refs[i]
        i += 1
        gate_ref = refs[i] if has_gate else None
        i += int(has_gate)
        s_ref, mn_ref, mx_ref, ssq_ref = refs[i:i + 4]

        @pl.when(pl.program_id(2) == 0)
        def _init():
            s_ref[:] = jnp.zeros_like(s_ref)
            ssq_ref[:] = jnp.zeros_like(ssq_ref)
            mn_ref[:] = jnp.full_like(mn_ref, _BIG)
            mx_ref[:] = jnp.full_like(mx_ref, -_BIG)

        j = pl.program_id(1)
        nb = s_ref.shape[0]
        dtype = ein_ref.dtype
        precision = mxu_precision(dtype)
        # in-register one-hot: edge e belongs to local row r iff its
        # receiver id equals j*Nb + r; padding edges carry id -1 and never
        # match, so they are excluded from every moment
        rows = j * nb + jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
        ids = ids_ref[:]  # [Eb, 1]
        mine32 = (ids == rows).astype(jnp.float32)  # [Eb, Nb]
        minef = mine32.astype(dtype)
        msg = ein_ref[:]
        if has_recv:
            # in-kernel receiver gather: each one-hot row copies exactly one
            # row of the receiver-projected node block (exact in any dtype).
            # Edges owned by other row blocks get a zero gather row — their
            # (wrong) message is zeroed by the same one-hot in every
            # contraction below.
            msg = jax.lax.dot_general(
                minef,
                nrecv_ref[:],
                (((1,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32,
            ).astype(dtype) + msg
        if has_gate:
            msg = msg * gate_ref[:]
        msg32 = msg.astype(jnp.float32)
        fp32 = jax.lax.Precision.HIGHEST

        def scatter(values, precision):
            # [Nb, Cb] += one-hot.T @ values: contract over the edge axis
            return jax.lax.dot_general(
                mine32 if values.dtype == jnp.float32 else minef,
                values,
                (((0,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32,
            )

        # sum / sumsq: f32 accumulation. The squares are f32 whatever the
        # stream dtype (see reference_multi_agg), so their contraction is
        # always fp32: at DEFAULT the MXU would round them back to bf16
        s_ref[:] += scatter(msg, precision)
        ssq_ref[:] += scatter(msg32 * msg32, fp32)

        # min / max: segmented running extremum over each receiver's
        # contiguous run of edges. After steps d = 1, 2, 4, ... the value on
        # edge e covers the last 2d edges of its run, so ceil(log2(run))
        # steps suffice and real runs are bounded by max_degree (the dummy
        # node's longer run stays unspecified, like every moment of it).
        eb, cb = msg32.shape
        idf = jnp.broadcast_to(ids, (eb, cb))
        edge = jax.lax.broadcasted_iota(jnp.int32, (eb, cb), 0)
        lo = hi = msg32
        d = 1
        while d < min(max_degree, eb):
            # roll by d along the edge axis: row e sees row e - d (rows
            # e < d wrap around and are masked out)
            same = (pltpu.roll(idf, d, 0) == idf) & (edge >= d)
            lo = jnp.where(same, jnp.minimum(lo, pltpu.roll(lo, d, 0)), lo)
            hi = jnp.where(same, jnp.maximum(hi, pltpu.roll(hi, d, 0)), hi)
            d *= 2
        # a run ends where the next edge has another receiver (or the
        # window ends: a run split across two windows contributes its two
        # partial extrema, merged by the accumulators' min/max)
        ends = (pltpu.roll(idf, eb - 1, 0) != idf) | (edge == eb - 1)
        # run-end values ride the one-hot contraction onto their node rows:
        # one 1.0 * x product per (node, window), exact under fp32
        # contraction; rows with no run end in this window are left alone
        present = scatter(ends.astype(jnp.float32), None) > 0.5
        mn_ref[:] = jnp.where(
            present,
            jnp.minimum(mn_ref[:], scatter(jnp.where(ends, lo, 0.0), fp32)),
            mn_ref[:],
        )
        mx_ref[:] = jnp.where(
            present,
            jnp.maximum(mx_ref[:], scatter(jnp.where(ends, hi, 0.0), fp32)),
            mx_ref[:],
        )

    return kernel


# the launch's tiles: output rows a block, edges a streamed window, channels
# a block. Stated here and nowhere else; a change to them is an edit of this
# line, claimed in a benchmark cell
BLOCK_ROWS, BLOCK_EDGES, BLOCK_COLS = 128, 512, 128


def normalize_tiles(
    c, dtype, has_recv, has_gate,
    block_rows=BLOCK_ROWS, block_edges=BLOCK_EDGES, block_cols=BLOCK_COLS,
):
    """Clamp requested tiles to what the launch runs: ``block_cols`` to the
    lane-padded channel width, ``block_edges`` by the VMEM-fit shrink loop.

    The one clamp, applied by ``fused_multi_agg`` BEFORE the tiles become
    ``custom_jvp`` non-differentiable arguments, so two requests that clamp
    to one program share one executable instead of keying the jit cache on
    the unclamped request.
    """
    nb, eb = block_rows, block_edges
    c128 = c + (-c) % 128
    cb = min(block_cols, c128)

    # VMEM fit: shrink the edge window until the resident working set —
    # double-buffered streams, the four f32 accumulators, the one-hot and
    # the [Eb, Cb] f32 temporaries of the min/max scan (msg32, lo, hi, ids,
    # iota, two rolled copies) — fits comfortably. As in the fused edge
    # kernel, the redundant-revisit cost is eb-invariant
    # (K ~ Nb*max_degree/eb), so shrinking eb is nearly free.
    itemsize = jnp.dtype(dtype).itemsize

    def _vmem_estimate(eb_):
        return (
            2 * eb_ * cb * itemsize * (1 + int(has_gate))  # edge streams
            + 2 * nb * cb * itemsize * int(has_recv)       # node_recv block
            + 2 * 4 * nb * cb * 4                          # accumulators
            + eb_ * nb * 4                                 # one-hot
            + 8 * eb_ * cb * 4                             # scan temporaries
        )

    while eb > 128 and _vmem_estimate(eb) > 12 * 1024 * 1024:
        eb //= 2
    return nb, eb, cb


def _forward(
    node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
    nb, eb, cb, interpret,
):
    e, c = edge_in.shape
    dtype = edge_in.dtype
    has_recv = node_recv is not None
    has_gate = gate is not None
    if has_recv:
        assert node_recv.shape[1] == c, (node_recv.shape, c)
    if has_gate:
        assert gate.shape == edge_in.shape, (gate.shape, edge_in.shape)

    ids = segment_ids.astype(jnp.int32)
    ein = _pad_to(_pad_to(edge_in, eb, 0), cb, 1)
    c_pad = ein.shape[1]
    operands = []
    if has_recv:
        nrecv = _pad_to(_pad_to(node_recv.astype(dtype), nb, 0), cb, 1)
        n_pad = nrecv.shape[0]
    else:
        n_pad = num_segments + (-num_segments) % nb

    # K inner windows cover the worst legal row block (degree-capped), +1
    # for edge-block misalignment; trailing zero blocks so estart[j] + k is
    # always in range (same scheme as pallas_segment._forward)
    k_windows = (nb * max_degree + eb - 1) // eb + 1
    k_windows = min(k_windows, ein.shape[0] // eb)
    k_windows = max(k_windows, 1)
    ein = jnp.pad(ein, ((0, k_windows * eb), (0, 0)))
    e_pad = ein.shape[0]
    if has_gate:
        g = _pad_to(_pad_to(gate.astype(dtype), eb, 0), cb, 1)
        g = jnp.pad(g, ((0, k_windows * eb), (0, 0)))

    ids_col = jnp.full((e_pad, 1), -1, jnp.int32).at[:e, 0].set(ids)

    j_blocks = n_pad // nb
    row_starts = jnp.searchsorted(
        ids, jnp.arange(j_blocks, dtype=jnp.int32) * nb, side="left"
    ).astype(jnp.int32)
    estart_block = row_starts // eb

    def edge_index(c_i, j, k, estart):
        return (estart[j] + k, c_i)

    def ids_index(c_i, j, k, estart):
        return (estart[j] + k, 0)

    def nrecv_index(c_i, j, k, estart):
        return (j, c_i)

    def out_index(c_i, j, k, estart):
        return (j, c_i)

    in_specs = [pl.BlockSpec((eb, 1), ids_index)]
    operands = [ids_col]
    if has_recv:
        in_specs.append(pl.BlockSpec((nb, cb), nrecv_index))
        operands.append(nrecv)
    in_specs.append(pl.BlockSpec((eb, cb), edge_index))
    operands.append(ein)
    if has_gate:
        in_specs.append(pl.BlockSpec((eb, cb), edge_index))
        operands.append(g)

    grid = (c_pad // cb, j_blocks, k_windows)
    moment = jax.ShapeDtypeStruct((n_pad, c_pad), jnp.float32)
    s, mn, mx, ssq = pl.pallas_call(
        _make_kernel(has_recv, has_gate, max_degree),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((nb, cb), out_index)] * 4,
        ),
        out_shape=[moment] * 4,
        interpret=interpret,
        name=tr.HG_MULTI_AGG,
    )(estart_block, *operands)

    # count is a [E]-read / [N]-write segment sum — negligible traffic next
    # to the [E, C] streams, and it drives the empty-segment fixup that the
    # dense segment_min/segment_max already apply (empty -> 0, not +/-BIG)
    cnt = jax.ops.segment_sum(
        jnp.ones((e,), jnp.float32), ids, num_segments=num_segments
    )
    nonempty = (cnt > 0.0)[:, None]
    s = s[:num_segments, :c]
    mn = jnp.where(nonempty, mn[:num_segments, :c], 0.0)
    mx = jnp.where(nonempty, mx[:num_segments, :c], 0.0)
    ssq = ssq[:num_segments, :c]
    return s, cnt, mn, mx, ssq


def fused_multi_agg(
    node_recv,
    edge_in,
    gate,
    segment_ids,
    num_segments: int,
    max_degree: int = 32,
    block_rows: int = BLOCK_ROWS,
    block_edges: int = BLOCK_EDGES,
    block_cols: int = BLOCK_COLS,
    interpret: bool = False,
):
    """Fused multi-moment aggregation of ``(node_recv[ids] + edge_in) *
    gate`` for receiver-sorted edges — (sum, count, min, max, sumsq), each
    f32, messages never materialized in HBM. ``node_recv`` and ``gate``
    are optional (None): PNA passes (node_recv, edge_in, None), PNAPlus
    adds its rbf Hadamard ``gate``, PNAEq passes its post-MLP message as
    ``edge_in`` alone.

    ``segment_ids`` MUST be ascending and segments holding more than
    ``max_degree`` edges get UNSPECIFIED moments — same contract and same
    blast-radius containment as ``sorted_segment_sum`` (the spill can also
    starve LATER segments inside the same row block; the framework routes
    every padding edge to the FINAL dummy node, so real segments stay
    exact — data/graph.py). The dummy-node row is garbage, masked
    downstream like every other kernel output here.

    Differentiable to arbitrary order: custom-JVP with the plain-jnp dense
    reference as tangent rule, so reverse mode recomputes the edge
    messages from the gathered inputs instead of storing [E, C] residuals.
    Tiles past the clamp (``normalize_tiles``) run, and compile, as the
    clamped ones.
    """
    tiles = normalize_tiles(
        edge_in.shape[1], edge_in.dtype, node_recv is not None,
        gate is not None, block_rows, block_edges, block_cols,
    )
    return _fused_multi_agg(
        node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
        *tiles, interpret,
    )


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_multi_agg(
    node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
    block_rows, block_edges, block_cols, interpret,
):
    with tr.scope(tr.HG_MULTI_AGG):
        return _forward(
            node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
            block_rows, block_edges, block_cols, interpret,
        )


@_fused_multi_agg.defjvp
def _jvp(num_segments, max_degree, block_rows, block_edges, block_cols,
         interpret, primals, tangents):
    node_recv, edge_in, gate, segment_ids = primals
    t_nr, t_ei, t_g, _ = tangents
    out = _fused_multi_agg(
        node_recv, edge_in, gate, segment_ids, num_segments, max_degree,
        block_rows, block_edges, block_cols, interpret,
    )
    # tangent in PLAIN jnp: the dense reference pushed through jax.jvp.
    # Reverse mode transposes it into a gather + elementwise + segment-op
    # backward that RECOMPUTES the messages from the (node-sized) gathered
    # inputs — the recompute schedule, not a stored-residual one — and
    # grad-of-grad just differentiates this rule again (energy-force).
    fn = lambda nr, ei, g: reference_multi_agg(
        nr, ei, g, segment_ids, num_segments
    )
    with tr.scope(tr.HG_MULTI_AGG + tr.TANGENT):
        _, t_out = jax.jvp(
            fn, (node_recv, edge_in, gate), (t_nr, t_ei, t_g)
        )
    return out, t_out
