"""Pallas TPU kernel: sorted-segment sum of edge messages.

The scatter-add ``out[i] = sum_{e: recv[e]==i} msg[e]`` sits on the hot path
of every message-passing model here (ops/segment.py -> jax.ops.segment_sum,
the torch_scatter analog, SURVEY §2.3 item 2). XLA lowers it to a serialized
scatter; with receivers *sorted* (free at batching time — edge order is
semantically irrelevant) the reduction becomes CSR-contiguous and maps onto
the MXU as a block-diagonal one-hot matmul:

- grid ``(C_blocks, row_blocks, K)``: for output row-block ``j``, the K
  inner steps stream the edge windows that can touch its rows (degree-capped
  graphs bound edges-per-row-block by ``Nb * max_degree``), and the output
  block is revisited across K as a standard reduction accumulator;
- the raw receiver ids stream beside the messages (4 bytes/edge) and the
  kernel builds the one-hot selector in-register with an iota compare
  ``ids == j*Nb + iota(Nb)`` — nothing but the payload ever touches HBM
  (an earlier revision materialized an [E, Nb] f32 one-hot operand: 128x
  the bandwidth of the ids and an extra scatter to build it);
- per step: ``acc[Nb, Cb] += onehot.T @ msg_window`` — an [Nb, Eb] x
  [Eb, Cb] MXU contraction instead of a scatter.

The backward pass of a segment sum is a gather, which XLA already does
well. Differentiation is a ``jax.custom_jvp`` whose tangent rule is the
PLAIN ``jax.ops.segment_sum`` of the tangent (a segment sum is linear):
reverse mode transposes that jnp tangent into the ``dout[recv]`` gather —
identical backward cost to the r5 custom-VJP — and, because no Pallas call
ever appears on a tangent path, the op composes under ``jax.grad`` to ANY
order. That second-order capability is what lets energy-force training
(forces = -dE/dpos inside the loss, differentiated again by the training
grad) use this kernel; the r5 custom_vjp was first-order only and raised
pallas_call's missing-JVP NotImplementedError on exactly that workload
(the since-dropped grad-energy guard in config/config.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ..utils import tracer as tr


def mxu_precision(dtype):
    """Contraction precision for the kernels' MXU dots. At DEFAULT the MXU
    rounds f32 operands to bf16 — measured on a v5e, a plain f32 segment
    sum came out 1.8e-3 off ``jax.ops.segment_sum``'s exact adds, and a
    one-hot "gather" no longer copies its row exactly, which the fused
    kernels assume. So f32 streams ask for fp32 contraction; bf16 streams
    are exact at DEFAULT (products of bf16 values, f32 accumulation)."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def _kernel(estart_ref, ids_ref, msg_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # in-register one-hot: edge e belongs to local row r iff its receiver id
    # equals j*Nb + r; padding edges carry id -1 and never match
    nb = out_ref.shape[0]
    rows = j * nb + jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    mine = (ids_ref[:] == rows).astype(msg_ref.dtype)  # [Eb, Nb]
    out_ref[:] += jax.lax.dot_general(
        mine,
        msg_ref[:],
        (((0,), (0,)), ((), ())),  # contract over the edge axis
        precision=mxu_precision(msg_ref.dtype),
        preferred_element_type=jnp.float32,
    )


def _pad_to(x, multiple, axis):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# the launch's tiles: output rows a block, edges a streamed window, channels
# a block. Stated here and nowhere else; a change to them is an edit of this
# line, claimed in a benchmark cell
BLOCK_ROWS, BLOCK_EDGES, BLOCK_COLS = 128, 512, 512


def normalize_tiles(c, block_rows=BLOCK_ROWS, block_edges=BLOCK_EDGES,
                    block_cols=BLOCK_COLS):
    """Clamp requested tiles to what the launch runs for ``c`` channels
    (``block_cols`` never exceeds the lane-padded channel width): the one
    clamp, applied by ``sorted_segment_sum`` before the tiles become
    ``custom_jvp`` non-differentiable arguments."""
    return block_rows, block_edges, min(block_cols, max(c, 128))


def sorted_segment_sum(
    messages,
    segment_ids,
    num_segments: int,
    max_degree: int = 32,
    block_rows: int = BLOCK_ROWS,
    block_edges: int = BLOCK_EDGES,
    block_cols: int = BLOCK_COLS,
    interpret: bool = False,
):
    """``segment_sum`` for receiver-sorted edges via the Pallas kernel.

    ``segment_ids`` MUST be ascending (sorted receivers), and any segment
    holding more than ``max_degree`` edges gets an UNSPECIFIED value (its
    trailing edges fall outside the K streamed windows) — and can starve
    LATER segments inside the same ``block_rows`` row block, whose edges
    get pushed past those windows (subsequent row blocks are unaffected:
    each gets its own ``estart``). Real nodes of this
    framework's batches satisfy the cap (data/neighbors.py caps in-degree;
    ``GraphLoader(sort_edges=True)`` sorts receivers; the loader validates
    real in-degrees against the bound) — but the final *padding* node
    receives every padding edge and will exceed it: its slot must be masked
    downstream, which every consumer of the dummy-node convention already
    does (data/graph.py padding docs).
    Messages are [E, C] float; returns [num_segments, C]. Tiles past the
    clamp (``normalize_tiles``) run, and compile, as the clamped ones.
    """
    tiles = normalize_tiles(
        messages.shape[1], block_rows, block_edges, block_cols
    )
    return _sorted_segment_sum(
        messages, segment_ids, num_segments, max_degree, *tiles, interpret
    )


@functools.partial(
    jax.custom_jvp, nondiff_argnums=(2, 3, 4, 5, 6, 7)
)
def _sorted_segment_sum(
    messages, segment_ids, num_segments, max_degree, block_rows, block_edges,
    block_cols, interpret,
):
    with tr.scope(tr.HG_SORTED_SEGMENT):
        return _forward(
            messages, segment_ids, num_segments, max_degree, block_rows,
            block_edges, block_cols, interpret,
        )


def _forward(
    messages, segment_ids, num_segments, max_degree, nb, eb, cb, interpret,
):
    e, c = messages.shape
    dtype = messages.dtype

    ids = segment_ids.astype(jnp.int32)
    # messages stream in their own dtype (bf16 stays bf16 — half the HBM
    # traffic under mixed precision); the kernel's dot_general accumulates
    # in f32 via preferred_element_type either way.
    msg = _pad_to(messages, eb, 0)
    msg = _pad_to(msg, cb, 1)
    n_pad = num_segments + (-num_segments) % nb

    # K inner windows cover the worst legal row block (degree-capped), +1
    # for edge-block misalignment
    k_windows = (nb * max_degree + eb - 1) // eb + 1
    k_windows = min(k_windows, msg.shape[0] // eb)
    k_windows = max(k_windows, 1)
    # trailing zero blocks so estart[j] + k is always in range — never clamp
    # (a clamp would re-read one block for several k and double-count edges).
    # k_windows blocks of slack: estart can point one block past the data
    # when a trailing row block owns no edges.
    msg = jnp.pad(msg, ((0, k_windows * eb), (0, 0)))
    e_pad = msg.shape[0]

    # receiver ids stream beside the messages; padding edges get id -1 so
    # the in-kernel iota compare never selects them
    ids_col = jnp.full((e_pad, 1), -1, jnp.int32).at[:e, 0].set(ids)

    # first edge-block index each row block may need (receivers sorted)
    j_blocks = n_pad // nb
    row_starts = jnp.searchsorted(
        ids, jnp.arange(j_blocks, dtype=jnp.int32) * nb, side="left"
    ).astype(jnp.int32)
    estart_block = row_starts // eb

    def msg_index(c_i, j, k, estart):
        return (estart[j] + k, c_i)

    def ids_index(c_i, j, k, estart):
        return (estart[j] + k, 0)

    def out_index(c_i, j, k, estart):
        return (j, c_i)

    grid = (msg.shape[1] // cb, j_blocks, k_windows)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((eb, 1), ids_index),
                pl.BlockSpec((eb, cb), msg_index),
            ],
            out_specs=pl.BlockSpec((nb, cb), out_index),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, msg.shape[1]), jnp.float32),
        interpret=interpret,
        name=tr.HG_SORTED_SEGMENT,
    )(estart_block, ids_col, msg)
    return out[:num_segments, :c].astype(dtype)


@_sorted_segment_sum.defjvp
def _jvp(num_segments, max_degree, block_rows, block_edges, block_cols,
         interpret, primals, tangents):
    messages, segment_ids = primals
    t_msg, _ = tangents  # integer ids get a float0 tangent — no gradient
    out = _sorted_segment_sum(
        messages, segment_ids, num_segments, max_degree, block_rows,
        block_edges, block_cols, interpret,
    )
    # tangent in PLAIN jnp (a segment sum is linear in the messages): its
    # transpose is the ``dout[recv]`` gather — the same XLA-fast backward
    # as the r5 custom_vjp — and it is differentiable to any order, so
    # grad-of-grad (energy-force training) composes instead of hitting
    # pallas_call's missing JVP rule.
    with tr.scope(tr.HG_SORTED_SEGMENT + tr.TANGENT), \
            tr.scope(tr.HG_ROW_GATHER):
        t_out = jax.ops.segment_sum(
            t_msg, segment_ids, num_segments=num_segments
        ).astype(out.dtype)
    return out, t_out
