"""Pallas TPU kernel: segment-masked flash attention for GPS global attention.

GPS global attention (models/gps.py, reference hydragnn/globalAtt/gps.py:
125-141) is block-diagonal over graphs: node i attends node j iff both are
real and share a graph. The incumbent TPU paths materialize the score
matrix in HBM — ``[G, H, Nmax, Nmax]`` for the per-graph gathered layout,
``[H, N, N]`` for the flat masked fallback — and the masked fallback also
*computes* every cross-graph pair just to throw it away.

This kernel is FlashAttention-style online-softmax tiling (PAPERS.md: Dao
et al.; Rabe & Staats) specialized to the sorted block-diagonal layout the
batcher already produces (graphs contiguous along the flat node axis,
data/graph.py):

- grid ``(H, q_blocks, K)``: for query block ``j`` the K inner steps
  stream only the key/value blocks its graphs can touch. The window is
  scheduled like the sorted-segment kernels' ``estart`` scheme
  (ops/pallas_segment.py): ``node_graph`` ascends along the flat layout,
  so a searchsorted over it gives each q-block's first/last k-block as
  scalar-prefetch arrays. Cross-graph tiles are never visited: the block
  index map CLAMPS to the window's last block and ``pl.when`` skips the
  recompute, so an out-of-window step moves no data, but it is still a grid
  step (0.1-0.3 us each on a v5e, PERF.md section 6, PR 34), and K is the
  worst legal window's length for every query block;
- per visited tile: ``s = q @ k.T`` on the MXU (f32 accumulation),
  same-graph masking by an in-register compare of the streamed per-node
  graph-id column/row (padding nodes carry id -1 and never match), and
  the standard running-max/denominator update in f32 VMEM scratch. The
  ``[*, N, N]`` logits never exist in HBM — only q/k/v tiles and the
  final ``[N, H, d]`` output move;
- inputs stream in their own dtype (bf16 halves the traffic under mixed
  precision); probabilities are cast back to the streaming dtype for the
  ``p @ v`` MXU dot, accumulation stays f32 (the same contract as
  ops/pallas_segment.py).

The kernel also emits the running (max, denominator) statistics, which is
what makes the single-graph regime reusable: ``flash_block_summary``
returns the UN-normalized online-softmax partial ``(m, l, acc)`` of local
queries against one K/V block, and ``parallel/ring_attention.py`` merges
those partials across ring steps in plain jnp — the per-chip block of
ring attention rides the same inner loop instead of a dense einsum.

``flash_causal_attention`` is the decoder's entry (models/decoder.py): the same
tiling with a CAUSAL mask inside the segment mask (key index <= query index
within a graph: nodes of a graph are contiguous and ordered, so it is one
more in-register compare of two iotas and a window that ends at the query
block's own last row), grouped-query heads (the key/value index map divides
the head index by the group size) and a TILED backward: two more Pallas
launches under the forward's own schedule (``hg_flash_attention_bwd``), one
holding a query block and streaming its keys for ``dq``, one holding a
key/value block and streaming its queries for ``dk``/``dv``, both rebuilding
the probabilities from the forward's saved log-sum-exp. No ``[*, N, N]``
array exists in either direction, so a graph of 8192 nodes trains. It is a
first-order ``custom_vjp`` (the token loss needs no more); what its backward
keeps of the forward's result is the output and ONE float32 a (head, row) of
the log-sum-exp (the launch writes each across 128 lanes; both backward
launches read the row form), both tagged (ops/remat.py
``CAUSAL_FLASH_RESIDUAL_NAMES``) so that a decoder layer's remat keeps them and
the forward launch runs once a step. A static ``window``
W makes the launches a SLIDING layer's (models/afmoe.py): query ``i`` sees key
``j`` iff same graph, ``j <= i`` and ``i - j < W``. That is window arithmetic
(a query block's walk starts at the tile of ``row0 - W + 1``, a key block's
ends at the tile of ``col_last + W - 1``: ``_causal_windows``) and one more
iota compare in the three kernels' mask; the launches carry the name
``hg_flash_window`` / ``_bwd``, and ``window=None`` traces the kernels as they
were (the jaxprs of all three launches are the ones without the argument).
A learned ``select`` (models/keyevl2.py: the keys an indexer picks,
ops/pallas_dsa_indexer.py's bitmask) is one more AND in the three kernels'
mask, the held block's bits unpacked with one shift a tile (``dk``/``dv``
reads the transposed bitmask); every causal tile is still visited; the
launches carry the name ``hg_flash_sparse`` / ``_bwd``, and ``select=None``
traces the kernels as they were.

Where each launch's window loop runs. The self-attention and block-summary
launches (GPS, the ring: windows of 2-4 tiles of 128) run it as the grid's
third axis, above. The three causal launches run it INSIDE the kernel when a
head's streamed operands fit VMEM whole: grid ``(H, q_blocks)`` (``(Hq,
k_blocks)`` for ``dk``/``dv``), the head's keys and values (for ``dk``/``dv``
its queries, their cotangents and the two statistics rows) one block each,
fetched once a key/value head and prefetched during the previous head's last
block, and a ``fori_loop`` from the block's first tile to its last slicing
tile ``b`` out of the resident arrays: the same tile body in the same order,
at the window's own length (documents of a median 1,024 tokens under a bound
of 8,192 fill a quarter of the worst window). The rule is by shape, at trace
time: rows x (query/key width + value width) x itemsize of one head, one copy,
against ``CAUSAL_RESIDENT_BYTES`` (``_resident``); both decoder cells and
every pack up to 64k slots at 128-wide bf16 heads take the loop, a longer pack
keeps the grid schedule. The resident launches raise Mosaic's scoped VMEM
limit (``_compiler_params``); no other kernel here sets compiler parameters.

GPS differentiation is the house custom-JVP: only the primal runs Pallas; the
tangent rule is the plain-jnp per-graph gathered reference pushed through
``jax.jvp`` (G·Nmax² work, not N²), so reverse mode transposes to the
dense-recompute backward and the op composes under ``jax.grad`` to ANY
order — energy-force (grad-of-grad) training works. Call sites wrap the
op in ``jax.checkpoint`` (models/gps.py) so the tangent residuals (the
per-graph probability blocks) are recomputed in the backward instead of
stored by the forward: the training forward keeps the flash memory
profile, the backward pays the gathered-dense recompute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from ..utils import envflags
from ..utils import tracer as tr
from jax.experimental.pallas import tpu as pltpu

from .pallas_segment import _pad_to, mxu_precision
from .remat import CAUSAL_FLASH_RESIDUAL_NAMES, tag

# masking constant: large-negative instead of finfo.min so the f32
# running-max arithmetic (exp of differences) never overflows; shared by
# the kernel and the jnp references so their masked maxima agree exactly
_NEG = -1.0e30

# the launches' tiles, queries by keys: the self-attention and block-summary
# launches (graphs of tens of nodes), and the decoder's causal launch with its
# tiled backward (graphs of thousands). Stated here and nowhere else; a change
# to them is an edit of these lines, claimed in a benchmark cell
BLOCK_Q, BLOCK_K = 128, 128
CAUSAL_BLOCK_Q, CAUSAL_BLOCK_K = 512, 512
# the causal launches' schedule: a head's streamed operands (one copy: its
# keys and values, for the ``dk``/``dv`` launch its queries and their
# cotangents) up to this size stay in VMEM whole and the window's loop runs
# inside the kernel; a longer pack streams tiles under the grid
CAUSAL_RESIDENT_BYTES = 32 * 2 ** 20


def normalize_tiles(block_q=BLOCK_Q, block_k=BLOCK_K):
    """Snap requested tiles to the kernel's alignment contract: ``block_q``
    to the 16-row sublane tile (covers bf16), ``block_k`` to the 128-lane
    tile. The one clamp, applied by the three entry points before the tiles
    become ``custom_jvp`` / ``custom_vjp`` non-differentiable arguments; the
    launches themselves require aligned blocks."""
    bq = max(16, block_q - block_q % 16)
    bk = max(128, block_k - block_k % 128)
    return bq, bk


def _flash_route_enabled() -> bool:
    """Whether GPS attention routes to the Pallas flash kernel.

    Same trace-time contract as ``ops.segment._pallas_route_enabled``:
    ``HYDRAGNN_PALLAS_FLASH=0/1`` overrides; otherwise the default backend
    decides. Off-TPU forcing runs the kernel in interpret mode (the CPU
    dryrun / CI smoke route).
    """
    pref = envflags.env_force("HYDRAGNN_PALLAS_FLASH")
    if pref is not None:
        return pref
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# plain-jnp references: the flat-masked oracle (tests), the per-graph
# gathered tangent rule, and the one-block summary (ring attention)
# ---------------------------------------------------------------------------


def reference_masked_attention(q, k, v, node_graph, node_mask):
    """Flat ``[N, N]``-masked softmax attention — the dense oracle, stated
    exactly like the ``max_nodes_per_graph == 0`` fallback in models/gps.py
    (rows with no valid key are zeroed rather than left as softmax garbage,
    matching the kernel's empty-row convention)."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)).astype(q.dtype)
    same = (node_graph[:, None] == node_graph[None, :]) & (
        node_mask[:, None] & node_mask[None, :]
    )
    logits = jnp.einsum("ihd,jhd->hij", q, k) * scale
    logits = jnp.where(same[None], logits, jnp.asarray(_NEG, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v)
    has_key = jnp.any(same, axis=1)
    return jnp.where(has_key[:, None, None], out, 0.0)


def reference_gathered_attention(q, k, v, node_graph, node_mask, num_graphs,
                                 max_nodes_per_graph):
    """Per-graph gathered dense attention — the ``[G, Nmax]`` layout of
    models/gps.py restated over ``[N, H, d]`` operands. Same function as
    the masked oracle on real rows (graphs within the static bound); this
    is the kernel's TANGENT rule: G·Nmax² work instead of N²."""
    n, _, d = q.shape
    nmax = max_nodes_per_graph
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)).astype(q.dtype)
    counts = jnp.zeros((num_graphs,), jnp.int32).at[node_graph].add(
        node_mask.astype(jnp.int32)
    )
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    slot = jnp.arange(nmax, dtype=jnp.int32)
    valid = slot[None, :] < counts[:, None]
    idx = jnp.where(valid, starts[:, None] + slot[None, :], n - 1)
    qg, kg, vg = q[idx], k[idx], v[idx]  # [G, Nmax, H, d]
    logits = jnp.einsum("gihd,gjhd->ghij", qg, kg) * scale
    logits = jnp.where(
        valid[:, None, None, :], logits, jnp.asarray(_NEG, logits.dtype)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    og = jnp.einsum("ghij,gjhd->gihd", probs, vg)
    out = jnp.zeros_like(q).at[idx.reshape(-1)].add(
        og.reshape(idx.size, *q.shape[1:])
        * valid.reshape(-1, 1, 1).astype(q.dtype)
    )
    return out


def reference_block_summary(q, k, v, key_mask):
    """One online-softmax partial of all queries against ONE key/value
    block, in plain jnp: ``m = rowmax``, ``l = sum exp(s - m)``,
    ``acc = exp(s - m) @ v`` — the quantity ring attention merges across
    steps. Fully-masked rows return ``(_NEG, 0, 0)``."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)).astype(q.dtype)
    logits = jnp.einsum("qhd,khd->qhk", q, k) * scale
    logits = jnp.where(
        key_mask[None, None, :], logits, jnp.asarray(_NEG, logits.dtype)
    )
    m = jnp.max(logits, axis=-1)  # [n_q, H]
    p = jnp.where(
        key_mask[None, None, :], jnp.exp(logits - m[..., None]), 0.0
    )
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("qhk,khd->qhd", p, v)
    return m, l, acc


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _pair_mask(gid_rows, gid_cols, row0, col0, causal, rows_are_queries=True,
               window=None, select=None):
    """Same-graph mask of one tile from the streamed graph-id column
    ``[R, 1]`` and row ``[1, C]`` (padding nodes carry -1 on the key side
    and never match); under ``causal`` also key index <= query index, from
    the tile's first flat row/column index, under a sliding ``window``
    query index - key index < ``window``, and under a learned selection the
    tile's unpacked bits ``select [R, C]``."""
    keys = gid_cols if rows_are_queries else gid_rows
    mask = (gid_rows == gid_cols) & (keys >= 0)
    if causal:
        shape = (gid_rows.shape[0], gid_cols.shape[1])
        r = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        c = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = mask & ((c <= r) if rows_are_queries else (r <= c))
        if window is not None:
            mask = mask & ((r - c < window) if rows_are_queries else (c - r < window))
    if select is not None:
        mask = mask & select
    return mask


def _selected(sel_ref, b):
    """The bits of tile ``b`` (of the walked side) in a held block of the
    selection bitmask ``[groups, rows, 512]`` (ops/pallas_dsa_indexer.py's
    layout): one leading-axis slice and one shift."""
    groups = sel_ref.shape[0]
    return (jax.lax.shift_right_arithmetic(sel_ref[b % groups], b // groups) & 1) != 0


def _softmax_tile(q, k_ref, v_ref, at, mask_tile, m_scr, l_scr, acc_scr, scale):
    """One tile of the online softmax, into the running statistics: ``q``
    ``[Bq, d]`` against the key and value tile at index ``at`` of their refs
    (the whole block under the grid, one tile's rows of the resident head
    under the in-kernel loop), masked by ``mask_tile()``. One body, in one
    order, for both schedules."""
    s = jax.lax.dot_general(
        q,
        k_ref[at],
        (((1,), (1,)), ((), ())),  # contract the head dim: q @ k.T
        precision=mxu_precision(q.dtype),
        preferred_element_type=jnp.float32,
    ) * scale  # [Bq, Bk] f32
    mask = mask_tile()
    s = jnp.where(mask, s, _NEG)
    m_prev = m_scr[:, 0:1]
    l_prev = l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    # fully-masked tiles keep m_new == m_prev == _NEG: exp(0) == 1 on
    # the correction, so the explicit where() is what zeroes them
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_scr[:] = jnp.broadcast_to(
        l_prev * corr + jnp.sum(p, axis=1, keepdims=True), l_scr.shape
    )
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype),  # bf16 streams hit the MXU fast path
        v_ref[at],
        (((1,), (0,)), ((), ())),
        precision=mxu_precision(v_ref.dtype),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)


def _tile_rows(b, block):
    """Rows of tile ``b`` of a head's resident array."""
    return pl.ds(pl.multiple_of(b * block, block), block)


def _kernel(kstart_ref, klast_ref, gidq_ref, gidk_ref, q_ref, k_ref, v_ref,
            *refs, scale, emit_stats, causal=False, resident_block_k=0,
            window=None, sparse=False):
    # stats outputs exist only for the block-summary (ring) launch: the
    # self-attention launch would have to WRITE two [H, N, 128] f32 arrays
    # to HBM just to discard them (pallas outputs cannot be DCE'd)
    sel_ref = None
    if sparse:  # the held query block's selection bits, an input
        sel_ref, *refs = refs
    select = lambda b: _selected(sel_ref, b) if sparse else None
    if emit_stats == "lse":  # one array: each row's log-sum-exp
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    elif emit_stats:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)

    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _finalize():
        l = l_scr[:, 0:1]
        # rows with no valid key (padding queries): l == 0, acc == 0 -> 0
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if emit_stats == "lse":
            # rows with no key keep a finite value (and a zero output)
            lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
        elif emit_stats:
            m_ref[0] = m_scr[:]
            l_ref[0] = l_scr[:]

    if resident_block_k:
        # the head's keys, values and key ids are resident: walk this query
        # block's own window, first tile to last, and no step more
        bk = resident_block_k

        def _tile(kb, _):
            q = q_ref[0]
            _softmax_tile(
                q, k_ref, v_ref, (0, _tile_rows(kb, bk)),
                lambda: _pair_mask(gidq_ref[:], gidk_ref[kb], j * q.shape[0],
                                   kb * bk, causal, window=window, select=select(kb)),
                m_scr, l_scr, acc_scr, scale,
            )

        _init()
        jax.lax.fori_loop(kstart_ref[j], klast_ref[j] + 1, _tile, None)
        _finalize()
        return

    kk = pl.program_id(2)
    pl.when(kk == 0)(_init)

    # out-of-window steps clamp their block index to the window's last
    # block (no DMA: the block is already resident) and skip the update;
    # each is still a grid step
    @pl.when(kstart_ref[j] + kk <= klast_ref[j])
    def _step():
        q = q_ref[0]  # [Bq, d_pad]
        _softmax_tile(
            q, k_ref, v_ref, (0,),
            lambda: _pair_mask(
                gidq_ref[:], gidk_ref[:], j * q.shape[0],
                (kstart_ref[j] + kk) * k_ref.shape[1], causal, window=window,
                select=select(kstart_ref[j] + kk),
            ),
            m_scr, l_scr, acc_scr, scale,
        )

    pl.when(kk == pl.num_programs(2) - 1)(_finalize)


def _lane_multiple(d: int) -> int:
    """What a head width is padded to a multiple of. The lane tile, 128;
    a width that is a whole number of HALF tiles and wider than one tile
    (MLA's 192-wide queries and keys) streams as it is: the block spans the
    array's whole minor axis, and neither the score product's contraction
    nor the operand's bytes grow to 256."""
    return 64 if d > 128 and d % 64 == 0 else 128


def _heads_first(x, block):
    x = _pad_to(_pad_to(x, block, 0), _lane_multiple(x.shape[2]), 2)
    return jnp.transpose(x, (1, 0, 2))  # [H, N_pad, d_pad]


def _resident(n_pad, width, dtype) -> bool:
    """The causal launches' schedule, by shape: whether a head's streamed
    operands (``n_pad`` rows, ``width`` lanes in all, one copy) stay in VMEM
    whole, so that the window's loop runs inside the kernel."""
    return n_pad * width * jnp.dtype(dtype).itemsize <= CAUSAL_RESIDENT_BYTES


def _compiler_params(resident: bool):
    """Mosaic's scoped VMEM limit for a launch that holds a head resident:
    two copies of it (Pallas double-buffers every block: the next head's
    arrives during this head's last block) and room for the tiles."""
    if not resident:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=2 * CAUSAL_RESIDENT_BYTES + 32 * 2 ** 20)


def _causal_name(window, select=None) -> str:
    """A launch's name in the device trace: the sliding launches and those
    under a learned selection carry their own, so that a trace tells the
    kinds of one step apart."""
    if select is not None:
        return tr.HG_FLASH_SPARSE
    return tr.HG_FLASH_ATTENTION if window is None else tr.HG_FLASH_WINDOW


def _walked(resident: bool, id_row, block, widths, windows, head):
    """The side of a launch that its window walks (keys and values of the
    forward and ``dq`` launches, queries and their cotangents of the
    ``dk``/``dv`` launch), under either schedule: -> (the grid's axes after
    (head, held block), the id row ``[1, n_pad]`` as the kernel reads it, its
    spec, one spec per operand width of ``widths``). Resident: no inner axis,
    a head's arrays one block each (their block index moves with ``head(h)``
    only, so they are fetched once a key/value head) and the ids as
    ``[blocks, 1, block]`` (a tile's ids are an index of the leading axis,
    not a dynamic lane slice). Streamed: ``windows`` inner steps whose block
    index clamps to the window's last block."""
    n_pad = id_row.shape[1]
    if resident:
        return ((), id_row.reshape(n_pad // block, 1, block),
                pl.BlockSpec((n_pad // block, 1, block), lambda *_: (0, 0, 0)),
                [pl.BlockSpec((1, n_pad, w), lambda h_i, *_: (head(h_i), 0, 0))
                 for w in widths])

    def tile(j, kk, first, last):
        return jnp.minimum(first[j] + kk, last[j])

    return ((windows,), id_row,
            pl.BlockSpec((1, block), lambda h_i, *a: (0, tile(*a))),
            [pl.BlockSpec((1, block, w), lambda h_i, *a: (head(h_i), tile(*a), 0))
             for w in widths])


def _forward(q, k, v, gid_q, gid_k, kstart, klast, k_windows,
             block_q, block_k, interpret, emit_stats=False, causal=False,
             padded=False, window=None, select=None):
    """Shared launch: q ``[Nq, H, d]`` against k/v ``[Nk, H, d]`` with
    per-q-block key-window schedule (kstart/klast in k-block units) and
    per-node graph ids (-1 = never a valid key). Returns the normalized
    ``o [Nq, H, d]`` (operand dtype); with ``emit_stats`` also the f32
    running statistics ``(m [Nq, H], l [Nq, H])`` as extra HBM outputs —
    only the block-summary launch pays for them. ``k``/``v`` may carry fewer
    heads than ``q`` (grouped-query: head ``h`` reads key/value head
    ``h // group``). ``padded`` returns the launch's own arrays instead
    (``o [H, Nq_pad, d_pad]``, ``m``/``l [H, Nq_pad, 128]`` lane-broadcast):
    what the tiled backward streams. A ``causal`` launch whose head fits
    (``_resident``) takes the grid ``(H, q_blocks)``: a head's keys, values
    and key ids are one block each and each query block's window is a loop
    inside the kernel, at its own length. ``window`` (causal launches only)
    is the sliding bound of the mask; the schedule it is given already stops
    at it, and the launch carries the name ``hg_flash_window``. ``select``
    (causal launches only) is a learned selection's bitmask over the padded
    queries, ANDed into every tile's mask (``hg_flash_sparse``)."""
    nq, h, d = q.shape
    nk = k.shape[0]
    group = h // k.shape[1]
    bq, bk = block_q, block_k
    scale = 1.0 / float(d) ** 0.5

    qt = _heads_first(q, bq)
    kt = _heads_first(k, bk)
    vt = _heads_first(v, bk)
    # queries and keys share a width, values (and the output) have their own
    d_pad, dv_pad = qt.shape[2], vt.shape[2]
    nq_pad, nk_pad = qt.shape[1], kt.shape[1]
    j_blocks = nq_pad // bq
    k_blocks = nk_pad // bk
    k_windows = max(1, min(k_windows, k_blocks))
    resident = causal and _resident(nk_pad, d_pad + dv_pad, kt.dtype)

    gq = jnp.full((nq_pad, 1), -1, jnp.int32).at[:nq, 0].set(
        gid_q.astype(jnp.int32)
    )
    gk = jnp.full((1, nk_pad), -1, jnp.int32).at[0, :nk].set(
        gid_k.astype(jnp.int32)
    )
    kstart = jnp.clip(kstart.astype(jnp.int32), 0, k_blocks - 1)
    klast = jnp.clip(klast.astype(jnp.int32), 0, k_blocks - 1)

    def held(h_i, j, *_):  # this query block's: q, gid column, outputs
        return (h_i, j, 0)

    inner, gk, gidk_spec, (k_spec, v_spec) = _walked(
        resident, gk, bk, (d_pad, dv_pad), k_windows, lambda h_i: h_i // group)
    sel_specs, sel_args = [], []
    if select is not None:  # the query block's bits, held beside it
        sel_specs = [pl.BlockSpec((select.shape[0], bq, select.shape[2]), lambda h_i, j, *_: (0, j, 0))]
        sel_args = [select]
    out_specs = [pl.BlockSpec((1, bq, dv_pad), held)]
    out_shape = [jax.ShapeDtypeStruct((h, nq_pad, dv_pad), q.dtype)]
    if emit_stats:
        stats = 1 if emit_stats == "lse" else 2
        out_specs += [pl.BlockSpec((1, bq, 128), held)] * stats
        out_shape += [jax.ShapeDtypeStruct((h, nq_pad, 128), jnp.float32)] * stats
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, emit_stats=emit_stats,
                          causal=causal, resident_block_k=bk if resident else 0,
                          window=window, sparse=select is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, j_blocks) + inner,
            in_specs=[
                pl.BlockSpec((bq, 1), lambda h_i, j, *_: (j, 0)),
                gidk_spec,
                pl.BlockSpec((1, bq, d_pad), held),
                k_spec,
                v_spec,
            ] + sel_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, dv_pad), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=_causal_name(window, select),
        compiler_params=_compiler_params(resident),
    )(kstart, klast, gq, gk, qt, kt, vt, *sel_args)
    if padded:
        return out
    o = jnp.transpose(out[0], (1, 0, 2))[:nq, :, :v.shape[2]]
    if not emit_stats:
        return o
    m = jnp.transpose(out[1][:, :, 0])[:nq]  # [Nq, H]
    l = jnp.transpose(out[2][:, :, 0])[:nq]
    return o, m, l


def _block_windows(node_graph, n, block_q, block_k, max_nodes_per_graph):
    """Per-q-block key-window schedule over the flat node layout.

    ``node_graph`` ascends (graphs contiguous, padding nodes in the final
    slot — data/graph.py), so the window of q-block ``j`` spans from the
    first node of the graph owning its first row to the last node of the
    graph owning its last row. The static inner-step count covers the
    worst legal window: a q block can touch at most
    ``block_q + 2·(Nmax - 1)`` nodes.
    """
    ng = node_graph.astype(jnp.int32)
    j_blocks = (n + block_q - 1) // block_q
    row0 = jnp.minimum(
        jnp.arange(j_blocks, dtype=jnp.int32) * block_q, n - 1
    )
    row1 = jnp.minimum(row0 + block_q - 1, n - 1)
    first = jnp.searchsorted(ng, ng[row0], side="left").astype(jnp.int32)
    last = jnp.searchsorted(ng, ng[row1], side="right").astype(jnp.int32) - 1
    k_windows = (block_q + 2 * max(max_nodes_per_graph - 1, 0)
                 + block_k - 1) // block_k + 1
    return first // block_k, last // block_k, k_windows


def flash_self_attention(
    q,
    k,
    v,
    node_graph,
    node_mask,
    num_graphs: int,
    max_nodes_per_graph: int,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
):
    """Segment-masked flash self-attention over the flat node array.

    ``q``/``k``/``v``: ``[N, H, d]``; attention is restricted to same-graph
    real-node pairs (``node_graph``/``node_mask``), exactly the semantics
    of both dense paths in models/gps.py. Requires the batcher's layout:
    graphs CONTIGUOUS along the node axis (``node_graph`` non-decreasing,
    padding nodes in the final slot) — the block schedule derives from it.
    A real graph larger than the static ``max_nodes_per_graph`` bound gets
    an UNSPECIFIED value (its key window is under-covered); the model
    layer poisons that case to NaN, same as the gathered-dense path.
    Padding rows come out 0 (the dense oracle leaves softmax garbage
    there; both are masked downstream).

    ``block_q`` is snapped to the sublane tile (16 covers bf16), ``block_k``
    to the 128-lane tile (``normalize_tiles``). Returns ``[N, H, d]`` in the
    operand dtype; logits/softmax accumulate in f32 and never touch HBM.
    Differentiable to arbitrary order (custom-JVP whose tangent is the
    plain-jnp gathered-dense reference), so energy-force training
    composes; wrap call sites in ``jax.checkpoint`` to keep the tangent
    residuals out of the training forward.
    """
    return _flash_self_attention(
        q, k, v, node_graph, node_mask, num_graphs, max_nodes_per_graph,
        *normalize_tiles(block_q, block_k), interpret,
    )


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_self_attention(
    q, k, v, node_graph, node_mask, num_graphs, max_nodes_per_graph,
    block_q, block_k, interpret,
):
    n = q.shape[0]
    with tr.scope(tr.HG_FLASH_ATTENTION):
        gid = jnp.where(node_mask, node_graph.astype(jnp.int32), -1)
        kstart, klast, k_windows = _block_windows(
            node_graph, n, block_q, block_k, max_nodes_per_graph
        )
        return _forward(
            q, k, v, gid, gid, kstart, klast, k_windows, block_q, block_k,
            interpret,
        )


@_flash_self_attention.defjvp
def _flash_jvp(num_graphs, max_nodes_per_graph, block_q, block_k, interpret,
               primals, tangents):
    q, k, v, node_graph, node_mask = primals
    t_q, t_k, t_v, _, _ = tangents
    out = _flash_self_attention(
        q, k, v, node_graph, node_mask, num_graphs, max_nodes_per_graph,
        block_q, block_k, interpret,
    )
    # tangent in PLAIN jnp — the per-graph gathered reference (G·Nmax²,
    # not N²) pushed through jax.jvp: linear in the tangents, built from
    # transposable primitives, differentiable to any order. Reverse mode
    # transposes it into the dense-recompute backward; jax.checkpoint at
    # the call site pushes its residuals (the per-graph probability
    # blocks) into the backward pass.
    fn = lambda q_, k_, v_: reference_gathered_attention(
        q_, k_, v_, node_graph, node_mask, num_graphs, max_nodes_per_graph
    )
    with tr.scope(tr.HG_FLASH_ATTENTION + tr.TANGENT):
        _, t_out = jax.jvp(fn, (q, k, v), (t_q, t_k, t_v))
    return out, t_out


def flash_block_summary(
    q,
    k,
    v,
    key_mask,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
):
    """Online-softmax partial of local queries against ONE key/value block
    — the single-graph regime of the flash kernel, reusing its inner loop.

    ``q [n_q, H, d]`` against ``k/v [n_k, H, d]`` with ``key_mask [n_k]``;
    returns ``(m [n_q, H], l [n_q, H], acc [n_q, H, d])`` such that the
    normalized attention over several blocks is the standard running-max
    merge of their partials (parallel/ring_attention.py does the merging
    in plain jnp between ``ppermute`` rotations). Fully-masked rows give
    ``(-1e30, 0, 0)``. Statistics are f32 inside the kernel and cast to
    the operand dtype on return (the ring carries match the dense route's
    dtypes either way).
    """
    return _flash_block_summary(
        q, k, v, key_mask, *normalize_tiles(block_q, block_k), interpret
    )


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5, 6))
def _flash_block_summary(q, k, v, key_mask, block_q, block_k, interpret):
    nq, nk = q.shape[0], k.shape[0]
    with tr.scope(tr.HG_FLASH_ATTENTION):
        gid_q = jnp.zeros((nq,), jnp.int32)
        gid_k = jnp.where(key_mask, 0, -1).astype(jnp.int32)
        k_blocks = (nk + block_k - 1) // block_k
        kstart = jnp.zeros(
            (max(1, (nq + block_q - 1) // block_q),), jnp.int32
        )
        klast = jnp.full_like(kstart, k_blocks - 1)
        o, m, l = _forward(
            q, k, v, gid_q, gid_k, kstart, klast, k_blocks, block_q,
            block_k, interpret, emit_stats=True,
        )
        dt = q.dtype
        # un-normalize: acc = o * l (exact where l > 0; both zero where
        # l == 0)
        return m.astype(dt), l.astype(dt), o * l[..., None].astype(dt)


@_flash_block_summary.defjvp
def _summary_jvp(block_q, block_k, interpret, primals, tangents):
    q, k, v, key_mask = primals
    t_q, t_k, t_v, _ = tangents
    out = _flash_block_summary(q, k, v, key_mask, block_q, block_k, interpret)
    fn = lambda q_, k_, v_: jax.tree_util.tree_map(
        lambda x: x.astype(q.dtype),
        reference_block_summary(q_, k_, v_, key_mask),
    )
    with tr.scope(tr.HG_FLASH_ATTENTION + tr.TANGENT):
        _, t_out = jax.jvp(fn, (q, k, v), (t_q, t_k, t_v))
    return out, t_out


# ---------------------------------------------------------------------------
# causal grouped-query attention with a tiled backward (the decoder stack)
# ---------------------------------------------------------------------------


def reference_causal_attention(q, k, v, node_graph, node_mask, window=None,
                               select=None):
    """Flat ``[N, N]``-masked causal grouped-query attention in plain jnp:
    node ``i`` attends node ``j`` iff both are real, share a graph and
    ``j <= i`` (and, under a sliding ``window``, ``i - j < window``; under a
    learned selection, bool ``select [N, N]``, iff ``select[i, j]``). ``q [N, Hq, d]``, ``k [N, Hk, d]``, ``v [N, Hk, dv]`` with
    ``Hq`` a multiple of ``Hk``. The oracle of the kernel and the route off
    the TPU; scores and softmax in float32."""
    n, hq, d = q.shape
    group = hq // k.shape[1]
    kf = jnp.repeat(k, group, axis=1)
    vf = jnp.repeat(v, group, axis=1)
    idx = jnp.arange(n)
    allowed = (
        (node_graph[:, None] == node_graph[None, :])
        & (node_mask[:, None] & node_mask[None, :])
        & (idx[None, :] <= idx[:, None])
    )
    if window is not None:
        allowed = allowed & (idx[:, None] - idx[None, :] < window)
    if select is not None:
        allowed = allowed & select
    logits = jnp.einsum(
        "ihd,jhd->hij", q, kf, preferred_element_type=jnp.float32
    ) * (1.0 / float(d) ** 0.5)
    logits = jnp.where(allowed[None], logits, _NEG)
    probs = jnp.where(allowed[None], jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("hij,jhd->ihd", probs.astype(v.dtype), vf)


def _causal_windows(node_graph, node_mask, block_q, block_k, max_nodes_per_graph,
                    window=None):
    """The causal schedule, both ways round. A query block's keys run from
    the first node of the graph owning its first row (under a sliding
    ``window`` W no earlier than row ``row0 - W + 1``) to its own last real
    row; a key block's queries run from its own first row to the last node of
    the graph owning its last real row (no later than ``col_last + W - 1``). A block of padding alone (padding is
    last) keeps its own tile, fully masked. -> (kstart, klast, k_windows) in
    k-block units per q block, (qstart, qlast, q_windows) in q-block units per
    k block. The static step counts cover the worst legal window: the grid
    schedule runs them for every block, the in-kernel loop does not."""
    ng = node_graph.astype(jnp.int32)
    n = ng.shape[0]
    nmax = max(max_nodes_per_graph, 1)
    reach = nmax if window is None else min(nmax, window)  # keys a query can see
    real = jnp.max(jnp.where(node_mask, jnp.arange(1, n + 1, dtype=jnp.int32), 0))

    def rows(block):
        blocks = (n + block - 1) // block
        row0 = jnp.minimum(jnp.arange(blocks, dtype=jnp.int32) * block, n - 1)
        last = jnp.clip(jnp.minimum(row0 + block - 1, real - 1), row0, n - 1)
        return row0, last

    q0, q1 = rows(block_q)
    first = jnp.searchsorted(ng, ng[q0], side="left").astype(jnp.int32)
    if window is not None:
        first = jnp.maximum(first, q0 - (window - 1))
    kstart = jnp.where(q0 < real, first, q0) // block_k
    k_windows = (block_q + reach - 1 + block_k - 1) // block_k + 1
    k0, k1 = rows(block_k)
    last = jnp.searchsorted(ng, ng[k1], side="right").astype(jnp.int32) - 1
    if window is not None:
        last = jnp.minimum(last, k1 + (window - 1))
    qlast = jnp.where(k0 < real, last, k1) // block_q
    q_windows = (block_k + reach - 1 + block_q - 1) // block_q + 1
    return (kstart, q1 // block_k, k_windows,
            k0 // block_q, qlast, q_windows)


def _dq_kernel(kstart_ref, klast_ref, gidq_ref, gidk_ref, q_ref, k_ref, v_ref,
               do_ref, o_ref, lse_ref, *refs, scale,
               resident_block_k=0, window=None, sparse=False):
    """One query block held, its key/value tiles walked for ``dq``: by the
    grid's third axis, or (``resident_block_k``) by a loop over the head's
    resident keys and values, as the forward does. ``lse_ref`` holds the
    head's statistics as lane-major rows ``[1, q_blocks, 1, Bq]``, the form
    the ``dk``/``dv`` launch reads. ``sparse``: the held block's selection
    bits come first among ``refs``."""
    sel_ref = None
    if sparse:
        sel_ref, *refs = refs
    dq_ref, delta_scr, lse_scr, acc_scr = refs
    j = pl.program_id(1)

    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        delta = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=1, keepdims=True,
        )
        delta_scr[:] = jnp.broadcast_to(delta, delta_scr.shape)
        # this block's statistics arrive as a lane-major row: across 128
        # sublanes, then turned, they are the column the tiles read
        row = lse_ref[0, j]  # [1, Bq]
        lse_scr[:] = jnp.transpose(jnp.broadcast_to(row, (lse_scr.shape[1], row.shape[1])))

    def _tile(kb, k, v, gidk):
        q, do = q_ref[0], do_ref[0]
        nt = (((1,), (1,)), ((), ()))
        prec = mxu_precision(q.dtype)
        s = jax.lax.dot_general(
            q, k, nt, precision=prec, preferred_element_type=jnp.float32
        ) * scale
        mask = _pair_mask(
            gidq_ref[:], gidk, j * q.shape[0], kb * k.shape[0], True,
            window=window, select=_selected(sel_ref, kb) if sparse else None,
        )
        p = jnp.where(mask, jnp.exp(s - lse_scr[:, 0:1]), 0.0)
        dp = jax.lax.dot_general(
            do, v, nt, precision=prec, preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_scr[:, 0:1]) * scale
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32,
        )

    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)

    if resident_block_k:
        def _walk(kb, _):
            rows = _tile_rows(kb, resident_block_k)
            _tile(kb, k_ref[0, rows], v_ref[0, rows], gidk_ref[kb])

        _init()
        jax.lax.fori_loop(kstart_ref[j], klast_ref[j] + 1, _walk, None)
        _finalize()
        return
    kk = pl.program_id(2)
    pl.when(kk == 0)(_init)
    pl.when(kstart_ref[j] + kk <= klast_ref[j])(
        lambda: _tile(kstart_ref[j] + kk, k_ref[0], v_ref[0], gidk_ref[:]))
    pl.when(kk == pl.num_programs(2) - 1)(_finalize)


def _dkv_kernel(qstart_ref, qlast_ref, gidk_ref, gidq_ref, k_ref, v_ref,
                q_ref, do_ref, lse_ref, delta_ref, *refs, scale, resident_block_q=0,
                window=None, sparse=False):
    """One key/value block held, its query tiles walked (by the grid's third
    axis, or by a loop over the query head's resident ``q``, ``do`` and
    statistics); every product is written transposed (``s.T = k @ q.T``) so
    that no tile is transposed in the kernel: the statistics arrive as
    lane-major rows ``[*, Bq]``. ``sparse``: the held key block's bits of
    the TRANSPOSED selection (rows keys, bits queries) come first among
    ``refs``."""
    sel_ref = None
    if sparse:
        sel_ref, *refs = refs
    dk_ref, dv_ref, dk_scr, dv_scr = refs
    i = pl.program_id(1)

    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(qb, q, do, gidq, lse, delta):
        k, v = k_ref[0], v_ref[0]
        nt = (((1,), (1,)), ((), ()))
        nn = (((1,), (0,)), ((), ()))
        prec = mxu_precision(q.dtype)
        st = jax.lax.dot_general(
            k, q, nt, precision=prec, preferred_element_type=jnp.float32
        ) * scale  # [Bk, Bq]
        mask = _pair_mask(
            gidk_ref[:], gidq, i * k.shape[0], qb * q.shape[0], True,
            rows_are_queries=False, window=window,
            select=_selected(sel_ref, qb) if sparse else None,
        )
        pt = jnp.where(mask, jnp.exp(st - lse), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, nn, precision=prec,
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v, do, nt, precision=prec, preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, nn, precision=prec,
            preferred_element_type=jnp.float32,
        )

    def _finalize():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]

    if resident_block_q:
        def _walk(qb, _):
            rows = _tile_rows(qb, resident_block_q)
            _tile(qb, q_ref[0, rows], do_ref[0, rows], gidq_ref[qb],
                  lse_ref[0, qb], delta_ref[0, qb])

        _init()
        jax.lax.fori_loop(qstart_ref[i], qlast_ref[i] + 1, _walk, None)
        _finalize()
        return
    qq = pl.program_id(2)
    pl.when(qq == 0)(_init)
    pl.when(qstart_ref[i] + qq <= qlast_ref[i])(
        lambda: _tile(qstart_ref[i] + qq, q_ref[0], do_ref[0], gidq_ref[:],
                      lse_ref[0][0:1, :], delta_ref[0][0:1, :]))
    pl.when(qq == pl.num_programs(2) - 1)(_finalize)


def causal_schedule_steps(node_graph, node_mask, max_nodes_per_graph: int, d: int, dv: int,
                          dtype, block_q: int = CAUSAL_BLOCK_Q,
                          block_k: int = CAUSAL_BLOCK_K, window=None):
    """What one head of a forward launch of :func:`flash_causal_attention`
    runs on these operands: -> (tiles visited, the sum over query blocks of
    their windows' lengths; steps scheduled for them: the same sum where the
    loop runs inside the kernel, ``q_blocks x k_windows`` under the grid).
    float32 scalars; the arithmetic and the rule are the launch's own, a
    sliding ``window``'s too."""
    bq, bk = normalize_tiles(block_q, block_k)
    n = node_graph.shape[0]
    ks, kl, kw, _, _, _ = _causal_windows(node_graph, node_mask, bq, bk, max_nodes_per_graph, window)
    visited = jnp.sum((kl - ks + 1).astype(jnp.float32))
    pad = lambda x, m: -(-x // m) * m
    k_blocks = pad(n, bk) // bk
    if _resident(k_blocks * bk, pad(d, _lane_multiple(d)) + pad(dv, _lane_multiple(dv)), dtype):
        return visited, visited
    return visited, jnp.float32(ks.shape[0] * max(1, min(kw, k_blocks)))


def _causal_prep(node_graph, node_mask, max_nodes_per_graph, block_q, block_k,
                 window=None):
    gid = jnp.where(node_mask, node_graph.astype(jnp.int32), -1)
    return gid, _causal_windows(
        node_graph, node_mask, block_q, block_k, max_nodes_per_graph, window
    )


def _causal_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                block_q, block_k, interpret, window=None, select=None):
    gid, (ks, kl, kw, _, _, _) = _causal_prep(
        node_graph, node_mask, max_nodes_per_graph, block_q, block_k, window
    )
    with tr.scope(_causal_name(window, select)):
        o_pad, lse = _forward(
            q, k, v, gid, gid, ks, kl, kw, block_q, block_k, interpret,
            emit_stats="lse", causal=True, padded=True, window=window,
            select=select,
        )
        o = jnp.transpose(o_pad, (1, 0, 2))[:q.shape[0], :, :v.shape[2]]
    return o, lse  # lse [H, Nq_pad, 128], lane-broadcast


def flash_causal_attention(
    q,
    k,
    v,
    node_graph,
    node_mask,
    max_nodes_per_graph: int,
    block_q: int = CAUSAL_BLOCK_Q,
    block_k: int = CAUSAL_BLOCK_K,
    interpret: bool = False,
    window=None,
    select=None,
):
    """Causal grouped-query flash attention over the flat node array.

    ``q [N, Hq, d]``, ``k [N, Hk, d]``, ``v [N, Hk, dv]`` (``Hq`` a multiple
    of ``Hk``; ``dv`` may differ from ``d``, as latent attention's 192-wide
    queries and keys beside 128-wide values do: the scale is ``1/sqrt(d)``,
    the output ``[N, Hq, dv]``); node ``i`` attends the real nodes ``j <= i``
    of its own graph. Same
    layout contract as :func:`flash_self_attention` (graphs contiguous,
    ``node_graph`` non-decreasing, padding last); a graph past
    ``max_nodes_per_graph`` is under-covered and the caller poisons it.
    Forward and backward are Pallas launches under one schedule (each
    block's own window, walked inside the kernel where a head fits VMEM:
    the module docstring); reverse mode only, first order. A static
    ``window`` W (a sliding layer) also bounds ``i - j < W``: every block's
    window then starts no earlier than W - 1 rows before it, the mask gains
    one compare, and the three launches are named ``hg_flash_window`` /
    ``_bwd``; ``None`` is the launches as they were. A learned ``select``
    (ops/pallas_dsa_indexer.py ``dsa_select``'s bitmask over the padded
    queries; the tiles must be its ``SELECT_TILE``) is one more AND in every
    tile's mask of the three launches, named ``hg_flash_sparse`` / ``_bwd``,
    and the call then returns ``(o, lse)``: each (query head, padded row)'s
    log-sum-exp over its selection, float32 ``[Hq, N_pad]``, which takes no
    gradient (the indexer's loss reads it); ``None`` is the launches as they
    were."""
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be a positive count of keys, got {window}")
    if select is not None:
        from .pallas_dsa_indexer import SELECT_TILE

        if window is not None or normalize_tiles(block_q, block_k) != (SELECT_TILE, SELECT_TILE):
            raise ValueError(f"a selection takes no window and tiles of {SELECT_TILE}")
        return _flash_sparse_attention(q, k, v, node_graph, node_mask, select, max_nodes_per_graph,
                                       SELECT_TILE, SELECT_TILE, interpret)
    return _flash_causal_attention(
        q, k, v, node_graph, node_mask, max_nodes_per_graph,
        *normalize_tiles(block_q, block_k), interpret,
        None if window is None else int(window),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_causal_attention(q, k, v, node_graph, node_mask,
                            max_nodes_per_graph, block_q, block_k, interpret,
                            window):
    return _causal_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                       block_q, block_k, interpret, window)[0]


def _causal_vjp_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                    block_q, block_k, interpret, window):
    o, lse = _causal_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                         block_q, block_k, interpret, window)
    # what the backward reads of the launch's result, under the names a
    # decoder layer's remat keeps (models/decoder.py ``remat_in_training``): the
    # output as it is returned, and ONE float32 a (head, padded row) of the
    # statistics (the launch writes it across 128 lanes)
    out_name, lse_name = CAUSAL_FLASH_RESIDUAL_NAMES
    o, lse_row = tag(o, out_name), tag(lse[:, :, 0], lse_name)
    return o, (q, k, v, o, lse_row, node_graph, node_mask)


def _causal_vjp_bwd(max_nodes_per_graph, block_q, block_k, interpret, window,
                    res, do, select=None):
    """The ``dq`` and ``dk``/``dv`` launches; under a learned ``select`` the
    ``dk``/``dv`` launch reads its transpose (``transpose_select``)."""
    q, k, v, o, lse_row, node_graph, node_mask = res
    n, hq, d = q.shape
    hk = k.shape[1]
    group = hq // hk
    bq, bk = block_q, block_k
    scale = 1.0 / float(d) ** 0.5
    gid, (ks, kl, kw, qs, ql, qw) = _causal_prep(
        node_graph, node_mask, max_nodes_per_graph, bq, bk, window
    )
    bwd_name = _causal_name(window, select) + tr.BWD
    with tr.scope(bwd_name):
        qt, dot, ot = (_heads_first(x, bq) for x in (q, do.astype(q.dtype), o))
        kt, vt = _heads_first(k, bk), _heads_first(v, bk)
        nq_pad, nk_pad = qt.shape[1], kt.shape[1]
        d_pad, dv_pad, dv = qt.shape[2], vt.shape[2], v.shape[2]
        j_blocks, k_blocks = nq_pad // bq, nk_pad // bk
        gcol = lambda npad: jnp.full((npad, 1), -1, jnp.int32).at[:n, 0].set(gid)
        grow = lambda npad: jnp.full((1, npad), -1, jnp.int32).at[0, :n].set(gid)
        clip = lambda a, hi: jnp.clip(a.astype(jnp.int32), 0, hi - 1)
        ks, kl = clip(ks, k_blocks), clip(kl, k_blocks)
        qs, ql = clip(qs, j_blocks), clip(ql, j_blocks)
        kw, qw = max(1, min(kw, k_blocks)), max(1, min(qw, j_blocks))

        # ---- dq: the forward's schedule, in the forward's place (the grid's
        # third axis, or the loop over the head's resident keys and values).
        # Both launches read the kept row form of the statistics, a head's
        # rows one block (a tile's: an index of the second axis); ``dq`` holds
        # a query block and turns its row into a column inside the kernel
        tiled = lambda x: x.reshape(hq, j_blocks, 1, bq)
        stat_rows = pl.BlockSpec((1, j_blocks, 1, bq), lambda h_i, *_: (h_i, 0, 0, 0))
        held = lambda h_i, j, *_: (h_i, j, 0)
        resident = _resident(nk_pad, d_pad + dv_pad, kt.dtype)
        inner, gidk, gidk_spec, (k_spec, v_spec) = _walked(
            resident, grow(nk_pad), bk, (d_pad, dv_pad), kw, lambda h_i: h_i // group)
        sel_specs, sel_args = [], []
        if select is not None:
            sel_specs = [pl.BlockSpec((select.shape[0], bq, select.shape[2]), lambda h_i, j, *_: (0, j, 0))]
            sel_args = [select]
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale,
                              resident_block_k=bk if resident else 0,
                              window=window, sparse=select is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(hq, j_blocks) + inner,
                in_specs=[
                    pl.BlockSpec((bq, 1), lambda h_i, j, *_: (j, 0)),
                    gidk_spec,
                    pl.BlockSpec((1, bq, d_pad), held),
                    k_spec,
                    v_spec,
                    pl.BlockSpec((1, bq, dv_pad), held),
                    pl.BlockSpec((1, bq, dv_pad), held),
                    stat_rows,
                ] + sel_specs,
                out_specs=pl.BlockSpec((1, bq, d_pad), held),
                scratch_shapes=[
                    pltpu.VMEM((bq, 128), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                    pltpu.VMEM((bq, d_pad), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((hq, nq_pad, d_pad), q.dtype),
            interpret=interpret,
            name=bwd_name,
            compiler_params=_compiler_params(resident),
        )(ks, kl, gcol(nq_pad), gidk, qt, kt, vt, dot, ot, tiled(lse_row), *sel_args)

        # ---- dk, dv: one key/value block held, per QUERY head; the group's
        # heads are summed after (float32 partials). The other way round: the
        # query head's q, do and statistics are what is streamed or resident
        delta_row = jnp.sum(
            dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)
        kheld = lambda h_i, i, *_: (h_i // group, i, 0)
        out_held = lambda h_i, i, *_: (h_i, i, 0)
        resident = _resident(nq_pad, d_pad + dv_pad, qt.dtype)
        inner, gidq, gidq_spec, (q_spec, do_spec) = _walked(
            resident, grow(nq_pad), bq, (d_pad, dv_pad), qw, lambda h_i: h_i)
        if resident:
            lse_row, delta_row = tiled(lse_row), tiled(delta_row)
            stat_spec = stat_rows
        else:
            rows8 = lambda x: jnp.broadcast_to(x[:, None, :], (hq, 8, nq_pad))
            lse_row, delta_row = rows8(lse_row), rows8(delta_row)
            stat_spec = pl.BlockSpec((1, 8, bq), lambda h_i, i, qq, s_, l_: (
                h_i, 0, jnp.minimum(s_[i] + qq, l_[i])))
        if select is not None:  # the held key block's bits over the queries
            from .pallas_dsa_indexer import transpose_select

            sel_t = transpose_select(select, nk_pad)
            sel_specs = [pl.BlockSpec((sel_t.shape[0], bk, sel_t.shape[2]), lambda h_i, i, *_: (0, i, 0))]
            sel_args = [sel_t]
        dk_h, dv_h = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale,
                              resident_block_q=bq if resident else 0,
                              window=window, sparse=select is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(hq, k_blocks) + inner,
                in_specs=[
                    pl.BlockSpec((bk, 1), lambda h_i, i, *_: (i, 0)),
                    gidq_spec,
                    pl.BlockSpec((1, bk, d_pad), kheld),
                    pl.BlockSpec((1, bk, dv_pad), kheld),
                    q_spec,
                    do_spec,
                    stat_spec,
                    stat_spec,
                ] + sel_specs,
                out_specs=[pl.BlockSpec((1, bk, d_pad), out_held),
                           pl.BlockSpec((1, bk, dv_pad), out_held)],
                scratch_shapes=[pltpu.VMEM((bk, d_pad), jnp.float32),
                                pltpu.VMEM((bk, dv_pad), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((hq, nk_pad, d_pad), jnp.float32),
                       jax.ShapeDtypeStruct((hq, nk_pad, dv_pad), jnp.float32)],
            interpret=interpret,
            name=bwd_name,
            compiler_params=_compiler_params(resident),
        )(qs, ql, gcol(nk_pad), gidq, kt, vt, qt, dot, lse_row, delta_row, *sel_args)

        def per_kv_head(x, width):
            x = x.reshape(hk, group, nk_pad, x.shape[2]).sum(axis=1)
            return jnp.transpose(x, (1, 0, 2))[:n, :, :width]

        dq = jnp.transpose(dq, (1, 0, 2))[:n, :, :d]
        return (dq, per_kv_head(dk_h, d).astype(k.dtype),
                per_kv_head(dv_h, dv).astype(v.dtype), None, None)


_flash_causal_attention.defvjp(_causal_vjp_fwd, _causal_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_sparse_attention(q, k, v, node_graph, node_mask, select,
                            max_nodes_per_graph, block_q, block_k, interpret):
    o, lse = _causal_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                         block_q, block_k, interpret, select=select)
    return o, lse[:, :, 0]


def _sparse_vjp_fwd(q, k, v, node_graph, node_mask, select,
                    max_nodes_per_graph, block_q, block_k, interpret):
    o, lse = _causal_fwd(q, k, v, node_graph, node_mask, max_nodes_per_graph,
                         block_q, block_k, interpret, select=select)
    # kept across a decoder layer's remat, as the causal launch's
    out_name, lse_name = CAUSAL_FLASH_RESIDUAL_NAMES
    o, lse_row = tag(o, out_name), tag(lse[:, :, 0], lse_name)
    return (o, lse_row), (q, k, v, o, lse_row, node_graph, node_mask, select)


def _sparse_vjp_bwd(max_nodes_per_graph, block_q, block_k, interpret, res, cts):
    # the log-sum-exp is an output without a gradient: its cotangent is dropped
    do, _ = cts
    *kept, select = res
    return _causal_vjp_bwd(max_nodes_per_graph, block_q, block_k, interpret, None,
                           tuple(kept), do, select)[:3] + (None, None, None)


_flash_sparse_attention.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)
