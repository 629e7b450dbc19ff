"""Pallas TPU kernel: grouped matrix product over the experts held.

An expert layer sends every token to one expert (top-1, models/zaya.py) or to
several (top-k, models/joyai.py: a token is then ``k`` ASSIGNMENTS, each its
own row); the chip holds some of the experts. The rows routed to the experts
held are laid out GROUPED BY EXPERT in a group-aligned buffer: group ``g``
starts at a multiple of ``block_m`` and is padded with zero rows to the next
multiple (``aligned_layout``). Group sizes are ragged and known only on the
device; the buffer's static length ``A + G * block_m`` for ``A`` assignments
holds any routing, so no token is ever dropped, not even when every token
picks one expert. A top-k layer may state a budget of rows below that worst
case (``aligned_layout(..., rows=)``): the layout then reports how many rows
did not fit (``overrun``), and the caller poisons the step; it never cuts in
silence.

Because a row tile then belongs to exactly one group, the kernel is a plain
tiled matmul whose weight block index comes from a scalar-prefetched
``tile_group`` table: no masks, no straddling. One kernel body, three
launches under one name:

- forward   ``y = x @ w[g]``                 grid (row tiles, N tiles, K tiles)
- backward  ``dx = dy @ w[g].T``             the same launch with the weight
  block contracted on its last axis (an NT product, no transpose);
- backward  ``dw[g] = x[rows of g].T @ dy``  grid (K tiles, N tiles, row
  tiles): consecutive row tiles of a group accumulate into one resident
  output block; ``x`` arrives transposed (an XLA transpose) so the product
  is NN. Every group owns at least one tile, so every ``dw`` block is
  written.

Tiles past the used count are skipped with ``pl.when`` (their rows are zero).
Operands stream in their own dtype, accumulation is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import tracer as tr
from .pallas_segment import _pad_to, mxu_precision

def _round_up(x: int, m: int) -> int:
    return ((int(x) + m - 1) // m) * m


# what a launch's double-buffered blocks and accumulator may take of the
# 16 MiB of scoped VMEM a v5e kernel gets by default
_VMEM_BUDGET = 10 * 2**20


# the launch's tiles: rows (also the alignment of each group's rows), output
# columns, contraction. Stated here and nowhere else; a change to them is an
# edit of this line, claimed in a benchmark cell
BLOCK_M, BLOCK_N, BLOCK_K = 512, 1024, 512


def normalize_tiles(rows, k, n, block_m=BLOCK_M, block_n=BLOCK_N,
                    block_k=BLOCK_K, dtype="bfloat16"):
    """Snap requested tiles to the kernel's alignment contract: ``block_m``
    to the 16-row sublane tile (covers bf16) and no taller than the rows there
    are, ``block_n``/``block_k`` to the 128-lane tile and no wider than the
    lane-padded operand; then ``block_n``, then ``block_k`` halved until the
    blocks of the streaming ``dtype`` fit the VMEM budget (float32 streams
    take half the tile bf16 streams do). The one clamp, applied by
    ``grouped_matmul`` before the tiles become ``custom_vjp``
    non-differentiable arguments."""
    bm = max(16, min(block_m - block_m % 16, _round_up(max(rows, 1), 16)))
    bn = max(128, min(block_n - block_n % 128, _round_up(n, 128)))
    bk = max(128, min(block_k - block_k % 128, _round_up(k, 128)))
    item = jnp.dtype(dtype).itemsize
    vmem = lambda: 2 * item * (bm * bk + bk * bn + bm * bn) + 4 * max(bm, bk) * bn
    while vmem() > _VMEM_BUDGET and bn > 128:
        bn = max(128, (bn // 2) - (bn // 2) % 128)
    while vmem() > _VMEM_BUDGET and bk > 128:
        bk = max(128, (bk // 2) - (bk // 2) % 128)
    return bm, bn, bk


def aligned_rows(tokens: int, groups: int, block_m: int) -> int:
    """Static length of the group-aligned buffer: any routing fits."""
    return _round_up(tokens, block_m) + groups * block_m


# positions of a running count that a search reads at once (``_first_reaching``)
_SEARCH_CHUNK = 512


def _first_reaching(cum, group, target):
    """For each query ``q``, the first ``i`` with ``cum[group[q], i] >=
    target[q]``, where each row of ``cum [G, A]`` is non-decreasing and
    reaches the target: the chunks whose last count falls short of it are
    counted, then the positions that fall short in the chunk that reaches it
    (one row gather a query)."""
    g, a = cum.shape
    c = min(_SEARCH_CHUNK, a)
    n = -(-a // c)
    blocks = jnp.pad(cum, ((0, 0), (0, n * c - a)), mode="edge").reshape(g, n, c)
    short = lambda rows: jnp.sum((rows < target[:, None]).astype(jnp.int32), axis=1)
    k = jnp.minimum(short(blocks[:, :, -1][group]), n - 1)
    return k * c + short(blocks[group, k])


def aligned_layout(slot, groups: int, block_m: int, rows: int = 0):
    """Where each assignment goes. ``slot [A]`` is the assignment's local
    expert in ``[0, groups)``, or ``groups`` for one that is not computed here
    (its expert lives elsewhere, or it is padding). ``rows`` > 0 is a budget:
    the buffer's length ``R`` (a multiple of ``block_m``) in place of the
    worst case ``aligned_rows(A, groups, block_m)``. A group's rows keep the
    assignments' order.

    -> dict of ``dest [A]`` (the assignment's row in the aligned buffer, or
    ``R``, the appended zero row, when it is not computed here), ``src [R]``
    (the assignment of each aligned row, or ``A``, the appended zero row),
    ``tile_group [R / block_m]``, ``n_tiles []`` (tiles in use), ``counts
    [groups]`` and ``overrun []`` (rows past a budget: they are not computed,
    and the caller must not let the step stand).

    Built by counting, with no sort and no scatter: a running count of each
    group's assignments (``[groups, A]``, the assignments on the lanes) gives
    an assignment its rank in its group, and the row of each rank is found by
    a search of that group's count.
    """
    a = slot.shape[0]
    r = aligned_rows(a, groups, block_m)
    if rows:
        r = min(r, _round_up(rows, block_m))
    slot = slot.astype(jnp.int32)
    onehot = slot[None, :] == jnp.arange(groups, dtype=jnp.int32)[:, None]
    cum = jnp.cumsum(onehot.astype(jnp.int32), axis=1)
    counts = cum[:, -1]
    tiles = jnp.maximum((counts + block_m - 1) // block_m, 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * block_m
    held = slot < groups
    # a held assignment's row: its group's first row plus its rank there
    dest = jnp.sum(jnp.where(onehot, row_start[:, None] + cum - 1, 0), axis=0)
    # a row past a budget is not computed (and counted): the worst-case
    # buffer has none
    fits = dest < r
    overrun = jnp.sum((held & ~fits).astype(jnp.int32))
    dest = jnp.where(held & fits, dest, r)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(r // block_m, dtype=jnp.int32),
                         side="right"),
        groups - 1,
    ).astype(jnp.int32)
    # the inverse: row ``row_start[g] + j`` holds group g's assignment of rank j
    row = jnp.arange(r, dtype=jnp.int32)
    group = jnp.repeat(tile_group, block_m)
    of_group = lambda v: jnp.sum(jnp.where(group[:, None] == jnp.arange(groups), v, 0), axis=1)
    j = row - of_group(row_start)
    src = jnp.where(j < of_group(counts), _first_reaching(cum, group, j + 1), a)
    return {"dest": dest, "src": src, "tile_group": tile_group,
            "n_tiles": jnp.minimum(tile_end[-1], r // block_m).astype(jnp.int32),
            "counts": counts, "overrun": overrun}


@jax.custom_vjp
def permute_rows(x, index, inverse):
    """``concat(x, 0)[index]``: rows of ``x`` moved to where ``index`` says,
    a zero row where it points one past the end. ``inverse`` is the same map
    the other way round, so the cotangent is a gather too, not a scatter."""
    zero = jnp.zeros((1,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, zero], axis=0)[index]


def _permute_fwd(x, index, inverse):
    return permute_rows(x, index, inverse), (index, inverse)


def _permute_bwd(res, g):
    index, inverse = res
    return permute_rows(g, inverse, index), None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


def reference_grouped_matmul(x, w, tile_group, block_m: int):
    """``y[r] = x[r] @ w[group of r's tile]`` in plain jnp: the oracle of the
    kernel and the route off the TPU (one masked product a group)."""
    row_group = jnp.repeat(tile_group, block_m)
    y = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for g in range(w.shape[0]):
        yg = jnp.dot(x, w[g], preferred_element_type=jnp.float32,
                     precision=mxu_precision(x.dtype))
        y = jnp.where((row_group == g)[:, None], yg, y)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _kernel(tile_group_ref, n_tiles_ref, x_ref, w_ref, o_ref, acc_ref, *,
            mode):
    """``mode`` "nn": rows x w[g]; "nt": rows x w[g].T (grid: row tile, out
    tile, contraction tile). "dw": xT tile x dy tile into the group's block
    (grid: K tile, N tile, row tile)."""
    if mode == "dw":
        m = pl.program_id(2)
        prev = tile_group_ref[jnp.maximum(m - 1, 0)]
        first = (m == 0) | (prev != tile_group_ref[m])

        @pl.when(first)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(m < n_tiles_ref[0])
        def _step():
            acc_ref[:] += jax.lax.dot_general(
                x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
                precision=mxu_precision(x_ref.dtype),
                preferred_element_type=jnp.float32,
            )

        # the block stays resident while the group lasts and is written back
        # when the next group's index takes its place
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)
        return

    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_tiles_ref[0])
    def _step():
        dims = (((1,), (0,)), ((), ())) if mode == "nn" else (((1,), (1,)), ((), ()))
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[0], dims,
            precision=mxu_precision(x_ref.dtype),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _launch_rows(x, w, tile_group, n_tiles, bm, bn, bk, transpose_w, interpret):
    """``x [R, K] @ w[g] [K, N]`` (or ``w[g] [N, K]`` contracted on its last
    axis when ``transpose_w``) -> ``[R, N]``."""
    r, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    xp = _pad_to(x, bk, 1)
    if transpose_w:
        wp = _pad_to(_pad_to(w, bn, 1), bk, 2)
        w_spec = pl.BlockSpec((1, bn, bk), lambda i, j, kk, tg, nt: (tg[i], j, kk))
    else:
        wp = _pad_to(_pad_to(w, bk, 1), bn, 2)
        w_spec = pl.BlockSpec((1, bk, bn), lambda i, j, kk, tg, nt: (tg[i], kk, j))
    n_pad, k_pad = _round_up(n, bn), xp.shape[1]
    out = pl.pallas_call(
        functools.partial(_kernel, mode="nt" if transpose_w else "nn"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r // bm, n_pad // bn, k_pad // bk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, tg, nt: (i, kk)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, tg, nt: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, n_pad), x.dtype),
        interpret=interpret,
        name=tr.HG_GROUPED_EXPERT + (tr.BWD if transpose_w else ""),
    )(tile_group, n_tiles.reshape(1), xp, wp)
    return out[:, :n]


def _launch_dw(x, dy, groups, tile_group, n_tiles, bm, bn, bk, out_dtype,
               interpret):
    """``dw[g] = x[rows of g].T @ dy[rows of g]`` -> ``[G, K, N]``."""
    r, k = x.shape
    n = dy.shape[1]
    xt = _pad_to(x, bk, 1).T  # [K_pad, R]
    dyp = _pad_to(dy, bn, 1)
    k_pad, n_pad = xt.shape[0], dyp.shape[1]
    out = pl.pallas_call(
        functools.partial(_kernel, mode="dw"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k_pad // bk, n_pad // bn, r // bm),
            in_specs=[
                pl.BlockSpec((bk, bm), lambda kt, j, m, tg, nt: (kt, m)),
                pl.BlockSpec((bm, bn), lambda kt, j, m, tg, nt: (m, j)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn), lambda kt, j, m, tg, nt: (tg[m], kt, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k_pad, n_pad), out_dtype),
        interpret=interpret,
        name=tr.HG_GROUPED_EXPERT + tr.BWD,
    )(tile_group, n_tiles.reshape(1), xt, dyp)
    return out[:, :k, :n]


def grouped_matmul(x, w, tile_group, n_tiles, block_m: int = BLOCK_M,
                   block_n: int = BLOCK_N, block_k: int = BLOCK_K,
                   interpret: bool = False):
    """``y[r] = x[r] @ w[tile_group[r // block_m]]`` for a group-aligned
    ``x [R, K]`` (``aligned_layout``) and ``w [G, K, N]``; ``n_tiles`` row
    tiles are in use, the rest are zero. ``block_m`` is the one the layout
    was built with (``normalize_tiles`` of the tokens' rows: already
    clamped), ``R`` a multiple of it; ``block_n``/``block_k`` are clamped
    here to this product's widths. Reverse mode only, first order: both
    backward products are launches of this kernel."""
    _, bn, bk = normalize_tiles(x.shape[0], w.shape[1], w.shape[2], block_m,
                                block_n, block_k, x.dtype)
    return _grouped_matmul(x, w, tile_group, n_tiles, block_m, bn, bk,
                           interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _grouped_matmul(x, w, tile_group, n_tiles, block_m, block_n, block_k,
                    interpret):
    with tr.scope(tr.HG_GROUPED_EXPERT):
        return _launch_rows(x, w, tile_group, n_tiles, block_m, block_n,
                            block_k, False, interpret)


def _gmm_fwd(x, w, tile_group, n_tiles, block_m, block_n, block_k, interpret):
    y = _grouped_matmul(x, w, tile_group, n_tiles, block_m, block_n, block_k,
                        interpret)
    return y, (x, w, tile_group, n_tiles)


def _gmm_bwd(block_m, block_n, block_k, interpret, res, dy):
    x, w, tile_group, n_tiles = res
    dy = dy.astype(x.dtype)
    with tr.scope(tr.HG_GROUPED_EXPERT + tr.BWD):
        # dx contracts N: the forward's N tile is this launch's K tile
        _, bn_dx, bk_dx = normalize_tiles(
            x.shape[0], w.shape[2], w.shape[1], block_m, block_n, block_k,
            x.dtype)
        dx = _launch_rows(dy, w, tile_group, n_tiles, block_m, bn_dx, bk_dx,
                          True, interpret)
        _, bn_dw, bk_dw = normalize_tiles(
            x.shape[0], w.shape[1], w.shape[2], block_m, block_n, block_k,
            x.dtype)
        dw = _launch_dw(x, dy, w.shape[0], tile_group, n_tiles, block_m,
                        bn_dw, bk_dw, w.dtype, interpret)
    return dx, dw, None, None


_grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)
