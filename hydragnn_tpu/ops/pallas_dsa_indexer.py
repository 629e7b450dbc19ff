"""Pallas TPU kernels: a learned sparse-attention indexer (DeepSeek-V3.2's
DSA, as models/keyevl2.py uses it) over the flat packed token layout.

For query ``t`` and key ``s`` of its document with ``s <= t`` the indexer
scores, in float32,

    I[t, s] = H^-1/2 d^-1/2 sum_j w[t, j] ReLU(qI[t, j] . kI[s])

(``H`` heads of ``d`` channels on the query side, ONE key head, a weight a
query head and token), and the query attends only the ``min(n_t, topk)``
keys of largest score, ``n_t`` its index in the document plus one; a tie at
the threshold goes to the lower position. Two launches:

- ``hg_dsa_indexer`` (``dsa_select``): a block of ``SELECT_BLOCK_Q`` queries
  scores every key of its window (first row's document start to last row:
  the causal flash schedule, ``_causal_windows``), keeps the scores resident
  in VMEM as the int32 order key of the float, and finds each row's
  ``topk``-th largest key EXACTLY: 32 rounds of compare-and-count that build
  the threshold ``tau`` bit by bit from the top, then, for the ties at
  ``tau``, the position of the last one taken the same way (ceil(log2 N)
  rounds). It writes the selection as a bitmask (the layout below), ``tau``,
  the tie bound, and the log-sum-exp of the scores over the selection.
- ``hg_dsa_indexer_bwd`` (``dsa_index_loss``): the indexer's loss,
  ``sum_t KL(p_t || softmax over S_t of I[t, .])``, ``p_t`` the main
  attention's distribution over ``S_t`` averaged over its heads (from its
  queries, keys and the flash launch's log-sum-exp), AND its gradient with
  respect to ``qI``, ``kI`` and ``w`` (``dI = softmax_S(I) - p`` on the
  selected pairs), in one pass over the selected tiles: a query block held,
  its window's key tiles streamed by the grid. ``p`` and everything but the
  indexer's three operands take no gradient, so the gradient is complete in
  the forward pass; a ``custom_vjp`` keeps it (tagged: a decoder layer's
  remat keeps it, so the pass runs once a step) and scales it by the
  cotangent.

The bitmask. ``W = select_words(n)`` int32 words a row (a multiple of
``SELECT_TILE``, ``32 W >= n``); bit ``b`` of word ``w`` of row ``t`` is key
``b W + w``; stored as ``[W / SELECT_TILE, rows, SELECT_TILE]`` so that the
words of key tile ``kb`` (``SELECT_TILE`` keys) are the leading index ``kb %
(W / SELECT_TILE)`` at bit ``kb // (W / SELECT_TILE)``: a tile is one
leading-axis slice and one shift in any kernel that holds a row block. The
causal flash launches read it with their keys walked (forward, ``dq``) and
its transpose (``transpose_select``: rows are keys, bits are queries) with
their queries walked (``dk``/``dv``).

Off the TPU the same functions have plain ``jnp`` references
(``reference_select``, ``reference_index_loss``): the route the decoder
stack takes on the CPU, and the kernels' oracles.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import tracer as tr
from .pallas_segment import mxu_precision
from .remat import DSA_RESIDUAL_NAMES, tag

# keys (and, transposed, queries) a word column of the bitmask covers at one
# bit: the causal flash launches' tile (``pallas_flash_attention.CAUSAL_BLOCK_K``)
SELECT_TILE = 512
# query rows of a ``hg_dsa_indexer`` block: their scores stay resident, 4 B a
# (row, key): 16 MiB at 32,768 keys
SELECT_BLOCK_Q = 128
# query rows of a ``hg_dsa_indexer_bwd`` block
LOSS_BLOCK_Q = 256
_INT_MIN = -(2 ** 31)
_FLIP = 0x7FFFFFFF


def select_words(n: int, tile: int = SELECT_TILE) -> int:
    """Words a row of the bitmask over ``n`` keys: ``ceil(n / 32)`` rounded up
    to a whole tile."""
    return max(tile, -(-(-(-n // 32)) // tile) * tile)


def _pad_rows(n: int) -> int:
    return -(-n // SELECT_TILE) * SELECT_TILE


# ---------------------------------------------------------------------------
# the bitmask: packing, unpacking and the transpose, in plain jnp
# ---------------------------------------------------------------------------


def pack_select(sel, n_keys: int):
    """bool ``[rows, n_keys]`` -> the bitmask ``[W / SELECT_TILE, rows,
    SELECT_TILE]`` int32 (the module docstring's layout)."""
    rows = sel.shape[0]
    w = select_words(n_keys)
    full = jnp.zeros((rows, 32 * w), jnp.uint32).at[:, :sel.shape[1]].set(sel.astype(jnp.uint32))
    words = jnp.sum(full.reshape(rows, 32, w) << jnp.arange(32, dtype=jnp.uint32)[None, :, None], axis=1,
                    dtype=jnp.uint32)
    return _tiled(jax.lax.bitcast_convert_type(words, jnp.int32))


def unpack_select(words, n_keys: int):
    """The bitmask -> bool ``[rows, n_keys]``."""
    flat = jax.lax.bitcast_convert_type(_flat(words), jnp.uint32)  # [rows, W]
    bits = (flat[:, None, :] >> jnp.arange(32, dtype=jnp.uint32)[None, :, None]) & 1
    return bits.reshape(flat.shape[0], -1)[:, :n_keys] != 0


def _tiled(flat):
    rows, w = flat.shape
    return jnp.transpose(flat.reshape(rows, w // SELECT_TILE, SELECT_TILE), (1, 0, 2))


def _flat(words):
    g, rows, tile = words.shape
    return jnp.transpose(words, (1, 0, 2)).reshape(rows, g * tile)


def _bit_transpose32(a):
    """``a [32, ...]`` uint32 -> ``b`` with bit ``c`` of ``b[r]`` = bit ``r``
    of ``a[c]``: each 32 x 32 bit block transposed by five rounds of masked
    swaps of its off-diagonal halves."""
    for j, m in ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F), (2, 0x33333333), (1, 0x55555555)):
        x = a.reshape((32 // (2 * j), 2, j) + a.shape[1:])
        lo, hi = x[:, 0], x[:, 1]
        t = ((lo >> j) ^ hi) & jnp.uint32(m)
        a = jnp.stack([lo ^ (t << j), hi ^ t], axis=1).reshape(a.shape)
    return a


def transpose_select(words, n_keys: int):
    """The bitmask of queries over keys -> that of keys over queries (rows
    ``n_keys``, bits the query rows): ``[W / SELECT_TILE, n_keys,
    SELECT_TILE]``. Plain jnp on the packed words (five masked swaps and one
    transpose of the word array), no bool matrix."""
    _, rows, _ = words.shape
    w = words.shape[0] * SELECT_TILE
    if w != select_words(max(rows, n_keys)):
        raise ValueError(f"a bitmask of {w} words a row cannot be transposed to {max(rows, n_keys)} rows")
    flat = jax.lax.bitcast_convert_type(_flat(words), jnp.uint32)
    a = jnp.zeros((32 * w, w), jnp.uint32).at[:rows].set(flat).reshape(32, w, w)  # [c, u, w]
    b = _bit_transpose32(a)  # bit c of b[r, u, w] = bit r of a[c, u, w]
    flat_t = jnp.transpose(b, (0, 2, 1)).reshape(32 * w, w)[:n_keys]  # row r w + w', word u
    return _tiled(jax.lax.bitcast_convert_type(flat_t, jnp.int32))


# ---------------------------------------------------------------------------
# plain-jnp references
# ---------------------------------------------------------------------------


def index_scores(qi, ki, w):
    """``I [T, T]`` float32 for every (query, key) pair, unmasked. ``qi [T,
    H, d]``, ``ki [T, d]``, ``w [T, H]``."""
    h, d = qi.shape[1], qi.shape[2]
    a = jnp.einsum("thd,sd->ths", qi.astype(jnp.float32), ki.astype(jnp.float32), precision="highest")
    return jnp.einsum("th,ths->ts", w.astype(jnp.float32), jnp.maximum(a, 0.0),
                      precision="highest") / math.sqrt(h * d)


def allowed_pairs(node_graph, node_mask):
    """``[T, T]`` bool: key ``s`` is real, of query ``t``'s document and
    ``s <= t``."""
    idx = jnp.arange(node_graph.shape[0])
    return ((node_graph[:, None] == node_graph[None, :]) & node_mask[:, None] & node_mask[None, :]
            & (idx[None, :] <= idx[:, None]))


def reference_select(qi, ki, w, node_graph, node_mask, topk: int):
    """-> (selection bool ``[T, T]``, the scores' log-sum-exp over it
    ``[T]``): each real query's ``min(n_t, topk)`` keys of largest score
    (``lax.top_k`` takes the lower position first at a tie)."""
    t = qi.shape[0]
    ok = allowed_pairs(node_graph, node_mask)
    scores = index_scores(qi, ki, w)
    masked = jnp.where(ok, scores, -jnp.inf)
    k = min(int(topk), t)
    _, idx = jax.lax.top_k(masked, k)
    sel = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], idx].set(True) & ok
    lse = jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf), axis=-1)
    return sel, jnp.where(jnp.any(sel, axis=-1), lse, 0.0)


def head_mean_probs(q, k, sel):
    """``p [T, T]``: the main attention's softmax over the selection, each
    query head's, averaged over the heads (float32; a row with no key is 0).
    ``q [T, Hq, d]``, ``k [T, Hk, d]``."""
    hq, hk, d = q.shape[1], k.shape[1], q.shape[2]
    kf = jnp.repeat(k, hq // hk, axis=1).astype(jnp.float32)
    s = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), kf, precision="highest") / math.sqrt(d)
    s = jnp.where(sel[None], s, -jnp.inf)
    p = jnp.where(sel[None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.where(jnp.any(sel, axis=-1), jnp.mean(p, axis=0), 0.0)


def reference_index_loss(qi, ki, w, q, k, sel):
    """``sum_t KL(p_t || softmax over S_t of I[t, .])``: ``p`` (from ``q``,
    ``k``) takes no gradient."""
    p = jax.lax.stop_gradient(head_mean_probs(q, k, sel))
    scores = jnp.where(sel, index_scores(qi, ki, w), -jnp.inf)
    logq = jnp.where(sel, jax.nn.log_softmax(scores, axis=-1), 0.0)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - logq), 0.0))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _order_key(x):
    """float32 -> int32 of the same order (``-0 < +0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ _FLIP)


def _from_key(key):
    bits = jnp.where(key >= 0, key, key ^ _FLIP)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _index_tile(qi_ref, kt, w_ref, scale, heads: int):
    """The scores of the held query block ``[Bq, T]`` against key rows ``kt
    [T, d]``, float32."""
    acc = None
    for h in range(heads):
        a = jax.lax.dot_general(qi_ref[h], kt, (((1,), (1,)), ((), ())),
                                precision=mxu_precision(kt.dtype), preferred_element_type=jnp.float32)
        term = w_ref[:, h:h + 1].astype(jnp.float32) * jnp.maximum(a, 0.0)
        acc = term if acc is None else acc + term
    return acc * scale


def _tile_mask(gidq_ref, gidk, row0, col0, shape):
    r = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (gidq_ref[:] == gidk) & (gidk >= 0) & (c <= r)


def _select_kernel(kstart_ref, klast_ref, need_ref, gidq_ref, gidk_ref, qi_ref, ki_ref, w_ref,
                   sel_ref, tau_ref, tie_ref, lse_ref, key_scr, *, scale, heads, pos_bits):
    j = pl.program_id(0)
    bq = qi_ref.shape[1]
    groups = sel_ref.shape[0]
    ks, kl = kstart_ref[j], klast_ref[j]
    shape = (bq, SELECT_TILE)
    col = lambda kb: pl.multiple_of(kb * SELECT_TILE, SELECT_TILE)

    def score(kb, _):
        s = _index_tile(qi_ref, ki_ref[pl.ds(col(kb), SELECT_TILE), :], w_ref, scale, heads)
        ok = _tile_mask(gidq_ref, gidk_ref[kb], j * bq, kb * SELECT_TILE, shape)
        key_scr[kb] = jnp.where(ok, _order_key(s), _INT_MIN)

    jax.lax.fori_loop(ks, kl + 1, score, None)
    need = need_ref[...]  # [bq, 1]
    zero = jnp.zeros((bq, 1), jnp.int32)

    def count(pred):
        return jax.lax.fori_loop(
            ks, kl + 1, lambda kb, c: c + jnp.sum(pred(kb).astype(jnp.int32), axis=1, keepdims=True), zero)

    # tau: the largest t with #(key >= t) >= need, built from the top bit in
    # the unsigned order (``u = key ^ INT_MIN``)
    def tau_bit(i, prefix):
        cand = prefix | jax.lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(count(lambda kb: key_scr[kb] >= (cand ^ _INT_MIN)) >= need, cand, prefix)

    tau = jax.lax.fori_loop(0, 32, tau_bit, zero) ^ _INT_MIN
    rest = need - count(lambda kb: key_scr[kb] > tau)  # ties at tau still to take

    # the ties' bound: the largest p with #(key == tau, position < p) < rest;
    # every tie at a position <= p is taken
    def pos_of(kb):
        return kb * SELECT_TILE + jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def tie_bit(i, prefix):
        cand = prefix | jax.lax.shift_left(jnp.int32(1), pos_bits - 1 - i)
        fewer = count(lambda kb: (key_scr[kb] == tau) & (pos_of(kb) < cand)) < rest
        return jnp.where(fewer, cand, prefix)

    tie = jax.lax.fori_loop(0, pos_bits, tie_bit, zero)
    sel_ref[...] = jnp.zeros(sel_ref.shape, jnp.int32)

    def emit(kb, carry):
        m, l = carry
        key = key_scr[kb]
        sel = ((key > tau) | ((key == tau) & (pos_of(kb) <= tie))) & (key != _INT_MIN)
        g = kb % groups
        sel_ref[g] = sel_ref[g] | jax.lax.shift_left(sel.astype(jnp.int32), kb // groups)
        x = _from_key(key)
        m_new = jnp.maximum(m, jnp.max(jnp.where(sel, x, -jnp.inf), axis=1, keepdims=True))
        safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        l = l * jnp.exp(m - safe) + jnp.sum(jnp.where(sel, jnp.exp(x - safe), 0.0), axis=1, keepdims=True)
        return m_new, l

    m, l = jax.lax.fori_loop(ks, kl + 1, emit, (jnp.full((bq, 1), -jnp.inf, jnp.float32),
                                                jnp.zeros((bq, 1), jnp.float32)))
    tau_ref[...] = tau
    tie_ref[...] = tie
    lse_ref[...] = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), 0.0)


def _vmem_params(mib: int):
    return pltpu.CompilerParams(vmem_limit_bytes=mib * 2 ** 20)


def _windows(node_graph, node_mask, block_q, max_nodes):
    from .pallas_flash_attention import _causal_windows

    ks, kl, kw, _, _, _ = _causal_windows(node_graph, node_mask, block_q, SELECT_TILE, max_nodes)
    return ks.astype(jnp.int32), kl.astype(jnp.int32), kw


def _need(node_mask, pos, topk: int, rows: int):
    need = jnp.where(node_mask, jnp.minimum(pos.astype(jnp.int32) + 1, int(topk)), 0)
    return jnp.zeros((rows, 1), jnp.int32).at[:need.shape[0], 0].set(need)


def _gid(node_graph, node_mask, rows: int):
    gid = jnp.where(node_mask, node_graph.astype(jnp.int32), -1)
    return jnp.full((rows,), -1, jnp.int32).at[:gid.shape[0]].set(gid)


def _rows(x, rows: int):
    return jnp.zeros((rows,) + x.shape[1:], x.dtype).at[:x.shape[0]].set(x)


def dsa_select(qi, ki, w, node_graph, node_mask, pos, topk: int, max_nodes_per_graph: int,
               interpret: bool = False):
    """The ``hg_dsa_indexer`` launch: -> (the bitmask ``[W / SELECT_TILE,
    N_pad, SELECT_TILE]`` int32 over queries ``N_pad = N`` rounded up to
    ``SELECT_TILE``, the threshold's order key ``tau [N]`` int32, the tie
    bound ``[N]`` int32, the log-sum-exp of the scores over the selection
    ``[N]`` float32). ``qi [N, H, d]``, ``ki [N, d]``, ``w [N, H]``; ``pos``
    each node's index in its document. No gradient. Same layout contract as
    the causal flash launches (documents contiguous, padding last)."""
    n, heads, d = qi.shape
    rows = _pad_rows(n)
    bq = SELECT_BLOCK_Q
    groups = select_words(rows) // SELECT_TILE
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    ks, kl, _ = _windows(node_graph, node_mask, bq, max_nodes_per_graph)
    gid = _gid(node_graph, node_mask, rows)
    need = _need(node_mask, pos, topk, rows)
    qt = jnp.transpose(_rows(qi, rows), (1, 0, 2))  # [H, rows, d]
    n_tiles = rows // SELECT_TILE
    col = lambda j, *_: (j, 0)
    with tr.scope(tr.HG_DSA_INDEXER):
        sel, tau, tie, lse = pl.pallas_call(
            functools.partial(_select_kernel, scale=1.0 / math.sqrt(heads * d), heads=heads,
                              pos_bits=max(1, (rows - 1).bit_length())),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(rows // bq,),
                in_specs=[
                    pl.BlockSpec((bq, 1), col),
                    pl.BlockSpec((bq, 1), col),
                    pl.BlockSpec((n_tiles, 1, SELECT_TILE), lambda *_: (0, 0, 0)),
                    pl.BlockSpec((heads, bq, d), lambda j, *_: (0, j, 0)),
                    pl.BlockSpec((rows, d), lambda *_: (0, 0)),
                    pl.BlockSpec((bq, heads), col),
                ],
                out_specs=[pl.BlockSpec((groups, bq, SELECT_TILE), lambda j, *_: (0, j, 0)),
                           pl.BlockSpec((bq, 1), col), pl.BlockSpec((bq, 1), col), pl.BlockSpec((bq, 1), col)],
                scratch_shapes=[pltpu.VMEM((n_tiles, bq, SELECT_TILE), jnp.int32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((groups, rows, SELECT_TILE), jnp.int32),
                       jax.ShapeDtypeStruct((rows, 1), jnp.int32), jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
            interpret=interpret,
            name=tr.HG_DSA_INDEXER,
            compiler_params=_vmem_params(96),
        )(ks, kl, need, gid[:, None], gid.reshape(n_tiles, 1, SELECT_TILE), qt, _rows(ki, rows),
          _rows(w, rows))
    # kept across a decoder layer's remat under its name: the launch runs
    # once a step
    return tag((sel, tau[:n, 0], tie[:n, 0], lse[:n, 0]), DSA_RESIDUAL_NAMES[0])


def _loss_kernel(kstart_ref, klast_ref, sel_ref, q_ref, lse_ref, k_ref, qi_ref, ki_ref,
                 w_ref, lsei_ref, kl_ref, dqi_ref, dw_ref, dki_ref, dqi_scr, dw_scr, acc_scr, *,
                 scale, main_scale, heads, group):
    j, kk = pl.program_id(0), pl.program_id(1)
    bq = q_ref.shape[1]
    hq = q_ref.shape[0]
    groups = sel_ref.shape[0]
    kb = kstart_ref[j] + kk
    nt = (((1,), (1,)), ((), ()))

    @pl.when((j == 0) & (kk == 0))
    def _():
        dki_ref[...] = jnp.zeros(dki_ref.shape, dki_ref.dtype)

    @pl.when(kk == 0)
    def _():
        dqi_scr[...] = jnp.zeros(dqi_scr.shape, jnp.float32)
        dw_scr[...] = jnp.zeros(dw_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(kb <= klast_ref[j])
    def _():
        words = sel_ref[kb % groups]
        sel = (jax.lax.shift_right_arithmetic(words, kb // groups) & 1) != 0
        prec = mxu_precision(q_ref.dtype)

        def main_head(h, p):
            s = jax.lax.dot_general(q_ref[h], k_ref[h // group], nt, precision=prec,
                                    preferred_element_type=jnp.float32) * main_scale
            return p + jnp.exp(s - lse_ref[h])

        p = jax.lax.fori_loop(0, hq, main_head, jnp.zeros((bq, SELECT_TILE), jnp.float32))
        p = jnp.where(sel, p * (1.0 / hq), 0.0)
        kt = ki_ref[...]  # [TILE, d]
        iprec = mxu_precision(kt.dtype)
        index = jnp.where(sel, _index_tile(qi_ref, kt, w_ref, scale, heads), 0.0)
        qprob = jnp.where(sel, jnp.exp(index - lsei_ref[...]), 0.0)
        d_index = qprob - p
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        stats = jnp.concatenate([jnp.sum(plogp, axis=1, keepdims=True),
                                 jnp.sum(p * index, axis=1, keepdims=True),
                                 jnp.sum(p, axis=1, keepdims=True)], axis=1)
        acc_scr[:, 0:3] += stats
        dk = None
        for h in range(heads):
            qh = qi_ref[h]
            a = jax.lax.dot_general(qh, kt, nt, precision=iprec, preferred_element_type=jnp.float32)
            dw_scr[:, h:h + 1] += scale * jnp.sum(d_index * jnp.maximum(a, 0.0), axis=1, keepdims=True)
            da = jnp.where(a > 0, d_index * (scale * w_ref[:, h:h + 1].astype(jnp.float32)), 0.0)
            dqi_scr[h] += jax.lax.dot_general(da.astype(kt.dtype), kt, (((1,), (0,)), ((), ())),
                                              precision=iprec, preferred_element_type=jnp.float32)
            term = jax.lax.dot_general(da.astype(qh.dtype), qh, (((0,), (0,)), ((), ())),
                                       precision=iprec, preferred_element_type=jnp.float32)
            dk = term if dk is None else dk + term
        rows = pl.ds(pl.multiple_of(kb * SELECT_TILE, SELECT_TILE), SELECT_TILE)
        dki_ref[rows, :] += dk

    @pl.when(kk == pl.num_programs(1) - 1)
    def _():
        a = acc_scr[...]
        kl_ref[...] = a[:, 0:1] - a[:, 1:2] + lsei_ref[...] * a[:, 2:3]
        dqi_ref[...] = dqi_scr[...]
        dw_ref[...] = dw_scr[:, :dw_ref.shape[1]]


def _index_loss_launch(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph, node_mask, max_nodes, interpret):
    n, heads, d = qi.shape
    hq, hk, dm = q.shape[1], k.shape[1], q.shape[2]
    rows = _pad_rows(n)
    bq = LOSS_BLOCK_Q
    groups = sel.shape[0]
    ks, kl, kw = _windows(node_graph, node_mask, bq, max_nodes)
    k_blocks = rows // SELECT_TILE
    kw = max(1, min(kw, k_blocks))
    heads_first = lambda a: jnp.transpose(_rows(a, rows), (1, 0, 2))
    lse_col = jnp.pad(lse_row, ((0, 0), (0, max(0, rows - lse_row.shape[1]))))[:, :rows, None]
    tile = lambda j, kk, s, l: jnp.minimum(s[j] + kk, l[j])
    held = lambda j, *_: (j, 0)
    out = pl.pallas_call(
        functools.partial(_loss_kernel, scale=1.0 / math.sqrt(heads * d), main_scale=1.0 / math.sqrt(dm),
                          heads=heads, group=hq // hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // bq, kw),
            in_specs=[
                pl.BlockSpec((groups, bq, SELECT_TILE), lambda j, *_: (0, j, 0)),
                pl.BlockSpec((hq, bq, dm), lambda j, *_: (0, j, 0)),
                pl.BlockSpec((hq, bq, 1), lambda j, *_: (0, j, 0)),
                pl.BlockSpec((hk, SELECT_TILE, dm), lambda j, kk, s, l: (0, tile(j, kk, s, l), 0)),
                pl.BlockSpec((heads, bq, d), lambda j, *_: (0, j, 0)),
                pl.BlockSpec((SELECT_TILE, d), lambda j, kk, s, l: (tile(j, kk, s, l), 0)),
                pl.BlockSpec((bq, heads), held),
                pl.BlockSpec((bq, 1), held),
            ],
            out_specs=[pl.BlockSpec((bq, 1), held),
                       pl.BlockSpec((heads, bq, d), lambda j, *_: (0, j, 0)),
                       pl.BlockSpec((bq, heads), held),
                       pl.BlockSpec((rows, d), lambda *_: (0, 0))],
            scratch_shapes=[pltpu.VMEM((heads, bq, d), jnp.float32), pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((heads, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, heads), jnp.float32),
                   jax.ShapeDtypeStruct((rows, d), jnp.float32)],
        interpret=interpret,
        name=tr.HG_DSA_INDEXER + tr.BWD,
        compiler_params=_vmem_params(96),
    )(ks, kl, sel, heads_first(q), lse_col, heads_first(k), heads_first(qi),
      _rows(ki, rows), _rows(w, rows), _rows(lse_index[:, None], rows))
    kl_row, dqi, dw, dki = out
    real = node_mask.astype(jnp.float32)
    return (jnp.sum(kl_row[:n, 0] * real), jnp.transpose(dqi, (1, 0, 2))[:n] * real[:, None, None],
            dki[:n], dw[:n] * real[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _index_loss(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph, node_mask, max_nodes, interpret):
    with tr.scope(tr.HG_DSA_INDEXER + tr.BWD):
        return _index_loss_launch(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph, node_mask,
                                  max_nodes, interpret)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph, node_mask, max_nodes, interpret):
    with tr.scope(tr.HG_DSA_INDEXER + tr.BWD):
        value, dqi, dki, dw = _index_loss_launch(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph,
                                                 node_mask, max_nodes, interpret)
    # the gradient is whole after the forward pass: kept across the layer's
    # remat under its name, so the launch runs once a step
    grads = tag((dqi.astype(qi.dtype), dki.astype(ki.dtype), dw.astype(w.dtype)), DSA_RESIDUAL_NAMES[1])
    return value, grads


def _index_loss_bwd(max_nodes, interpret, grads, g):
    dqi, dki, dw = grads
    scale = lambda a: (a.astype(jnp.float32) * g).astype(a.dtype)
    return (scale(dqi), scale(dki), scale(dw), None, None, None, None, None, None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def dsa_index_loss(qi, ki, w, q, k, lse_row, sel, lse_index, node_graph, node_mask, max_nodes_per_graph: int,
                   interpret: bool = False):
    """The ``hg_dsa_indexer_bwd`` launch: ``sum over real t of KL(p_t ||
    softmax over S_t of I[t, .])`` (a float32 scalar), differentiable in
    ``qi``, ``ki`` and ``w`` only. ``q [N, Hq, dm]``, ``k [N, Hk, dm]``
    (bf16 or float32) and ``lse_row [Hq, >= N]`` (the causal flash launch's
    log-sum-exp over the same selection) make ``p``; ``sel``, ``lse_index``
    are ``dsa_select``'s."""
    stop = jax.lax.stop_gradient
    return _index_loss(qi, ki, w, stop(q), stop(k), stop(lse_row), sel, stop(lse_index), node_graph,
                       node_mask, int(max_nodes_per_graph), bool(interpret))
