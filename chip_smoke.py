#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the main path still starts on the TPU.

One process, no arguments, run from the repo root:

    python chip_smoke.py

It refuses any backend but ``tpu`` before building data, then drives

1. the kernel legs: the decoder's two kernels (causal grouped-query flash
   attention, forward and tiled backward; the grouped expert product and its
   two backward products) at the ZAYA cell's shapes against plain ``jnp``;
   each of the four older Pallas kernels compiled
   (``interpret=False``) at the widths the repo's cells use and compared with
   its plain-``jnp`` reference, first the bf16 fused-edge call at the
   benchmark cells' own shape (forward and tangent), then the receiver
   gather's transposed kernel call and a message layer's pair of row
   gathers (ordered against plain, bit for bit) at that shape; then the
   token head alone at the ZAYA and JOYAI cells' shapes, its gradient formed
   in the forward scan against the earlier checkpointed rule (``head_times``);
2. the main leg: SC25-shaped EGNN (hidden 866, 4 conv layers) through
   ``run_training`` -> ``run_prediction`` -> ``run_server`` in this process,
   with the lowered programs checked for Mosaic custom calls and the kernel
   route compared with the dense route on one real batch;
3. the second-order leg: one ``compute_grad_energy`` epoch at the
   ``examples/md17`` widths through the sorted-segment kernel, then an EGNN's
   energy-force gradient through the receiver gather's transposed kernel call
   against the same gradient through the plain gather;
4. with more than one device, the same EGNN through the mesh step
   (``Optimizer.zero_stage: 2``) over all of them.

Nothing is caught and reported as data: a failed check raises, the exit code
is non-zero and no result line is printed. A passing run ends its stdout with
two lines: ``chip_smoke report: {...}`` (each leg's result, time-to-first-step,
compile seconds, cache hits/misses, cache directory), then, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``, the
one line the driver's chip check parses.

Every timing printed here is a set-up fact (compile, first step), not a
throughput.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from unittest import mock

import numpy as np

# The one model with a history on the chip: bench.py `_production_workload`
# (reference: examples/multibranch/multibranch_GFM260_SC25.json).
HIDDEN, HEAD_DIM, CONV_LAYERS, BATCH, NUM_GRAPHS = 866, 889, 4, 32, 128


def require_tpu() -> dict:
    """First act: initialise JAX and stamp the device, or refuse."""
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"chip_smoke: JAX initialised backend {backend!r}, not 'tpu'; "
            "nothing was run"
        )
    devices = jax.devices()
    stamp = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # metadata only decorates the banner
        libtpu = "unknown"
    print(
        f"platform: {stamp['platform']}  device_kind: {stamp['kind']}  "
        f"devices: {stamp['count']}  jax {jax.__version__}  "
        f"jaxlib {jaxlib.__version__}  libtpu {libtpu}",
        flush=True,
    )
    return stamp


def _rel_err(out, ref, norm=lambda x: np.max(np.abs(x))) -> float:
    """max|out - ref| over max|ref|: one number per comparison, insensitive
    to the near-zero entries an elementwise rtol would trip on. ``norm`` may
    be another norm (``np.linalg.norm``: relative L2)."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    assert out.shape == ref.shape, (out.shape, ref.shape)
    assert np.isfinite(out).all(), "non-finite kernel output"
    return float(norm(out - ref) / (norm(ref) + 1e-30))


def _timed_ms(fn, *args, repeats: int = 5):
    """``fn(*args)`` once to compile, then the median wall time of
    ``repeats`` calls in milliseconds, each waited for (a set-up fact)."""
    import jax

    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return out, float(np.median(times))


def _check(name: str, err: float, tol: float) -> None:
    print(f"  {name}: rel_err {err:.3e} (tol {tol:.0e})", flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: rel_err {err:.3e} exceeds {tol:.0e}")


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------

# Tolerances, as max|out-ref|/max|ref| against an f32 reference computed at
# matmul precision "highest" from the SAME (possibly bf16-rounded) inputs:
#  - f32 streams: the kernels ask the MXU for fp32 contraction
#    (ops/pallas_segment.py mxu_precision), so only the summation order
#    differs from the reference. Measured on a v5e: 1e-7 .. 1.7e-6 (flash,
#    whose exp() adds a few ulp); 2e-5 leaves 10x and still fails on a
#    single dropped edge (O(1e-3) here) or a bf16-rounded operand (2e-3).
#  - bf16 streams: up to four intermediates (gathered pre-activation,
#    hidden, message, output) are rounded to bf16, 2^-8 = 3.9e-3 each, so
#    1.6e-2. Measured: 2.6e-3 .. 3.0e-3.
TOL = {"float32": 2e-5, "bfloat16": 1.6e-2}
# The bf16 TANGENT of the fused edge message is read by its relative L2 error:
# where a pre-activation lies within bf16 rounding of zero the relu mask
# flips against the f32 reference, an O(1) error in some 0.1% of the
# elements, so the max norm reads one flip (4.5e-2 .. 6e-2) whatever the
# kernel does. L2 reads 1.2e-2 .. 1.3e-2 (CPU, widths 128 and 866); 3e-2
# still fails on one lost edge window of 512 in 196608 (5e-2).
TOL_TANGENT_L2 = 3e-2
# The causal flash kernel's backward rebuilds each probability from the saved
# log-sum-exp and subtracts two sums of 8192 terms (dp - delta): measured on a
# v5e 5.9e-5 (float32 streams; 50x under one bf16-rounded operand, 3e-3) and
# 3.0e-3 .. 3.4e-3 (bf16 streams).
TOL_DECODER_BWD = {"float32": 3e-4, "bfloat16": 3.2e-2}


def _sorted_ids(rng, n_nodes: int, max_degree: int, n_padding: int):
    """Receiver-sorted edge ids the way GraphLoader(sort_edges=True) lays a
    padded batch out: real nodes hold 0..max_degree edges, every padding
    edge lands on the FINAL dummy node (whose output row is unspecified)."""
    deg = rng.integers(0, max_degree + 1, n_nodes - 1)
    ids = np.repeat(np.arange(n_nodes - 1), deg)
    return np.concatenate([ids, np.full(n_padding, n_nodes - 1)]).astype(np.int32)


# The fused-edge call of the benchmark's EGNN-866 cells (PERF.md section 4):
# 12136 node slots (95 row blocks of 128 -> [12160, 896]), 196608 edge slots,
# in-degree bound 36, mean degree 16.
CELL_SHAPE = {"n_nodes": 12136, "edges": 196608, "max_degree": 36,
              "mean_degree": 15.6}


def _cell_ids(rng, n_nodes: int, edges: int, max_degree: int,
              mean_degree: float):
    """``_sorted_ids`` at a FIXED edge count: capped-Poisson real degrees,
    the rest of the ``edges`` slots padding on the dummy node."""
    deg = np.minimum(rng.poisson(mean_degree, n_nodes - 1), max_degree)
    ids = np.repeat(np.arange(n_nodes - 1), deg)
    assert ids.shape[0] < edges, (ids.shape[0], edges)
    return np.concatenate(
        [ids, np.full(edges - ids.shape[0], n_nodes - 1)]).astype(np.int32)


def kernel_cases(channels=(866, 256), n_nodes=2400, max_degree=20,
                 interpret=False, cell_shape=CELL_SHAPE):
    """Yield ``(name, dtype_name, check)``: ``check()`` compiles one kernel
    at one width and dtype, with the tiles its entry point runs when given
    none (the training step's), and returns ``[(label, rel_err[, tol]), ...]``
    against the kernel's plain-jnp reference (``tol`` where the comparison is
    not the dtype's ``TOL``)."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops.pallas_flash_attention import (
        flash_self_attention,
        reference_gathered_attention,
    )
    from hydragnn_tpu.ops.pallas_fused_edge import (
        fused_edge_message_sum,
        reference_edge_message_sum,
    )
    from hydragnn_tpu.ops.pallas_multi_agg import (
        fused_multi_agg,
        reference_multi_agg,
    )
    from hydragnn_tpu.ops.pallas_segment import sorted_segment_sum

    rng = np.random.default_rng(0)
    ids_np = _sorted_ids(rng, n_nodes, max_degree, n_padding=300)
    ids = jnp.asarray(ids_np)
    e = ids_np.shape[0]
    real = slice(0, n_nodes - 1)  # the dummy node's row is unspecified
    f32 = lambda x: x.astype(jnp.float32)

    def arr(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def segment(c, dtype):
        msg = arr((e, c), dtype)
        out = jax.jit(lambda m: sorted_segment_sum(
            m, ids, n_nodes, max_degree, interpret=interpret))(msg)
        ref = jax.ops.segment_sum(f32(msg), ids, num_segments=n_nodes)
        return [("forward", _rel_err(out[real], ref[real]))]

    def fused_edge(c, dtype, ids=ids, n_nodes=n_nodes, max_degree=max_degree,
                   tangent=False):
        e = ids.shape[0]
        real = slice(0, n_nodes - 1)
        nrecv, ein = arr((n_nodes, c), dtype), arr((e, c), dtype)
        w, b = arr((c, c), dtype, c ** -0.5), arr((c,), dtype)
        kernel = lambda nr, x, w_, b_: fused_edge_message_sum(
            nr, x, w_, b_, ids, n_nodes, max_degree, interpret=interpret)
        dense = lambda nr, x, w_, b_: reference_edge_message_sum(
            nr, x, w_, b_, ids, n_nodes)
        primals = (nrecv, ein, w, b)
        if not tangent:
            out = jax.jit(kernel)(*primals)
            with jax.default_matmul_precision("highest"):
                ref = dense(*map(f32, primals))
            return [("forward", _rel_err(out[real], ref[real]))]
        # the training step's use: the primal through the kernel, the tangent
        # through the custom-JVP rule, both in the stream dtype
        tangents = (arr((n_nodes, c), dtype), arr((e, c), dtype),
                    arr((c, c), dtype, c ** -0.5), arr((c,), dtype))
        out, t_out = jax.jit(
            lambda p, t: jax.jvp(kernel, p, t))(primals, tangents)
        with jax.default_matmul_precision("highest"):
            ref, t_ref = jax.jit(lambda p, t: jax.jvp(dense, p, t))(
                tuple(map(f32, primals)), tuple(map(f32, tangents)))
        return [("forward", _rel_err(out[real], ref[real])),
                ("tangent L2",
                 _rel_err(t_out[real], t_ref[real], np.linalg.norm),
                 TOL_TANGENT_L2)]

    def multi_agg(c, dtype):
        nrecv, ein = arr((n_nodes, c), dtype), arr((e, c), dtype)
        # the Hadamard gate operand (PNAPlus) rides the 256-wide case only
        gate = arr((e, c), dtype) if c == 256 else None
        outs = jax.jit(lambda nr, x, g: fused_multi_agg(
            nr, x, g, ids, n_nodes, max_degree, interpret=interpret,
        ))(nrecv, ein, gate)
        # the reference forms the message in the stream dtype exactly like
        # the kernel, then takes f32 moments
        refs = reference_multi_agg(nrecv, ein, gate, ids, n_nodes)
        return [
            (moment, _rel_err(o[real], r[real]))
            for o, r, moment in zip(
                outs, refs, ("sum", "count", "min", "max", "sumsq"))
        ]

    def flash(sizes, dtype):
        nmax, n_real, pad = max(sizes), sum(sizes), 9
        node_graph = jnp.asarray(np.concatenate(
            [np.full(s, i) for i, s in enumerate(sizes)]
            + [np.full(pad, len(sizes))]).astype(np.int32))
        node_mask = jnp.asarray(np.arange(n_real + pad) < n_real)
        n, g = n_real + pad, len(sizes) + 1
        q, k, v = (arr((n, 8, 32), dtype) for _ in range(3))
        out = jax.jit(lambda q_, k_, v_: flash_self_attention(
            q_, k_, v_, node_graph, node_mask, g, nmax,
            interpret=interpret))(q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = reference_gathered_attention(
                f32(q), f32(k), f32(v), node_graph, node_mask, g, nmax)
        return [("forward", _rel_err(out[:n_real], ref[:n_real]))]

    def gather_transpose(c, dtype, ids, n_nodes, max_degree):
        """The VJP of ``ops/segment.py gather(sorted_ids=True)`` (the
        sorted-segment kernel, routed as the step routes it) against the
        scatter-add JAX derives from ``x[ids]``, both read against a float32
        sum of the same cotangents; padding edges carry a zero cotangent, as
        in a step, so the dummy node's row is compared too."""
        from hydragnn_tpu.ops.segment import gather

        e = ids.shape[0]
        x = arr((n_nodes, c), dtype)
        ct = jnp.where((ids < n_nodes - 1)[:, None], arr((e, c), dtype), 0)
        vjp_of = lambda take: jax.jit(
            lambda v, t: jax.vjp(take, v)[1](t)[0])
        routed = vjp_of(lambda v: gather(v, ids, True, max_degree))
        scatter = vjp_of(lambda v: v[ids])
        assert "hg_sorted_segment" in str(jax.make_jaxpr(routed)(x, ct)), (
            "the sorted gather did not take the kernel route")
        ref = jax.ops.segment_sum(f32(ct), ids, num_segments=n_nodes)
        out, ms = _timed_ms(routed, x, ct)
        out_scatter, ms_scatter = _timed_ms(scatter, x, ct)
        return [(f"vjp {ms:.2f} ms", _rel_err(out, ref)),
                # read, not held: XLA accumulates in the operand dtype
                (f"the scatter-add it replaces {ms_scatter:.2f} ms",
                 _rel_err(out_scatter, ref), 1.0)]

    def row_gather(c, dtype, ids, n_nodes, max_degree):
        """A message layer's two row gathers, ``(x W_r + b)[recv]`` and
        ``(x W_s)[send]``, as ``models/layers.py hoisted_pair_dense`` orders
        them (product, gather, product, gather) against the same sum spelled
        plainly, which XLA schedules products first: the rows equal to the
        bit, and both wall times read (alone a pair may schedule either way;
        the step's own trace is what PERF.md holds)."""
        import types

        from flax import linen as nn
        from hydragnn_tpu.models.layers import hoisted_pair_dense

        e = ids.shape[0]
        send = jnp.asarray(rng.integers(0, n_nodes, e).astype(np.int32))
        batch = types.SimpleNamespace(senders=send, receivers=ids)

        class Ordered(nn.Module):
            @nn.compact
            def __call__(self, v):
                return hoisted_pair_dense(c, v, batch, "recv", "send",
                                          sorted_ids=True, max_degree=max_degree)

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, v):
                return (nn.Dense(c, name="recv")(v)[ids]
                        + nn.Dense(c, use_bias=False, name="send")(v)[send])

        x = arr((n_nodes, c), dtype)
        params = {"params": {
            "recv": {"kernel": arr((c, c), dtype, c ** -0.5),
                     "bias": arr((c,), dtype)},
            "send": {"kernel": arr((c, c), dtype, c ** -0.5)}}}
        out, ms = _timed_ms(jax.jit(Ordered().apply), params, x)
        ref, ms_plain = _timed_ms(jax.jit(Plain().apply), params, x)
        assert out.dtype == ref.dtype == jnp.dtype(dtype), (out.dtype, ref.dtype)
        differing = float(jnp.sum(f32(out) != f32(ref))) / out.size
        return [(f"ordered {ms:.2f} ms, plain {ms_plain:.2f} ms, "
                 "share of entries that differ", differing, 0.0)]

    if cell_shape:
        # FIRST, the shape the bf16 training step runs since the edge length
        # joins the feature stream in bf16 (models/layers.py
        # pair_message_factored): layer 3's call used to receive f32
        cell = cell_shape
        cell_ids = jnp.asarray(_cell_ids(rng, **cell))
        yield (f"fused_edge cell c={channels[0]} n={cell['n_nodes']} "
               f"e={cell['edges']} deg<={cell['max_degree']} bfloat16",
               "bfloat16",
               lambda: fused_edge(channels[0], jnp.bfloat16, cell_ids,
                                  cell["n_nodes"], cell["max_degree"],
                                  tangent=True))
        # the receiver gather's transpose at the same shape: four of a
        # step's eight edge-sized scatter-adds are this call
        yield (f"gather_transpose cell c={channels[0]} n={cell['n_nodes']} "
               f"e={cell['edges']} deg<={cell['max_degree']} bfloat16",
               "bfloat16",
               lambda: gather_transpose(channels[0], jnp.bfloat16, cell_ids,
                                        cell["n_nodes"], cell["max_degree"]))
        # a layer's pair of row gathers at the same shape, in the order the
        # step gives them: twelve such gathers a step
        yield (f"row_gather cell c={channels[0]} n={cell['n_nodes']} "
               f"e={cell['edges']} deg<={cell['max_degree']} bfloat16",
               "bfloat16",
               lambda: row_gather(channels[0], jnp.bfloat16, cell_ids,
                                  cell["n_nodes"], cell["max_degree"]))
    for dtype in (jnp.bfloat16, jnp.float32):
        dt = jnp.dtype(dtype).name
        for c in channels:
            for kernel in (segment, fused_edge, multi_agg):
                yield (f"{kernel.__name__} c={c} {dt}", dt,
                       lambda k=kernel, c=c, d=dtype: k(c, d))
        # flash at 8 heads x 32 (the GPS cells' hidden 256): a packed batch
        # of <= 70-node graphs, and one long graph of >= 1024 nodes
        for sizes in ([70, 64, 31, 70, 58, 70, 12, 66, 70, 45, 70, 70, 53,
                       69, 70, 41], [1100]):
            yield (f"flash graphs={len(sizes)} nmax={max(sizes)} {dt}", dt,
                   lambda s=sizes, d=dtype: flash(s, d))


def kernel_leg(**shape) -> None:
    for name, dt, check in kernel_cases(**shape):
        for label, err, *tol in check():
            _check(f"{name} {label}", err, tol[0] if tol else TOL[dt])


def _blocked_causal_reference(q, k, v, node_graph, node_mask, block=1024, window=None):
    """Causal same-graph attention in plain jnp, one block of queries at a
    time (``[H, block, N]`` scores), so that 32768 nodes fit; under
    ``window`` a query sees its ``window`` latest keys only."""
    import jax
    import jax.numpy as jnp

    n, hq, d = q.shape
    kf = jnp.repeat(k, hq // k.shape[1], axis=1)
    vf = jnp.repeat(v, hq // v.shape[1], axis=1)
    idx = jnp.arange(n, dtype=jnp.int32)

    def one(args):
        qb, ib, gb, rb = args
        s = jnp.einsum("ihd,jhd->hij", qb, kf) / np.sqrt(d)
        ok = ((gb[:, None] == node_graph[None, :]) & (rb[:, None] & node_mask[None, :])
              & (idx[None, :] <= ib[:, None]))
        if window is not None:
            ok = ok & (ib[:, None] - idx[None, :] < window)
        p = jnp.where(ok[None], jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1), 0.0)
        return jnp.einsum("hij,jhd->ihd", p, vf)

    blocks = lambda a: a.reshape((-1, block) + a.shape[1:])
    out = jax.lax.map(jax.checkpoint(one), (blocks(q), blocks(idx), blocks(node_graph), blocks(node_mask)))
    return out.reshape(n, hq, v.shape[2])


def decoder_kernel_leg(tokens=32768, heads=8, kv_heads=2, head_dim=128,
                       longest=8192, groups=8, width=2048, interpret=False,
                       dtypes=("bfloat16", "float32"), value_dim=None,
                       width_out=None, topk=0, experts=0, capacity=2.0,
                       block=1024, window=None) -> dict:
    """The decoder's two kernels alone at a cell's shapes, forward and
    backward, against plain jnp: causal grouped-query flash attention over
    ``[tokens, heads x head_dim]`` (values ``value_dim`` wide, ``head_dim``
    unless given) with a longest graph of ``longest`` nodes and a graph
    boundary inside a tile; the grouped product over ``groups`` experts of
    ragged size, ``width -> width_out`` (``width`` unless given). The
    defaults are the ZAYA cell's (one slot a token); ``topk`` > 0 takes the
    JOYAI cell's layout instead: every token chooses ``topk`` of ``experts``,
    ``groups`` of them held, rows within ``capacity`` times the balanced
    count, dispatch and combine around the product, and the routing alone
    (``routing_times``) (``joyai_kernel_leg``).
    ``window`` adds the SLIDING launches on the same operands beside the full
    ones (``trinity_kernel_leg``: a step of that cell holds both kinds).
    Prints each launch's time (a set-up fact, not a throughput)."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops import pallas_grouped_matmul as gm
    from hydragnn_tpu.ops.pallas_flash_attention import flash_causal_attention

    rng = np.random.default_rng(0)
    sizes, left = [longest], tokens - longest - 37
    while left > 0:
        sizes.append(int(min(left, rng.integers(max(longest // 64, 1), max(longest // 2, 2)))))
        left -= sizes[-1]
    n_real = sum(sizes)
    node_graph = jnp.asarray(np.concatenate(
        [np.full(s, i) for i, s in enumerate(sizes)] + [np.full(tokens - n_real, len(sizes))]).astype(np.int32))
    node_mask = jnp.asarray(np.arange(tokens) < n_real)
    times = {}

    def timed(name, fn, *args):
        out, ms = _timed_ms(fn, *args, repeats=1)
        times[name] = round(ms, 2)
        return out

    for dt in dtypes:
        dtype = jnp.dtype(dt)
        arr = lambda shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32).astype(dtype)
        dv = value_dim or head_dim
        q, k, v = arr((tokens, heads, head_dim)), arr((tokens, kv_heads, head_dim)), arr((tokens, kv_heads, dv))
        w = arr((tokens, heads, dv)) * node_mask[:, None, None].astype(dtype)
        f32 = lambda a: a.astype(jnp.float32)
        for win in (None, window) if window else (None,):
            blocked = lambda q_, k_, v_, g_, m_: _blocked_causal_reference(q_, k_, v_, g_, m_, block, win)
            with jax.default_matmul_precision("highest"):
                ref_loss = lambda q_, k_, v_: jnp.sum(blocked(q_, k_, v_, node_graph, node_mask) * f32(w))
                ref_out = jax.jit(blocked)(f32(q), f32(k), f32(v), node_graph, node_mask)
                ref_grads = jax.jit(jax.grad(ref_loss, (0, 1, 2)))(f32(q), f32(k), f32(v))
            causal = lambda q_, k_, v_: flash_causal_attention(
                q_, k_, v_, node_graph, node_mask, longest, interpret=interpret, window=win)
            bwd = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(f32(causal(q_, k_, v_)) * f32(w)), (0, 1, 2)))
            tag = (f"flash_window({win})" if win else "flash_causal") + f" {dt}" + (
                f" {heads}x{head_dim}/{dv}" if value_dim else "")
            out = timed(tag + " fwd_ms", jax.jit(causal), q, k, v)
            _check(tag + " forward", _rel_err(out[:n_real], ref_out[:n_real]), TOL[dt])
            grads = timed(tag + " fwd+bwd_ms", bwd, q, k, v)
            for name, got, want in zip("qkv", grads, ref_grads):
                _check(f"{tag} d{name}", _rel_err(got, want), TOL_DECODER_BWD[dt])

        # ---- grouped product: ragged groups, one of them empty
        wide = width_out or width
        x, wts = arr((tokens, width)), arr((groups, width, wide), 1.0 / np.sqrt(width))
        cot = arr((tokens, wide))
        if topk:
            # every token chooses ``topk`` of ``experts``; the first ``groups`` are held, the first of them by nobody
            from hydragnn_tpu.models import decoder as dc

            choice = jnp.asarray(np.stack(
                [rng.choice(np.arange(1, experts), size=topk, replace=False) for _ in range(tokens)]).astype(np.int32))
            gate = arr((tokens, topk)).astype(jnp.float32)
            bm = gm.normalize_tiles(tokens * topk, width, wide, dtype=dt)[0]
            balanced = int(np.ceil(capacity * tokens * topk * groups / experts))
            rows_budget = -(-balanced // bm) * bm + groups * bm

            def through(kernel):
                def f(x_, w_):
                    lay = dc.topk_layout(choice, node_mask, tuple(range(groups)), experts, bm, rows_budget)
                    rows = dc.dispatch_rows(x_, lay["token"])
                    if kernel:
                        y = gm.grouped_matmul(rows, w_, lay["tile_group"], lay["n_tiles"], bm, interpret=interpret)
                    else:
                        y = gm.reference_grouped_matmul(rows, w_, lay["tile_group"], bm)
                    gate_row = jnp.concatenate([gate.reshape(-1), jnp.zeros((1,))])[lay["src"]]
                    return dc.combine_rows(y, gate_row, lay["token"], tokens)
                return f

            lay = jax.jit(lambda: dc.topk_layout(choice, node_mask, tuple(range(groups)), experts, bm, rows_budget))()
            assert int(lay["overrun"]) == 0 and int(lay["counts"][0]) == 0 and int(lay["counts"].sum()) > 0
            print(f"  top-{topk} of {experts}, {groups} held: {int(lay['counts'].sum())} rows of {tokens} tokens in "
                  f"{lay['src'].shape[0]} row slots, {int(lay['n_tiles'])} tiles of {bm} in use", flush=True)
            times.update(routing_times(x, choice, node_mask, groups, experts, bm, rows_budget, rng))
        else:
            share = rng.dirichlet(np.ones(groups - 1) * 2.0)
            slot_np = rng.choice(groups - 1, size=tokens, p=share)
            slot_np[rng.random(tokens) < 0.05] = groups  # not held here
            slot = jnp.asarray(slot_np.astype(np.int32))
            bm = gm.normalize_tiles(tokens, width, wide, dtype=dt)[0]

            def through(kernel):
                def f(x_, w_):
                    lay = gm.aligned_layout(slot, groups, bm)
                    rows = gm.permute_rows(x_, lay["src"], lay["dest"])
                    if kernel:
                        y = gm.grouped_matmul(rows, w_, lay["tile_group"], lay["n_tiles"], bm, interpret=interpret)
                    else:
                        y = gm.reference_grouped_matmul(rows, w_, lay["tile_group"], bm)
                    return gm.permute_rows(y, lay["dest"], lay["src"])
                return f

        tag = f"grouped_expert {dt} groups={groups} {width}->{wide}" + (f" top-{topk}" if topk else "")
        out = timed(tag + " fwd_ms", jax.jit(through(True)), x, wts)
        grad_of = lambda f: jax.jit(jax.grad(lambda x_, w_: jnp.sum(f32(f(x_, w_)) * f32(cot)), (0, 1)))
        grads = timed(tag + " fwd+bwd_ms", grad_of(through(True)), x, wts)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(through(False))(f32(x), f32(wts))
            ref_g = grad_of(through(False))(f32(x), f32(wts))
        _check(tag + " forward", _rel_err(out, ref), TOL[dt])
        for name, got, want in zip(("dx", "dw"), grads, ref_g):
            _check(f"{tag} {name}", _rel_err(got, want), TOL[dt])
    print("  launch times (ms): " + json.dumps(times), flush=True)
    return {"launch_ms": times}


def routing_times(x, choice, node_mask, groups, experts, block_m, rows_budget, rng) -> dict:
    """The top-k routing alone at a cell's shapes, milliseconds: the layout of
    a given ``choice [T, k]``, then the whole router (float32 scores over all
    ``experts`` at ``highest``, top-k, gates, layout, each row's gate, the
    loads), forward and forward + backward to the stream ``x`` and the
    router's matrix."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.models import decoder as dc
    from hydragnn_tpu.ops import pallas_grouped_matmul as gm

    held, topk = tuple(range(groups)), choice.shape[1]
    # the router reads no expert width
    spec = dc.ExpertSpec(num_experts=experts, top_k=topk, experts_held=held, width=0, shared=0, scale=1.0)
    w_r = jnp.asarray(rng.normal(size=(x.shape[1], experts)) / np.sqrt(x.shape[1]), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(rows_budget,)), jnp.float32)

    def router(x_, w_):
        ch, gate = dc.route({"router": w_}, jnp.zeros((experts,), jnp.float32), x_, spec)
        lay = dc.topk_layout(ch, node_mask, held, experts, block_m, rows_budget)
        gate_row = gm.permute_rows(gate.reshape(-1, 1), lay["src"], lay["dest"])[:, 0]
        return gate_row, dc.expert_loads(ch, node_mask, experts), lay["counts"]

    tag = f"routing top-{topk} of {experts} held={groups}"
    layout = jax.jit(lambda ch: dc.topk_layout(ch, node_mask, held, experts, block_m, rows_budget))
    times = {}
    for name, fn, args in (
            ("layout_ms", layout, (choice,)),
            ("router fwd_ms", jax.jit(router), (x, w_r)),
            ("router fwd+bwd_ms", jax.jit(jax.grad(lambda x_, w_: jnp.sum(router(x_, w_)[0] * probe), (0, 1))),
             (x, w_r))):
        times[f"{tag} {name}"] = round(_timed_ms(fn, *args)[1], 3)
    return times


def joyai_kernel_leg(interpret=False, **small) -> dict:
    """``decoder_kernel_leg`` at the JOYAI cell's shapes: latent attention's
    32 heads of 192-wide queries and keys beside 128-wide values over 16,384
    tokens, and the top-8 layout (16 of 256 experts held, 2048 -> 768) with
    dispatch and combine; bfloat16, the cell's precision."""
    shapes = dict(tokens=16384, heads=32, kv_heads=32, head_dim=192, value_dim=128, longest=8192,
                  groups=16, width=2048, width_out=768, topk=8, experts=256, dtypes=("bfloat16",), block=256)
    return decoder_kernel_leg(interpret=interpret, **{**shapes, **small})


def trinity_kernel_leg(interpret=False, **small) -> dict:
    """``decoder_kernel_leg`` at the Trinity cell's shapes: 32 query heads on
    4 key/value heads of 128 over 16,384 tokens with a document of 12,288, the
    full launches AND the sliding ones (window 2,048) on the same operands,
    and the top-8 layout (8 of 128 experts held, 2048 -> 1024) with dispatch
    and combine; bfloat16, the cell's precision."""
    shapes = dict(tokens=16384, heads=32, kv_heads=4, head_dim=128, longest=12288, window=2048,
                  groups=8, width=2048, width_out=1024, topk=8, experts=128, dtypes=("bfloat16",), block=256)
    return decoder_kernel_leg(interpret=interpret, **{**shapes, **small})


def dsa_times(tokens=32768, heads=32, kv_heads=4, head_dim=128, index_heads=16, index_dim=64, topk=2048,
              sizes=(20000, 7000, 3000, 1500), interpret=False, dtype="bfloat16") -> dict:
    """The learned sparse attention's launches alone at the Keye-VL-2.0
    cell's shapes, milliseconds: the indexer's selection (``hg_dsa_indexer``)
    over documents of ``sizes`` tokens, the launch of its loss and gradient
    (``hg_dsa_indexer_bwd``), and one masked causal launch under that
    selection (``hg_flash_sparse``), forward and forward + backward. Checks
    that every row selects ``min(n_t, topk)`` keys and that the launches'
    outputs are finite. The first readings a schedule is sized from."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops import pallas_dsa_indexer as dsa
    from hydragnn_tpu.ops.pallas_flash_attention import flash_causal_attention

    rng = np.random.default_rng(0)
    n_real = sum(sizes)
    node_graph = jnp.asarray(np.concatenate(
        [np.full(s, i) for i, s in enumerate(sizes)] + [np.full(tokens - n_real, len(sizes))]).astype(np.int32))
    node_mask = jnp.asarray(np.arange(tokens) < n_real)
    pos_np = np.concatenate([np.arange(s) for s in sizes] + [np.zeros(tokens - n_real, int)])
    pos = jnp.asarray(pos_np, jnp.int32)
    arr = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32).astype(jnp.dtype(dtype))
    qi, ki, w = arr((tokens, index_heads, index_dim)), arr((tokens, index_dim)), arr((tokens, index_heads))
    q, k, v = arr((tokens, heads, head_dim)), arr((tokens, kv_heads, head_dim)), arr((tokens, kv_heads, head_dim))
    nmax = max(sizes)
    select = jax.jit(lambda *a: dsa.dsa_select(*a, node_graph, node_mask, pos, topk, nmax, interpret))
    (words, _, _, lse_i), sel_ms = _timed_ms(select, qi, ki, w, repeats=3)
    counts = jax.jit(lambda x: jnp.sum(jax.lax.population_count(x), axis=(0, 2)))(words)[:tokens]
    want = np.where(np.asarray(node_mask), np.minimum(pos_np + 1, topk), 0)
    if not np.array_equal(np.asarray(counts), want):
        raise AssertionError("hg_dsa_indexer: a row does not hold min(n_t, topk) keys")
    attend = jax.jit(lambda q_, k_, v_: flash_causal_attention(q_, k_, v_, node_graph, node_mask, nmax,
                                                               interpret=interpret, select=words))
    (o, lse), fwd_ms = _timed_ms(attend, q, k, v, repeats=3)
    grad = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(attend(q_, k_, v_)[0].astype(jnp.float32)), (0, 1, 2)))
    grads, both_ms = _timed_ms(grad, q, k, v, repeats=3)
    loss = jax.jit(jax.value_and_grad(lambda a, b, c: dsa.dsa_index_loss(
        a, b, c, q, k, lse, words, lse_i, node_graph, node_mask, nmax, interpret), (0, 1, 2)))
    (value, igrads), loss_ms = _timed_ms(loss, qi, ki, w, repeats=3)
    for name, x in (("o", o), ("dq", grads[0]), ("index loss", value), ("dqI", igrads[0])):
        if not np.isfinite(np.asarray(x, np.float32)).all():
            raise AssertionError(f"{name}: non-finite")
    tag = f"dsa {tokens} tokens top-{topk}"
    times = {f"{tag} hg_dsa_indexer_ms": round(sel_ms, 2), f"{tag} hg_dsa_indexer_bwd_ms": round(loss_ms, 2),
             f"{tag} hg_flash_sparse fwd_ms": round(fwd_ms, 2), f"{tag} hg_flash_sparse fwd+bwd_ms": round(both_ms, 2),
             f"{tag} selected_pairs": int(want.sum())}
    print("  dsa times (ms): " + json.dumps(times), flush=True)
    return {"launch_ms": times}


def _checkpointed_cross_entropy(hidden, head, targets, weights, chunk_rows, den):
    """The token head's earlier rule, timed beside ``chunked_cross_entropy``:
    a scan of checkpointed chunks, so the backward scan computes each chunk's
    logits again before its two gradient products."""
    import jax
    import jax.numpy as jnp

    t = hidden.shape[0]
    chunk = max(1, min(int(chunk_rows), t))
    pad = (-t) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n_chunks = (t + pad) // chunk

    @jax.checkpoint
    def one(h, tgt, w):
        logits = jnp.dot(h, head.astype(h.dtype), preferred_element_type=jnp.float32,
                         precision="highest" if h.dtype == jnp.float32 else None)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (lse - picked))

    total, _ = jax.lax.scan(lambda c, xs: (c + one(*xs), None), jnp.zeros((), jnp.float32),
                            (hidden.reshape(n_chunks, chunk, -1), targets.reshape(n_chunks, chunk),
                             weights.reshape(n_chunks, chunk)))
    return total / den


def head_times(cells=(("zaya", 32768, 32784, 1), ("joyai", 16384, 16160, 2)), width=2048, chunk=4096,
               dtype="bfloat16", mtp_weight=0.3) -> dict:
    """The token head alone at the decoder cells' shapes (``cells``: name,
    rows, vocabulary, head passes; a second pass weighs ``mtp_weight``, as the
    JOYAI cell's multi-token-prediction pass through the same head):
    ``value_and_grad`` over the hidden rows and the head, milliseconds, under
    the earlier rule (each chunk checkpointed, its logits computed again in
    the backward scan) and under ``train/loss.py chunked_cross_entropy`` (the
    gradient formed in the forward scan), with each compiled program's
    temporary bytes. Checks that the two agree. The reading the change is
    sized from."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.train.loss import chunked_cross_entropy

    rng = np.random.default_rng(0)
    times = {}
    for name, rows, vocab, passes in cells:
        arr = lambda shape, s=1.0: jnp.asarray(s * rng.normal(size=shape), jnp.float32).astype(jnp.dtype(dtype))
        hidden = [arr((rows, width)) for _ in range(passes)]
        head = arr((width, vocab), width ** -0.5)
        targets = [jnp.asarray(rng.integers(0, vocab, rows), jnp.int32) for _ in range(passes)]
        weights = [jnp.asarray(rng.random(rows) < 0.95, jnp.float32) for _ in range(passes)]
        den = jnp.maximum(jnp.sum(weights[0]), 1.0)

        def program(rule):
            def loss(hs, hd):
                return sum((mtp_weight if i else 1.0) * rule(h, hd, t, w, chunk, den)
                           for i, (h, t, w) in enumerate(zip(hs, targets, weights)))
            return jax.jit(jax.value_and_grad(loss, (0, 1)))

        out, ms, temp = {}, {}, {}
        for rule_name, rule in (("checkpointed", _checkpointed_cross_entropy), ("grad_in_forward",
                                                                                 chunked_cross_entropy)):
            fn = program(rule)
            temp[rule_name] = int(fn.lower(hidden, head).compile().memory_analysis().temp_size_in_bytes)
            out[rule_name], ms[rule_name] = _timed_ms(fn, hidden, head)
        (l0, (dh0, dw0)), (l1, (dh1, dw1)) = out["checkpointed"], out["grad_in_forward"]
        _check(f"head {name} loss", _rel_err(l1, l0), 1e-6)
        for i in range(passes):
            _check(f"head {name} dh[{i}]", _rel_err(dh1[i], dh0[i]), 1e-2)
        _check(f"head {name} dhead", _rel_err(dw1, dw0), 1e-2)
        tag = f"head {name} [{rows}, {width}] x [{width}, {vocab}] chunk {chunk} x {passes}"
        times.update({f"{tag} {k}_ms": round(v, 2) for k, v in ms.items()})
        times.update({f"{tag} {k}_temp_bytes": v for k, v in temp.items()})
    print("  head times (ms): " + json.dumps(times), flush=True)
    return {"head_ms": times}


# ---------------------------------------------------------------------------
# main leg
# ---------------------------------------------------------------------------


def egnn_config(hidden=HIDDEN, head_dim=HEAD_DIM, batch_size=BATCH,
                num_epoch=2, **training):
    """The architecture dict of bench.py `_production_workload`."""
    return {
        "Verbosity": {"level": 1},
        "Dataset": {
            "name": "oc20_shaped",
            "node_features": {
                "name": ["atomic_number", "cartesian_coordinates", "forces"],
                "dim": [1, 3, 3],
            },
            "graph_features": {"name": ["energy"], "dim": [1]},
        },
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "EGNN",
                "equivariance": True,
                "radius": 5.0,
                "max_neighbours": 20,
                "hidden_dim": hidden,
                "num_conv_layers": CONV_LAYERS,
                "task_weights": [1.0, 100.0],
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 2,
                        "dim_sharedlayers": 50,
                        "num_headlayers": 3,
                        "dim_headlayers": [head_dim] * 3,
                    },
                    "node": {
                        "num_headlayers": 3,
                        "dim_headlayers": [head_dim] * 3,
                        "type": "mlp",
                    },
                },
            },
            "Variables_of_interest": {
                "input_node_features": [0, 1],
                "output_names": ["energy", "forces"],
                "output_index": [0, 2],
                "type": ["graph", "node"],
            },
            "Training": {
                "batch_size": batch_size,
                "num_epoch": num_epoch,
                "loss_function_type": "mae",
                "pack_batches": True,
                "mixed_precision": True,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
                **training,
            },
        },
    }


def _assert_kernel_routes_on(config) -> None:
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["use_sorted_aggregation"] is True, arch["use_sorted_aggregation"]
    assert arch["use_fused_edge_kernel"] is True, arch["use_fused_edge_kernel"]
    assert arch["max_in_degree"] > 0, arch["max_in_degree"]


def _assert_losses(hist, falling=True) -> None:
    losses = [*hist["train"], *hist["val"], *hist["test"]]
    assert losses and np.isfinite(losses).all(), hist
    if falling:
        # the epoch-mean MAE under AdamW 1e-3 falls from the first epoch on
        # this data; 2% is the bf16 + few-steps-per-epoch noise allowance
        assert hist["train"][1] <= hist["train"][0] * 1.02, hist["train"]


def _mosaic_calls(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def main_leg(hidden=HIDDEN, head_dim=HEAD_DIM, num_graphs=NUM_GRAPHS,
             batch_size=BATCH, check_lowering=True) -> dict:
    import jax

    import hydragnn_tpu
    from hydragnn_tpu.data import neighbors
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.data.synthetic import oc20_shaped_dataset
    from hydragnn_tpu.train import make_eval_step, make_optimizer, make_train_step
    from hydragnn_tpu.utils.timers import Timer

    datasets = split_dataset(oc20_shaped_dataset(num_graphs), 0.7, seed=0)
    t0 = time.perf_counter()
    model, state, hist, config, loaders, _ = hydragnn_tpu.run_training(
        egnn_config(hidden, head_dim, batch_size), datasets=datasets
    )
    train_s = time.perf_counter() - t0
    ttfs = Timer.totals()["time_to_first_step"]
    _assert_kernel_routes_on(config)
    _assert_losses(hist)
    # graphs this small stay on scipy's KD-tree: the native cell-list
    # library (g++-built) is never asked for, so it cannot degrade silently
    assert neighbors._native is None, "smoke unexpectedly built native neighbors"

    training = config["NeuralNetwork"]["Training"]
    batch = next(iter(loaders[2]))
    if check_lowering:
        # no hidden route: the programs the loop ran must carry the Mosaic
        # kernels, at least one per conv layer
        eval_step = make_eval_step(model, mixed_precision=True)
        n_fwd = _mosaic_calls(eval_step.lower(state, batch))
        train_step = make_train_step(
            model, make_optimizer(training["Optimizer"]), mixed_precision=True
        )
        n_train = _mosaic_calls(
            train_step.lower(state, batch, jax.random.PRNGKey(0))
        )
        print(f"  tpu_custom_call sites: forward {n_fwd}, train step {n_train}")
        assert n_fwd >= CONV_LAYERS and n_train >= CONV_LAYERS, (n_fwd, n_train)

    # kernel route == dense route on one real batch, f32 at "highest": the
    # two differ only in summation order (tolerance as in the kernel leg)
    outs = {}
    for route in ("1", "0"):  # read at trace time (ops/segment.py)
        with mock.patch.dict(os.environ, HYDRAGNN_PALLAS_SEGMENT=route), \
                jax.default_matmul_precision("highest"):
            outs[route] = make_eval_step(model)(state, batch)[2]
    for name in outs["1"]:
        mask = np.asarray(
            batch.graph_mask if name == "energy" else batch.node_mask)
        _check(f"model {name}: kernel vs dense route",
               _rel_err(np.asarray(outs["1"][name])[mask],
                        np.asarray(outs["0"][name])[mask]), TOL["float32"])

    tot, tasks, preds, trues = hydragnn_tpu.run_prediction(
        config, model_state=state, datasets=datasets
    )
    n_test = len(datasets[2])
    n_test_nodes = sum(g.num_nodes for g in datasets[2])
    assert np.isfinite(tot) and np.isfinite(list(tasks.values())).all(), tasks
    assert preds["energy"].shape == (n_test, 1), preds["energy"].shape
    assert preds["forces"].shape == (n_test_nodes, 3), preds["forces"].shape
    assert all(np.isfinite(p).all() for p in preds.values())
    # run_prediction of the in-memory state repeats the loop's last test pass
    assert abs(tot - hist["test"][-1]) <= 1e-5 * max(1.0, abs(tot)), (
        tot, hist["test"][-1])

    requests = datasets[2][:4]
    with hydragnn_tpu.run_server(config, datasets=datasets) as server:
        assert server.wait_ready(600), f"server not ready: {server.failed}"
        handles = [server.submit(g) for g in requests]
        results = [h.result(120) for h in handles]
        stats = server.stats()
    assert stats["completed"] == len(requests) and stats["failed_batches"] == 0, stats
    assert stats["retrace_violations"] == 0, stats
    for g, r in zip(requests, results):
        assert r["energy"].shape == (1,) and r["forces"].shape == (g.num_nodes, 3)
        assert np.isfinite(r["energy"]).all() and np.isfinite(r["forces"]).all()
    print(f"  served {stats['completed']} requests in {stats['batches']} "
          f"batch(es) from checkpoint {stats['current_checkpoint']}")
    return {
        "losses": {k: [float(x) for x in hist[k]] for k in ("train", "val", "test")},
        "time_to_first_step_s": round(ttfs, 3),
        "train_wall_s": round(train_s, 3),
    }


def second_order_leg(check_lowering=True) -> dict:
    """compute_grad_energy (forces = -dE/dpos inside the loss, differentiated
    again by the training grad) through the sorted-segment kernel's
    custom_jvp, at the examples/md17/md17.json widths."""
    import jax

    import hydragnn_tpu
    from hydragnn_tpu.data import md17_shaped_dataset
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.train import make_optimizer, make_train_step

    config = {
        "Verbosity": {"level": 1},
        "Dataset": {"name": "md17_shaped",
                    "node_features": {"name": ["atomic_number"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet", "radius": 5.0, "max_neighbours": 32,
                "hidden_dim": 64, "num_conv_layers": 3, "task_weights": [1.0],
                "output_heads": {"node": {
                    "num_headlayers": 2, "dim_headlayers": [64, 64],
                    "type": "mlp"}},
            },
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["graph_energy"],
                "output_index": [0], "output_dim": [1], "type": ["node"],
            },
            "Training": {
                "num_epoch": 2, "batch_size": 32, "compute_grad_energy": True,
                "loss_function_type": "mae",
                "Optimizer": {"type": "AdamW", "learning_rate": 2e-3},
            },
        },
    }
    datasets = split_dataset(md17_shaped_dataset(128), 0.7, seed=0)
    model, state, hist, config, loaders, _ = hydragnn_tpu.run_training(
        config, datasets=datasets
    )
    _assert_kernel_routes_on(config)
    _assert_losses(hist)
    if check_lowering:
        step = make_train_step(
            model, make_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"]),
            compute_grad_energy=True,
        )
        n_calls = _mosaic_calls(step.lower(
            state, next(iter(loaders[0])), jax.random.PRNGKey(0)))
        print(f"  tpu_custom_call sites in the energy-force train step: {n_calls}")
        n_layers = config["NeuralNetwork"]["Architecture"]["num_conv_layers"]
        assert n_calls >= n_layers, n_calls
    return {"losses": {"train": [float(x) for x in hist["train"]]},
            "egnn_force_gradient_gap": egnn_force_gradient_gap()}


def egnn_force_gradient_gap(hidden=64, tol=5e-3) -> float:
    """Grad-of-grad through the receiver gather's transpose (ops/segment.py
    ``gather(sorted_ids=True)``: a linear call whose JVP is itself on the
    tangent): the energy-force training gradient of a two-layer EGNN (the
    fused call's tangent rule in both layers) on a packed, padded
    Lennard-Jones batch, every parameter leaf against the same step with the
    plain gather. Returns the largest gap, by each leaf's largest entry."""
    import jax

    import hydragnn_tpu.models.layers as layers
    import hydragnn_tpu.ops.pallas_fused_edge as fused
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data import GraphLoader, lennard_jones_dataset
    from hydragnn_tpu.data.pipeline import split_dataset
    from hydragnn_tpu.models import create_model, init_model
    from hydragnn_tpu.train.loss import compute_loss

    datasets = split_dataset(lennard_jones_dataset(64), 0.75, seed=0)
    config = update_config({
        "Verbosity": {"level": 0},
        "Dataset": {"node_features": {"name": ["type"], "dim": [1]}},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "EGNN", "radius": 2.5, "max_neighbours": 32,
                "hidden_dim": hidden, "num_conv_layers": 2,
                "task_weights": [1.0],
                "output_heads": {"node": {
                    "num_headlayers": 2, "dim_headlayers": [hidden, hidden],
                    "type": "mlp"}},
            },
            "Variables_of_interest": {
                "input_node_features": [0], "output_names": ["graph_energy"],
                "output_index": [0], "output_dim": [1], "type": ["node"],
            },
            "Training": {
                "num_epoch": 1, "batch_size": 16, "compute_grad_energy": True,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        },
    }, *datasets)
    _assert_kernel_routes_on(config)
    batch = next(iter(GraphLoader(
        datasets[0], 16, seed=0, drop_last=True, sort_edges=True, pack=True,
        max_in_degree=config["NeuralNetwork"]["Architecture"]["max_in_degree"])))
    assert not np.asarray(batch.edge_mask).all(), "no padding edge in the batch"
    model = create_model(config)
    variables = init_model(model, batch, seed=0)

    def loss(params):
        return compute_loss(
            model, {"params": params, "batch_stats": variables.get("batch_stats", {})},
            batch, model.cfg, True, jax.random.PRNGKey(0), True)[0]

    def leaves():
        grad = jax.jit(jax.grad(loss))
        text = str(jax.make_jaxpr(grad)(variables["params"]))
        return text.count("= linear_call["), [
            np.asarray(g, np.float64) for g in jax.tree_util.tree_leaves(
                grad(variables["params"]))]

    calls, routed = leaves()
    assert calls > 0, "the energy-force gradient holds no transposed gather"
    plain_gather = lambda values, index, *a, **k: values[index]
    with mock.patch.object(layers, "gather", plain_gather), \
            mock.patch.object(fused, "gather", plain_gather):
        plain_calls, plain = leaves()
    # the fused rule's closing sums stay linear calls under the plain gather
    assert 0 < plain_calls < calls, (plain_calls, calls)
    floor = 1e-3 * max(np.abs(g).max() for g in plain)
    gap = max(np.abs(a - b).max() / max(np.abs(b).max(), floor)
              for a, b in zip(routed, plain))
    print(f"  EGNN energy-force gradient, transposed gather against plain: "
          f"{calls} linear calls, largest leaf gap {gap:.3e} (tol {tol:.0e})",
          flush=True)
    assert np.isfinite(gap) and gap <= tol, gap
    return float(gap)


# ---------------------------------------------------------------------------
# multi-device leg (builder-run on a four-chip host; skipped on one chip)
# ---------------------------------------------------------------------------


def _peak_bytes_per_device() -> list:
    import jax

    return [
        {"id": d.id, "peak_bytes_in_use": d.memory_stats()["peak_bytes_in_use"]}
        for d in jax.local_devices()
    ]


def mesh_leg(hidden=HIDDEN, head_dim=HEAD_DIM, batch_size=BATCH) -> dict:
    """The same EGNN through the mesh step over every local device.

    ``Optimizer.zero_stage: 2`` is the setting that reaches
    ``make_mesh_train_step`` on one host. The train split is exactly one
    global batch, so epoch 0's train loss IS the first-step loss, and the
    one-chip value on the same global batch is the plain single-device
    step's loss per shard row, combined by real-graph count exactly as the
    mesh step's pmean does (parallel/engine.py unrouted_grads)."""
    import jax
    import jax.numpy as jnp

    import hydragnn_tpu
    from hydragnn_tpu.data.synthetic import oc20_shaped_dataset
    from hydragnn_tpu.models import init_model
    from hydragnn_tpu.train import TrainState, make_optimizer, make_train_step

    n_dev = jax.local_device_count()
    graphs = oc20_shaped_dataset(batch_size + 16)
    datasets = (graphs[:batch_size], graphs[batch_size:batch_size + 8],
                graphs[batch_size + 8:])
    config = egnn_config(hidden, head_dim, batch_size, pack_batches=False,
                         num_pad_buckets=1)
    config["NeuralNetwork"]["Training"]["Optimizer"]["zero_stage"] = 2
    model, state, hist, config, loaders, _ = hydragnn_tpu.run_training(
        config, datasets=datasets
    )
    _assert_kernel_routes_on(config)
    # finite only: with ONE step per epoch the second value is the loss
    # right after the first AdamW step, which at width 866 overshoots
    # (measured on 4 chips: 1.819 -> 2.746) where an epoch mean would fall
    _assert_losses(hist, falling=False)

    # every device holds a slice of the ZeRO moments ...
    moments = [
        x for x in jax.tree_util.tree_leaves(state.opt_state)
        if hasattr(x, "sharding") and x.ndim >= 1
        and not x.sharding.is_fully_replicated
    ]
    assert moments, "no optimizer-state leaf is sharded under zero_stage 2"
    for x in moments:
        assert len({s.device for s in x.addressable_shards}) == n_dev
        assert x.addressable_shards[0].data.shape[0] * n_dev == x.shape[0]
    # ... and did work: its peak is at least one replica of the parameters
    # (an idle v5e chip reports ~27 KB, so "> 0" would prove nothing)
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state.params))
    per_device = _peak_bytes_per_device()
    assert all(d["peak_bytes_in_use"] >= param_bytes for d in per_device), (
        param_bytes, per_device)

    # one-chip reference for the first step, on the same global batch
    train_loader = loaders[0]
    train_loader.set_epoch(0)
    stacked = next(iter(train_loader))
    assert np.asarray(stacked.graph_mask).shape[0] == n_dev
    rows = [jax.tree_util.tree_map(lambda x, i=i: np.asarray(x)[i], stacked)
            for i in range(n_dev)]
    counts = [float(np.asarray(r.graph_mask).sum()) for r in rows]
    assert all(c > 0 for c in counts), counts  # every device holds a shard
    training = config["NeuralNetwork"]["Training"]
    tx = make_optimizer(training["Optimizer"])
    variables = init_model(model, rows[0], seed=int(training.get("seed", 0)))
    step = make_train_step(model, tx, mixed_precision=True)
    row_losses = []
    for row in rows:
        fresh = TrainState.create(
            jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), variables), tx)
        row_losses.append(float(step(fresh, row, jax.random.PRNGKey(0))[1]))
    one_chip = float(np.dot(row_losses, counts) / np.sum(counts))
    mesh_loss = float(hist["train"][0])
    # __graft_entry__.dryrun_multichip's assert_matches tolerance
    delta = abs(mesh_loss - one_chip)
    print(f"  first-step loss: mesh {mesh_loss:.6f} vs one-chip {one_chip:.6f} "
          f"(|delta| {delta:.2e})")
    assert delta <= 5e-4 * max(1.0, abs(one_chip)), (mesh_loss, one_chip)
    return {
        "devices": n_dev,
        "first_step_loss": mesh_loss,
        "one_chip_loss": one_chip,
        "sharded_moment_leaves": len(moments),
        "param_bytes": int(param_bytes),
        "graphs_per_device": counts,
        "per_device": per_device,
        "losses": {"train": [float(x) for x in hist["train"]]},
    }


def result_line(device: dict) -> str:
    """The last stdout line of a passing run. The driver's chip check accepts
    exactly these keys and no other; everything else goes in the report line
    above it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main() -> int:
    t_start = time.perf_counter()
    device = require_tpu()
    import jax

    from hydragnn_tpu.train.compile_plane import (
        compile_metrics,
        setup_compile_cache,
    )

    # cache every program, however quick its compile: the second run of a
    # pair must then report hits and NO miss, with no threshold to straddle
    os.environ.setdefault("HYDRAGNN_COMPILE_CACHE_MIN_SECS", "0")
    cache_dir = setup_compile_cache()
    repo = os.path.dirname(os.path.abspath(__file__))
    # run logs, checkpoints and tuned tables land in a fresh directory, so
    # the smoke reads nothing it did not write in this run; only the compile
    # cache (train/compile_plane.py compile_cache_dir) outlives it
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    os.chdir(workdir)
    todo = [("kernels", kernel_leg), ("decoder_kernels", decoder_kernel_leg),
            ("joyai_kernels", joyai_kernel_leg), ("trinity_kernels", trinity_kernel_leg), ("dsa", dsa_times),
            ("head", head_times), ("main", main_leg),
            ("second_order", second_order_leg)]
    if jax.local_device_count() > 1:
        todo.append(("mesh", mesh_leg))
    legs = {}
    try:
        for name, leg in todo:
            print(f"[{name}]", flush=True)
            t0 = time.perf_counter()
            legs[name] = {"ok": True, **(leg() or {}),
                          "wall_s": round(time.perf_counter() - t0, 1)}
    finally:
        os.chdir(repo)
        shutil.rmtree(workdir, ignore_errors=True)
    metrics = compile_metrics()
    print("chip_smoke report: " + json.dumps({
        "legs": legs,
        "time_to_first_step_s": legs["main"]["time_to_first_step_s"],
        "compile_s": round(metrics["backend_compile_s"], 2),
        "cache_hits": int(metrics["cache_hits"]),
        "cache_misses": int(metrics["cache_misses"]),
        "cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t_start, 1),
    }))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
