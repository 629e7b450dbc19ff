"""Shared building blocks: MLP, masked batch norm, activation resolver."""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.remat import kernel_remat, tag as remat_tag
from ..ops.segment import fused_edge_message_sum as _fused_edge_message_sum
from ..ops.segment import gather


def mirrored_lecun_normal():
    """LeCun-normal kernel init with columns drawn in ``(w, -w)`` pairs.

    For a ReLU layer whose inputs are nonnegative (everything downstream of
    a ReLU encoder — exactly the decoder-head position), a zero-bias unit is
    dead on the WHOLE dataset iff ``w·x < 0`` for every sample; with few
    units the probability that every unit draws dead is seed-visible (a
    hidden-8 matrix run measured GIN/EGNN stalled at the conv-free minimum
    at Training.seed=0). Pairing each column with its negation guarantees
    that for any input with ``w·x != 0`` one unit of the pair is active, so
    no seed can produce a fully dead layer and gradients always flow.
    The ReLU gates break the pair symmetry after the first update, and the
    per-column scale is the usual lecun_normal (same as flax's default), so
    trained behavior is unchanged. This replaces the round-3 workaround of
    pinning a measured healthy seed.
    """

    base = nn.initializers.lecun_normal()

    def init(key, shape, dtype=jnp.float_):
        if len(shape) != 2:
            return base(key, shape, dtype)
        fan_in, fan_out = shape
        half = (fan_out + 1) // 2
        w = base(key, (fan_in, half), dtype)
        return jnp.concatenate([w, -w[:, : fan_out - half]], axis=1)

    return init

ACTIVATIONS = {
    "relu": nn.relu,
    "gelu": nn.gelu,
    "silu": nn.silu,
    "swish": nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": nn.sigmoid,
    "elu": nn.elu,
    "leaky_relu": nn.leaky_relu,
    "softplus": nn.softplus,
    "identity": lambda x: x,
}


def get_activation(name: str) -> Callable:
    """(reference activation selection: hydragnn/utils/model/model.py and
    loss/activation test, tests/test_loss_and_activation_functions.py)"""
    try:
        return ACTIVATIONS[name.lower()]
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; known: {sorted(ACTIVATIONS)}")


class MLP(nn.Module):
    """Dense stack with activation between layers, none after the last
    (matches the reference's Sequential(Linear, act, ..., Linear) head MLPs,
    Base.py:372-392)."""

    features: Sequence[int]
    activation: str = "relu"
    final_activation: bool = False
    # decoder-position MLPs (nonnegative inputs) use the mirrored init so no
    # rng draw can produce a fully ReLU-dead layer; see mirrored_lecun_normal
    mirror_init: bool = False
    # recovery slope for narrow decoder MLPs: with plain ReLU a dead unit
    # has exactly zero gradient forever, and a 4-10 unit decoder measurably
    # dies DURING training at some seeds (alive at init, killed by early
    # updates + weight decay; the run then sits at the constant-prediction
    # floor while the encoder still carries 0.9-correlated features).
    # Call sites pass 0.1: it keeps every unit recoverable within an
    # early-stopping patience window (0.01 measured too slow — a
    # soft-dead layer's 100x attenuation left gradients under the
    # recovery rate). Applied only when the configured activation is
    # relu, to every activation this MLP applies (including the
    # final_activation=True one of shared decoder stacks — those feed
    # further head layers, so slightly-negative features are benign).
    recovery_slope: float = 0.0

    @nn.compact
    def __call__(self, x):
        act = get_activation(self.activation)
        if self.recovery_slope and self.activation.lower() == "relu":
            slope = self.recovery_slope
            act = lambda v: nn.leaky_relu(v, negative_slope=slope)
        for i, f in enumerate(self.features):
            last = i == len(self.features) - 1
            if self.mirror_init and (not last or self.final_activation):
                x = nn.Dense(f, kernel_init=mirrored_lecun_normal())(x)
            else:
                x = nn.Dense(f)(x)
            if not last or self.final_activation:
                x = act(x)
        return x


class MaskedBatchNorm(nn.Module):
    """BatchNorm1d over *real* nodes only.

    The reference applies torch BatchNorm1d after every conv (Base.py:214,466).
    With padded static batches the statistics must exclude padding rows, hence
    this masked variant; running stats live in the ``batch_stats`` collection.
    """

    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x, mask: Optional[jnp.ndarray] = None, train: bool = True):
        features = x.shape[-1]
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((features,), jnp.float32)
        )
        ra_count = self.variable(
            "batch_stats", "count", lambda: jnp.zeros((), jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (features,))
        bias = self.param("bias", nn.initializers.zeros, (features,))

        if train:
            if mask is None:
                n = jnp.asarray(float(x.shape[0]), x.dtype)
                mean = jnp.mean(x, axis=0)
                var = jnp.var(x, axis=0)
            else:
                m = mask[:, None].astype(x.dtype)
                n = jnp.maximum(jnp.sum(m), 1.0)
                mean = jnp.sum(x * m, axis=0) / n
                var = jnp.sum(((x - mean) ** 2) * m, axis=0) / n
            if not self.is_initializing():
                # count-weighted EMA: a remainder batch with few real rows
                # moves the running stats proportionally less (plain
                # equal-weight EMA lets one tiny ragged batch poison eval
                # statistics; for constant batch sizes this reduces exactly
                # to the torch BatchNorm1d update the reference relies on)
                c_new = self.momentum * ra_count.value + (1 - self.momentum) * n
                w_old = self.momentum * ra_count.value / jnp.maximum(c_new, 1e-8)
                w_new = 1.0 - w_old
                ra_mean.value = w_old * ra_mean.value + w_new * mean
                ra_var.value = w_old * ra_var.value + w_new * var
                ra_count.value = c_new
        else:
            mean, var = ra_mean.value, ra_var.value

        y = (x - mean) / jnp.sqrt(var + self.epsilon)
        y = y * scale + bias
        # numerics tap (obs/numerics.py): the pre-activation normalized
        # output, named by module path — a no-op unless Telemetry.numerics
        # armed a collection context at trace time. Batch norm is the first
        # place a collapsing variance shows (1/sqrt(var) blowing up), one
        # layer before the activation probe in models/base.py sees it.
        from ..obs.numerics import collection_active, probe

        if collection_active():
            try:
                pname = "/".join(str(p) for p in self.path)
            except Exception:
                pname = self.name or "batchnorm"
            probe(f"bn:{pname}", y, mask)
        return y


def pair_message_factored(dim, inv, batch, name_recv, name_send, edge_terms=()):
    """The factored first edge-MLP layer, distributed over its concat
    inputs: a NODE-sized receiver projection (``[N, C]``, carrying the one
    bias — same total as the post-concat layer) and ONE edge-aligned
    operand (bias-free sender projection gathered by ``senders``, plus a
    bias-free projection per ``edge_terms`` entry). Returns
    ``(node_recv [N, C], edge_in [E, C])``.

    This is the SINGLE spelling of the recv-bias/send-no-bias parameter
    convention — ``hoisted_pair_dense``, ``fused_pair_dense_sum`` and the
    PNA family's pre-message (models/pna.py) all build on it, which is
    what keeps their parameter trees checkpoint-interchangeable. Keeping
    ``node_recv`` un-gathered is what lets the fused kernels run the
    receiver gather in-register (ops/pallas_fused_edge.py,
    ops/pallas_multi_agg.py).

    An edge term joins the feature stream in the feature stream's dtype
    (``inv.dtype``): geometry stays float32 under mixed precision (the
    coordinate update's ``segment_mean`` promotes), and one f32 ``[E, 1]``
    length would otherwise promote every ``[E, C]`` array downstream of
    it, the Pallas kernels' streams included. The identity in a float32
    step."""
    edge_in = gather(
        nn.Dense(dim, use_bias=False, name=name_send)(inv), batch.senders)
    # ORDER, not arithmetic: the receiver projection waits for the sender
    # gather, so a layer runs product, gather, product, gather. On TPU a row
    # gather writes at the speed of HBM only while its node-sized operand is
    # still in VMEM, and XLA keeps it there when the op that made it is
    # scheduled directly before the gather; with both products hoisted ahead
    # of both gathers one operand is left in HBM and its gather fetches it a
    # row at a time, six times slower. The barrier's transpose orders the
    # backward the same way (PERF.md section 6, PR 32;
    # tests/test_fused_edge.py holds the compiled step to it).
    inv, edge_in = jax.lax.optimization_barrier((inv, edge_in))
    node_recv = nn.Dense(dim, name=name_recv)(inv)
    for name, arr in edge_terms:
        edge_in = edge_in + nn.Dense(dim, use_bias=False, name=name)(
            arr.astype(inv.dtype)
        )
    return node_recv, edge_in


def hoisted_pair_dense(dim, inv, batch, name_recv, name_send, edge_terms=(),
                       sorted_ids=False, max_degree=0):
    """First edge-MLP layer distributed over its concat inputs and computed
    on node-sized operands BEFORE the edge gather:

        Dense(concat[x_i, x_j, e...]) == Dense_r(x)_i + Dense_s(x)_j
                                          + sum_k Dense_k(e_k)

    (parameters via ``pair_message_factored`` above). The node-side
    matmuls run on [N, C] instead of [E, 2C]: at degree ~20 that is ~20x
    fewer MXU FLOPs and half the gather bytes for this layer, with
    identical function class to the reference's post-concat edge MLPs
    (e.g. EGCLStack.py:238-247, PNAPlusStack.py:268).

    ``edge_terms`` is an iterable of (name, [E, d] array) extra edge-aligned
    operands, each getting its own bias-free projection.

    ``sorted_ids`` / ``max_degree`` are the flags the caller's module hands
    ``segment_sum``: with them the RECEIVER gather's transpose runs as a
    sorted segment sum (ops/segment.py ``gather``) instead of XLA's
    scatter-add. The sender gather has no such order and stays as it is.

    When the downstream consumer is relu -> Dense -> relu -> segment_sum and
    nothing else reads the per-edge messages, prefer
    ``fused_pair_dense_sum`` below: same parameters, but the whole chain
    runs in one VMEM-resident Pallas kernel on TPU.
    """
    node_recv, edge_in = pair_message_factored(
        dim, inv, batch, name_recv, name_send, edge_terms
    )
    return gather(node_recv, batch.receivers, sorted_ids, max_degree) + edge_in


class _FusedEdgeDense(nn.Module):
    """Params of the second edge-dense layer (``kernel``/``bias``, named
    and initialized exactly like ``nn.Dense`` so the fused and unfused
    routes share one checkpoint format) + the fused Pallas/dense call.

    The op is remat-wrapped per ``Training.remat_policy`` (ops/remat.py;
    default ``full`` = the historical bare ``jax.checkpoint``) so the
    plain-jnp tangent rule's residuals (pre-activation, relu masks —
    [E, C] arrays) are recomputed in the backward instead of materialized
    in the forward: the training forward stays VMEM-resident, which is
    the point of the fusion. The output carries the ``fused_edge_sum``
    checkpoint-name tag for the ``names`` policy's save set.
    """

    features: int
    max_in_degree: int
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, node_recv, edge_in, receivers, num_segments):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (edge_in.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        dtype = jnp.result_type(node_recv, edge_in, kernel, bias)
        max_degree = self.max_in_degree

        def call(nr, ei, w, b):
            return remat_tag(_fused_edge_message_sum(
                nr.astype(dtype), ei.astype(dtype), w.astype(dtype),
                b.astype(dtype), receivers, num_segments, max_degree,
            ), "fused_edge_sum")

        return kernel_remat(call, self.remat_policy)(
            node_recv, edge_in, kernel, bias
        )


def fused_pair_dense_sum(dim, inv, batch, name_recv, name_send, name_out,
                         edge_terms=(), max_in_degree: int = 0,
                         remat_policy: str = "full"):
    """Fused counterpart of the whole EGNN-style edge hot path:

        hoisted_pair_dense -> relu -> Dense(name_out) -> relu -> segment_sum

    in ONE op (ops/segment.py fused_edge_message_sum; the Pallas kernel on
    TPU keeps per-edge messages VMEM-resident). Same parameter tree as the
    unfused spelling — ``name_recv``/``name_send``/``edge_terms`` denses
    here, ``kernel``/``bias`` under ``name_out`` — so checkpoints and
    A/B inits are interchangeable between routes.

    The receiver projection stays NODE-sized ([N, C], gathered in-kernel by
    the receiver-sorted one-hot); the sender projection and the edge-local
    terms collapse into the single edge-aligned operand the kernel streams.
    Requires receiver-sorted batches and a static in-degree bound, like
    ``segment_sum(sorted_ids=True)``; padding edges land on the dummy node,
    whose garbage row every consumer already masks (data/graph.py).
    """
    node_recv, edge_in = pair_message_factored(
        dim, inv, batch, name_recv, name_send, edge_terms
    )
    return _FusedEdgeDense(dim, max_in_degree, remat_policy, name=name_out)(
        node_recv, edge_in, batch.receivers, batch.num_nodes
    )
