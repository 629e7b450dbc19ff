"""Operations and bytes that the AFMOE configuration's kernels REQUIRE for
given real work (no padding, no recompute), for their roofline shares
(``metrics/window_flash_roofline_share.train.py``,
``metrics/full_flash_roofline_share.train.py``,
``metrics/trinity_expert_roofline_share.train.py``). One step holds causal
flash launches of two kinds, over other pair counts and other numbers of
layers, and expert layers that are not all the layers: ``kernel_work.py``
counts one kind on every layer."""

from __future__ import annotations

from typing import Dict, Tuple

import kernel_work

BF16 = 2
SLIDING = "sliding_attention"


def sliding_layers(arch: Dict) -> int:
    return sum(1 for kind in arch["layer_types"] if kind == SLIDING)


def full_layers(arch: Dict) -> int:
    return len(arch["layer_types"]) - sliding_layers(arch)


def expert_layers(arch: Dict) -> int:
    return int(arch["num_conv_layers"]) - int(arch["num_dense_layers"])


def flash_work(arch: Dict, pairs: float, tokens: float, layers: int) -> Tuple[float, float]:
    """Causal grouped-query attention of ``layers`` layers on ``pairs``
    (query, key) pairs (ONE layer's, as ``count:window_pairs`` and
    ``count:causal_pairs`` count them: inside the window for a sliding layer)
    and ``tokens`` real tokens, forward and backward:
    ``kernel_work.flash_attention_work`` (7 products of 2 d FLOPs a pair and
    query head; q, k, v, o and their cotangents once a pass, the keys and
    values at the key-value heads' count) a layer."""
    flops, nbytes = kernel_work.flash_attention_work(arch, pairs, tokens)
    return flops * layers, nbytes * layers


def expert_work(arch: Dict, rows: float, steps: float) -> Tuple[float, float]:
    """The three products of the gated expert MLP on ``rows`` rows computed
    here (``count:expert_rows_here``: a token is 0 to k rows, summed over
    layers and steps), forward and backward: each product 2 D F FLOPs a row
    forward and twice that backward. Bytes: activations in and out of each
    product once a pass, and each held expert's weights read once forward,
    once for dx, and its gradient written once, per EXPERT layer and step;
    bf16."""
    d_model, f = int(arch["hidden_dim"]), int(arch["moe_intermediate_size"])
    held = len(arch["experts_held"])
    flops = 3 * 3 * 2.0 * d_model * f * rows
    act = 3 * 3 * (d_model + f) * BF16 * rows
    weights = 3 * 3 * held * expert_layers(arch) * d_model * f * BF16 * steps
    return flops, act + weights
