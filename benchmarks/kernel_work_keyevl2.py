"""Operations and bytes that the KEYEVL2 configuration's sparse-attention
kernels REQUIRE for given real work (no padding, no recompute), for their
roofline shares (``metrics/dsa_indexer_roofline_share.train.py``,
``metrics/sparse_flash_roofline_share.train.py``). Counted over the window,
forward and backward, every layer, from the configuration's shapes and the
program's counters (``count:causal_pairs``, ``count:dsa_selected_pairs``:
ONE layer's pairs, summed over the window's steps)."""

from __future__ import annotations

from typing import Dict, Tuple

import kernel_work

BF16 = 2
F32 = 4
INT32 = 4


def layers(arch: Dict) -> int:
    return int(arch["num_conv_layers"])


def bitmask_bytes(tokens_slots: float) -> float:
    """One layer's selection bitmask: a bit a (query slot, key slot)."""
    return tokens_slots * tokens_slots / 8.0


def indexer_work(arch: Dict, causal_pairs: float, selected_pairs: float, tokens: float, slots: float,
                 steps: float) -> Tuple[float, float]:
    """The indexer's two launches over all layers. Forward (``hg_dsa_indexer``):
    its scores on every causal pair, ``2 HI dI`` FLOPs a pair. The loss and its
    gradient (``hg_dsa_indexer_bwd``) on the SELECTED pairs: the scores again,
    ``dqI`` and ``dkI`` (``3 x 2 HI dI``), and the main heads' scores that make
    ``p`` (``2 Hq d``). Bytes: ``qI``, ``kI``, ``w`` read once a launch (bf16),
    the main queries and keys once by the loss, the gradients written once
    (float32); the bitmask written once and read once, a step."""
    hi, di = int(arch["indexer_num_heads"]), int(arch["indexer_head_dim"])
    hq, hk, d = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]), int(arch["head_dim"])
    flops = 2.0 * hi * di * causal_pairs + (3 * 2.0 * hi * di + 2.0 * hq * d) * selected_pairs
    index_in = (hi * di + di + hi) * BF16
    per_token = 2 * index_in + (hq + hk) * d * BF16 + (hi * di + di + hi) * F32
    return flops * layers(arch), (per_token * tokens + 2 * bitmask_bytes(slots) * steps) * layers(arch)


def sparse_flash_work(arch: Dict, selected_pairs: float, tokens: float, slots: float,
                      steps: float) -> Tuple[float, float]:
    """The causal flash launches under the selection over all layers, forward
    and backward, counted on the SELECTED pairs (``kernel_work.
    flash_attention_work``: 7 products of ``2 d`` FLOPs a pair and query head;
    q, k, v, o and their cotangents once a pass, the keys and values at the
    key-value heads' count), plus the bitmask read by each of the three
    launches a step: what a schedule that visits only the selected pairs
    could still win."""
    flops, nbytes = kernel_work.flash_attention_work(arch, selected_pairs, tokens)
    return flops * layers(arch), (nbytes + 3 * bitmask_bytes(slots) * steps) * layers(arch)
