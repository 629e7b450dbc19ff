"""Operations and bytes that the decoder's two kernels REQUIRE for given real
work (no padding, no recompute), for their roofline shares
(``metrics/*_roofline_share.train.py``). Counted per training step, forward
and backward, from the configuration's shapes and the program's counters."""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def flash_attention_work(arch: Dict, causal_pairs: float, tokens: float) -> Tuple[float, float]:
    """Causal grouped-query attention of ONE layer on ``causal_pairs`` (query,
    key) pairs within documents and ``tokens`` real tokens, forward and
    backward. Forward: scores and values, 2 products of 2 d FLOPs a pair and
    query head. Backward: 5 such products (scores again, dv, dp, dq, dk).
    Bytes: q, k, v read and o written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward; bf16."""
    hq, hk, d = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]), int(arch["head_dim"])
    flops = (2 + 5) * 2.0 * d * hq * causal_pairs
    per_token = d * BF16 * ((2 * hq + 2 * hk) + (4 * hq + 4 * hk))
    return flops, per_token * tokens


def grouped_expert_work(arch: Dict, rows: float, steps: float) -> Tuple[float, float]:
    """The three products of the gated expert MLP on ``rows`` tokens routed to
    experts held (summed over layers and over ``steps`` steps by the counter), forward and backward:
    each product 2 D F FLOPs a row forward and twice that backward. Bytes:
    activations in and out of each product once a pass, and each held
    expert's weights read once forward, once for dx, and its gradient written
    once, per layer; bf16."""
    d_model, f = int(arch["hidden_dim"]), int(arch["moe_intermediate_size"])
    held, layers = len(arch["experts_held"]), int(arch["num_conv_layers"])
    flops = 3 * 3 * 2.0 * d_model * f * rows
    act = 3 * 3 * (d_model + f) * BF16 * rows
    weights = 3 * 3 * held * layers * d_model * f * BF16 * steps
    return flops, act + weights


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_per_s_bf16"], nbytes / peaks["hbm_bytes_per_s"])
