"""Operations and bytes that the JOYAI configuration's two kernels REQUIRE for
given real work (no padding, no recompute), for their roofline shares
(``metrics/mla_flash_roofline_share.train.py``,
``metrics/topk_expert_roofline_share.train.py``). Counted forward and
backward from the configuration's shapes and the program's counters;
``kernel_work.py`` counts one head width and one row a routed token, which do
not describe this model."""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def attention_blocks(arch: Dict) -> int:
    """MLA blocks a step runs: the layers and the multi-token-prediction module's."""
    return int(arch["num_conv_layers"]) + int(arch["num_nextn_predict_layers"])


def expert_layers(arch: Dict) -> int:
    return attention_blocks(arch) - int(arch["first_k_dense_replace"])


def mla_flash_work(arch: Dict, causal_pairs: float, tokens: float) -> Tuple[float, float]:
    """Causal attention of ALL blocks on ``causal_pairs`` (query, key) pairs
    within documents (one block's, as ``count:causal_pairs`` counts them) and
    ``tokens`` real tokens, forward and backward, with queries and keys
    ``d_qk`` wide and values ``d_v`` wide, every head its own keys. A pair and
    head costs forward 2 d_qk (scores) + 2 d_v (values); backward 2 d_qk
    (scores again) + 2 d_v (dv) + 2 d_v (dp) + 2 d_qk (dq) + 2 d_qk (dk).
    Bytes a token and head: q, k, v read and o written forward; q, k, v, o, do
    read and dq, dk, dv written backward; bf16."""
    h = int(arch["num_attention_heads"])
    d_qk = int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])
    d_v = int(arch["v_head_dim"])
    blocks = attention_blocks(arch)
    flops = (8.0 * d_qk + 6.0 * d_v) * h * causal_pairs * blocks
    nbytes = (6.0 * d_qk + 6.0 * d_v) * BF16 * h * tokens * blocks
    return flops, nbytes


def topk_expert_work(arch: Dict, rows: float, steps: float) -> Tuple[float, float]:
    """The three products of the gated expert MLP on ``rows`` rows computed
    here (``count:expert_rows_here``: a token is 0 to k rows, summed over
    layers and steps), forward and backward: each product 2 D F FLOPs a row
    forward and twice that backward. Bytes: activations in and out of each
    product once a pass, and each held expert's weights read once forward,
    once for dx, and its gradient written once, per expert layer and step;
    bf16."""
    d_model, f = int(arch["hidden_dim"]), int(arch["moe_intermediate_size"])
    held = len(arch["experts_held"])
    flops = 3 * 3 * 2.0 * d_model * f * rows
    act = 3 * 3 * (d_model + f) * BF16 * rows
    weights = 3 * 3 * held * expert_layers(arch) * d_model * f * BF16 * steps
    return flops, act + weights
