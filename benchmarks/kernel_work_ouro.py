"""Operations and bytes that the OURO configuration's causal flash launches
REQUIRE for given real work (no padding, no recompute), for their roofline
share (``metrics/loop_flash_roofline_share.train.py``). The held layers run
``total_ut_steps`` times a step, so the launches attend every layer's pairs
once a pass: counted over the window, forward and backward, from the
configuration's shapes and the program's counters (``count:causal_pairs``:
ONE layer application's pairs; ``count:tokens``: real tokens x layer
applications; each summed over the window's steps)."""

from __future__ import annotations

from typing import Dict, Tuple

import kernel_work


def applications(arch: Dict) -> int:
    """Layer applications a step: the held layers once a pass."""
    return int(arch["num_conv_layers"]) * int(arch["total_ut_steps"])


def loop_flash_work(arch: Dict, causal_pairs: float, tokens: float) -> Tuple[float, float]:
    """``kernel_work.flash_attention_work`` over every application: 7 products
    of ``2 d`` FLOPs a pair and query head; q, k, v, o and their cotangents
    once a pass of each application (``tokens`` counts them already)."""
    return kernel_work.flash_attention_work(arch, causal_pairs * applications(arch), tokens)
