"""Int8 quantization primitives (docs/SERVING.md "Quantization").

Per-channel symmetric int8 for inference weights: each output channel of a
dense kernel gets its own fp32 scale (``amax / 127`` over the input axis),
so the quantization error of one wide-ranged channel never bleeds into its
neighbors — the standard post-training recipe (Jacob et al. 2018). Symmetric
(no zero point) keeps the integer matmul a plain ``lax.dot_general`` with an
int32 accumulator and the dequant a single fused multiply.

Two consumers (serve/quantize.py):

- weight-only: kernels live in HBM as int8 + a ``[1, out]`` scale;
  ``dequantize`` runs inside the jitted predict, where XLA fuses the
  convert+scale into the matmul's operand read — activations stay f32;
- w8a8: activations are quantized against a *static* calibrated scale
  (max-abs over template batches / 127 — no per-batch reduction in the
  serving path), then ``int8_matmul`` accumulates int8 x int8 in int32 and
  one ``a_scale * w_scale`` multiply rescales the product.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

#: symmetric int8 range: +-127 (the -128 slot is unused so negation is
#: closed and the scale math stays symmetric)
INT8_MAX = 127.0


def quantize_per_channel(w) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-output-channel int8 quantization of a dense kernel.

    ``w`` is ``[in, out]`` (or branch-banked ``[B, in, out]``); the scale
    reduces over the input axis (``-2``) with keepdims, giving ``[1, out]``
    (``[B, 1, out]``) so ``q * scale`` broadcasts back to the kernel shape.
    All-zero channels get scale 1.0 — they quantize to 0 and dequantize to
    0 exactly, without a 0/0 in the round."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True).astype(jnp.float32)
    scale = jnp.where(amax > 0.0, amax / INT8_MAX, 1.0)
    q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)
    return q, scale


def dequantize(q, scale, dtype=jnp.float32):
    """``q * scale`` in ``dtype`` — inside a jitted predict XLA keeps the
    int8 array resident and fuses the convert into the consuming matmul."""
    return q.astype(dtype) * scale.astype(dtype)


def quantize_activations(x, act_scale):
    """Quantize activations against a static calibrated scale (w8a8).
    Out-of-range activations saturate at +-127 — the max-abs calibration
    over the warmed template batches makes saturation the tail case."""
    return jnp.clip(
        jnp.round(x / act_scale), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)


def int8_matmul(x_q, w_q) -> jnp.ndarray:
    """int8 x int8 contraction with an int32 accumulator: contracts the
    last axis of ``x_q`` against the first of ``w_q`` (the dense-layer
    layout). ``preferred_element_type=int32`` is the whole point — an int8
    accumulator would overflow at K > ~2, and f32 accumulation would
    forfeit the integer MXU path this mode exists for. XLA's own product:
    there is no kernel of this repo, and no tile, behind it."""
    return lax.dot_general(
        x_q,
        w_q,
        (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
