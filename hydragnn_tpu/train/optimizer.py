"""Optimizer selection (optax) + ReduceLROnPlateau schedule.

Replaces the reference's torch optimizer factory and DeepSpeed FusedLAMB
(hydragnn/utils/optimizer/optimizer.py:12-113) with optax; the ZeRO
``ZeroRedundancyOptimizer`` analog is optimizer-state sharding handled by the
parallel layer (optimizer state inherits the parameter sharding or is sharded
over the data axis — see hydragnn_tpu/parallel).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import optax

# param-tree top-level collections frozen by Architecture.freeze_conv_layers
# (reference: Base._freeze_conv freezes graph_convs + feature_layers params,
# hydragnn/models/Base.py:247-251; flax setup lists name them <attr>_<i>).
# The MACE module tree names its encoder blocks conv{i}/radial_embedding/
# node_embedding; readouts stay trainable.
_FROZEN_PREFIXES = (
    "graph_convs",
    "feature_layers",
    "conv",
    "radial_embedding",
    "node_embedding",
)


def freeze_conv_mask(params) -> Any:
    """True for every leaf under a conv/feature-layer module (to be zeroed)."""
    return {
        k: jax.tree_util.tree_map(
            lambda _: any(k.startswith(p) for p in _FROZEN_PREFIXES), v
        )
        for k, v in params.items()
    }


def make_optimizer(
    opt_config: Dict[str, Any], freeze_conv: bool = False
) -> optax.GradientTransformation:
    """(reference: select_optimizer, optimizer.py:104-113)

    ``freeze_conv=True`` zeroes updates to conv-stack parameters via a masked
    transform — the optax analog of requires_grad=False
    (reference: Base.py:175-176, 247-251).

    ``Optimizer.clip_grad_norm`` (off by default) prepends global-norm
    gradient clipping — the stability guard for deep multiplicative stacks
    (e.g. PaiNN-update chains in conv node heads), where a single outlier
    step can blow the scalar/vector product streams past float range.

    ``Optimizer.warmup_steps`` (off by default) ramps the rate linearly over
    the first N optimizer steps: a decoder with a top-1 router does not
    survive AdamW's first unit-sized updates at the full rate
    (models/zaya.py)."""
    kind = opt_config.get("type", "AdamW")
    lr = float(opt_config.get("learning_rate", 1e-3))
    clip = float(opt_config.get("clip_grad_norm", 0.0) or 0.0)
    warmup = int(opt_config.get("warmup_steps", 0) or 0)
    if kind not in _OPT_TABLE:
        raise ValueError(f"unknown optimizer {kind!r}; known: {sorted(_OPT_TABLE)}")

    def build(learning_rate):
        tx = _OPT_TABLE[kind](learning_rate)
        if clip > 0.0:
            tx = optax.chain(optax.clip_by_global_norm(clip), tx)
        if freeze_conv:
            tx = optax.chain(
                tx, optax.masked(optax.set_to_zero(), freeze_conv_mask)
            )
        return tx

    # inject_hyperparams makes learning_rate runtime-adjustable so the
    # plateau scheduler can scale it between epochs without recompiling.
    if warmup <= 0:
        return optax.inject_hyperparams(
            lambda learning_rate: build(learning_rate)
        )(learning_rate=lr)
    # Optimizer.warmup_steps: a linear ramp counted in optimizer steps (step k
    # of 1..W runs at k/W of the rate), a factor beside learning_rate, so the
    # epoch ramp and the plateau scheduler keep scaling the rate itself
    return optax.inject_hyperparams(
        lambda learning_rate, warmup_factor: build(learning_rate * warmup_factor)
    )(
        learning_rate=lr,
        warmup_factor=lambda count: jax.numpy.minimum((count + 1.0) / warmup, 1.0),
    )


_OPT_TABLE = {
    "SGD": optax.sgd,
    "Adam": optax.adam,
    "Adadelta": optax.adadelta,
    "Adagrad": optax.adagrad,
    "Adamax": optax.adamax,
    "AdamW": optax.adamw,
    "RMSprop": optax.rmsprop,
    # FusedLAMB (DeepSpeed CUDA kernel) -> optax.lamb: XLA fuses on TPU
    "FusedLAMB": optax.lamb,
    "LAMB": optax.lamb,
}


@dataclasses.dataclass
class ReduceLROnPlateau:
    """Host-side plateau scheduler with torch semantics
    (reference: run_training.py:102-104 — mode=min, factor=0.5, patience=5,
    min_lr=1e-5; stepped on validation loss each epoch,
    train_validate_test.py:197)."""

    factor: float = 0.5
    patience: int = 5
    min_lr: float = 1e-5
    best: float = float("inf")
    bad_epochs: int = 0

    def step(self, val_loss: float, current_lr: float) -> float:
        if val_loss < self.best:
            self.best = val_loss
            self.bad_epochs = 0
            return current_lr
        self.bad_epochs += 1
        if self.bad_epochs > self.patience:
            self.bad_epochs = 0
            return max(current_lr * self.factor, self.min_lr)
        return current_lr
