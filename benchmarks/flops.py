"""Operations a training step requires, from the configuration's shapes:
matrix products only (2 * rows * contract * out), no recompute.

The forward count of a model is ``forward_flops`` of its
``reference/<mpnn_type>.py``, found by name. Rows are REAL nodes, edges and
graphs (no padding), so the quotient by time is model FLOP/s, not hardware
FLOP/s.
"""

from __future__ import annotations

import importlib

# each product y = xW costs one forward and two backward (dx, dW)
TRAIN_PASSES = 3.0


def mlp_flops(rows: float, fan_in: int, features) -> float:
    total = 0.0
    for f in features:
        total += 2.0 * rows * fan_in * f
        fan_in = f
    return total


def train_step_flops(arch: dict, input_dim: int, nodes: float, edges: float, graphs: float) -> float:
    ref = importlib.import_module(f"reference.{arch['mpnn_type'].lower()}")
    return TRAIN_PASSES * ref.forward_flops(arch, input_dim, nodes, edges, graphs)
