"""The Ouro-2.6B cell cut to a size the CPU holds (`tiny.tiny_ctx` shrinks
the hidden width only): every width, the depth, the vocabulary, the
documents and the packing budget shrink, every mechanism stays (two held
layers run four times a step under one set of weights, an exit after each
pass weighted by the exit gate, 4 heads of 16 on 4 key/value heads, the
untied head over the whole vocabulary).

    python benchmarks/tests/tiny_ouro.py [seconds] [trace]    # a rehearsal's result line
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from tiny import tiny_ctx as base_tiny_ctx  # noqa: E402
import common  # noqa: E402

CELL = "ouro_2p6b_docs_t8k_train"
SCALE = 0.06  # 61 of the 1,024 documents


def tiny_ctx(workload: str = CELL):
    ctx = base_tiny_ctx(workload, hidden=64)
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    arch.update(num_conv_layers=2, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                intermediate_size=96, vocab_size=97, loss_chunk_rows=64)
    ctx["traffic"]["generator_params"].update(median_tokens=28.0, sigma=0.6, min_tokens=5, max_tokens=60,
                                               vocab_size=97)
    ctx["traffic"]["training_overrides"].update(batch_size=8, pack_node_slots=192, pack_graph_slots=12)
    return ctx


def main():
    import importlib

    import jax

    seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 2.0
    trace = bool(int(sys.argv[2])) if len(sys.argv) > 2 else False
    ctx = tiny_ctx()
    driver = importlib.import_module(f"drive_{ctx['traffic']['kind']}")
    result = driver.drive(ctx, 2**31 + 12345, seconds, trace, T0, jax.devices(), common.cache_dirs(), scale=SCALE)
    common.emit(result)


if __name__ == "__main__":
    main()
