"""The Keye-VL-2.0 cell cut to a size the CPU holds (`tiny.tiny_ctx` shrinks
the hidden width and the heads' layers only): every width, the selection's
budget, the vocabulary, the documents and the packing budget shrink, every
mechanism stays (four sparse-attention layers whose indexer picks 8 keys under
documents of 5-60 tokens, 4 query / 2 key-value heads, an indexer of 4 heads,
4 of 16 experts held and 4 a token under softmax scores, the auxiliary loss,
a row budget).

    python benchmarks/tests/tiny_keyevl2.py [seconds] [trace]    # a rehearsal's result line
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

CELL = "keye_vl2_longdocs_t32k_train"
SCALE = 0.06  # 61 of the 1,024 documents


def tiny_ctx(workload: str = CELL):
    ctx = base_tiny_ctx(workload, hidden=64)
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    arch.update(num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
                num_experts=16, num_experts_per_tok=4, experts_held=[0, 1, 2, 3], expert_row_capacity=3.0,
                indexer_num_heads=4, indexer_head_dim=16, indexer_topk=8, vocab_size=97, loss_chunk_rows=64)
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
