"""The Trinity cell cut to a size the CPU holds (`tiny.tiny_ctx` shrinks the
hidden width and the heads' layers only): every width, the window, the
vocabulary, the documents and the packing budget shrink, every mechanism
stays (a dense first layer and one period of 3 sliding + 1 full expert
layers, window 16 under documents of 5-60 tokens, 4 query / 2 key-value heads,
4 of 16 experts held and 4 a token, the gate, head norms, four norms a layer,
the embedding scale, a row budget).

    python benchmarks/tests/tiny_trinity.py [seconds] [trace]    # a rehearsal's result line
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

CELL = "trinity_mini_longdocs_t16k_train"
SCALE = 0.06  # 61 of the 1,024 documents


def tiny_ctx(workload: str = CELL):
    ctx = base_tiny_ctx(workload, hidden=64)
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    arch.update(num_attention_heads=4, num_key_value_heads=2, head_dim=16, sliding_window=16,
                intermediate_size=128, moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
                experts_held=[0, 1, 2, 3], expert_row_capacity=3.0, vocab_size=97, loss_chunk_rows=64)
    ctx["traffic"]["generator_params"].update(median_tokens=28.0, sigma=0.6, min_tokens=5, max_tokens=60,
                                               vocab_size=97)
    ctx["traffic"]["training_overrides"].update(batch_size=8, pack_node_slots=192, pack_graph_slots=12)
    # the cell's limits are read at its own size; 170 tokens a step and 40 a held expert read otherwise
    # (CPU, 4 seeds: grad_gap_median 5.8e-4 .. 8.3e-4, dparam_gap_median 3.4e-4 .. 6.9e-4; the program without
    # the window 8.6e-3 .. 1.2e-2 and 2.3e-3 .. 3.1e-3; the worst leaf 4.4e-3 .. 1.2e-2 against 0.095 .. 0.128)
    ctx["traffic"]["limits"] = {"grad_gap_median": 0.003, "dparam_gap_median": 0.002}
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
