"""The JOYAI cell cut to a size the CPU holds (`tiny.tiny_ctx` shrinks the
hidden width and the heads' layers only): every width, the vocabulary, the
documents and the packing budget shrink, every mechanism stays (a dense first
layer, two expert layers with 4 of 16 experts held and 4 a token, unequal
head widths, the module on, a row budget).

    python benchmarks/tests/tiny_joyai.py [seconds] [trace]    # a rehearsal's result line
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

CELL = "joyai_flash_docs_t16k_train"
SCALE = 0.03  # 61 of the 2,048 documents


def tiny_ctx(workload: str = CELL):
    ctx = base_tiny_ctx(workload, hidden=64)
    arch = ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]
    arch.update(num_conv_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
                n_routed_experts=16, num_experts_per_tok=4, experts_held=[0, 1, 2, 3], expert_row_capacity=3.0,
                vocab_size=97, loss_chunk_rows=64)
    ctx["traffic"]["generator_params"].update(median_tokens=12.0, sigma=0.8, min_tokens=3, max_tokens=40,
                                               vocab_size=97)
    ctx["traffic"]["training_overrides"].update(batch_size=8, pack_node_slots=160, pack_graph_slots=12)
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
