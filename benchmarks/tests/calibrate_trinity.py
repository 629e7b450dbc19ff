"""`calibrate_joyai.py` for the Trinity cell: the same readings, controls and
routing counts through the reference step that keeps AdamW's moments on the
host. `TRINITY_NO_WINDOW=1` reads a program whose sliding layers attend the
whole document (the fault the cell's limits must catch), by handing
`models/decoder.py causal_attention` no window.

    python3 benchmarks/tests/calibrate_trinity.py <workload> <n_seeds> <n_control_seeds> [out.jsonl]
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import calibrate_tokens  # noqa: E402
import drive_train_tokens  # noqa: E402
import drive_train_tokens_lean  # noqa: E402


def main():
    drive_train_tokens.reference_readings = drive_train_tokens_lean.reference_readings
    if os.environ.get("BENCH_TINY"):
        import tiny_trinity
        import tiny_zaya

        tiny_zaya.tiny_ctx = tiny_trinity.tiny_ctx
    if os.environ.get("TRINITY_NO_WINDOW"):
        from hydragnn_tpu.models import decoder as dc

        attend = dc.causal_attention
        dc.causal_attention = lambda q, k, v, aux, max_nodes, window=None: attend(q, k, v, aux, max_nodes, None)
    calibrate_tokens.main()


if __name__ == "__main__":
    main()
