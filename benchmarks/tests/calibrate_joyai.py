"""`calibrate_tokens.py` for the `train_tokens_lean` cell: the same readings,
controls and routing counts, through the reference step that keeps AdamW's
moments on the host (`drive_train_tokens_lean.py`).

    python3 benchmarks/tests/calibrate_joyai.py <workload> <n_seeds> <n_control_seeds> [out.jsonl]
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
        import tiny_joyai
        import tiny_zaya

        tiny_zaya.tiny_ctx = tiny_joyai.tiny_ctx
    calibrate_tokens.main()


if __name__ == "__main__":
    main()
