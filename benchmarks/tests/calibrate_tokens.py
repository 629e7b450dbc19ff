"""`calibrate.py` for a `train_tokens` cell: the same readings through the
donating reference step (`drive_train_tokens.py`), and before them the count
the discrete router asks for: of the first step's (token, layer) routings, how
many the float32 reference and the reference with bfloat16 rounding (the
program's precision, the router float32 in both) decide differently, and the
program's own count of tokens routed to experts held beside the reference's.

    python3 benchmarks/tests/calibrate_tokens.py <workload> <n_seeds> <n_control_seeds> [out.jsonl]
"""

import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import calibrate  # noqa: E402
import compare  # noqa: E402
import drive_train_tokens  # noqa: E402


def routing_rows(cell, seeds, out):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import common as rc

    ref, arch, records = cell.env.ref, cell.arch, cell.step_records[0][0]
    n = sum(r["x"].shape[0] for r in records)
    b = {k: jnp.asarray(v) for k, v in rc.batch_records(
        records, rc.pad_to(n + 1, 128), rc.pad_to(n, 128), len(records) + 1).items()}
    choices = jax.jit(lambda p, mode: ref.forward(p, b, arch, mode)[1], static_argnums=1)
    held = np.asarray(arch["experts_held"])
    for seed in seeds:
        params = rc.make_weights(cell.env.spec, seed)["params"]
        f32, low = np.asarray(choices(params, "f32")), np.asarray(choices(params, "bf16"))
        real = np.asarray(b["node_w"]) > 0
        calibrate.emit(out, {
            "kind": "routing", "seed": seed, "routings": int(real.sum()) * f32.shape[0],
            "differ_f32_vs_bf16": int((f32 != low)[:, real].sum()),
            "differ_share": float((f32 != low)[:, real].mean()),
            "reference_routed_here": int(np.isin(f32, held)[:, real].sum())})
        del params


def main():
    argv = sys.argv[1:]
    run = calibrate.run

    def run_with_routing(ctx, seeds, control_seeds, f32_seeds, out, scale):
        compare.reference_readings = functools.partial(
            drive_train_tokens.reference_readings,
            warmup_steps=drive_train_tokens.warmup_of(ctx["traffic"]))
        cell_cls = calibrate.Cell

        class Cell(cell_cls):
            def free(self):
                routing_rows(self, control_seeds, out)
                super().free()

        calibrate.Cell = Cell
        try:
            return run(ctx, seeds, control_seeds, f32_seeds, out, scale)
        finally:
            calibrate.Cell = cell_cls

    calibrate.run = run_with_routing
    if os.environ.get("BENCH_TINY"):
        import tiny
        import tiny_zaya

        tiny.tiny_ctx = lambda workload: tiny_zaya.tiny_ctx(workload)
    sys.argv = [sys.argv[0]] + argv
    calibrate.main()


if __name__ == "__main__":
    main()
