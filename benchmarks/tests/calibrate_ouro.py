"""`calibrate.py` for the Ouro-2.6B cell, through the reference step that
keeps AdamW's moments on the host (`drive_train_tokens_lean.py`): the
program's readings on seeds (the sound readings); then, on the control seeds
and against the SAME float32 references, two faults of the program itself:
`fault_three_passes`, a program that runs three passes over the held layers
where the configuration states four (its state and its exits are those of
three), and `fault_gate_stopped`, one whose head gives its rows' weights no
gradient (the exit distribution then trains through the entropy term alone:
what the chunked cross-entropy gave before its weights took a cotangent);
then the reference in the nearest lower precision (the control) and the
half-batch fault planted in the reference, on the control seeds.

    python3 benchmarks/tests/calibrate_ouro.py <workload> <n_seeds> <n_control_seeds> [out.jsonl] [--seeds a,b,...]

`--seeds` reads those seeds in place of `calibrate.py`'s own. On the chip at
the cell's own size; `BENCH_TINY=1` rehearses on the CPU.
"""

import contextlib
import copy
import functools
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import calibrate  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
import drive_train_tokens  # noqa: E402
import drive_train_tokens_lean  # noqa: E402

SEEDS = []


@contextlib.contextmanager
def program_fault(kind: str, ctx):
    """-> the cell's context for the fault, the program changed for the
    block's duration."""
    from hydragnn_tpu.train import loss as ls

    if kind == "fault_three_passes":
        ctx = copy.deepcopy(ctx)
        ctx["config"]["program_config"]["NeuralNetwork"]["Architecture"]["total_ut_steps"] = 3
        yield ctx
        return
    chunked = ls.chunked_cross_entropy

    def stopped(hidden, head, targets, weights, chunk_rows, den=1.0):
        import jax

        return chunked(hidden, head, targets, jax.lax.stop_gradient(weights), chunk_rows, den)

    ls.chunked_cross_entropy = stopped
    try:
        yield ctx
    finally:
        ls.chunked_cross_entropy = chunked


def run(ctx, seeds, control_seeds, f32_seeds, out, scale):
    import jax

    if SEEDS:
        seeds, control_seeds = SEEDS, SEEDS[:len(control_seeds)]
    compare.reference_readings = functools.partial(
        drive_train_tokens_lean.reference_readings, warmup_steps=drive_train_tokens.warmup_of(ctx["traffic"]))
    cell = calibrate.Cell(ctx, scale)
    for seed in seeds:
        cell.program(seed, out, "program")
    cell.free()
    for kind in ("fault_three_passes", "fault_gate_stopped"):
        with program_fault(kind, ctx) as faulty_ctx:
            faulty = calibrate.Cell(faulty_ctx, scale)
            faulty.step_records, faulty.refs = cell.step_records, cell.refs
            for seed in control_seeds:
                faulty.program(seed, out, kind)
            faulty.free()
    mode = compare.CONTROL_MODE[ctx["config"]["precision"]]
    for seed in control_seeds:
        for kind, kw in (("control_" + mode, {"mode": mode}), ("fault_half_batch", {"drop_half": True})):
            t = time.perf_counter()
            got = cell.reference(seed, **kw)
            calibrate.emit(out, {"kind": kind, "seed": seed, **calibrate.values(got, cell.refs[seed], cell.limits),
                                 "loss": got["loss"], "seconds": time.perf_counter() - t})
    calibrate.emit(out, {"kind": "device", "peak": common.device_stamp(jax.devices(), 1),
                         "memory_stats": jax.devices()[0].memory_stats()})


def main():
    if "--seeds" in sys.argv:
        i = sys.argv.index("--seeds")
        SEEDS.extend(int(s) for s in sys.argv[i + 1].split(","))
        del sys.argv[i:i + 2]
    calibrate.run = run
    if os.environ.get("BENCH_TINY"):
        import tiny
        import tiny_ouro

        tiny.tiny_ctx = lambda workload: tiny_ouro.tiny_ctx(workload)
    calibrate.main()


if __name__ == "__main__":
    main()
