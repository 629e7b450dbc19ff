"""Readings that the limits of `correct` are set from, one process per cell:

    python3 benchmarks/tests/calibrate.py <workload> <n_seeds> <n_control_seeds> [out.jsonl] [--f32 seed,seed,...]

For each seed the program's first three steps (the timed path's own compiled
step, loader and call) against the f32 reference: the lower readings. For the
control seeds the reference in the nearest lower precision put in the
program's place, and the half-batch fault planted in the reference: the upper
readings. Every row goes through the harness's own comparison with the
traffic file's limits, so `correct` is what a run would have said.

`--f32` is the look a worst-leaf gap asks for: the same seeds through the
program with `mixed_precision` off and float32 products at `highest`, beside
the program as configured. Gaps that collapse there are rounding of the
configured precision, not a fault of the program's arithmetic.

On the chip at the cell's own size; `BENCH_TINY=1` rehearses on the CPU.
"""

import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402


def emit(out, row):
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def values(prog, ref, limits):
    ok, compared, notes = compare.compare(prog, ref, limits)
    row = {"correct": ok, **{k: v["value"] for k, v in compared.items()}}
    row["grad_gap_leaf"], row["dparam_gap_leaf"] = notes["grad_gap_leaf"], notes["dparam_gap_leaf"]
    row["grad_top"], row["dparam_top"] = notes["grad_top"], notes["dparam_top"]
    row["grad_gap_leaf_grad_rel"] = notes["grad_gap_leaf_grad_rel"]
    row["dparam_gap_leaf_grad_rel"] = notes["dparam_gap_leaf_grad_rel"]
    return row


class Cell:
    """One set-up of the cell's timed path, read on any number of seeds."""

    def __init__(self, ctx, scale):
        import drive_train

        self.drive_train = drive_train
        self.env = drive_train.setup(ctx, common.cache_dirs(), scale)
        self.arch = self.env.arch
        self.lr = float(self.env.training["Optimizer"]["learning_rate"])
        self.limits = ctx["traffic"].get("limits", {})
        self.step_records = None
        self.refs = {}

    def reference(self, seed, **kw):
        return compare.reference_readings(self.arch["mpnn_type"], self.arch, int(self.arch["input_dim"]),
                                          seed, self.step_records, self.lr, **kw)

    def program(self, seed, out, kind):
        t = time.perf_counter()
        state, step, captured, _ = self.drive_train.first_steps(self.env, seed)
        prog = step.readings()
        del state, step
        if self.step_records is None:
            self.step_records = compare.match_records(captured, self.env.records)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        if seed not in self.refs:
            self.refs[seed] = self.reference(seed)
        emit(out, {"kind": kind, "seed": seed, **values(prog, self.refs[seed], self.limits),
                   "loss": prog["loss"], "ref_loss": self.refs[seed]["loss"],
                   "program_s": t_prog, "reference_s": time.perf_counter() - t})

    def free(self):
        self.env.loader = self.env.raw_step = self.env.model = None


def run(ctx, seeds, control_seeds, f32_seeds, out, scale):
    import jax

    cell = Cell(ctx, scale)
    for seed in list(seeds) + [s for s in f32_seeds if s not in seeds]:
        cell.program(seed, out, "program")
    cell.free()
    mode = compare.CONTROL_MODE[ctx["config"]["precision"]]
    for seed in control_seeds:
        for kind, kw in (("control_" + mode, {"mode": mode}), ("fault_half_batch", {"drop_half": True})):
            t = time.perf_counter()
            got = cell.reference(seed, **kw)
            emit(out, {"kind": kind, "seed": seed, **values(got, cell.refs[seed], cell.limits),
                       "loss": got["loss"], "seconds": time.perf_counter() - t})
    emit(out, {"kind": "device", "peak": common.device_stamp(jax.devices(), 1),
               "memory_stats": jax.devices()[0].memory_stats()})
    for batch in (None, 96) if f32_seeds else ():
        try:
            look(ctx, f32_seeds, batch, None if batch else cell, out, scale)
            break
        except Exception as e:  # noqa: BLE001 - float32 at the cell's own batch may not fit the chip
            emit(out, {"kind": "look_failed", "batch": batch, "error": repr(e)[:400]})


def look(ctx, seeds, batch, configured, out, scale):
    """The seeds through the program as configured and through the program
    in float32 at `highest`, at the cell's own batch or a smaller one."""
    import jax

    tag = f"_b{batch}" if batch else ""
    if batch:
        ctx = copy.deepcopy(ctx)
        ctx["traffic"]["training_overrides"]["batch_size"] = batch
        jax.config.update("jax_default_matmul_precision", None)
        configured = Cell(ctx, scale)
        for seed in seeds:
            configured.program(seed, out, "program" + tag)
        configured.free()
    plain = copy.deepcopy(ctx)
    plain["config"]["program_config"]["NeuralNetwork"]["Training"]["mixed_precision"] = False
    jax.config.update("jax_default_matmul_precision", "highest")
    f32 = Cell(plain, scale)
    f32.step_records, f32.refs = configured.step_records, configured.refs
    for seed in seeds:
        f32.program(seed, out, "program_f32_highest" + tag)
    emit(out, {"kind": "device_f32" + tag, "memory_stats": jax.devices()[0].memory_stats()})


def main():
    argv = sys.argv[1:]
    f32_seeds = []
    if "--f32" in argv:
        i = argv.index("--f32")
        f32_seeds = [int(s) for s in argv[i + 1].split(",")]
        del argv[i:i + 2]
    workload, n, n_control = argv[0], int(argv[1]), int(argv[2])
    out = argv[3] if len(argv) > 3 else None
    if os.environ.get("BENCH_TINY"):
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        ctx, scale = tiny.tiny_ctx(workload), 0.02
    else:
        bench = common.load_json(common.ROOT, "BENCHMARK.json")
        cell = tiny.find_cell(workload, bench)
        ctx, scale = common.cell_from_files(cell, bench), 1.0
        common.cache_dirs()
        common.require_chips(int(cell["chips"]))
    seeds = [2**31 + 1000 * (i + 1) + 7 for i in range(n)]
    run(ctx, seeds, seeds[:n_control], f32_seeds, out, scale)


if __name__ == "__main__":
    main()
