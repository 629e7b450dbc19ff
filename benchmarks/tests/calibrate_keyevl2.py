"""`calibrate.py` for the Keye-VL-2.0 cell, through the reference step that
keeps AdamW's moments on the host (`drive_train_tokens_lean.py`): the
program's readings on seeds; then, on the first seed, what the reference's
departure (each group's auxiliary loss, where the program's is the step's)
adds to each leaf's gap (`aux_departure`); then, on the control seeds and
against the SAME float32 references, two faults of the program itself:
`fault_dense`, a program whose layers attend every earlier key of the
document (the selection off, all else equal), and `fault_no_index_loss`, one
whose indexer loss is off (its gradient zero: the indexer never trains); then
the reference in the nearest lower precision (the control) on the control
seeds.

    python3 benchmarks/tests/calibrate_keyevl2.py <workload> <n_seeds> <n_control_seeds> [out.jsonl] [--seeds a,b,...]

`--seeds` reads those seeds in place of `calibrate.py`'s own.
"""

import contextlib
import functools
import json
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
def program_fault(kind: str):
    """The stack's sparse attention replaced for the block's duration."""
    from hydragnn_tpu.models import decoder as dc

    sparse = dc.sparse_attention

    def dense(q, k, v, qi, ki, w, aux, max_nodes, topk):
        return sparse(q, k, v, qi, ki, w, aux, max_nodes, 2 ** 30)

    def no_index_loss(*args):
        o, loss = sparse(*args)
        return o, 0.0 * loss

    dc.sparse_attention = {"fault_dense": dense, "fault_no_index_loss": no_index_loss}[kind]
    try:
        yield
    finally:
        dc.sparse_attention = sparse


def first_step_reference(cell, seed, step_loads=None):
    """The float32 reference's first-step gradient (leaf norms) and loss,
    its groups as the harness takes them; ``step_loads`` gives the auxiliary
    loss the step's loads. -> (norms, loss, the step's loads)."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    from reference import keyevl2 as ref

    arch = cell.arch
    start = rc.make_weights(ref.weight_spec(arch, int(arch["input_dim"])), seed)
    p, buffers = start["params"], start["batch_stats"]
    if step_loads is not None:
        buffers = dict(buffers, step_loads=step_loads)
    steps = [shards[0] for shards in cell.step_records]
    every = [g for recs in steps for g in drive_train_tokens.token_groups(recs, drive_train_tokens.MICRO_TOKENS)]
    n_pad = rc.pad_to(max(sum(r["x"].shape[0] for r in g) for g in every) + 1, 128)
    e_pad = rc.pad_to(max(sum(r["senders"].shape[0] for r in g) for g in every), 128)
    g_pad = max(len(g) for g in every) + 1
    accumulate, _ = drive_train_tokens._reference_programs(arch["mpnn_type"], json.dumps(arch, sort_keys=True), "f32")
    pairs = lambda g: sum(max(r["x"].shape[0] - 1, 0) for r in g)
    groups = drive_train_tokens.token_groups(steps[0], drive_train_tokens.MICRO_TOKENS)
    total = max(sum(pairs(g) for g in groups), 1)
    acc, loss, loads = jax.tree_util.tree_map(jnp.zeros_like, p), 0.0, 0.0
    for g in groups:
        batch = {k: jnp.asarray(v) for k, v in rc.batch_records(g, n_pad, e_pad, g_pad).items()}
        acc, part, group_loads = accumulate(p, acc, batch, pairs(g) / total, buffers)
        loss, loads = loss + float(part), loads + group_loads
    norms, _ = compare.leaf_norms_fn()
    return compare.flat_norms(jax.device_get(norms(acc, 1.0))), loss, loads


def aux_departure(cell, seed, prog, out):
    """Each leaf's gap of the program's first gradient against the reference
    whose auxiliary loss is each group's (the harness's) and against the one
    whose loss is the step's, and of the two references against each other."""
    t = time.perf_counter()
    group, loss_group, loads = first_step_reference(cell, seed)
    step, loss_step, _ = first_step_reference(cell, seed, step_loads=loads)
    top = lambda gaps: [[n, float("%.3g" % gaps[n])] for n in sorted(gaps, key=gaps.get, reverse=True)[:6]]
    row = {"kind": "aux_departure", "seed": seed,
           # the first pass is the harness's reference again: its worst leaf's gap
           "group_vs_harness": max(compare.leaf_gaps(group, cell.refs[seed]["grad"]).values()),
           "loss_group_vs_step": abs(loss_group - loss_step) / abs(loss_step),
           "loss1_vs_step": abs(prog["loss"][0] - loss_step) / abs(loss_step),
           "loss1_vs_group": abs(prog["loss"][0] - loss_group) / abs(loss_group)}
    for name, a, b in (("group_vs_step", group, step), ("program_vs_step", prog["grad"], step),
                       ("program_vs_group", prog["grad"], group)):
        gaps = compare.leaf_gaps(a, b)
        row[name] = {"grad_gap": max(gaps.values()), "top": top(gaps),
                     "router": {n: float("%.3g" % v) for n, v in gaps.items() if n.endswith("/router")}}
    calibrate.emit(out, {**row, "seconds": time.perf_counter() - t})


def run(ctx, seeds, control_seeds, f32_seeds, out, scale):
    import jax

    if SEEDS:
        seeds, control_seeds = SEEDS, SEEDS[:len(control_seeds)]
    compare.reference_readings = functools.partial(
        drive_train_tokens_lean.reference_readings, warmup_steps=drive_train_tokens.warmup_of(ctx["traffic"]))
    programs = {}
    values = calibrate.values

    def kept(prog, ref, limits):
        programs.setdefault(id(ref), prog)
        return values(prog, ref, limits)

    calibrate.values = kept
    cell = calibrate.Cell(ctx, scale)
    for seed in seeds:
        cell.program(seed, out, "program")
    calibrate.values = values
    cell.free()
    aux_departure(cell, seeds[0], programs[id(cell.refs[seeds[0]])], out)
    for kind in ("fault_dense", "fault_no_index_loss"):
        with program_fault(kind):
            faulty = calibrate.Cell(ctx, scale)
            faulty.step_records, faulty.refs = cell.step_records, cell.refs
            for seed in control_seeds:
                faulty.program(seed, out, kind)
            faulty.free()
    mode = compare.CONTROL_MODE[ctx["config"]["precision"]]
    for seed in control_seeds:
        t = time.perf_counter()
        got = cell.reference(seed, mode=mode)
        calibrate.emit(out, {"kind": "control_" + mode, "seed": seed,
                             **calibrate.values(got, cell.refs[seed], cell.limits),
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
        import tiny_keyevl2

        tiny.tiny_ctx = lambda workload: tiny_keyevl2.tiny_ctx(workload)
    calibrate.main()


if __name__ == "__main__":
    main()
