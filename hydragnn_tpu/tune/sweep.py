"""The offline tile sweep: measure candidates, persist winners.

Measurement reuses bench.py's timing discipline — jit the kernel call with
the candidate's static block constants, warm it up (compile + first
dispatch excluded), then take the median of k timed dispatches behind
``jax.block_until_ready``. Off-TPU the kernels run in interpret mode, so
CI exercises the whole plane (sweep -> table write -> cache hit -> routed
plan) on CPU; interpret-mode medians are meaningless as *tile* guidance
but key under ``device="cpu"`` and are therefore invisible to TPU runs.

Operands are synthetic but shape-exact: each spec slot's padded sizes,
the model's channel widths, degree-capped sorted segment ids — the same
static facts the routing layer hands :func:`tune.runtime.tile_plan`, so a
sweep's table keys are the keys training will look up.
"""

from __future__ import annotations

import statistics
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import plans
from .table import TunedTable, device_kind

# sweep knobs: medians over K timed dispatches after W warm-ups — small
# because each candidate is one executable of one kernel, not a train step
DEFAULT_TRIALS = 5
DEFAULT_WARMUP = 2


def measure(fn: Callable[[], Any], n_trials: int = DEFAULT_TRIALS,
            n_warmup: int = DEFAULT_WARMUP) -> float:
    """Median wall seconds of ``fn()`` over ``n_trials`` dispatches, after
    ``n_warmup`` untimed ones (compile + first-touch excluded), every
    dispatch fenced by ``block_until_ready`` — bench.py's discipline."""
    import jax

    for _ in range(max(1, n_warmup)):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, n_trials)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _sorted_ids(edges: int, num_segments: int, max_degree: int):
    """Degree-capped ascending segment ids: each segment owns
    ``min(max_degree, ceil(edges/num_segments))`` consecutive edges,
    overflow edges land on the final (dummy-node) segment — the same
    layout GraphLoader(sort_edges=True) produces for a padded batch."""
    import numpy as np

    deg = max(1, min(max_degree or 1, -(-edges // max(num_segments, 1))))
    ids = np.minimum(np.arange(edges) // deg, num_segments - 1)
    return ids.astype(np.int32)


def build_call(kernel: str, shapes: Dict[str, Any], dtype: str,
               plan: Dict[str, int],
               interpret: Optional[bool] = None) -> Callable[[], Any]:
    """A zero-arg jitted dispatch of ``kernel`` on synthetic shape-exact
    operands with ``plan``'s block constants baked in as statics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)

    def _arr(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    if kernel == plans.SEGMENT:
        e, c = int(shapes["edges"]), int(shapes["channels"])
        n, d = int(shapes["num_segments"]), int(shapes["max_degree"])
        from ..ops.pallas_segment import sorted_segment_sum

        msg = _arr((e, c))
        ids = jnp.asarray(_sorted_ids(e, n, d))
        fn = jax.jit(lambda m: sorted_segment_sum(
            m, ids, n, d, plan["block_rows"], plan["block_edges"],
            plan["block_cols"], interpret,
        ))
        return lambda: fn(msg)
    if kernel == plans.FUSED_EDGE:
        e, ci, co = int(shapes["edges"]), int(shapes["ci"]), int(shapes["co"])
        n, d = int(shapes["num_segments"]), int(shapes["max_degree"])
        from ..ops.pallas_fused_edge import fused_edge_message_sum

        nrecv, ein = _arr((n, ci)), _arr((e, ci))
        w, b = _arr((ci, co)), _arr((co,))
        ids = jnp.asarray(_sorted_ids(e, n, d))
        fn = jax.jit(lambda nr, x: fused_edge_message_sum(
            nr, x, w, b, ids, n, d, plan["block_rows"],
            plan["block_edges"], plan["block_cols"], interpret,
        ))
        return lambda: fn(nrecv, ein)
    if kernel == plans.MULTI_AGG:
        e, c = int(shapes["edges"]), int(shapes["channels"])
        n, d = int(shapes["num_segments"]), int(shapes["max_degree"])
        from ..ops.pallas_multi_agg import fused_multi_agg

        nrecv = _arr((n, c)) if shapes.get("has_recv", True) else None
        gate = _arr((e, c)) if shapes.get("has_gate", False) else None
        ein = _arr((e, c))
        ids = jnp.asarray(_sorted_ids(e, n, d))
        fn = jax.jit(lambda nr, x, g: fused_multi_agg(
            nr, x, g, ids, n, d, plan["block_rows"], plan["block_edges"],
            plan["block_cols"], interpret,
        ))
        return lambda: fn(nrecv, ein, gate)
    if kernel == plans.FLASH:
        n, h, dh = int(shapes["nodes"]), int(shapes["heads"]), int(shapes["head_dim"])
        nmax = int(shapes["max_nodes_per_graph"])
        from ..ops.pallas_flash_attention import flash_self_attention

        q, k, v = _arr((n, h, dh)), _arr((n, h, dh)), _arr((n, h, dh))
        node_graph = jnp.asarray(
            np.minimum(np.arange(n) // max(nmax, 1),
                       max(-(-n // max(nmax, 1)) - 1, 0)).astype(np.int32))
        node_mask = jnp.ones((n,), bool)
        num_graphs = int(node_graph[-1]) + 1 if n else 1
        fn = jax.jit(lambda q_, k_, v_: flash_self_attention(
            q_, k_, v_, node_graph, node_mask, num_graphs, nmax,
            plan["block_q"], plan["block_k"], interpret,
        ))
        return lambda: fn(q, k, v)
    raise KeyError(f"unknown kernel {kernel!r}")


def sweep_kernel(
    kernel: str,
    shapes: Dict[str, Any],
    dtype: str,
    table: TunedTable,
    budget: int = 0,
    trials: int = DEFAULT_TRIALS,
    interpret: Optional[bool] = None,
    force: bool = False,
) -> Dict[str, Any]:
    """Sweep one kernel on one shape signature and publish the winner.

    Returns a result record: ``cached=True`` when the table already held
    this key (nothing measured — the CLI's second invocation is 100% of
    these), else the candidate census, the winning plan, and the
    default-plan/winner medians for the BENCH_TUNE A/B cells.
    Candidates that fail to compile or run are skipped with a warning —
    an over-budget tile on real hardware is a skipped point, not a failed
    sweep.
    """
    from .runtime import _shape_key

    spec = plans.KERNELS[kernel]
    dev = device_kind()
    key_shape = _shape_key(shapes)
    existing = table.lookup(kernel, spec.version, dev, dtype, key_shape)
    if existing is not None and not force:
        return {"kernel": kernel, "cached": True, "plan": existing,
                "shape": key_shape}

    cands = plans.candidates(kernel, shapes, budget)
    default = plans.default_plan(kernel, shapes)
    t_sweep0 = time.perf_counter()
    timed: List[Tuple[float, Dict[str, int]]] = []
    default_s: Optional[float] = None
    for plan in cands:
        try:
            sec = measure(build_call(kernel, shapes, dtype, plan, interpret),
                          n_trials=trials)
        except Exception as e:  # over-budget tile, interpret quirk, ...
            warnings.warn(
                f"tune sweep: candidate {plan} for {kernel} failed ({e}); "
                "skipping",
                RuntimeWarning,
                stacklevel=2,
            )
            continue
        timed.append((sec, plan))
        if plan == default:
            default_s = sec
    if not timed:
        raise RuntimeError(
            f"tune sweep: every candidate failed for kernel {kernel!r} "
            f"shapes {key_shape} — nothing to publish"
        )
    best_s, best = min(timed, key=lambda t: t[0])
    table.store(
        kernel, spec.version, dev, dtype, key_shape, best,
        measured_us=best_s * 1e6,
        meta={
            "candidates": len(timed),
            "default_us": default_s * 1e6 if default_s is not None else None,
            "trials": trials,
        },
    )
    _sweep_gauge().set(time.perf_counter() - t_sweep0, kernel=kernel)
    return {
        "kernel": kernel, "cached": False, "plan": best, "shape": key_shape,
        "candidates": len(timed), "best_us": best_s * 1e6,
        "default_us": default_s * 1e6 if default_s is not None else None,
    }


def _sweep_gauge():
    from ..obs.registry import registry

    return registry().gauge(
        "hydragnn_tune_sweep_seconds",
        "Wall seconds of the last tile sweep per kernel (docs/TUNING.md)",
        labelnames=("kernel",),
    )


def config_slots(config: Dict[str, Any],
                 ladder=None) -> List[Tuple[str, Dict[str, Any], str]]:
    """The (kernel, shapes, dtype) sweep slots a completed config implies:
    one slot per enabled kernel per SpecLadder level, built from the same
    static facts the routing layer will hand ``tile_plan`` at trace time.

    ``ladder`` is the data pipeline's SpecLadder; the CLI obtains it via
    ``api.prepare_data`` (the config alone does not know the pad levels).
    """
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"].get("Training", {})
    hidden = int(arch.get("hidden_dim") or 0)
    max_deg = int(arch.get("max_in_degree") or 0)
    heads = int(arch.get("global_attn_heads") or 0)
    nmax = int(arch.get("max_nodes_per_graph") or 0)
    dtype = "bfloat16" if training.get("mixed_precision") else "float32"
    pna = str(arch.get("mpnn_type", "")).upper().startswith("PNA")
    specs = list(ladder.specs) if ladder is not None else []
    slots: List[Tuple[str, Dict[str, Any], str]] = []
    for ps in specs:
        n, e = int(ps.n_nodes), int(ps.n_edges)
        if arch.get("use_sorted_aggregation") and max_deg:
            slots.append((plans.SEGMENT, {
                "edges": e, "channels": hidden, "num_segments": n,
                "max_degree": max_deg,
            }, dtype))
        if arch.get("use_fused_edge_kernel") and max_deg:
            slots.append((plans.FUSED_EDGE, {
                "edges": e, "ci": hidden, "co": hidden, "num_segments": n,
                "max_degree": max_deg, "dtype": dtype,
            }, dtype))
        if pna and arch.get("use_sorted_aggregation") and max_deg:
            slots.append((plans.MULTI_AGG, {
                "edges": e, "channels": hidden, "num_segments": n,
                "max_degree": max_deg, "has_recv": True, "has_gate": False,
                "dtype": dtype,
            }, dtype))
        if arch.get("use_flash_attention") and heads and nmax:
            slots.append((plans.FLASH, {
                "nodes": n, "heads": heads, "head_dim": hidden // heads,
                "max_nodes_per_graph": nmax,
            }, dtype))
    return slots


def sweep_slots(
    slots: List[Tuple[str, Dict[str, Any], str]],
    table: TunedTable,
    budget: int = 0,
    trials: int = DEFAULT_TRIALS,
    interpret: Optional[bool] = None,
    force: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> Dict[str, Any]:
    """Sweep every slot into ``table`` (traced as a ``tune_sweep`` span
    when a tracer is live) and return the census the CLI prints:
    ``{"entries": N, "hits": H, "swept": S, "results": [...]}``."""
    from ..obs import trace

    results = []
    tr = trace.active()
    span = (tr.span("tune_sweep", slots=len(slots)) if tr is not None
            else _nullcontext())
    with span:
        for kernel, shapes, dtype in slots:
            res = sweep_kernel(
                kernel, shapes, dtype, table, budget=budget, trials=trials,
                interpret=interpret, force=force,
            )
            results.append(res)
            if log:
                if res.get("cached"):
                    log(f"  {kernel}: HIT (cached) plan={res['plan']}")
                else:
                    d, b = res.get("default_us"), res.get("best_us")
                    gain = f" ({d / b:.2f}x vs default)" if d and b else ""
                    log(f"  {kernel}: swept {res['candidates']} candidates"
                        f" best={b:.1f}us{gain} plan={res['plan']}")
    hits = sum(1 for r in results if r.get("cached"))
    from .runtime import _entries_gauge

    _entries_gauge().set(float(table.size()))
    return {
        "entries": len(results),
        "hits": hits,
        "swept": len(results) - hits,
        "results": results,
    }


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
