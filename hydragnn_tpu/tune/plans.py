"""Tile plans: the tunable block/grid parameters of each Pallas kernel.

A *tile plan* is a plain ``{param: int}`` dict naming exactly the block
constants a kernel entry point takes as ``custom_jvp`` nondiff arguments
(``block_rows``/``block_edges``/... — ops/pallas_*.py). This module is the
registry of what is tunable: per kernel its pinned defaults (the values the
kernel signatures carry, so a missing tuned-table entry reproduces today's
behavior bit-identically), its candidate grid for sweeps, and its
normalization — the same clamp the kernel applies internally, applied
BEFORE a plan becomes a jit-specialization or tuned-table key.

Normalization is load-bearing twice over:

- ops/pallas_multi_agg.py clamps ``block_cols`` to the lane-padded channel
  width *inside* ``_forward``, but the nondiff argnums (and hence the jit
  executable cache) key on the caller's *unclamped* value — two requests
  that run the identical program used to compile twice. Each kernel now
  exports its clamp as ``normalize_tiles`` and the routing layer funnels
  every plan through :func:`normalize` first, so equivalent plans share
  one executable.
- the tuned table (tune/table.py) stores normalized plans under keys of
  normalized shapes: a sweep cannot record two entries that differ only in
  how far past the clamp they asked.

``KERNELS`` keys are the tuned-table kernel ids; versions come from each
kernel module's ``KERNEL_VERSION`` so a schedule change invalidates its
tuned entries by construction.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Iterable, List, Tuple

SEGMENT = "segment_sum"
FUSED_EDGE = "fused_edge"
MULTI_AGG = "multi_agg"
FLASH = "flash_attention"
FLASH_CAUSAL = "flash_attention_causal"
GROUPED_EXPERT = "grouped_expert"
INT8_DOT = "int8_dot"


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """What is tunable about one kernel: its tuned-table id, parameter
    names (the nondiff kwargs of the entry point), pinned defaults, and
    the sweep's candidate grid per parameter."""

    kernel: str
    params: Tuple[str, ...]
    defaults: Dict[str, int]
    grid: Dict[str, Tuple[int, ...]]

    @property
    def version(self) -> int:
        return kernel_version(self.kernel)


KERNELS: Dict[str, KernelSpec] = {
    SEGMENT: KernelSpec(
        kernel=SEGMENT,
        params=("block_rows", "block_edges", "block_cols"),
        defaults={"block_rows": 128, "block_edges": 512, "block_cols": 512},
        grid={
            "block_rows": (64, 128, 256),
            "block_edges": (256, 512, 1024),
            "block_cols": (128, 256, 512),
        },
    ),
    FUSED_EDGE: KernelSpec(
        kernel=FUSED_EDGE,
        params=("block_rows", "block_edges", "block_cols"),
        defaults={"block_rows": 128, "block_edges": 512, "block_cols": 512},
        grid={
            "block_rows": (64, 128, 256),
            "block_edges": (256, 512, 1024),
            "block_cols": (256, 512, 1024),
        },
    ),
    MULTI_AGG: KernelSpec(
        kernel=MULTI_AGG,
        params=("block_rows", "block_edges", "block_cols"),
        defaults={"block_rows": 128, "block_edges": 512, "block_cols": 128},
        grid={
            "block_rows": (64, 128, 256),
            "block_edges": (256, 512, 1024),
            "block_cols": (128, 256),
        },
    ),
    FLASH: KernelSpec(
        kernel=FLASH,
        params=("block_q", "block_k"),
        defaults={"block_q": 128, "block_k": 128},
        grid={
            "block_q": (64, 128, 256),
            "block_k": (128, 256, 512),
        },
    ),
    # the decoder's causal grouped-query launch of the same kernel, forward
    # and tiled backward (flash_causal_attention): graphs of thousands of
    # nodes, so its pinned tiles are larger than the GPS launch's
    FLASH_CAUSAL: KernelSpec(
        kernel=FLASH_CAUSAL,
        params=("block_q", "block_k"),
        defaults={"block_q": 512, "block_k": 512},
        grid={
            "block_q": (256, 512, 1024),
            "block_k": (256, 512, 1024),
        },
    ),
    # grouped product over the experts held (ops/pallas_grouped_matmul.py):
    # block_m is also the alignment of each expert's rows
    GROUPED_EXPERT: KernelSpec(
        kernel=GROUPED_EXPERT,
        params=("block_m", "block_n", "block_k"),
        defaults={"block_m": 512, "block_n": 1024, "block_k": 512},
        grid={
            "block_m": (256, 512, 1024),
            "block_n": (512, 1024, 2048),
            "block_k": (256, 512, 1024),
        },
    ),
    # int8 inference matmul (ops/quant.py int8_matmul): its own table axis
    # keyed under dtype="int8" so quantized executables are tuned and
    # looked up separately from the f32/bf16 plans for the same shapes
    INT8_DOT: KernelSpec(
        kernel=INT8_DOT,
        params=("block_m", "block_n", "block_k"),
        defaults={"block_m": 128, "block_n": 128, "block_k": 128},
        grid={
            "block_m": (64, 128, 256),
            "block_n": (128, 256),
            "block_k": (128, 256, 512),
        },
    ),
}


def kernel_version(kernel: str) -> int:
    """The kernel module's ``KERNEL_VERSION`` — imported lazily so plan
    bookkeeping (table keys, CLI listings) does not pull jax in first."""
    if kernel == SEGMENT:
        from ..ops import pallas_segment as m
    elif kernel == FUSED_EDGE:
        from ..ops import pallas_fused_edge as m
    elif kernel == MULTI_AGG:
        from ..ops import pallas_multi_agg as m
    elif kernel in (FLASH, FLASH_CAUSAL):
        from ..ops import pallas_flash_attention as m
    elif kernel == GROUPED_EXPERT:
        from ..ops import pallas_grouped_matmul as m
    elif kernel == INT8_DOT:
        from ..ops import quant as m
    else:
        raise KeyError(f"unknown kernel {kernel!r}")
    return int(m.KERNEL_VERSION)


def normalize(kernel: str, plan: Dict[str, int],
              shapes: Dict[str, Any]) -> Dict[str, int]:
    """Clamp ``plan`` exactly the way the kernel's ``_forward`` will, via
    the kernel module's own ``normalize_tiles`` (one clamp site — the
    routing layer, the table keys and the kernel cannot drift apart).

    ``shapes`` carries the operand facts each clamp needs:
    ``channels`` (segment/multi_agg), ``ci``/``co`` (fused_edge),
    ``dtype`` (fused_edge/multi_agg VMEM estimates, a numpy dtype name),
    ``has_recv``/``has_gate`` (multi_agg operand census).
    """
    p = {**KERNELS[kernel].defaults, **{k: int(v) for k, v in plan.items()}}
    if kernel == SEGMENT:
        from ..ops.pallas_segment import normalize_tiles

        nb, eb, cb = normalize_tiles(
            int(shapes["channels"]),
            p["block_rows"], p["block_edges"], p["block_cols"],
        )
        return {"block_rows": nb, "block_edges": eb, "block_cols": cb}
    if kernel == FUSED_EDGE:
        from ..ops.pallas_fused_edge import normalize_tiles

        nb, eb, cb = normalize_tiles(
            int(shapes["ci"]), int(shapes["co"]),
            shapes.get("dtype", "float32"),
            p["block_rows"], p["block_edges"], p["block_cols"],
        )
        return {"block_rows": nb, "block_edges": eb, "block_cols": cb}
    if kernel == MULTI_AGG:
        from ..ops.pallas_multi_agg import normalize_tiles

        nb, eb, cb = normalize_tiles(
            int(shapes["channels"]), shapes.get("dtype", "float32"),
            bool(shapes.get("has_recv", True)),
            bool(shapes.get("has_gate", False)),
            p["block_rows"], p["block_edges"], p["block_cols"],
        )
        return {"block_rows": nb, "block_edges": eb, "block_cols": cb}
    if kernel in (FLASH, FLASH_CAUSAL):
        from ..ops.pallas_flash_attention import normalize_tiles

        bq, bk = normalize_tiles(p["block_q"], p["block_k"])
        return {"block_q": bq, "block_k": bk}
    if kernel == GROUPED_EXPERT:
        from ..ops.pallas_grouped_matmul import normalize_tiles

        bm, bn, bk = normalize_tiles(
            int(shapes.get("rows", 0)), int(shapes.get("k", 0)),
            int(shapes.get("n", 0)),
            p["block_m"], p["block_n"], p["block_k"],
            shapes.get("dtype", "bfloat16"),
        )
        return {"block_m": bm, "block_n": bn, "block_k": bk}
    if kernel == INT8_DOT:
        from ..ops.quant import normalize_tiles

        bm, bn, bk = normalize_tiles(
            int(shapes.get("rows", 0)), int(shapes.get("cols", 0)),
            int(shapes.get("k", 0)),
            p["block_m"], p["block_n"], p["block_k"],
        )
        return {"block_m": bm, "block_n": bn, "block_k": bk}
    raise KeyError(f"unknown kernel {kernel!r}")


def default_plan(kernel: str, shapes: Dict[str, Any]) -> Dict[str, int]:
    """The pinned defaults, normalized for these shapes — what a kernel
    with no tuned-table entry runs (bit-identical to the pre-tune-plane
    behavior: the kernel applied the same clamp internally)."""
    return normalize(kernel, KERNELS[kernel].defaults, shapes)


def candidates(kernel: str, shapes: Dict[str, Any],
               budget: int = 0) -> List[Dict[str, int]]:
    """The sweep's candidate plans: the grid's cartesian product,
    normalized and deduplicated (distinct requests that clamp to the same
    program are ONE candidate), pinned defaults first, capped at
    ``budget`` candidates when positive."""
    spec = KERNELS[kernel]
    seen: Dict[Tuple[int, ...], Dict[str, int]] = {}
    pool: Iterable[Tuple[int, ...]] = itertools.product(
        *(spec.grid[p] for p in spec.params)
    )
    plans = [dict(spec.defaults)]
    plans += [dict(zip(spec.params, combo)) for combo in pool]
    for plan in plans:
        norm = normalize(kernel, plan, shapes)
        key = tuple(norm[p] for p in spec.params)
        if key not in seen:
            seen[key] = norm
    out = list(seen.values())
    if budget and budget > 0:
        out = out[: max(1, int(budget))]
    return out
