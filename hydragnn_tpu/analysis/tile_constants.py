"""tile_constants: no hand-pinned Pallas block/tile literals outside the
autotuning plane.

The failure history: PR 16 retired the hand-picked block constants the
kernel routing layer had been pinning at every call site (and found, in
the process, that ops/segment.py cached a multi_agg specialization on the
*unclamped* ``block_cols`` — two call sites could request the same
effective tile yet compile twice, or worse, share a table key that the
kernel then clamped differently). The fix is structural: tile choices
route through ``tune.runtime.tile_plan`` — the tuned table when an entry
matches, the pinned defaults (normalized by the kernel's own clamp)
otherwise — so the jit key, the table key, and the kernel's actual tile
are the same value by construction (docs/TUNING.md).

Rule (package-wide, two exemptions):

- a numeric literal passed as a ``block_rows`` / ``block_edges`` /
  ``block_cols`` / ``block_q`` / ``block_k`` keyword is
  a finding — route the call through ``tile_plan`` (or waive with the
  reason the pinned value is load-bearing);
- ``ops/pallas_*.py`` is exempt: the kernel modules OWN their pinned
  defaults (the signature defaults the tuner falls back to);
- ``tune/`` is exempt: plans.py owns the candidate grids and default
  plans the plane sweeps over.

Tests and run-scripts are outside the package walk and may pin literals
freely (a test that exercises one specific tile shape is the point).
"""

from __future__ import annotations

import ast
from typing import List

from .core import Checker, Finding, Repo, register, walk_calls

CHECKER_ID = "tile_constants"

# the tile-plan keyword surface across the four Pallas kernels
TILE_KWARGS = frozenset((
    "block_rows", "block_edges", "block_cols",
    "block_q", "block_k",
))


def _exempt(rel: str) -> bool:
    norm = rel.replace("\\", "/")
    base = norm.rsplit("/", 1)[-1]
    if base.startswith("pallas_") and "/ops/" in f"/{norm}":
        return True  # kernel modules own their pinned defaults
    return "/tune/" in f"/{norm}"  # plans.py owns grids and defaults


def run(repo: Repo) -> List[Finding]:
    findings: List[Finding] = []
    for rel in repo.python_files():
        if _exempt(rel):
            continue
        src = repo.source(rel)
        if src.tree is None:
            continue
        for call in walk_calls(src.tree):
            for kw in call.keywords:
                if kw.arg not in TILE_KWARGS:
                    continue
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(
                    v.value, (int, float)
                ) and not isinstance(v.value, bool):
                    findings.append(Finding(
                        CHECKER_ID, rel, v.lineno,
                        f"hand-pinned tile literal {kw.arg}={v.value!r} — "
                        "kernel call sites must route block constants "
                        "through the tuned-table lookup",
                        hint="plan = tune.runtime.tile_plan(<kernel>, "
                             "<shapes>, dtype) and pass "
                             f"{kw.arg}=plan[{kw.arg!r}] (or waive with "
                             "the reason this pinned value is "
                             "load-bearing)",
                    ))
    return findings


register(Checker(
    id=CHECKER_ID,
    title="Pallas tile constants route through tile_plan, not literals",
    rationale=(
        "PR 16's multi_agg bug: a call site pinned an unclamped "
        "block_cols that became the jit specialization key while the "
        "kernel clamped it internally — tile choices must flow through "
        "tune.runtime.tile_plan so jit key, table key and actual tile "
        "agree (docs/TUNING.md)"
    ),
    run=run,
))
