"""sharding_rules: placement decisions live in the rule engine, nowhere
else.

The convention this encodes: ROADMAP item 1 collapsed the dp/zero/branch
builder trio into ONE sharding engine — an ordered regex->PartitionSpec
rule table (parallel/rules.py) consumed by one mesh-step builder
(parallel/engine.py). Its payoff (one before/after placement oracle, one
audit, one bit-identity test surface) only holds while the rule table is
the SINGLE source of placement truth. A ``with_sharding_constraint`` or
``NamedSharding`` call hand-placed in a model or training module is a
placement decision the table cannot see, the sharding inspector cannot
attribute, and the ``doctor diff`` sharding section cannot explain — the
exact per-builder drift the engine retired.

Scope: every package module OUTSIDE ``parallel/``. Flagged call targets:

- ``with_sharding_constraint(...)`` — in-step placement pins belong in
  the engine's ``_constrain`` (driven by the table's grads/params rules);
- ``NamedSharding(...)`` — device placement belongs in
  ``engine.place_state`` / the mesh helpers;
- ``shard_map(...)`` — per-device program
  boundaries belong in the engine's step builders.

Mentions in strings/comments and ``isinstance(x, NamedSharding)`` type
checks do not place anything and are not flagged. The one legitimate
outlier — models/gps.py's ring-attention ``shard_map``, a collective that
lives with the model's attention math — carries a pragma waiver.
"""

from __future__ import annotations

from typing import List

from .core import Checker, Finding, Repo, dotted, register, walk_calls

CHECKER_ID = "sharding_rules"

# call-target tails that constitute a placement decision
_FORBIDDEN = (
    "with_sharding_constraint",
    "NamedSharding",
    "shard_map",
)

_HINTS = {
    "with_sharding_constraint": (
        "express the pin as a rule (parallel/rules.py) so the engine's "
        "_constrain applies it — or move the code into parallel/"
    ),
    "NamedSharding": (
        "place state via parallel.engine.place_state(state, table, mesh) "
        "or the parallel/mesh.py helpers"
    ),
    "shard_map": (
        "per-device programs are built by parallel/engine.py's mesh-step "
        "builders; add a rule preset instead of a bespoke shard_map"
    ),
}


def run(repo: Repo) -> List[Finding]:
    findings: List[Finding] = []
    allowed_prefix = f"{repo.package}/parallel/"
    for rel in repo.python_files():
        norm = rel.replace("\\", "/")
        if norm.startswith(allowed_prefix):
            continue
        src = repo.source(rel)
        if src.tree is None:
            continue
        for call in walk_calls(src.tree):
            name = dotted(call.func)
            tail = name.rsplit(".", 1)[-1]
            if tail not in _FORBIDDEN:
                continue
            findings.append(Finding(
                CHECKER_ID, rel, call.lineno,
                f"{name}(...) outside parallel/ is a sharding decision "
                "the rule table cannot see",
                hint=_HINTS[tail],
            ))
    return findings


register(Checker(
    id=CHECKER_ID,
    title="sharding primitives only inside parallel/ (rule-engine monopoly)",
    rationale=(
        "ROADMAP item 1 replaced the dp/zero/branch builder trio with one "
        "rule-table engine; a hand-placed with_sharding_constraint/"
        "NamedSharding/shard_map elsewhere is placement the table, the "
        "sharding inspector, and doctor diff all miss — the per-builder "
        "drift the engine exists to end"
    ),
    run=run,
))
