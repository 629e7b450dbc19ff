"""Declarative sharding: ordered regex -> PartitionSpec rule tables.

ROADMAP item 1 (the dp/zero/branch unification): instead of three bespoke
step builders each hand-placing state, a *rule table* names the placement
of every state leaf — ordered regexes matched against the '/'-joined
param-tree path, first match wins, unmatched leaves fall back to an
explicit replicated default *with an audit finding* (obs/sharding.py).
The pattern is the GSPMD-style declarative sharding of every modern JAX
LLM trainer (SNIPPETS.md [3], fmengine's ``match_partition_rules``:
``re.search(rule, name)`` over the tree paths, scalars unpartitioned),
extended with the predicates the ZeRO and branch placements need:

- ``min_size`` — ZeRO thresholds as rule predicates (a rule passes over
  leaves smaller than the threshold instead of failing them);
- leading-axis divisibility — a rule whose spec shards the leading dim
  over a mesh axis passes over leaves whose leading dim does not divide
  it (the old ``_zero_leaf_eligible`` semantics, now per-rule);
- ``leading_eq`` — branch decoder banks match only at their exact
  ``[num_branches]`` leading extent (the old ``_path_branch_specs``
  predicate);
- ``scope`` — which state trees the rule covers: ``params`` /
  ``opt_state`` / ``batch_stats`` place between steps, ``grads``
  constrains inside the jitted step (the ZeRO-2 reduce-scatter site).

Axes are LOGICAL ("data" / "model") and resolve to the concrete mesh
axis names at build time, so one table drives both the legacy
``(branch, data)`` mesh (via the deprecation shims in dp.py/branch.py)
and the engine's 2D ``(data, model)`` mesh (parallel/engine.py).

ZeRO-1/2/3 and the reference's ``MultiTaskModelMP`` task-parallel mode
(PAPER.md §0.2) ship as presets; ``Parallel.rules`` in the run config
selects a preset by name or supplies an inline table (docs/PARALLELISM.md).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# logical axis tokens — resolved to concrete mesh axis names at build time
DATA = "data"
MODEL = "model"
_AXIS_TOKENS = (DATA, MODEL)

# state trees a rule may cover; "grads" is the in-step constraint scope
SCOPES = ("params", "opt_state", "batch_stats", "grads")
# the between-steps placement scopes (place_state walks exactly these)
PLACED_SCOPES = ("params", "opt_state", "batch_stats")

# decoder-bank top-level collection keys (models/base.py setup:
# self.graph_shared / self.heads_NN list / MACE per-layer readouts) — the
# one model-family fact the branch/mp presets encode
DECODER_PATTERN = r"(^|/)(graph_shared|heads_NN|readout)"

# expert banks of the decoder stack (models/zaya.py ``experts_gate`` /
# ``experts_up`` / ``experts_down``, ``[experts held, ...]``): the leading
# axis is the expert axis
EXPERT_PATTERN = r"(^|/)experts_(gate|up|down)$"

# default ZeRO eligibility threshold (parallel/mesh.py historical default)
DEFAULT_MIN_SIZE = 1024


@dataclasses.dataclass(frozen=True)
class Rule:
    """One ordered entry: regex over the '/'-joined tree path -> logical
    PartitionSpec, gated by size/shape predicates. ``axes=()`` is an
    explicit replicated placement (distinct from *unmatched*, which is
    replicated-with-audit)."""

    pattern: str
    axes: Tuple[Optional[str], ...] = ()
    scope: Tuple[str, ...] = ("params",)
    min_size: int = 0
    leading_eq: Optional[int] = None
    reason: str = ""

    def compiled(self) -> "re.Pattern[str]":
        return re.compile(self.pattern)

    def admits(self, leaf: Any, axis_sizes: Dict[str, int]) -> bool:
        """Shape/size predicate (the regex already matched): scalars never
        shard, ``min_size`` thresholds pass over small leaves, and a spec
        sharding the leading dim requires divisibility (exact extent when
        ``leading_eq`` is set)."""
        ndim = getattr(leaf, "ndim", 0)
        if self.axes and not ndim:
            return False
        if self.min_size and getattr(leaf, "size", 0) < self.min_size:
            return False
        if self.leading_eq is not None and (
            not ndim or leaf.shape[0] != self.leading_eq
        ):
            return False
        if self.axes and self.axes[0] is not None:
            n = axis_sizes.get(self.axes[0], 1)
            if not ndim or leaf.shape[0] % max(n, 1) != 0:
                return False
        return True

    def to_config(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "pattern": self.pattern,
            "spec": list(self.axes),
            "scope": list(self.scope),
        }
        if self.min_size:
            out["min_size"] = int(self.min_size)
        if self.leading_eq is not None:
            out["leading_eq"] = int(self.leading_eq)
        if self.reason:
            out["reason"] = self.reason
        return out


@dataclasses.dataclass(frozen=True)
class RuleTable:
    """An ordered rule list plus the mesh/step semantics it requires:
    ``model_size`` is the model-axis extent the mesh must provide (1 =
    pure data parallelism), ``routed`` selects the branch-routed step
    (per-branch data routing + decoder gradients reduced over ``data``
    only — the ``MultiTaskModelMP`` semantics, parallel/engine.py)."""

    name: str
    rules: Tuple[Rule, ...] = ()
    model_size: int = 1
    routed: bool = False

    # -- queries -------------------------------------------------------------

    def rules_for(self, scope: str) -> Tuple[Rule, ...]:
        return tuple(r for r in self.rules if scope in r.scope)

    def shards(self, scope: str) -> bool:
        """Whether any rule can place a non-replicated spec in ``scope``."""
        return any(r.axes for r in self.rules_for(scope))

    def to_config(self) -> Dict[str, Any]:
        """JSON-serializable form, recorded into the run config so
        checkpoint restore replays the identical placement."""
        return {
            "name": self.name,
            "model_size": int(self.model_size),
            "routed": bool(self.routed),
            "rules": [r.to_config() for r in self.rules],
        }


class RuleError(ValueError):
    """An invalid rule table — raised eagerly at resolve time, never from
    inside a traced step."""


# ---------------------------------------------------------------------------
# path rendering + matching
# ---------------------------------------------------------------------------


def path_str(path: Sequence[Any]) -> str:
    """'/'-joined tree path: dict keys, attr names (optax NamedTuple
    states), and sequence indices — ``0/mu/graph_shared0/Dense_0/kernel``.
    The string the rule regexes search (fmengine joins with '/' too)."""
    import jax

    parts: List[str] = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.FlattenedIndexKey):
            parts.append(str(p.key))
        else:  # future key types: their repr is still matchable
            parts.append(str(p))
    return "/".join(parts)


def match_rule(
    table: RuleTable,
    path: str,
    leaf: Any,
    scope: str,
    axis_sizes: Dict[str, int],
) -> Tuple[Optional[Rule], Tuple[Optional[str], ...]]:
    """First-match-wins lookup: ``(rule, logical_axes)``. Scalars are
    unpartitioned without consulting the table (they match implicitly —
    no audit). ``(None, ())`` means *unmatched*: the caller places the
    leaf replicated and must surface the audit finding."""
    if not getattr(leaf, "ndim", 0):
        return None, ()  # scalar: implicit replicated, audited by nobody
    for rule in table.rules_for(scope):
        if rule.compiled().search(path) and rule.admits(leaf, axis_sizes):
            return rule, rule.axes
    return None, ()


def spec_tree(tree: Any, table: RuleTable, scope: str, axis_map, axis_sizes):
    """Per-leaf concrete PartitionSpec pytree for ``tree`` (shard_map
    in/out specs and device placement share this one resolver) plus the
    audit list of unmatched non-scalar leaf paths."""
    import jax
    from jax.sharding import PartitionSpec as P

    unmatched: List[str] = []

    def spec_of(path, leaf):
        p = path_str(path)
        rule, axes = match_rule(table, p, leaf, scope, axis_sizes)
        if rule is None and getattr(leaf, "ndim", 0):
            unmatched.append(f"{scope}/{p}")
        return P(*[axis_map[a] if a is not None else None for a in axes])

    specs = jax.tree_util.tree_map_with_path(spec_of, tree)
    return specs, unmatched


def resolve_axes(mesh) -> Dict[str, str]:
    """Logical axis token -> concrete mesh axis name. Accepts both the
    legacy ``(branch, data)`` mesh (shims, existing tests) and the
    engine's ``(data, model)`` mesh; a missing model axis maps onto the
    data axis's complement only when one exists."""
    names = list(mesh.axis_names)
    out: Dict[str, str] = {}
    if DATA in names:
        out[DATA] = DATA
    else:
        raise RuleError(
            f"mesh axes {tuple(names)} carry no 'data' axis — the engine "
            "needs one (parallel/mesh.py make_mesh2d)"
        )
    model = next((n for n in (MODEL, "branch") if n in names), None)
    if model is not None:
        out[MODEL] = model
    return out


# ---------------------------------------------------------------------------
# validation (eager — api.py runs this before any jit is touched)
# ---------------------------------------------------------------------------


def validate_table(table: RuleTable) -> RuleTable:
    """Raise ``RuleError`` on the first structural problem: a bad regex,
    an unknown axis token or scope, an impossible predicate. Returns the
    table so callers can chain."""
    if not isinstance(table.name, str) or not table.name:
        raise RuleError("rule table needs a non-empty name")
    if int(table.model_size) < 1:
        raise RuleError(
            f"rule table {table.name!r}: model_size {table.model_size} < 1"
        )
    for i, rule in enumerate(table.rules):
        where = f"rule table {table.name!r} rule[{i}] ({rule.pattern!r})"
        try:
            re.compile(rule.pattern)
        except re.error as e:
            raise RuleError(f"{where}: bad regex: {e}") from None
        for a in rule.axes:
            if a is not None and a not in _AXIS_TOKENS:
                raise RuleError(
                    f"{where}: unknown axis {a!r} (use "
                    f"{'/'.join(_AXIS_TOKENS)} or null)"
                )
        if not rule.scope:
            raise RuleError(f"{where}: empty scope")
        for s in rule.scope:
            if s not in SCOPES:
                raise RuleError(
                    f"{where}: unknown scope {s!r} (use {'/'.join(SCOPES)})"
                )
        if rule.min_size < 0:
            raise RuleError(f"{where}: min_size {rule.min_size} < 0")
        if rule.leading_eq is not None and rule.leading_eq < 1:
            raise RuleError(f"{where}: leading_eq {rule.leading_eq} < 1")
        if "grads" in rule.scope and any(a == MODEL for a in rule.axes):
            raise RuleError(
                f"{where}: 'grads' scope cannot shard over the model axis "
                "(decoder gradients stay model-sharded by propagation; "
                "the grads scope is the ZeRO-2 data-axis constraint site)"
            )
    if table.routed and table.model_size < 2:
        raise RuleError(
            f"rule table {table.name!r}: routed (branch/mp) tables need "
            f"model_size >= 2 (have {table.model_size})"
        )
    if table.routed and not any(
        any(a == MODEL for a in r.axes) for r in table.rules
    ):
        raise RuleError(
            f"rule table {table.name!r}: routed tables must shard at "
            "least one rule over the model axis (the decoder banks)"
        )
    return table


# ---------------------------------------------------------------------------
# shipped presets
# ---------------------------------------------------------------------------

# explicit replicated default — the last rule of every preset, so a preset
# never produces *unmatched* leaves (the audit is for hand-written tables
# that forgot coverage, not for the shipped placements)
def _replicated_default() -> Rule:
    return Rule(
        pattern=r".*",
        axes=(),
        scope=PLACED_SCOPES,
        reason="explicit replicated default",
    )


def _zero_rules(stage: int, min_size: int) -> Tuple[Rule, ...]:
    out: List[Rule] = [
        Rule(
            pattern=r".*",
            axes=(DATA,),
            scope=("opt_state",),
            min_size=min_size,
            reason="ZeRO-1: optimizer moments sharded over data",
        )
    ]
    if stage >= 2:
        out.append(
            Rule(
                pattern=r".*",
                axes=(DATA,),
                scope=("grads",),
                min_size=min_size,
                reason="ZeRO-2: gradient reduce-scatter over data",
            )
        )
    if stage >= 3:
        out.append(
            Rule(
                pattern=r".*",
                axes=(DATA,),
                scope=("params",),
                min_size=min_size,
                reason="ZeRO-3: params stored sharded between steps",
            )
        )
    out.append(_replicated_default())
    return tuple(out)


def _branch_rules(num_branches: int) -> Tuple[Rule, ...]:
    return (
        Rule(
            pattern=DECODER_PATTERN,
            axes=(MODEL,),
            scope=PLACED_SCOPES,
            leading_eq=num_branches,
            reason=(
                "decoder banks [num_branches, ...] sharded over the model "
                "axis (MultiTaskModelMP task parallelism)"
            ),
        ),
        _replicated_default(),
    )


def expert_rule(experts_held: int) -> Rule:
    """The expert axis of the decoder stack's expert banks over the model
    axis, for inline tables. Which experts a chip holds is a statement of the
    configuration (``Architecture.experts_held``): the layer computes the
    tokens routed to those and adds nothing for the rest. With a model axis of
    one chip the bank is whole on it and the layer runs without an exchange;
    the exchange of tokens between the chips of a wider model axis does not
    exist yet (ROADMAP "Reach"), and no table here asks for it."""
    return Rule(
        pattern=EXPERT_PATTERN,
        axes=(MODEL,),
        scope=PLACED_SCOPES,
        leading_eq=int(experts_held),
        reason="expert banks [experts held, ...] over the model axis",
    )


PRESET_NAMES = ("dp", "zero1", "zero2", "zero3", "branch", "mp")


def preset(
    name: str,
    min_size: int = DEFAULT_MIN_SIZE,
    num_branches: Optional[int] = None,
) -> RuleTable:
    """Build a shipped preset table. ``branch`` and ``mp`` are the same
    placement (``mp`` is the reference-facing name for the
    ``MultiTaskModelMP`` encoder-replicated / decoder-model-sharded
    mode); both need ``num_branches``."""
    if name == "dp":
        return validate_table(RuleTable("dp", (_replicated_default(),)))
    if name in ("zero1", "zero2", "zero3"):
        stage = int(name[-1])
        return validate_table(
            RuleTable(name, _zero_rules(stage, int(min_size)))
        )
    if name in ("branch", "mp"):
        if not num_branches or num_branches < 2:
            raise RuleError(
                f"preset {name!r} needs num_branches >= 2 "
                f"(have {num_branches}) — a single-branch model has no "
                "decoder bank to shard"
            )
        return validate_table(
            RuleTable(
                name,
                _branch_rules(int(num_branches)),
                model_size=int(num_branches),
                routed=True,
            )
        )
    raise RuleError(
        f"unknown Parallel.rules preset {name!r}; shipped presets: "
        f"{', '.join(PRESET_NAMES)} (or an inline rule list — "
        "docs/PARALLELISM.md)"
    )


# ---------------------------------------------------------------------------
# config surface (Parallel section; api.py resolves this eagerly)
# ---------------------------------------------------------------------------


def table_from_config(spec: Any, section: Dict[str, Any]) -> RuleTable:
    """Inline-table parse: ``Parallel.rules`` as a list of rule dicts
    (``{pattern, spec, scope, min_size, leading_eq}``), with
    ``Parallel.model_size`` / ``Parallel.routed`` alongside."""
    if not isinstance(spec, (list, tuple)):
        raise RuleError(
            f"Parallel.rules must be a preset name or a rule list, got "
            f"{type(spec).__name__}"
        )
    rules: List[Rule] = []
    for i, entry in enumerate(spec):
        if not isinstance(entry, dict):
            raise RuleError(
                f"Parallel.rules[{i}] must be an object, got "
                f"{type(entry).__name__}"
            )
        unknown = set(entry) - {
            "pattern", "spec", "scope", "min_size", "leading_eq", "reason",
        }
        if unknown:
            raise RuleError(
                f"Parallel.rules[{i}]: unknown keys {sorted(unknown)}"
            )
        if "pattern" not in entry:
            raise RuleError(f"Parallel.rules[{i}]: missing 'pattern'")
        axes = entry.get("spec", [])
        if isinstance(axes, str):
            axes = [axes]
        scope = entry.get("scope", ["params"])
        if isinstance(scope, str):
            scope = [scope]
        rules.append(
            Rule(
                pattern=str(entry["pattern"]),
                axes=tuple(a if a is not None else None for a in axes),
                scope=tuple(str(s) for s in scope),
                min_size=int(entry.get("min_size", 0)),
                leading_eq=(
                    int(entry["leading_eq"])
                    if entry.get("leading_eq") is not None
                    else None
                ),
                reason=str(entry.get("reason", "")),
            )
        )
    return validate_table(
        RuleTable(
            name=str(section.get("name", "inline")),
            rules=tuple(rules),
            model_size=int(section.get("model_size", 1)),
            routed=bool(section.get("routed", False)),
        )
    )


def resolve(config: Dict[str, Any]) -> RuleTable:
    """The one resolution path (api.py): an explicit ``Parallel.rules``
    (preset name or inline list) wins; otherwise the table is derived
    from the legacy ``Training`` keys (``Optimizer.zero_stage`` /
    ``use_zero_redundancy`` / ``branch_parallel``) so every existing
    config keeps its exact placement. Conflicts between an explicit
    table and contradicting legacy keys raise eagerly."""
    training = config.get("NeuralNetwork", {}).get("Training", {})
    section = config.get("Parallel") or {}
    min_size = int(section.get("min_size", DEFAULT_MIN_SIZE))
    num_branches = _num_branches_of(config)
    opt = training.get("Optimizer", {})
    zero_stage = int(
        opt.get("zero_stage", 1 if opt.get("use_zero_redundancy") else 0)
    )
    branch_parallel = bool(training.get("branch_parallel", False))
    spec = section.get("rules")
    if spec is None:
        if branch_parallel and zero_stage >= 2:
            raise RuleError(
                "Optimizer.zero_stage >= 2 is not supported together with "
                "Training.branch_parallel (the branch table shards decoder "
                "banks, not gradients/moments); drop one of the two, or "
                "write an explicit Parallel.rules table"
            )
        if branch_parallel:
            return preset("branch", num_branches=num_branches)
        if zero_stage >= 1:
            return preset(f"zero{min(zero_stage, 3)}", min_size=min_size)
        return preset("dp")
    if isinstance(spec, str):
        table = preset(spec, min_size=min_size, num_branches=num_branches)
    else:
        table = table_from_config(spec, section)
    # explicit table + contradicting legacy keys: refuse, don't guess
    if branch_parallel and not table.routed:
        raise RuleError(
            f"Parallel.rules={table.name!r} is not a routed (branch/mp) "
            "table but Training.branch_parallel is set; drop "
            "branch_parallel or pick the 'branch'/'mp' preset"
        )
    if zero_stage >= 2 and not table.shards("grads"):
        raise RuleError(
            f"Parallel.rules={table.name!r} has no 'grads'-scope rule but "
            f"Optimizer.zero_stage={zero_stage} asks for gradient "
            "sharding; align the two (the zero2/zero3 presets carry it)"
        )
    return table


def table_from_recorded(recorded: Dict[str, Any]) -> RuleTable:
    """Rebuild a table from the ``Parallel.resolved_rules`` block a run
    config recorded (checkpoint restore replays the identical placement)."""
    return table_from_config(
        recorded.get("rules", []),
        {
            "name": recorded.get("name", "recorded"),
            "model_size": recorded.get("model_size", 1),
            "routed": recorded.get("routed", False),
        },
    )


def _num_branches_of(config: Dict[str, Any]) -> Optional[int]:
    arch = config.get("NeuralNetwork", {}).get("Architecture", {})
    try:
        from ..models.create import num_branches_from

        return int(num_branches_from(arch))
    except Exception:
        heads = arch.get("output_heads")
        return len(heads) if isinstance(heads, dict) else None
