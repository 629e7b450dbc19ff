"""graftlint core: the repo-native static-analysis plane's shared machinery.

Fourteen PRs of conventions — every env knob documented and parsed through
one helper, every event kind declared with a severity, no host syncs inside
jitted step builders, every background thread daemonized, every
checkpoint-adjacent write atomic — lived in docstrings and reviewers'
heads. This package turns them into machine-enforced contracts: one
checker per module (analysis/<checker>.py), findings typed with file:line
and a fix hint, pragma-comment waivers with mandatory reasons, JSON output
for CI, and a ``--baseline`` mode kept for local incremental use only (the
CI gate in run-scripts/ci.sh runs baseline-free and must stay at zero).

Checkers are pure host-side AST/text analysis — importing this package
must never import jax (the fixture tests are tier-1 and run with no
accelerator stack at all).

Waiver grammar (docs/ANALYSIS.md "Waivers")::

    some_flagged_line()  # graftlint: disable=checker-id -- why it is OK
    # graftlint: disable=checker-id,other-id -- reason covering both
    some_flagged_line()

A pragma waives matching findings on its own line or the line directly
below it. The reason after ``--`` is mandatory: a reasonless pragma is
itself a ``waiver`` finding, so silence always has a written cost.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import tokenize
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

ANALYSIS_SCHEMA_VERSION = 1

# pragma grammar: "# graftlint: disable=a,b -- reason" (reason mandatory;
# enforced by the built-in `waiver` checker below, not the regex)
_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*disable=(?P<ids>[a-z0-9_,\-]+)"
    r"(?:\s*--\s*(?P<reason>.*\S))?"
)


@dataclasses.dataclass
class Finding:
    """One typed violation: where, what, and how to fix it."""

    checker: str            # checker id (module name under analysis/)
    path: str               # repo-relative path
    line: int               # 1-based; 0 = whole-file/config-level finding
    message: str            # what is wrong, concretely
    hint: str = ""          # the fix the checker wants (or the waiver shape)
    waived: bool = False    # a pragma with a reason covers this finding
    waive_reason: str = ""  # that pragma's mandatory reason text

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        tail = f" [waived: {self.waive_reason}]" if self.waived else ""
        hint = f"\n    fix: {self.hint}" if self.hint and not self.waived else ""
        return f"{loc}: [{self.checker}] {self.message}{tail}{hint}"


class SourceFile:
    """One parsed python file: text, lines, AST (lazily), pragma map."""

    def __init__(self, root: str, relpath: str):
        self.relpath = relpath
        self.abspath = os.path.join(root, relpath)
        with open(self.abspath, "r", encoding="utf-8") as fh:
            self.text = fh.read()
        self.lines = self.text.splitlines()
        self._tree: Optional[ast.AST] = None
        self._parse_error: Optional[str] = None
        self._pragmas: Optional[Dict[int, List[Tuple[str, str]]]] = None

    @property
    def tree(self) -> Optional[ast.AST]:
        if self._tree is None and self._parse_error is None:
            try:
                self._tree = ast.parse(self.text, filename=self.relpath)
            except SyntaxError as e:
                self._parse_error = str(e)
        return self._tree

    @property
    def parse_error(self) -> Optional[str]:
        _ = self.tree
        return self._parse_error

    def pragmas(self) -> Dict[int, List[Tuple[str, str, bool]]]:
        """line -> [(checker_id, reason, standalone)] from real COMMENT
        tokens (not string literals that merely look like pragmas).
        ``standalone`` is True for comment-only lines — only those waive
        the line BELOW; a trailing comment waives its own line only."""
        if self._pragmas is not None:
            return self._pragmas
        out: Dict[int, List[Tuple[str, str, bool]]] = {}
        try:
            import io

            for tok in tokenize.generate_tokens(io.StringIO(self.text).readline):
                if tok.type != tokenize.COMMENT:
                    continue
                m = _PRAGMA_RE.search(tok.string)
                if not m:
                    continue
                reason = (m.group("reason") or "").strip()
                line_no = tok.start[0]
                standalone = (
                    line_no <= len(self.lines)
                    and self.lines[line_no - 1].lstrip().startswith("#")
                )
                for cid in m.group("ids").split(","):
                    cid = cid.strip().replace("-", "_")
                    if cid:
                        out.setdefault(line_no, []).append(
                            (cid, reason, standalone)
                        )
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass  # unparseable file: the checker reporting it still runs
        self._pragmas = out
        return out


class Repo:
    """The analysis target: a repo root with a ``hydragnn_tpu`` package,
    ``docs/``, ``tests/`` and ``run-scripts/`` beside it (fixtures build
    the same shape in a tmp dir)."""

    def __init__(self, root: str, package: str = "hydragnn_tpu"):
        self.root = os.path.abspath(root)
        self.package = package
        self._files: Dict[str, SourceFile] = {}

    # -- file discovery ------------------------------------------------------

    def python_files(self) -> List[str]:
        """Repo-relative paths of every package .py file (sorted; the
        analysis plane itself is included — it must obey its own rules)."""
        out = []
        pkg_root = os.path.join(self.root, self.package)
        for dirpath, dirnames, filenames in os.walk(pkg_root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(
                        os.path.relpath(os.path.join(dirpath, f), self.root)
                    )
        return sorted(out)

    def aux_files(self, *subdirs: str, exts: Tuple[str, ...] = (".py", ".sh")) -> List[str]:
        """Non-package evidence files (tests/, run-scripts/, ...)."""
        out = []
        for sub in subdirs:
            base = os.path.join(self.root, sub)
            if not os.path.isdir(base):
                continue
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for f in sorted(filenames):
                    if f.endswith(exts):
                        out.append(
                            os.path.relpath(os.path.join(dirpath, f), self.root)
                        )
        return sorted(out)

    def source(self, relpath: str) -> SourceFile:
        if relpath not in self._files:
            self._files[relpath] = SourceFile(self.root, relpath)
        return self._files[relpath]

    def read_text(self, relpath: str) -> Optional[str]:
        """Raw text of a repo file (docs, shell), or None when absent."""
        p = os.path.join(self.root, relpath)
        try:
            with open(p, "r", encoding="utf-8") as fh:
                return fh.read()
        except OSError:
            return None

    def has(self, relpath: str) -> bool:
        return os.path.exists(os.path.join(self.root, relpath))


# ---------------------------------------------------------------------------
# checker registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Checker:
    id: str
    title: str
    rationale: str  # the incident/convention that motivated it (docs/ANALYSIS.md)
    run: Callable[[Repo], List[Finding]]


_CHECKERS: List[Checker] = []


def register(checker: Checker) -> Checker:
    if any(c.id == checker.id for c in _CHECKERS):
        raise ValueError(f"duplicate checker id {checker.id!r}")
    _CHECKERS.append(checker)
    return checker


def checkers() -> List[Checker]:
    """All registered checkers (importing the sibling modules on first use
    — one checker = one module, docs/ANALYSIS.md catalog order)."""
    from . import (  # noqa: F401 — imported for their register() side effect
        atomic_write,
        config_keys,
        env_census,
        error_codes,
        fault_coverage,
        obs_contract,
        sharding_rules,
        threads,
        trace_hazard,
    )

    return list(_CHECKERS)


# ---------------------------------------------------------------------------
# shared AST helpers (used by several checkers)
# ---------------------------------------------------------------------------

def call_name(node: ast.Call) -> str:
    """Dotted name of a call target: ``os.environ.get`` -> that string,
    bare ``open`` -> "open". Unresolvable targets (lambdas, subscripts)
    render as ""."""
    return dotted(node.func)


def dotted(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def walk_calls(tree: ast.AST) -> Iterable[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


def expr_mentions(node: ast.AST, attr_base: str) -> bool:
    """Whether any attribute access on the name ``attr_base`` (e.g.
    ``state``) appears inside ``node``."""
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Attribute)
            and isinstance(sub.value, ast.Name)
            and sub.value.id == attr_base
        ):
            return True
    return False


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def _apply_waivers(repo: Repo, findings: List[Finding]) -> List[Finding]:
    """Mark findings covered by a same-line or line-above pragma; emit a
    ``waiver`` finding for every reasonless pragma (mandatory reasons)."""
    out: List[Finding] = []
    for f in findings:
        try:
            pragmas = repo.source(f.path).pragmas() if f.path.endswith(".py") else {}
        except OSError:
            pragmas = {}
        for line in (f.line, f.line - 1):
            for cid, reason, standalone in pragmas.get(line, ()):
                if line != f.line and not standalone:
                    continue  # a trailing comment covers its own line only
                if cid in (f.checker, "all") and reason:
                    f.waived, f.waive_reason = True, reason
        out.append(f)
    # reasonless pragmas are findings themselves — a waiver without a
    # written reason is exactly the silent convention-rot this plane exists
    # to stop
    for rel in repo.python_files():
        try:
            src = repo.source(rel)
        except OSError:
            continue
        for line, entries in src.pragmas().items():
            for cid, reason, _standalone in entries:
                if not reason:
                    out.append(Finding(
                        "waiver", rel, line,
                        f"graftlint pragma for {cid!r} has no reason",
                        hint="append ' -- <why this violation is acceptable>'"
                             " to the pragma",
                    ))
    return out


def run_checkers(
    repo: Repo, only: Optional[Iterable[str]] = None
) -> List[Finding]:
    """Run every (or the selected) checker over ``repo`` and apply
    waivers. A checker crash is itself a finding — the gate must never
    silently pass because an analyzer died."""
    findings: List[Finding] = []
    # files that do not parse fail loudly once, here, instead of once per
    # checker
    for rel in repo.python_files():
        src = repo.source(rel)
        if src.parse_error:
            findings.append(Finding(
                "parse", rel, 0, f"file does not parse: {src.parse_error}",
                hint="fix the syntax error",
            ))
    for checker in checkers():
        if only and checker.id not in only:
            continue
        try:
            findings.extend(checker.run(repo))
        except Exception as e:  # noqa: BLE001 — convert to a finding
            findings.append(Finding(
                checker.id, "", 0,
                f"checker crashed: {type(e).__name__}: {e}",
                hint="fix the checker (analysis/"
                     f"{checker.id}.py) — a dead checker gates nothing",
            ))
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.message))
    return _apply_waivers(repo, findings)


# ---------------------------------------------------------------------------
# baseline (local incremental use ONLY — ci.sh runs baseline-free)
# ---------------------------------------------------------------------------

def baseline_key(f: Finding) -> List[str]:
    # line numbers shift under unrelated edits; (checker, file, message)
    # is stable enough for an incremental burn-down session
    return [f.checker, f.path, f.message]


def apply_baseline(findings: List[Finding], baseline: List[List[str]]) -> List[Finding]:
    known = {tuple(k) for k in baseline}
    return [f for f in findings if tuple(baseline_key(f)) not in known]


def summarize(findings: List[Finding]) -> Dict[str, Any]:
    active = [f for f in findings if not f.waived]
    by_checker: Dict[str, int] = {}
    for f in active:
        by_checker[f.checker] = by_checker.get(f.checker, 0) + 1
    return {
        "v": ANALYSIS_SCHEMA_VERSION,
        "total": len(findings),
        "active": len(active),
        "waived": len(findings) - len(active),
        "by_checker": dict(sorted(by_checker.items())),
        "clean": not active,
    }


def to_json(findings: List[Finding]) -> str:
    return json.dumps(
        {
            "summary": summarize(findings),
            "findings": [f.to_dict() for f in findings],
        },
        indent=2,
    )


def default_root() -> str:
    """The repo root this package sits in (two levels above analysis/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))
