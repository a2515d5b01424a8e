#
# graft-lint framework — rule registry, source model, suppressions,
# baselines.  Eight PRs of review hardening kept re-fixing the same
# classes of drift by hand (CHANGES.md): unknown conf keys, fault-site
# lists diverging from docs, metric names minted outside the telemetry
# registry, thread targets that forget `adopt_trace_context`.  The rules
# in rules_*.py turn that review lore into machine-checked invariants by
# cross-checking the codebase against its OWN registries
# (`config._DEFAULTS`, `resilience.faults.KNOWN_SITES`,
# `telemetry.registry.METRIC_CATALOG`, the docs tables).
#
# Everything here is stdlib-only AST/token analysis: running the
# analyzer must never pay a jax import (the runtime jit sanitizer lives
# separately in jit_audit.py and imports jax lazily).  Registries are
# read by PARSING their defining modules, not importing them, so the
# analyzer always judges the tree on disk.
#
# Suppression syntax (docs/analysis.md):
#   x = risky()          # lint: disable=rule-name[,other-rule]
#   # lint: disable=rule-name        <- alone on a line: applies to the
#   #                                   next source line
#   # lint: disable-file=rule-name   <- anywhere: whole file
#
from __future__ import annotations

import ast
import io
import json
import re
import tokenize
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO_ROOT = Path(__file__).resolve().parents[2]

# analyzed python roots (ci/lint.py's ROOTS, widened to every python
# entrypoint the repo ships) and the markdown surface the doc rules scan
PY_ROOTS = (
    "spark_rapids_ml_tpu",
    "benchmark",
    "tests",
    "ci",
    "docs",
    "__graft_entry__.py",
)
DOC_FILES = (
    "README.md",
    "docs/configuration.md",
    "docs/resilience.md",
    "docs/observability.md",
    "docs/performance.md",
    "docs/analysis.md",
    "docs/statistics.md",
    "docs/troubleshooting.md",
)

_SUPPRESS_RE = re.compile(r"#\s*lint:\s*disable(-file)?\s*(?:=\s*([\w\-*,\s]+))?")


class _NotLiteral(Exception):
    pass


def safe_eval(node: ast.expr) -> Any:
    """Evaluate a constant expression: literals plus the arithmetic the
    registries use for readability (`512 * 1024 * 1024`, `2e12`).  No
    names, no calls except the container constructors — raises
    `_NotLiteral` on anything else."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Tuple):
        return tuple(safe_eval(e) for e in node.elts)
    if isinstance(node, ast.List):
        return [safe_eval(e) for e in node.elts]
    if isinstance(node, ast.Set):
        return {safe_eval(e) for e in node.elts}
    if isinstance(node, ast.Dict):
        return {
            safe_eval(k): safe_eval(v)
            for k, v in zip(node.keys, node.values)
            if k is not None
        }
    if isinstance(node, ast.UnaryOp) and isinstance(
        node.op, (ast.USub, ast.UAdd)
    ):
        v = safe_eval(node.operand)
        return -v if isinstance(node.op, ast.USub) else +v
    if isinstance(node, ast.BinOp) and isinstance(
        node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.Pow)
    ):
        left, right = safe_eval(node.left), safe_eval(node.right)
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, ast.Div):
            return left / right
        return left ** right
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) and (
        node.func.id in ("frozenset", "set", "tuple", "dict", "list")
        and not node.keywords
    ):
        args = [safe_eval(a) for a in node.args]
        return {"frozenset": frozenset, "set": set, "tuple": tuple,
                "dict": dict, "list": list}[node.func.id](*args)
    raise _NotLiteral(ast.dump(node))


def resolve_import(sf: "SourceFile", node: ast.ImportFrom) -> Optional[str]:
    """Repo-relative path of the module an `from X import ...` names
    (e.g. `from ..telemetry.registry import counter` inside
    resilience/retry.py -> 'spark_rapids_ml_tpu/telemetry/registry.py').
    Returns None for imports outside the analyzed tree (stdlib, jax)."""
    parts: List[str] = []
    if node.level:
        base = Path(sf.rel).parent.parts
        up = node.level - 1
        if up > len(base):
            return None
        parts = list(base[: len(base) - up] if up else base)
    if node.module:
        parts += node.module.split(".")
    if not parts:
        return None
    rel = "/".join(parts)
    # the repo root is sf.path with the rel components stripped back off
    root = sf.path
    for _ in Path(sf.rel).parts:
        root = root.parent
    for cand in (rel + ".py", rel + "/__init__.py"):
        if (root / cand).exists():
            return cand
    return None


@dataclass(frozen=True)
class Finding:
    """One rule violation, anchored to a repo-relative file and line."""

    file: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}"

    def sort_key(self) -> Tuple[str, int, str]:
        return (self.file, self.line, self.rule)


class SourceFile:
    """One analyzed file: text, lazy AST, comments and suppressions."""

    def __init__(self, path: Path, rel: str) -> None:
        self.path = path
        self.rel = rel
        self.text = path.read_text()
        self.lines = self.text.splitlines()
        self._tree: Optional[ast.AST] = None
        self._parse_error: Optional[str] = None
        self._comments: Optional[List[Tuple[int, str]]] = None
        self._suppress: Optional[Dict[int, Set[str]]] = None
        self._file_suppress: Optional[Set[str]] = None
        self.cache: Dict[str, Any] = {}  # per-file memo shared across rules

    @property
    def is_python(self) -> bool:
        return self.path.suffix == ".py"

    @property
    def in_package(self) -> bool:
        return self.rel.startswith("spark_rapids_ml_tpu/")

    @property
    def is_test(self) -> bool:
        return self.rel.startswith("tests/")

    @property
    def tree(self) -> Optional[ast.AST]:
        if self._tree is None and self._parse_error is None:
            try:
                self._tree = ast.parse(self.text, filename=self.rel)
            except SyntaxError as e:  # surfaced as a finding by run()
                self._parse_error = f"syntax error: {e.msg} (line {e.lineno})"
        return self._tree

    @property
    def parse_error(self) -> Optional[str]:
        self.tree  # force the parse attempt
        return self._parse_error

    @property
    def comments(self) -> List[Tuple[int, str]]:
        """(line, text) for every `#` comment (tokenize-accurate — never
        confuses a `#` inside a string literal for a comment)."""
        if self._comments is None:
            out: List[Tuple[int, str]] = []
            try:
                for tok in tokenize.generate_tokens(
                    io.StringIO(self.text).readline
                ):
                    if tok.type == tokenize.COMMENT:
                        out.append((tok.start[0], tok.string))
            except (tokenize.TokenError, IndentationError, SyntaxError):
                pass
            self._comments = out
        return self._comments

    def _load_suppressions(self) -> None:
        per_line: Dict[int, Set[str]] = {}
        whole_file: Set[str] = set()
        for line, text in self.comments:
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = {
                r.strip() for r in (m.group(2) or "*").split(",") if r.strip()
            }
            if m.group(1):  # disable-file
                whole_file |= rules
                continue
            per_line.setdefault(line, set()).update(rules)
            # a comment alone on its line suppresses the NEXT line too
            if self.lines[line - 1].lstrip().startswith("#"):
                per_line.setdefault(line + 1, set()).update(rules)
        self._suppress = per_line
        self._file_suppress = whole_file

    def suppressed(self, line: int, rule: str) -> bool:
        if self._suppress is None:
            self._load_suppressions()
        assert self._suppress is not None and self._file_suppress is not None
        if self._file_suppress & {rule, "*"}:
            return True
        return bool(self._suppress.get(line, set()) & {rule, "*"})


class Rule:
    """Base class: subclasses set `name`/`description` and yield
    Findings from `check(project)`.  Rules see the WHOLE project — the
    interesting invariants are cross-file (a call site vs a registry)."""

    name: str = ""
    description: str = ""

    def check(self, project: "Project") -> Iterable[Finding]:
        raise NotImplementedError


class Project:
    """The analyzed tree: every python file under the roots plus the
    scanned docs, with cached cross-file facts (registries parsed from
    their defining modules)."""

    def __init__(
        self, root: Optional[Path] = None,
        py_roots: Sequence[str] = PY_ROOTS,
        doc_files: Sequence[str] = DOC_FILES,
    ) -> None:
        self.root = Path(root) if root else REPO_ROOT
        self.files: List[SourceFile] = []
        self.docs: List[SourceFile] = []
        self.cache: Dict[str, Any] = {}
        seen: Set[str] = set()
        for r in py_roots:
            p = self.root / r
            if p.suffix == ".py":
                candidates = [p] if p.exists() else []
            else:
                candidates = sorted(p.rglob("*.py")) if p.is_dir() else []
            for f in candidates:
                rel = f.relative_to(self.root).as_posix()
                if "__pycache__" in rel or rel in seen:
                    continue
                seen.add(rel)
                self.files.append(SourceFile(f, rel))
        for r in doc_files:
            p = self.root / r
            if p.exists():
                self.docs.append(SourceFile(p, Path(r).as_posix()))

    def file(self, rel: str) -> Optional[SourceFile]:
        for f in self.files + self.docs:
            if f.rel == rel:
                return f
        return None

    def package_files(self) -> List[SourceFile]:
        return [f for f in self.files if f.in_package]

    def exists(self, rel: str) -> bool:
        return (self.root / rel).exists()

    # -- registries, parsed (never imported) -------------------------------

    def _module_literal(self, rel: str, name: str) -> Optional[Any]:
        """The literal value of module-level `NAME = <literal>` in `rel`
        (None when the file or assignment is missing / non-literal)."""
        sf = self.file(rel)
        if sf is None or sf.tree is None:
            return None
        for node in sf.tree.body:  # type: ignore[union-attr]
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            for t in targets:
                if isinstance(t, ast.Name) and t.id == name:
                    try:
                        return safe_eval(value)
                    except _NotLiteral:
                        return None
        return None

    def conf_defaults(self) -> Dict[str, Any]:
        """`config._DEFAULTS`, parsed from spark_rapids_ml_tpu/config.py."""
        if "conf_defaults" not in self.cache:
            raw = self._module_literal(
                "spark_rapids_ml_tpu/config.py", "_DEFAULTS"
            )
            self.cache["conf_defaults"] = dict(raw) if raw else {}
        return self.cache["conf_defaults"]

    def known_fault_sites(self) -> Set[str]:
        """`resilience.faults.KNOWN_SITES`."""
        if "fault_sites" not in self.cache:
            raw = self._module_literal(
                "spark_rapids_ml_tpu/resilience/faults.py", "KNOWN_SITES"
            )
            self.cache["fault_sites"] = set(raw) if raw else set()
        return self.cache["fault_sites"]

    def fault_kinds(self) -> Set[str]:
        """`resilience.faults.FAULT_KINDS`."""
        if "fault_kinds" not in self.cache:
            raw = self._module_literal(
                "spark_rapids_ml_tpu/resilience/faults.py", "FAULT_KINDS"
            )
            self.cache["fault_kinds"] = set(raw) if raw else set()
        return self.cache["fault_kinds"]

    def metric_catalog(self) -> Dict[str, Dict[str, Any]]:
        """`telemetry.registry.METRIC_CATALOG`."""
        if "metric_catalog" not in self.cache:
            raw = self._module_literal(
                "spark_rapids_ml_tpu/telemetry/registry.py", "METRIC_CATALOG"
            )
            self.cache["metric_catalog"] = dict(raw) if raw else {}
        return self.cache["metric_catalog"]

    def lock_catalog(self) -> Dict[str, Dict[str, Any]]:
        """`telemetry.locks.LOCK_CATALOG`."""
        if "lock_catalog" not in self.cache:
            raw = self._module_literal(
                "spark_rapids_ml_tpu/telemetry/locks.py", "LOCK_CATALOG"
            )
            self.cache["lock_catalog"] = dict(raw) if raw else {}
        return self.cache["lock_catalog"]


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def all_rules() -> List[Rule]:
    """Every shipped rule, builtin lint first (import here, not at module
    scope, so framework.py <-> rules_*.py never cycle)."""
    from . import (
        rules_builtin,
        rules_concurrency,
        rules_docs,
        rules_registry,
        rules_stats,
    )

    return [
        *rules_builtin.RULES,
        *rules_registry.RULES,
        *rules_stats.RULES,
        *rules_concurrency.RULES,
        *rules_docs.RULES,
    ]


def load_baseline(path: str) -> List[Dict[str, str]]:
    """Baseline file: JSON list of {"file", "rule", "message"} entries —
    known findings tolerated while they are burned down.  Line numbers
    are deliberately NOT part of the match (they shift on every edit)."""
    with open(path) as f:
        entries = json.load(f)
    if not isinstance(entries, list):
        raise ValueError(f"baseline {path}: expected a JSON list")
    return entries


def run_analysis(
    project: Optional[Project] = None,
    rules: Optional[Sequence[Rule]] = None,
    disable: Sequence[str] = (),
    baseline: Optional[Sequence[Dict[str, str]]] = None,
) -> List[Finding]:
    """Run `rules` (default: all) over `project` (default: this repo),
    returning unsuppressed findings in (file, line) order."""
    project = project or Project()
    active = [
        r for r in (rules if rules is not None else all_rules())
        if r.name not in set(disable)
    ]
    findings: List[Finding] = []
    for sf in project.files:
        if sf.parse_error:
            findings.append(Finding(sf.rel, 1, "parse", sf.parse_error))
    for rule in active:
        for f in rule.check(project):
            sf = project.file(f.file)
            if sf is not None and sf.suppressed(f.line, f.rule):
                continue
            findings.append(f)
    if baseline:
        known = {(b["file"], b["rule"], b["message"]) for b in baseline}
        findings = [
            f for f in findings if (f.file, f.rule, f.message) not in known
        ]
    return sorted(set(findings), key=Finding.sort_key)
