#
# module-ref rule — stale prose.  Comments, docstrings and the docs
# pages are full of cross-references ("see parallel/mesh.py", "the
# `distance_precision` conf"); when a file is renamed or a conf retired
# those references rot silently, and used to be cleaned up by hand.
# Two checks:
#
#   - a path-like reference with a directory component
#     (`resilience/faults.py`, `docs/performance.md`) must resolve
#     inside the repo — against the root, the package, or the referring
#     file's own directory.  Citations of the SOURCE reference repo are
#     exempt when the line (or the one above it) says "reference", the
#     house citation style.
#   - a backticked name the prose calls a conf (``the `elastic` conf``)
#     must be a live `config._DEFAULTS` key.
#
from __future__ import annotations

import ast
import re
from typing import Iterable, List, Tuple

from .framework import Finding, Project, Rule, SourceFile

_PATH_RE = re.compile(
    r"(?<![\w/.\-])((?:[A-Za-z_][\w\-]*/)+[A-Za-z_][\w\-]*"
    r"\.(?:py|md|sh|cpp|h|json|jsonl|ipynb))\b"
)
_CONF_REF_RES = (
    re.compile(r"`([a-z][a-z0-9_]{2,})`\s+(?:conf|config)\b"),
    re.compile(r"\b(?:conf|config\s+key|conf\s+key)s?\s+`([a-z][a-z0-9_]{2,})`"),
)
_REFERENCE_MARK = re.compile(r"\breference\b|\breference's\b", re.IGNORECASE)


def _scannable_lines(sf: SourceFile) -> List[Tuple[int, str]]:
    """(line, text) pairs worth scanning: whole markdown files; comments
    plus docstring lines of python files."""
    if not sf.is_python:
        return list(enumerate(sf.lines, 1))
    out = list(sf.comments)
    if sf.tree is not None:
        for node in ast.walk(sf.tree):
            if not isinstance(
                node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                       ast.AsyncFunctionDef)
            ):
                continue
            body = getattr(node, "body", [])
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                start = body[0].value.lineno
                for off, text in enumerate(
                    body[0].value.value.splitlines()
                ):
                    out.append((start + off, text))
    return sorted(out)


class ModuleRefRule(Rule):
    name = "module-ref"
    description = (
        "comments/docs reference only files and conf keys that exist"
    )

    def check(self, project: Project) -> Iterable[Finding]:
        defaults = project.conf_defaults()
        for sf in project.files + project.docs:
            lines = _scannable_lines(sf)
            by_no = dict(lines)
            for lineno, text in lines:
                if "http" in text:
                    continue  # URLs carry path-shaped tails
                exempt = bool(
                    _REFERENCE_MARK.search(text)
                    or _REFERENCE_MARK.search(by_no.get(lineno - 1, ""))
                )
                for m in _PATH_RE.finditer(text):
                    if exempt:
                        continue
                    ref = m.group(1)
                    if self._resolves(project, sf, ref):
                        continue
                    yield Finding(
                        sf.rel, lineno, self.name,
                        f"reference to `{ref}`, which does not exist in "
                        "the repo (renamed or removed?)",
                    )
                for pattern in _CONF_REF_RES:
                    for cm in pattern.finditer(text):
                        key = cm.group(1)
                        if defaults and key not in defaults:
                            yield Finding(
                                sf.rel, lineno, self.name,
                                f"prose names `{key}` as a conf, but it "
                                "is not in config._DEFAULTS (retired "
                                "key?)",
                            )

    def _resolves(
        self, project: Project, sf: SourceFile, ref: str
    ) -> bool:
        from pathlib import Path

        candidates = [
            ref,
            f"spark_rapids_ml_tpu/{ref}",
            (Path(sf.rel).parent / ref).as_posix(),
        ]
        return any(project.exists(c) for c in candidates)


RULES = [ModuleRefRule()]
