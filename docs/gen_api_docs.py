#!/usr/bin/env python
#
# API docs generator — the analog of the reference's Sphinx tree
# (`docs/source/` -> published `docs/site/` with per-class API pages).
# The build image has no sphinx/pdoc/mkdocs, so this is a small,
# dependency-free generator: it introspects the public modules and writes
# one markdown page per class plus a module index into docs/api/.
# Reproducible in CI (`ci/test.sh` runs it and fails on drift).
#
from __future__ import annotations

import importlib
import inspect
import os
import re
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "docs", "api")


# public API surface, one page per module (mirrors the reference's
# docs/source per-module toctree: feature/clustering/classification/...)
MODULES = [
    "spark_rapids_ml_tpu.feature",
    "spark_rapids_ml_tpu.clustering",
    "spark_rapids_ml_tpu.classification",
    "spark_rapids_ml_tpu.regression",
    "spark_rapids_ml_tpu.knn",
    "spark_rapids_ml_tpu.umap",
    "spark_rapids_ml_tpu.tuning",
    "spark_rapids_ml_tpu.pipeline",
    "spark_rapids_ml_tpu.evaluation",
    "spark_rapids_ml_tpu.metrics",
    "spark_rapids_ml_tpu.config",
    "spark_rapids_ml_tpu.data",
    "spark_rapids_ml_tpu.streaming",
    "spark_rapids_ml_tpu.stats",
    "spark_rapids_ml_tpu.monitor",
    "spark_rapids_ml_tpu.fused",
    "spark_rapids_ml_tpu.telemetry",
    "spark_rapids_ml_tpu.analysis",
    "spark_rapids_ml_tpu.tracing",
    "spark_rapids_ml_tpu.sklearn_api",
    "spark_rapids_ml_tpu.spark_interop",
    "spark_rapids_ml_tpu.parallel",
    "spark_rapids_ml_tpu.resilience",
    "spark_rapids_ml_tpu.serving",
]


def _anchor(name: str) -> str:
    # GitHub-style heading slug: lowercase, drop periods, KEEP underscores
    return name.lower().replace(".", "")


def _clean_doc(doc: str | None, indent: str = "") -> str:
    if not doc:
        return indent + "*Undocumented.*"
    return "\n".join(indent + line for line in inspect.cleandoc(doc).splitlines())


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default values whose repr embeds a memory address ("<function f at
    # 0x7f...>") change every process — the CI drift gate must compare
    # content, not ASLR
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _param_table(cls) -> str:
    """Spark Param table for estimator/model classes (the per-class
    parameter reference the Sphinx site renders from _param_mapping)."""
    try:
        inst = cls()
    except Exception:
        return ""
    params = getattr(inst, "params", None)
    if not params:
        return ""
    rows = []
    for p in params:
        try:
            default = (
                inst.getOrDefault(p) if inst.hasDefault(p) else "(unset)"
            )
        except Exception:
            default = "(unset)"
        doc = (p.doc or "").replace("|", "\\|").replace("\n", " ")
        rows.append(f"| `{p.name}` | `{default!r}` | {doc} |")
    if not rows:
        return ""
    return (
        "\n**Spark Params**\n\n| param | default | doc |\n|---|---|---|\n"
        + "\n".join(rows)
        + "\n"
    )


def _method_docs(cls) -> str:
    out = []
    for name, member in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if isinstance(member, property):
            out.append(f"#### `{name}` *(property)*\n\n"
                       + _clean_doc(member.__doc__) + "\n")
            continue
        fn = member
        if isinstance(member, (classmethod, staticmethod)):
            fn = member.__func__
        if not callable(fn):
            continue
        out.append(f"#### `{name}{_signature(fn)}`\n\n"
                   + _clean_doc(fn.__doc__) + "\n")
    return "\n".join(out)


def _module_doc(mod) -> str | None:
    """Module docstring, or the leading `#` comment block of the source
    (the house style documents modules in a comment header)."""
    if mod.__doc__:
        return mod.__doc__
    try:
        src = inspect.getsource(mod)
    except (OSError, TypeError):
        return None
    lines = []
    for line in src.splitlines():
        if line.startswith("#"):
            lines.append(line.lstrip("#").removeprefix(" "))
        elif line.strip() == "" and lines:
            break
        elif line.strip():
            break
    text = "\n".join(lines).strip()
    return text or None


def _public_members(mod):
    modname = mod.__name__
    # a facade module (spark_rapids_ml_tpu.classification) re-exports the
    # real definitions from models/<same>.py; both count as "defined
    # here", while Param mixins / typing imports / core plumbing pulled in
    # by the re-export do not
    own = {
        modname,
        modname.replace("spark_rapids_ml_tpu.", "spark_rapids_ml_tpu.models."),
    }
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    classes, funcs = [], []
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None:
            continue
        home = getattr(obj, "__module__", "")
        if not (home in own or home.startswith(modname + ".")):
            continue
        if inspect.isclass(obj):
            classes.append((n, obj))
        elif inspect.isfunction(obj):
            funcs.append((n, obj))
    return classes, funcs


def gen_module(modname: str) -> tuple[str, list[str]]:
    mod = importlib.import_module(modname)
    short = modname.split(".")[-1]
    classes, funcs = _public_members(mod)
    mod_doc = mod.__doc__
    if not mod_doc:
        # facade modules re-export from models/<name>.py; use its doc
        try:
            mod_doc = _module_doc(
                importlib.import_module(
                    modname.replace(
                        "spark_rapids_ml_tpu.", "spark_rapids_ml_tpu.models."
                    )
                )
            )
        except ImportError:
            mod_doc = None
    if not mod_doc:
        mod_doc = _module_doc(mod)
    lines = [f"# `{modname}`", "", _clean_doc(mod_doc), ""]
    toc = []
    for n, cls in classes:
        toc.append(f"- [`{n}`](#{_anchor(n)})")
    for n, fn in funcs:
        toc.append(f"- [`{n}()`](#{_anchor(n)})")
    lines += toc + [""]
    for n, cls in classes:
        lines += [
            f"## `{n}`",
            "",
            f"```python\n{modname}.{n}{_signature(cls)}\n```",
            "",
            _clean_doc(cls.__doc__),
            _param_table(cls),
            _method_docs(cls),
            "",
        ]
    for n, fn in funcs:
        lines += [
            f"## `{n}`",
            "",
            f"```python\n{modname}.{n}{_signature(fn)}\n```",
            "",
            _clean_doc(fn.__doc__),
            "",
        ]
    entries = [n for n, _ in classes] + [f"{n}()" for n, _ in funcs]
    return "\n".join(lines) + "\n", entries


def main() -> int:
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT, exist_ok=True)
    index = [
        "# API reference",
        "",
        "Generated by `docs/gen_api_docs.py` (run by `ci/test.sh`). One",
        "page per public module; estimator pages include the full Spark",
        "Param table with defaults.",
        "",
    ]
    total = 0
    for modname in MODULES:
        page, entries = gen_module(modname)
        short = modname.split(".")[-1]
        with open(os.path.join(OUT, f"{short}.md"), "w") as f:
            f.write(page)
        total += len(entries)
        shown = ", ".join(f"`{e}`" for e in entries[:8])
        more = "" if len(entries) <= 8 else f", … ({len(entries)} total)"
        index.append(f"- [{modname}]({short}.md) — {shown}{more}")
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"docs/api: {len(MODULES)} module pages, {total} documented symbols")
    return 0 if total else 1


if __name__ == "__main__":
    raise SystemExit(main())
