#
# chipbench/manifest.py: BENCHMARK.json and the files it names.  Whatever
# belongs to one configuration, one traffic mix, one estimator family, one
# data model or one per-layer metric is a file of its own, found here by its
# name, so a later PR adds files and entries and edits none.
#
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_manifest(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def adapter(name: str):
    """chipbench/estimators/<name>.py"""
    return _load_module("estimators", name)


def data_model(name: str):
    """chipbench/data_models/<name>.py: what a cell's rows are drawn from."""
    return _load_module("data_models", name)


def data_of(cfg: dict, adapter) -> dict:
    """The configuration file's `data` block: its data model's name and
    parameters.  A file without one draws the first cells' rows, a hidden
    direction labelled as its estimator family reads labels."""
    return cfg.get("data") or {"model": "hidden_direction", "labels": adapter.LABELS}


def data_problems(data: dict) -> list:
    """Why no rows can be drawn from `data`: no file for its model, a key
    the model needs is missing, a value it cannot draw.  [] when sound."""
    name = data.get("model")
    try:
        model = data_model(str(name))
    except FileNotFoundError as e:
        return [f"data model {name!r} has no file ({e})"]
    missing = [k for k in model.NEEDS if k not in data]
    if missing:
        return [f"data model {name}: the data block lacks {missing}"]
    try:
        model.check(data)
    except ValueError as e:
        return [f"data model {name}: {e}"]
    return []


def reader(metric: str):
    """chipbench/layer_metrics/<metric>.py, whose read(ctx) gives the
    metric's value, or None where it finds nothing to read."""
    return _load_module("layer_metrics", metric).read


def traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def cell(manifest: dict, workload: str) -> dict:
    """The workload entry with its configuration and traffic files read."""
    found = [w for w in manifest["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(has: {[w['name'] for w in manifest['workloads']]})")
    w = dict(found[0])
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    w["config_file"] = _load_json(os.path.join(ROOT, entry["file"]))
    w["traffic_file"] = traffic(w["traffic"])
    return w


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of `group` ('end_to_end' | 'per_layer') that `workload`
    reports: those that list it, and those that list no cells at all."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


# -- the rules a manifest has to meet before the driver reads it -------------

_NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
_UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def problems(manifest: dict) -> list:
    """Every way `manifest` breaks the benchmark's contract that can be
    seen without a run: names, units, files found by name, and that every
    per-layer metric lists its cells and each of them reports the
    end-to-end metric it moves (the rule PR 22 was refused over)."""
    import re

    out = []
    cells = {w["name"]: w for w in manifest.get("workloads", [])}
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    e2e = {m["name"]: m for m in manifest.get("end_to_end", [])}
    layer = manifest.get("per_layer", [])

    def reports(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    names = [x["name"] for group in ("configs", "workloads", "end_to_end", "per_layer")
             for x in manifest.get(group, [])]
    for name in names + [w["traffic"] for w in cells.values()] + [
            k for c in configs.values() for k in c.get("reduced", [])]:
        if not re.match(_NAME, name):
            out.append(f"name {name!r} has a character that is not allowed")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in manifest.get(group, [])]
        if len(seen) != len(set(seen)):
            out.append(f"{group}: a name appears twice")
    if len(set(e2e) | {m["name"] for m in layer}) != len(e2e) + len(layer):
        out.append("an end-to-end and a per-layer metric share a name")
    for m in list(e2e.values()) + layer:
        if not re.match(_UNIT, m.get("unit", "")):
            out.append(f"metric {m['name']}: unit {m.get('unit')!r} is not allowed")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better is {m.get('better')!r}")
        if m.get("source") not in _SOURCES:
            out.append(f"metric {m['name']}: source {m.get('source')!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                out.append(f"metric {m['name']} lists {cell!r}, which is no cell")
    if "setup_s" not in e2e:
        out.append("no end-to-end metric setup_s")
    for m in e2e.values():
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end metric {m['name']}: source {m['source']!r}")
        if not 0 < m.get("bound", 0) <= 0.1:
            out.append(f"end-to-end metric {m['name']}: bound {m.get('bound')!r}")
    for m in layer:
        if "workloads" not in m:
            out.append(f"per-layer metric {m['name']} lists no cells: it would be "
                       "read as reported in every cell")
        if m.get("moves") not in e2e:
            out.append(f"per-layer metric {m['name']} moves {m.get('moves')!r}, "
                       "which is no end-to-end metric")
            continue
        for cell in (m.get("workloads") or cells):
            if cell in cells and not reports(e2e[m["moves"]], cell):
                out.append(f"per-layer metric {m['name']} is reported on {cell}, "
                           f"where {m['moves']}, which it should move, is not")
        try:
            reader(m["name"])
        except (FileNotFoundError, AttributeError) as e:
            out.append(f"per-layer metric {m['name']}: no reader ({e})")
    pairs = set()
    for name, w in cells.items():
        if w["config"] not in configs:
            out.append(f"cell {name}: no configuration {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"cell {name}: its configuration and traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
        if w.get("chips") not in (1, 4):
            out.append(f"cell {name}: chips {w.get('chips')!r}")
        if not 1 <= len(w.get("why", "")) <= 200:
            out.append(f"cell {name}: why has {len(w.get('why', ''))} characters")
        if not os.path.isfile(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")):
            out.append(f"cell {name}: no traffic file for {w['traffic']!r}")
        mine = [m for m in e2e.values() if reports(m, name)]
        if len(mine) < 2 or not any(m["name"] == "setup_s" for m in mine):
            out.append(f"cell {name} reports setup_s and one more end-to-end metric?")
        if not any(reports(m, name) for m in layer):
            out.append(f"cell {name} reports no per-layer metric")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} of {len(cells)} cells ask for four chips")
    files = set()
    for name, c in configs.items():
        if not any(w["config"] == name for w in cells.values()):
            out.append(f"configuration {name} is used by no cell")
        path = c.get("file", "")
        if path in files or not any(path.startswith(p + "/") for p in manifest["paths"]):
            out.append(f"configuration {name}: file {path!r}")
        files.add(path)
        full = os.path.join(ROOT, path)
        if not os.path.isfile(full):
            out.append(f"configuration {name}: {path} is not there")
            continue
        held = _load_json(full)
        try:
            family = adapter(held["adapter"])
        except (FileNotFoundError, KeyError) as e:
            out.append(f"configuration {name}: no estimator file ({e})")
        else:
            out += [f"configuration {name}: {p}"
                    for p in data_problems(data_of(held, family))]
        if sorted(held.get("reduced", [])) != sorted(c.get("reduced", [])):
            out.append(f"configuration {name}: `reduced` differs from its file's")
        for key in ("why", "source"):
            if not 1 <= len(c.get(key, "")) <= 200:
                out.append(f"configuration {name}: {key} has {len(c.get(key, ''))} characters")
    if not 1 <= manifest.get("run_seconds", 0) <= 51:
        out.append(f"run_seconds {manifest.get('run_seconds')!r}")
    return out
