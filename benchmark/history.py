#
# Bench history — the longitudinal memory the BENCH_*.json artifacts
# never had.  Each bench run's payload (`bench.py` `_payload()`:
# {"metric", "value", "unit", "vs_baseline", "extra": {...}}) is
# NORMALIZED into flat per-section records and APPENDED to a JSONL
# history file, one line per (run, section):
#
#   {"run_id": "bench-...", "ts": 1754280000.0, "platform": "tpu x8",
#    "section": "pca", "metrics": {"pca_1Mx128_fit_sec": 1.51, ...}}
#
# Only numeric metrics are kept (config strings, error strings and the
# embedded `*_telemetry` dicts stay in the raw artifact); appends are
# idempotent per (run_id, section) so bench.py's per-section flushes
# never duplicate records.  `benchmark/compare.py` consumes this file to
# gate regressions against the median of the last k runs.
#
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_HISTORY = "BENCH_HISTORY.jsonl"

# extra-key prefix -> section.  First match wins; keys matching no
# prefix (platform, host_loadavg_*, total_budget_s, ...) are run-level
# metadata, not section metrics.
_SECTION_PREFIXES: Tuple[Tuple[str, str], ...] = (
    ("cv_", "cv_cached"),
    ("ann_", "ann"),
    ("ivfflat_", "ann"),
    ("ivfpq_", "ann"),
    ("cagra_", "ann"),
    ("knn_", "knn"),
    ("dbscan_", "dbscan"),
    # drift monitor (bench.py `drift` section): serving-side fold
    # overhead (us/row, lower-better), detection latency (sec), and the
    # shifted/clean score separation (informational)
    ("drift_", "drift"),
    ("epoch_cache_", "epoch_cache"),
    ("fused_", "fused_pca"),
    ("kmeans_", "kmeans"),
    ("logreg_", "logreg"),
    ("pca_", "pca"),
    ("rf_", "rf"),
    ("refconfig_", "refconfig"),
    # closed-loop serving control plane (bench.py `serving_control`
    # section): mixed-priority QPS, spike shed fraction, and hands-off
    # brownout recovery time.  MUST precede the broader `serving_`
    # prefix — first startswith match wins
    ("serving_control_", "serving_control"),
    # hundreds-of-models scale bench (bench.py `serving_scale` section):
    # mixed-priority QPS across >=200 pinned models with a background
    # fused fit, worst-model p99, interactive drops, pipelined-vs-
    # serialized speedup.  Same MUST-precede rule as serving_control_
    ("serving_scale_", "serving_scale"),
    ("serving_", "serving"),
    ("staging_", "staging"),
    ("streaming_", "streaming"),
    # statistic-program engine (bench.py `summarize` section): the fused
    # multi-statistic pass timings + fused-vs-sequential speedup; the
    # `_sec`/`_per_sec`/`_speedup_x`/`_overlap_fraction` suffixes pick
    # up the standard compare.py direction rules
    ("summarize_", "summarize"),
    # multi-host data path (bench.py `multiproc` section): 1p vs 2p
    # sharded-ingest throughput, the `_scaling_x` ratio (higher-better
    # in compare.py), and the priced pass_complete wire reduction
    ("multiproc_", "multiproc"),
    ("ingest_", "streaming"),
    ("umap_", "umap"),
    # progress observatory (bench.py `utilization` section): named-lock
    # overhead us/acquire, hang-doctor tick cost, and serving QPS with
    # the observatory ON vs OFF (`_observatory_speedup_x` gates the
    # within-noise-of-1.0 acceptance)
    ("utilization_", "utilization"),
    # pod observatory (bench.py `pod_observatory` section): the
    # cross-rank trace merge cost in seconds and the per-pass straggler
    # bookkeeping in us/pass — both lower-better via the standard
    # `_seconds` / `_report_us` suffix rules
    ("pod_observatory_", "pod_observatory"),
)

# run-level numeric context worth trending as its own pseudo-section
_HOST_KEYS = ("device_put_mb_s",)


def section_of(key: str) -> Optional[str]:
    """The bench section an extra key belongs to (None for run-level
    metadata)."""
    for prefix, section in _SECTION_PREFIXES:
        if key.startswith(prefix):
            return section
    if key in _HOST_KEYS:
        return "host"
    return None


def _numeric(v: Any) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    if v != v or v in (float("inf"), float("-inf")):  # NaN/Inf
        return None
    return float(v)


def run_id_of(payload: Dict[str, Any]) -> str:
    """The run id riding in the payload (`extra.bench_run_id`, stamped
    by bench.py), or a content-derived fallback for artifacts that
    predate the stamp."""
    rid = str(payload.get("extra", {}).get("bench_run_id", "") or "")
    if rid:
        return rid
    import hashlib

    h = hashlib.blake2b(
        json.dumps(payload, sort_keys=True, default=str).encode(),
        digest_size=8,
    )
    return f"bench-{h.hexdigest()}"


def normalize_run(
    payload: Dict[str, Any],
    run_id: Optional[str] = None,
    ts: Optional[float] = None,
) -> List[Dict[str, Any]]:
    """Flatten one bench payload into per-section records.  The headline
    (`value`/`vs_baseline`) lands in the `logreg` section next to the
    `logreg_*` extra keys; `*_error` strings and non-numeric values are
    dropped (they live in the raw artifact)."""
    extra = dict(payload.get("extra", {}) or {})
    rid = run_id or run_id_of(payload)
    ts = float(ts if ts is not None else time.time())
    platform = str(extra.get("platform", "") or "")
    sections: Dict[str, Dict[str, float]] = {}
    v = _numeric(payload.get("value"))
    if v is not None and v > 0:
        sections.setdefault("logreg", {})["logreg_rows_per_sec"] = v
    vb = _numeric(payload.get("vs_baseline"))
    if vb is not None and vb > 0:
        sections.setdefault("logreg", {})["logreg_vs_baseline"] = vb
    for key, raw in extra.items():
        if key.endswith("_error") or key.endswith("_telemetry"):
            continue
        sec = section_of(key)
        if sec is None:
            continue
        val = _numeric(raw)
        if val is None:
            continue
        sections.setdefault(sec, {})[key] = val
    return [
        {
            "run_id": rid,
            "ts": round(ts, 3),
            "platform": platform,
            "section": sec,
            "metrics": metrics,
        }
        for sec, metrics in sorted(sections.items())
        if metrics
    ]


def load_history(path: str) -> List[Dict[str, Any]]:
    """Every parseable record in the JSONL history, file order (=
    chronological: the file is append-only).  Corrupt lines are skipped
    — a torn write from a killed bench run must not wedge the
    comparator forever."""
    out: List[Dict[str, Any]] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if (
                isinstance(rec, dict)
                and rec.get("run_id")
                and rec.get("section")
                and isinstance(rec.get("metrics"), dict)
            ):
                out.append(rec)
    return out


def append_records(records: List[Dict[str, Any]], path: str) -> int:
    """Append records not already present (by (run_id, section)).
    Returns how many were appended."""
    if not records:
        return 0
    seen = {(r["run_id"], r["section"]) for r in load_history(path)}
    fresh = [
        r for r in records if (r["run_id"], r["section"]) not in seen
    ]
    if not fresh:
        return 0
    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    # ONE O_APPEND os.write for the whole batch: concurrent bench runs
    # sharing a history file and a SIGTERM
    # handler re-entering mid-flush interleave at write boundaries, not
    # mid-line — a buffered line-by-line append could tear records,
    # which load_history would then drop silently
    blob = "".join(
        json.dumps(r, sort_keys=True) + "\n" for r in fresh
    ).encode()
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, blob)
    finally:
        os.close(fd)
    return len(fresh)


def append_run(
    payload: Dict[str, Any],
    path: str,
    run_id: Optional[str] = None,
    ts: Optional[float] = None,
) -> int:
    """Normalize + append one bench payload.  Idempotent per
    (run_id, section): bench.py calls this after every completed section
    (the partial-flush cadence) — later calls only add sections that
    completed since."""
    return append_records(normalize_run(payload, run_id, ts), path)


def runs_in_order(
    history: List[Dict[str, Any]],
) -> List[str]:
    """Distinct run ids in first-appearance (chronological) order."""
    seen: List[str] = []
    for rec in history:
        rid = rec["run_id"]
        if rid not in seen:
            seen.append(rid)
    return seen


__all__ = [
    "DEFAULT_HISTORY",
    "append_records",
    "append_run",
    "load_history",
    "normalize_run",
    "run_id_of",
    "runs_in_order",
    "section_of",
]
