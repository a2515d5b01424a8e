#
# Telemetry exporters — the two formats production tooling already
# understands:
#
#   Chrome trace-event JSON   the recorded spans (tracing.py) as complete
#                             events, one track per thread, plus an
#                             instant-event track for the resilience
#                             markers (retries, injected faults, elastic
#                             recoveries, checkpoint resumes).  Loads
#                             directly in Perfetto (ui.perfetto.dev) or
#                             chrome://tracing.
#   Prometheus text format    every registry metric (counters, gauges —
#                             including the legacy dict views — and
#                             histograms) as `spark_rapids_ml_tpu_*`
#                             families.  `dump_prometheus()` renders the
#                             page; `start_http_server` serves it from a
#                             stdlib http endpoint gated by the
#                             `telemetry_port` conf (opt-in: 0 = off).
#
# A minimal text-format parser (`parse_prometheus`) rides along so tests
# and the CI smoke can round-trip the dump without a prometheus client
# dependency.
#
from __future__ import annotations

import json
import os
import re
import threading

from .locks import named_lock
from typing import Any, Dict, List, Optional, Tuple

# one label pair inside a sample's {...} body; values are quoted with
# \\ / \" / \n escapes per the exposition format
_RE_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')
_RE_ESCAPE = re.compile(r"\\(.)")
# OpenMetrics-style exemplar suffix (` # {labels} value timestamp`,
# end-anchored so an adversarial LABEL VALUE merely containing the
# shape cannot truncate a sample — inside a label its quotes are
# escaped, so the label-pair body below cannot match and the real
# sample value stays in place).  ANY exemplar labelset is recognized —
# our own dump writes `request_id=`, but foreign pages (federation
# output, other exporters) ship `trace_id=`-style exemplars and those
# must strip cleanly too, never leak into the sample value/labels.
_EXEMPLAR_BODY = r'(?:\w+="(?:[^"\\]|\\.)*"(?:,\w+="(?:[^"\\]|\\.)*")*)?'
_RE_EXEMPLAR = re.compile(
    r' # \{' + _EXEMPLAR_BODY + r'\} \S+ \S+$'
)
# capturing twin: the family-level parser keeps the exemplar (labels,
# value, timestamp) so merged fleet pages preserve request-id forensics
_RE_EXEMPLAR_CAP = re.compile(
    r' # \{(' + _EXEMPLAR_BODY + r')\} (\S+) (\S+)$'
)


def _unescape_one(m: "re.Match") -> str:
    c = m.group(1)
    return "\n" if c == "n" else c


def _parse_value(s: str):
    """Sample value as the exact number the dump wrote: integers stay
    int (counter sums across processes must be exact), everything else
    float."""
    try:
        return int(s)
    except ValueError:
        return float(s)

from .registry import REGISTRY, MetricsRegistry

# every exported family carries the library prefix so a shared scrape
# endpoint can't collide with the host application's metrics
PROM_PREFIX = "spark_rapids_ml_tpu_"

# synthetic Chrome-trace thread id for the instant-marker track: real
# thread ids are pthread handles and never reach this reserved value
MARKER_TID = 2**31 - 1


# ---------------------------------------------------------------------------
# Chrome trace events (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------


def chrome_trace(
    events: Optional[list] = None, run_id: Optional[str] = None
) -> Dict[str, Any]:
    """The recorded trace spans as a Chrome trace-event JSON object
    (`{"traceEvents": [...]}`).  `events` defaults to every thread's
    buffer (tracing.get_all_trace_events); `run_id` filters to one
    fit/transform run.  Spans become complete ("X") events on their
    recording thread's track; instant events (kind="instant") land on a
    dedicated "resilience markers" track so retries/recoveries stay
    visible at any zoom level.  Timestamps are absolute epoch
    microseconds, so traces from concurrent processes align."""
    from ..tracing import get_all_trace_events

    evs = events if events is not None else get_all_trace_events(run_id)
    if events is not None and run_id is not None:
        evs = [e for e in evs if e.run_id == run_id]
    pid = os.getpid()
    out: List[Dict[str, Any]] = []
    tids = {}
    for e in evs:
        args: Dict[str, Any] = {}
        if e.detail:
            args["detail"] = e.detail
        if getattr(e, "fields", None):
            args["fields"] = dict(e.fields)
        if e.run_id:
            args["run_id"] = e.run_id
        # the pod-global pass id (telemetry/fleet.py): the join key a
        # merged pod trace correlates cross-rank spans on
        if getattr(e, "pass_id", ""):
            args["pass_id"] = e.pass_id
        if getattr(e, "kind", "span") == "instant":
            out.append(
                {
                    "name": e.name,
                    "ph": "i",
                    "s": "p",  # process-scoped marker line
                    "ts": e.t0 * 1e6,
                    "pid": pid,
                    "tid": MARKER_TID,
                    "args": args,
                }
            )
        else:
            tids.setdefault(e.thread_id, None)
            out.append(
                {
                    "name": e.name,
                    "ph": "X",
                    "ts": e.t0 * 1e6,
                    "dur": max(e.seconds, 0.0) * 1e6,
                    "pid": pid,
                    "tid": e.thread_id,
                    "args": args,
                }
            )
    # track names: one per recording thread + the marker track
    meta = [
        {
            "name": "thread_name",
            "ph": "M",
            "pid": pid,
            "tid": MARKER_TID,
            "args": {"name": "resilience markers"},
        }
    ]
    for i, tid in enumerate(sorted(tids)):
        meta.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": f"thread-{i}" if i else "controller"},
            }
        )
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


def dump_chrome_trace(
    path: Optional[str] = None,
    events: Optional[list] = None,
    run_id: Optional[str] = None,
) -> str:
    """`chrome_trace` as a JSON string; also written to `path` when
    given (atomic tmp + replace, so a concurrent Perfetto load never
    sees a torn file)."""
    payload = json.dumps(chrome_trace(events, run_id))
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)
    return payload


# ---------------------------------------------------------------------------
# Prometheus text format
# ---------------------------------------------------------------------------


def _fmt_value(v: Any) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def _escape_label(v: str) -> str:
    """Prometheus exposition-format label escaping: backslash, quote,
    newline.  Without it a label value carrying a quote/comma breaks
    every consumer of the page (including our own parser)."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_labels(pairs: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    items = [f'{k}="{_escape_label(v)}"' for k, v in pairs]
    if extra:
        items.append(extra)
    return "{" + ",".join(items) + "}" if items else ""


def dump_prometheus(
    registry: Optional[MetricsRegistry] = None, exemplars: bool = False
) -> str:
    """Every registry metric in the Prometheus exposition text format
    (`# HELP` / `# TYPE` headers, `_bucket`/`_sum`/`_count` histogram
    series).  The legacy dict views (STAGE_COUNTS, CACHE_METRICS,
    RECOVERY_METRICS, ...) export as gauge families labeled by `key`, so
    `spark_rapids_ml_tpu_recovery{key="meshes_rebuilt"}` always equals
    `RECOVERY_METRICS["meshes_rebuilt"]`.

    `exemplars=True` appends each histogram labelset's recorded request
    ids to their `_bucket` lines in the OpenMetrics exemplar shape
    (` # {request_id="..."} value timestamp`) — opt-in because classic
    0.0.4 scrapers reject the syntax; `parse_prometheus` strips it
    either way.  The flight recorder's post-mortem bundles dump with
    exemplars on, so a latency bucket in the black box names the
    requests that landed in it."""
    reg = registry or REGISTRY
    if reg is REGISTRY:
        # fold the named locks' pending accounting into the lock_*
        # counter families first, so every scrape sees current numbers
        # (publication is deferred off the acquire hot path by design)
        from .locks import publish_lock_metrics

        publish_lock_metrics()
    lines: List[str] = []
    for m in reg.metrics():
        name = PROM_PREFIX + m.name
        if m.help:
            lines.append(f"# HELP {name} {m.help}")
        lines.append(f"# TYPE {name} {m.kind}")
        samples = m.samples()
        if m.kind == "histogram":
            for lk, h in samples.items():
                ex_by_bucket: Dict[int, Dict[str, Any]] = {}
                if exemplars:
                    for e in h.get("exemplars", ()):
                        for i, le in enumerate(m.buckets):
                            if e["value"] <= le:
                                ex_by_bucket[i] = e  # newest wins
                                break
                        else:
                            ex_by_bucket[len(m.buckets)] = e
                for i, (le, c) in enumerate(zip(m.buckets, h["buckets"])):
                    extra = 'le="%s"' % le
                    suffix = _fmt_exemplar(ex_by_bucket.get(i))
                    lines.append(
                        f"{name}_bucket{_fmt_labels(lk, extra)} {c}{suffix}"
                    )
                inf = 'le="+Inf"'
                suffix = _fmt_exemplar(ex_by_bucket.get(len(m.buckets)))
                lines.append(
                    f"{name}_bucket{_fmt_labels(lk, inf)} "
                    f"{h['count']}{suffix}"
                )
                lines.append(f"{name}_sum{_fmt_labels(lk)} "
                             f"{_fmt_value(h['sum'])}")
                lines.append(f"{name}_count{_fmt_labels(lk)} {h['count']}")
        else:
            for lk, v in samples.items():
                lines.append(f"{name}{_fmt_labels(lk)} {_fmt_value(v)}")
    return "\n".join(lines) + "\n"


def _fmt_exemplar(e: Optional[Dict[str, Any]]) -> str:
    if not e:
        return ""
    return (
        f' # {{request_id="{_escape_label(e["id"])}"}} '
        f"{_fmt_value(e['value'])} {round(e['t'], 3)}"
    )


def _parse_sample_line(
    line: str,
) -> Tuple[str, Tuple[Tuple[str, str], ...], str]:
    """One sample line -> (name, sorted label pairs, raw value string).
    Strips an OpenMetrics exemplar suffix when present, and tolerates
    the exposition format's OPTIONAL trailing timestamp (foreign pages
    — federation output, other exporters — emit `name{l} value ts`; the
    timestamp is dropped, never mistaken for the value).  Raises
    ValueError on malformed lines so a broken dump fails loudly."""
    line = _RE_EXEMPLAR.sub("", line)
    head, _, value = line.rpartition(" ")
    if not head:
        raise ValueError(f"malformed prometheus sample: {line!r}")
    if " " in head and (
        ("}" in head and not head.endswith("}")) or "{" not in head
    ):
        # the token we took as the value is a trailing timestamp: the
        # real value is the token before it (a head that still has a
        # space after its label block — or a label-less head with a
        # space — cannot be a bare metric name)
        head, _, value = head.rpartition(" ")
    labels: Tuple[Tuple[str, str], ...] = ()
    name = head
    if head.endswith("}"):
        name, _, rest = head.partition("{")
        body = rest[:-1]
        # escape-aware: values may contain \\, \" and \n (and
        # commas, which a naive split would sever)
        pairs = [
            (k, _RE_ESCAPE.sub(_unescape_one, v))
            for k, v in _RE_LABEL.findall(body)
        ]
        if body and not pairs:
            raise ValueError(f"malformed label in: {line!r}")
        labels = tuple(sorted(pairs))
    return name, labels, value


def parse_prometheus(
    text: str,
) -> Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float]:
    """Minimal text-format parser: `{(name, ((label, value), ...)): v}`.
    Enough to round-trip `dump_prometheus` in tests/CI without a
    prometheus client library; raises ValueError on malformed sample
    lines so a broken dump fails loudly."""
    out: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, labels, value = _parse_sample_line(line)
        out[(name, labels)] = float(value)
    return out


def parse_prometheus_families(text: str) -> Dict[str, Dict[str, Any]]:
    """Structured family-level parse — the exact round-trip the
    cross-process aggregator (telemetry/aggregate.py) stands on:

        {family: {"kind": counter|gauge|histogram|untyped,
                  "help": str,
                  "samples": {label_pairs: value}}}

    Histogram families reassemble their `_bucket`/`_sum`/`_count` series
    back into one value per labelset —
    `{"buckets": {le_str: count}, "sum": float, "count": int}` — keyed
    WITHOUT the `le` label, so bucket-wise merging is a dict walk.
    Escaped label values (backslash, quote, newline — and commas/spaces/
    braces, which need no escape but break naive splitters) round-trip
    byte-exactly; integer sample values stay `int` so counter sums
    across processes are exact.  OpenMetrics exemplars on `_bucket`
    lines are KEPT (`{"exemplars": [{"id", "value", "t"}, ...]}` beside
    the histogram sample, oldest first) so a fleet merge
    (telemetry/aggregate.py) preserves the request-id forensics instead
    of silently dropping them.  `render_families` is the inverse."""
    kinds: Dict[str, str] = {}
    helps: Dict[str, str] = {}
    raw: Dict[str, Dict[Tuple[Tuple[str, str], ...], Any]] = {}
    exemplars_raw: Dict[
        Tuple[str, Tuple[Tuple[str, str], ...]], List[Dict[str, Any]]
    ] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            if len(parts) == 4:
                kinds[parts[2]] = parts[3]
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            if len(parts) >= 3:
                helps[parts[2]] = parts[3] if len(parts) == 4 else ""
            continue
        if line.startswith("#"):
            continue
        ex = _RE_EXEMPLAR_CAP.search(line)
        name, labels, value = _parse_sample_line(line)
        raw.setdefault(name, {})[labels] = _parse_value(value)
        if ex is not None and name.endswith("_bucket"):
            # keep only request_id exemplars (the shape our dump writes
            # and render_families re-emits); foreign exemplar labelsets
            # were stripped from the sample above and are dropped here
            ex_labels = dict(_RE_LABEL.findall(ex.group(1)))
            rid = ex_labels.get("request_id")
            if rid is not None:
                base = tuple(p for p in labels if p[0] != "le")
                exemplars_raw.setdefault(
                    (name[:-len("_bucket")], base), []
                ).append({
                    "id": _RE_ESCAPE.sub(_unescape_one, rid),
                    "value": float(ex.group(2)),
                    "t": float(ex.group(3)),
                })
    out: Dict[str, Dict[str, Any]] = {}
    for fam, kind in kinds.items():
        entry: Dict[str, Any] = {"kind": kind, "help": helps.get(fam, "")}
        if kind == "histogram":
            samples: Dict[Tuple[Tuple[str, str], ...], Any] = {}
            for lk, v in raw.pop(fam + "_bucket", {}).items():
                le = dict(lk).get("le", "")
                base = tuple(p for p in lk if p[0] != "le")
                h = samples.setdefault(
                    base, {"buckets": {}, "sum": 0.0, "count": 0}
                )
                h["buckets"][le] = v
                exs = exemplars_raw.get((fam, base))
                if exs and "exemplars" not in h:
                    h["exemplars"] = sorted(exs, key=lambda e: e["t"])
            for lk, v in raw.pop(fam + "_sum", {}).items():
                samples.setdefault(
                    lk, {"buckets": {}, "sum": 0.0, "count": 0}
                )["sum"] = float(v)
            for lk, v in raw.pop(fam + "_count", {}).items():
                samples.setdefault(
                    lk, {"buckets": {}, "sum": 0.0, "count": 0}
                )["count"] = int(v)
            entry["samples"] = samples
        else:
            entry["samples"] = raw.pop(fam, {})
        out[fam] = entry
    # samples with no TYPE header (foreign pages): keep them, untyped
    for fam, samples in raw.items():
        out[fam] = {"kind": "untyped", "help": "", "samples": samples}
    return out


def render_families(families: Dict[str, Dict[str, Any]]) -> str:
    """`parse_prometheus_families`'s inverse: families back to the text
    exposition format (deterministic ordering: families as given,
    labelsets sorted), so merged pages are themselves scrapeable and
    re-parseable."""
    lines: List[str] = []
    for fam, entry in families.items():
        if entry.get("help"):
            lines.append(f"# HELP {fam} {entry['help']}")
        kind = entry.get("kind", "untyped")
        if kind != "untyped":
            lines.append(f"# TYPE {fam} {kind}")
        samples = entry.get("samples", {})
        if kind == "histogram":
            for lk in sorted(samples):
                h = samples[lk]
                les = sorted(
                    h["buckets"],
                    key=lambda s: float("inf") if s == "+Inf" else float(s),
                )
                # re-attach retained exemplars to their bucket lines
                # (newest per bucket wins, the dump_prometheus shape) so
                # merged pages keep the request-id forensics and still
                # re-parse through this module
                ex_by_le: Dict[str, Dict[str, Any]] = {}
                for e in h.get("exemplars", ()):
                    for le in les:
                        le_f = float("inf") if le == "+Inf" else float(le)
                        if e["value"] <= le_f:
                            ex_by_le[le] = e
                            break
                for le in les:
                    extra = f'le="{le}"'
                    suffix = _fmt_exemplar(ex_by_le.get(le))
                    lines.append(
                        f"{fam}_bucket{_fmt_labels(lk, extra)} "
                        f"{_fmt_value(h['buckets'][le])}{suffix}"
                    )
                lines.append(
                    f"{fam}_sum{_fmt_labels(lk)} {_fmt_value(h['sum'])}"
                )
                lines.append(
                    f"{fam}_count{_fmt_labels(lk)} {_fmt_value(h['count'])}"
                )
        else:
            for lk in sorted(samples):
                lines.append(
                    f"{fam}{_fmt_labels(lk)} {_fmt_value(samples[lk])}"
                )
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Opt-in stdlib HTTP endpoint (`telemetry_port` conf)
# ---------------------------------------------------------------------------

_server_lock = named_lock("telemetry_http")
_server = None


def start_http_server(
    port: int,
    registry: Optional[MetricsRegistry] = None,
    host: str = "127.0.0.1",
):
    """Serve `/metrics` (Prometheus text format) from a daemon-thread
    stdlib HTTP server on `port` (0 = ephemeral; read the bound port off
    the returned server's `.server_port`).  One server per process —
    repeat calls return the running one.  Binds LOOPBACK by default:
    the dump names datasets, staging sizes and failure activity, which
    must not leak to every network peer of a multi-tenant host — pass
    `host="0.0.0.0"` deliberately for a cluster-scraped deployment."""
    global _server
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    with _server_lock:
        if _server is not None:
            return _server
        reg = registry or REGISTRY

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - stdlib handler contract
                if self.path.split("?", 1)[0] not in ("/metrics", "/"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = dump_prometheus(reg).encode()
                self.send_response(200)
                # the full exposition-format content type: scrapers key
                # the parser off version AND charset (a bare text/plain
                # makes strict clients fall back to guessing)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam stderr
                pass

        srv = ThreadingHTTPServer((host, int(port)), _Handler)
        srv.daemon_threads = True
        t = threading.Thread(
            target=srv.serve_forever, name="telemetry-http", daemon=True
        )
        t.start()
        _server = srv
        from ..utils import get_logger

        get_logger("spark_rapids_ml_tpu.telemetry").info(
            f"telemetry endpoint: http://{host}:{srv.server_port}/metrics"
        )
        return srv


def stop_http_server() -> None:
    """Shut the endpoint down (tests; operator teardown).  Idempotent."""
    global _server
    with _server_lock:
        if _server is not None:
            _server.shutdown()
            _server.server_close()
            _server = None


def maybe_start_http_server():
    """Start the endpoint iff the `telemetry_port` conf is set (> 0) and
    no server is running yet — the cheap per-fit hook core.py calls.
    Never raises: an occupied port logs a warning instead of failing the
    fit it was meant to observe."""
    from ..config import get_config

    port = int(get_config("telemetry_port") or 0)
    if port <= 0 or _server is not None:
        return _server
    try:
        return start_http_server(port)
    except OSError as e:
        from ..utils import get_logger

        get_logger("spark_rapids_ml_tpu.telemetry").warning(
            f"telemetry endpoint on port {port} failed to start ({e}); "
            "metrics stay available via dump_prometheus()"
        )
        return None


__all__ = [
    "MARKER_TID",
    "PROM_PREFIX",
    "chrome_trace",
    "dump_chrome_trace",
    "dump_prometheus",
    "maybe_start_http_server",
    "parse_prometheus",
    "parse_prometheus_families",
    "render_families",
    "start_http_server",
    "stop_http_server",
]
