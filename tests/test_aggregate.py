#
# Cross-process metric aggregation (telemetry/aggregate.py) and the
# exact Prometheus text round-trip it stands on
# (exporters.parse_prometheus_families / render_families): counters sum
# EXACTLY across processes, gauges keep per-process series, histograms
# merge bucket-wise, and a dead process is reported ABSENT, never zero.
#
import os
import socket
import subprocess
import sys
import textwrap
import threading

import pytest

from spark_rapids_ml_tpu.telemetry.aggregate import (
    counter_total,
    dump_merged,
    merge_pages_from_files,
    merge_prometheus,
    scrape_endpoints,
)
from spark_rapids_ml_tpu.telemetry.exporters import (
    dump_prometheus,
    parse_prometheus,
    parse_prometheus_families,
    render_families,
)
from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# label values chosen to break naive parsers: escapes (backslash, quote,
# newline) plus the characters the exposition format does NOT escape but
# a split(",")/split("=") parser severs on
_NASTY = [
    'plain',
    'with spaces and =equals',
    'comma,separated,values',
    'brace}and{brace',
    'quote"inside',
    'back\\slash',
    'new\nline',
    'trailing backslash\\',
    ' # {request_id="fake"} 1 2',  # an exemplar-shaped label value
]


def _registry_with_nasty() -> MetricsRegistry:
    reg = MetricsRegistry()
    g = reg.gauge("nasty_gauge", "adversarial labels")
    for i, v in enumerate(_NASTY):
        g.set(i, key=v)
    c = reg.counter("nasty_counter", "help with spaces")
    c.inc(7, label=_NASTY[-1], action="oom")
    h = reg.histogram("nasty_hist", "hist", buckets=(0.1, 1.0))
    h.observe(0.05, key=_NASTY[4])
    h.observe(2.5, key=_NASTY[4])
    return reg


# ---------------------------------------------------------------------------
# parser round-trips (the satellite the aggregator depends on)
# ---------------------------------------------------------------------------


def test_adversarial_label_values_round_trip_exactly():
    page = dump_prometheus(_registry_with_nasty())
    fams = parse_prometheus_families(page)
    got = {
        dict(lk)["key"]
        for lk in fams["spark_rapids_ml_tpu_nasty_gauge"]["samples"]
    }
    assert got == set(_NASTY)
    # render -> parse is a fixed point (the merge output must itself be
    # scrapeable)
    assert parse_prometheus_families(render_families(fams)) == fams


def test_histogram_family_reassembles_buckets_sum_count():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 3.0):
        h.observe(v, model="m")
    fams = parse_prometheus_families(dump_prometheus(reg))
    sample = fams["spark_rapids_ml_tpu_lat"]["samples"][(("model", "m"),)]
    assert sample["buckets"] == {"0.1": 1, "1.0": 3, "+Inf": 4}
    assert sample["count"] == 4
    assert sample["sum"] == pytest.approx(4.05)


def test_integer_values_stay_int():
    reg = MetricsRegistry()
    reg.counter("c", "h").inc(2**53 + 1)  # past float53 exactness
    fams = parse_prometheus_families(dump_prometheus(reg))
    v = fams["spark_rapids_ml_tpu_c"]["samples"][()]
    assert isinstance(v, int) and v == 2**53 + 1


def test_exemplar_suffix_is_stripped_not_misparsed():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "h", buckets=(1.0,))
    h.observe(0.5, exemplar="req-x", model="m")
    with_ex = dump_prometheus(reg, exemplars=True)
    assert 'request_id="req-x"' in with_ex
    assert parse_prometheus(with_ex) == parse_prometheus(
        dump_prometheus(reg)
    )
    # the family parser KEEPS the exemplars (the merge preserves them);
    # everything else — buckets, sums, counts, labels — parses
    # identically to the exemplar-free page
    fams_ex = parse_prometheus_families(with_ex)
    sample = fams_ex["spark_rapids_ml_tpu_lat"]["samples"][
        (("model", "m"),)
    ]
    assert [e["id"] for e in sample.pop("exemplars")] == ["req-x"]
    assert fams_ex == parse_prometheus_families(dump_prometheus(reg))


def test_merge_preserves_bounded_exemplars_round_trip():
    """Satellite: a fleet merge keeps up to MERGE_MAX_EXEMPLARS
    request-id exemplars per histogram labelset (newest by timestamp),
    and the merged page re-renders them so a re-parse still carries the
    forensics — merged scrapes stop silently dropping request ids."""
    from spark_rapids_ml_tpu.telemetry.aggregate import (
        MERGE_MAX_EXEMPLARS,
        dump_merged,
    )

    pages = {}
    for proc in ("hostA", "hostB"):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "h", buckets=(0.01, 0.1, 1.0))
        for i in range(6):
            h.observe(
                0.02 * (i + 1), exemplar=f"req-{proc}-{i}", model="m"
            )
        pages[proc] = dump_prometheus(reg, exemplars=True)
    merged = merge_prometheus(pages)
    sample = merged["spark_rapids_ml_tpu_lat"]["samples"][
        (("model", "m"),)
    ]
    ids = [e["id"] for e in sample["exemplars"]]
    assert 0 < len(ids) <= MERGE_MAX_EXEMPLARS
    assert any(i.startswith("req-hostA") for i in ids)
    assert any(i.startswith("req-hostB") for i in ids)
    # counts merged exactly alongside (exemplars never perturb samples)
    assert sample["count"] == 12
    # the rendered merged page carries them and re-parses
    text = dump_merged(merged)
    assert "req-host" in text
    re_sample = parse_prometheus_families(text)[
        "spark_rapids_ml_tpu_lat"
    ]["samples"][(("model", "m"),)]
    assert re_sample["count"] == 12
    assert re_sample["exemplars"], "render dropped the exemplars"
    # a second-tier merge (pod level) stays bounded
    tier2 = merge_prometheus({"pod": text, "pod2": text})
    s2 = tier2["spark_rapids_ml_tpu_lat"]["samples"][(("model", "m"),)]
    assert len(s2["exemplars"]) <= MERGE_MAX_EXEMPLARS


def test_foreign_exemplar_labels_stripped_not_misparsed():
    """A foreign page's exemplar with a non-request_id labelset
    (trace_id, span_id — other exporters' shapes) must strip cleanly:
    the real bucket count survives, no phantom labelset appears, and
    the foreign exemplar is dropped (only request_id exemplars are
    retained for re-rendering)."""
    page = (
        "# TYPE x histogram\n"
        'x_bucket{le="1.0"} 42 # {trace_id="abc"} 0.93 1700000000\n'
        'x_bucket{le="+Inf"} 42\n'
        "x_sum 39.0\n"
        "x_count 42\n"
    )
    fams = parse_prometheus_families(page)
    sample = fams["x"]["samples"][()]
    assert sample["buckets"]["1.0"] == 42
    assert sample["count"] == 42
    assert "exemplars" not in sample
    assert list(fams["x"]["samples"]) == [()]
    # the simple parser strips it identically
    assert parse_prometheus(page)[("x_bucket", (("le", "1.0"),))] == 42.0


def test_trailing_timestamp_tolerated_not_misparsed():
    # the exposition format allows an OPTIONAL trailing timestamp on
    # sample lines (federation output, foreign exporters); it must be
    # dropped, never mistaken for the value or folded into the name
    page = (
        'http_requests_total{code="200"} 1027 1395066363000\n'
        "bare_metric 7 1395066363000\n"
        'spaced{key="x y"} 3.5 1395066363000\n'
    )
    fams = parse_prometheus_families(page)
    assert fams["http_requests_total"]["samples"][
        (("code", "200"),)
    ] == 1027
    assert fams["bare_metric"]["samples"][()] == 7
    assert fams["spaced"]["samples"][(("key", "x y"),)] == 3.5
    flat = parse_prometheus(page)
    assert flat[("http_requests_total", (("code", "200"),))] == 1027


def test_malformed_sample_raises():
    with pytest.raises(ValueError):
        parse_prometheus("just_a_name_no_value\n")


# ---------------------------------------------------------------------------
# merge semantics
# ---------------------------------------------------------------------------


def _page(retries: int, solver_it: int, lat_obs) -> str:
    reg = MetricsRegistry()
    reg.counter("retries_total", "h").inc(
        retries, label="fit_kernel", action="oom"
    )
    reg.gauge("solver_iteration", "h").set(solver_it, solver="lbfgs")
    h = reg.histogram("lat", "h", buckets=(0.1, 1.0))
    for v in lat_obs:
        h.observe(v, model="m")
    return dump_prometheus(reg)


def test_counters_sum_exactly_across_processes():
    merged = merge_prometheus({
        "rank0": _page(3, 5, [0.05]),
        "rank1": _page(9, 2, [0.5]),
    })
    fam = "spark_rapids_ml_tpu_retries_total"
    total = counter_total(merged, fam, label="fit_kernel", action="oom")
    assert total == 12 and isinstance(total, int)
    # no process label on counter series — it is ONE fleet number
    (lk,) = merged[fam]["samples"]
    assert "process" not in dict(lk)


def test_gauges_keep_per_process_series():
    merged = merge_prometheus({
        "rank0": _page(1, 5, []),
        "rank1": _page(1, 2, []),
    })
    samples = merged["spark_rapids_ml_tpu_solver_iteration"]["samples"]
    by_proc = {dict(lk)["process"]: v for lk, v in samples.items()}
    assert by_proc == {"rank0": 5, "rank1": 2}


def test_histograms_merge_bucket_wise_preserving_total_count():
    merged = merge_prometheus({
        "rank0": _page(1, 1, [0.05, 0.5]),
        "rank1": _page(1, 1, [0.5, 3.0]),
    })
    h = merged["spark_rapids_ml_tpu_lat"]["samples"][(("model", "m"),)]
    assert h["buckets"] == {"0.1": 1, "1.0": 3, "+Inf": 4}
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(4.05)
    # the merged page re-parses (aggregation tiers stack)
    assert parse_prometheus_families(dump_merged(merged))


def test_tiered_merge_namespaces_process_never_duplicates():
    # host pages -> pod page -> fleet page: the second-tier merge must
    # NAMESPACE the existing process label (pod1/hostA), not append a
    # duplicate `process` pair (an invalid page; dict(lk) drops one)
    pod1 = dump_merged(merge_prometheus({
        "hostA": _page(1, 5, []), "hostB": _page(1, 2, []),
    }))
    pod2 = dump_merged(merge_prometheus({"hostC": _page(1, 9, [])}))
    fleet = merge_prometheus({"pod1": pod1, "pod2": pod2})
    samples = fleet["spark_rapids_ml_tpu_solver_iteration"]["samples"]
    for lk in samples:
        names = [k for k, _ in lk]
        assert names.count("process") == 1, lk
    by_proc = {dict(lk)["process"]: v for lk, v in samples.items()}
    assert by_proc == {
        "pod1/hostA": 5, "pod1/hostB": 2, "pod2/hostC": 9,
    }
    # counters still sum exactly through the tiers...
    assert counter_total(
        fleet, "spark_rapids_ml_tpu_retries_total"
    ) == 3
    # ...and the fleet page itself renders valid and re-parses
    assert parse_prometheus_families(dump_merged(fleet))


def test_family_missing_from_one_process_merges_over_reporters():
    reg = MetricsRegistry()
    reg.counter("only_here", "h").inc(4)
    merged = merge_prometheus({
        "a": dump_prometheus(reg),
        "b": _page(1, 1, []),
    })
    assert merged["spark_rapids_ml_tpu_only_here"]["samples"][()] == 4


def test_merge_pages_from_files(tmp_path):
    p0, p1 = tmp_path / "r0.prom", tmp_path / "r1.prom"
    p0.write_text(_page(2, 1, []))
    p1.write_text(_page(5, 1, []))
    merged = merge_pages_from_files({"r0": str(p0), "r1": str(p1)})
    assert counter_total(
        merged, "spark_rapids_ml_tpu_retries_total"
    ) == 7


# ---------------------------------------------------------------------------
# the scraper: live endpoints merge, dead processes are ABSENT
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_scrape_merges_live_and_reports_dead_absent():
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    page = _page(6, 3, [0.5])

    class _H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = page.encode()
            self.send_response(200)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), _H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    dead_port = _free_port()
    try:
        res = scrape_endpoints(
            {
                "alive": f"http://127.0.0.1:{srv.server_port}/metrics",
                "dead": f"http://127.0.0.1:{dead_port}/metrics",
            },
            timeout_s=5.0,
        )
    finally:
        srv.shutdown()
        srv.server_close()
    assert set(res.pages) == {"alive"}
    assert set(res.absent) == {"dead"} and res.absent["dead"]
    # the dead process contributes NOTHING — not zeros: the counter is
    # exactly the live process's value and no gauge series names it
    fam = "spark_rapids_ml_tpu_retries_total"
    assert counter_total(res.merged, fam) == 6
    gs = res.merged["spark_rapids_ml_tpu_solver_iteration"]["samples"]
    assert {dict(lk)["process"] for lk in gs} == {"alive"}
    assert parse_prometheus_families(res.dump())


def test_scrape_real_telemetry_endpoint():
    """End-to-end over the real `/metrics` endpoint machinery: the
    scraper consumes what exporters.start_http_server serves (incl. the
    versioned charset content type)."""
    import urllib.request

    from spark_rapids_ml_tpu.telemetry.exporters import (
        start_http_server,
        stop_http_server,
    )

    stop_http_server()
    reg = MetricsRegistry()
    reg.counter("retries_total", "h").inc(2, label="x", action="oom")
    srv = start_http_server(0, registry=reg)
    try:
        url = f"http://127.0.0.1:{srv.server_port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as resp:
            assert resp.headers["Content-Type"] == (
                "text/plain; version=0.0.4; charset=utf-8"
            )
        res = scrape_endpoints({"p0": url})
        assert not res.absent
        assert counter_total(
            res.merged, "spark_rapids_ml_tpu_retries_total"
        ) == 2
    finally:
        stop_http_server()


# ---------------------------------------------------------------------------
# two real processes (jax-free subprocesses; runs everywhere)
# ---------------------------------------------------------------------------

_PROC = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, sys.argv[2])
    from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry
    from spark_rapids_ml_tpu.telemetry.exporters import dump_prometheus
    reg = MetricsRegistry()
    n = int(sys.argv[1])
    reg.counter("retries_total", "h").inc(
        n, label="fit_kernel", action="transient"
    )
    reg.gauge("device_bytes_in_use", "h").set(1000 + n, device="0")
    sys.stdout.write(dump_prometheus(reg))
    """
)


def test_two_process_pages_sum_exactly():
    pages = {}
    for rank, n in (("rank0", 3), ("rank1", 8)):
        out = subprocess.run(
            [sys.executable, "-c", _PROC, str(n), REPO],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        pages[rank] = out.stdout
    merged = merge_prometheus(pages)
    assert counter_total(
        merged, "spark_rapids_ml_tpu_retries_total",
        label="fit_kernel", action="transient",
    ) == 11
    gs = merged["spark_rapids_ml_tpu_device_bytes_in_use"]["samples"]
    assert {dict(lk)["process"]: v for lk, v in gs.items()} == {
        "rank0": 1003, "rank1": 1008,
    }


# ---------------------------------------------------------------------------
# the 2-rank jax.distributed probe (pod parity; skips where the jaxlib
# build has no cross-process CPU collectives)
# ---------------------------------------------------------------------------

_RANK = textwrap.dedent(
    """
    import os, sys
    pid, nproc, port, outdir = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config

    set_config(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
        retry_backoff_s=0.01,
        retry_jitter=0.0,
    )
    assert init_distributed()

    # a real fit on the 2-rank mesh with ONE injected transient retry
    # per rank: the per-rank registry counts it, the controller merges
    from spark_rapids_ml_tpu.resilience import fault_inject
    from spark_rapids_ml_tpu.classification import LogisticRegression

    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 8)).astype(np.float64)
    y = (X[:, 0] > 0).astype(np.float64)
    lo, hi = (0, 200) if pid == 0 else (200, 400)
    with fault_inject("fit_kernel", "timeout", times=1):
        set_config(dispatch_deadline_s=30.0)
        LogisticRegression(maxIter=5).fit((X[lo:hi], y[lo:hi]))

    from spark_rapids_ml_tpu.telemetry.exporters import dump_prometheus
    with open(os.path.join(outdir, f"rank{pid}.prom"), "w") as f:
        f.write(dump_prometheus())
    """
)


def test_two_rank_distributed_retries_sum_exactly(tmp_path):
    """The ROADMAP-item-1 CI seam: two real jax.distributed ranks each
    run a fit with one injected retryable fault and dump their
    registries; the merged page's `retries_total` is the EXACT sum of
    the per-rank pages."""
    script = tmp_path / "rank.py"
    script.write_text(_RANK)
    port = _free_port()
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["SRMT_REPO"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), "2", str(port),
             str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, err[-4000:]
    paths = {
        f"rank{i}": str(tmp_path / f"rank{i}.prom") for i in range(2)
    }
    per_rank = []
    fam = "spark_rapids_ml_tpu_retries_total"
    for p in paths.values():
        fams = parse_prometheus_families(open(p).read())
        per_rank.append(sum(fams[fam]["samples"].values()))
    assert all(n >= 1 for n in per_rank), per_rank
    merged = merge_pages_from_files(paths)
    assert counter_total(merged, fam) == sum(per_rank)
    assert counter_total(
        merged, fam, label="fit_kernel", action="transient"
    ) == 2
