#
# Multi-host data path tests — the per-process parallel ingest split,
# the pass_complete cross-process reduction seam (parallel/context.py),
# and the 2-rank parity contract: with integer-representable data every
# partial sum is exact, so the wire reduce's rank-ordered fold must be
# BYTE-identical to a single-process pass over the same parquet file.
#
# The 2-rank tests stand only on the jax.distributed coordination
# service: the wire reduce backend is exactly what lets pods whose XLA
# backend has no cross-process collectives still fit with parallel
# ingest.
#
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# single-process units: ingest partitioning, keys, seam no-ops
# ---------------------------------------------------------------------------


def test_process_ingest_ranges_cover_exactly():
    from spark_rapids_ml_tpu.streaming import process_ingest_ranges

    for n_total, n_proc in [(1003, 2), (10, 4), (7, 8), (0, 3), (5, 1)]:
        ranges = process_ingest_ranges(n_total, n_proc)
        assert len(ranges) == n_proc
        # contiguous tiling of [0, n_total), balanced to within one row
        assert ranges[0][0] == 0 and ranges[-1][1] == n_total
        for (lo_a, hi_a), (lo_b, _) in zip(ranges, ranges[1:]):
            assert hi_a == lo_b
        sizes = [hi - lo for lo, hi in ranges]
        assert sum(sizes) == n_total
        assert max(sizes) - min(sizes) <= 1


def test_process_row_group_shares_cover_all_groups(tmp_path):
    import pandas as pd

    from spark_rapids_ml_tpu.fused import process_row_group_shares

    p = str(tmp_path / "g.parquet")
    X = np.arange(400 * 3, dtype=np.float32).reshape(400, 3)
    pd.DataFrame({"features": list(X)}).to_parquet(p, row_group_size=60)

    shares = process_row_group_shares(p, 2)
    assert shares is not None and len(shares) == 2
    flat = [g for sh in shares for g in sh]
    assert flat == list(range(7))  # 400/60 -> 7 groups, covered once
    assert all(sh == sorted(sh) for sh in shares)

    # fewer groups than processes / directory datasets: modulo fallback
    assert process_row_group_shares(p, 99) is None
    assert process_row_group_shares(str(tmp_path), 2) is None
    assert process_row_group_shares(p, 1) is None


def test_chunk_stream_key_carries_process_index(monkeypatch):
    import jax

    from spark_rapids_ml_tpu import streaming

    p = os.path.join(REPO, "README.md")  # any stat-able path
    key0 = streaming._chunk_stream_key(
        p, "features", (), None, None, 128, np.float32, None
    )
    assert key0 is not None and key0[3] == int(jax.process_index())
    monkeypatch.setattr(jax, "process_index", lambda: 3)
    key3 = streaming._chunk_stream_key(
        p, "features", (), None, None, 128, np.float32, None
    )
    assert key3[3] == 3 and key0 != key3


def test_reduce_seam_single_process_passthrough():
    from spark_rapids_ml_tpu.parallel.context import (
        allgather_bytes,
        broadcast_bytes,
        check_rank_agreement,
        content_fingerprint,
        cross_process_reduce_ready,
        reduce_blob_list,
        reduce_host_arrays,
    )

    arrays = {"a": np.arange(6, dtype=np.float64), "n": np.int64(7)}
    out = reduce_host_arrays(dict(arrays), "t")
    np.testing.assert_array_equal(out["a"], arrays["a"])
    assert allgather_bytes("t", b"payload") == [b"payload"]
    assert broadcast_bytes("t", b"root") == b"root"
    assert reduce_blob_list("t", b"blob") == [b"blob"]
    assert cross_process_reduce_ready()
    # agreement check is a no-op single-process (never raises)
    check_rank_agreement("t", content_fingerprint("t", arrays))


def test_content_fingerprint_is_layout_not_values():
    from spark_rapids_ml_tpu.parallel.context import content_fingerprint

    a = {"s1": np.zeros(4), "sw": np.zeros(())}
    b = {"s1": np.ones(4) * 9, "sw": np.ones(())}
    assert content_fingerprint("t", a) == content_fingerprint("t", b)
    assert content_fingerprint("t", a) != content_fingerprint("u", a)
    c = {"s1": np.zeros(5), "sw": np.zeros(())}
    assert content_fingerprint("t", a) != content_fingerprint("t", c)


def test_reinit_rereads_coordinator_address_from_config(monkeypatch):
    """A coordinator that restarted elsewhere publishes its new address
    via set_config; reinit_distributed must hand THAT address to the
    bootstrap, never the first call's cached value."""
    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import context

    seen = []
    monkeypatch.setattr(context, "shutdown_distributed", lambda: None)
    monkeypatch.setattr(
        context,
        "init_distributed",
        lambda coordinator_address=None, num_processes=None, process_id=None: (
            seen.append(coordinator_address) or True
        ),
    )
    set_config(coordinator_address="10.0.0.1:1234")
    try:
        assert context.reinit_distributed()
        set_config(coordinator_address="10.0.0.2:5678")
        assert context.reinit_distributed()
        # explicit argument still wins over config
        assert context.reinit_distributed(coordinator_address="10.9.9.9:1")
    finally:
        set_config(coordinator_address="")
    assert seen == ["10.0.0.1:1234", "10.0.0.2:5678", "10.9.9.9:1"]


def test_spill_dir_files_are_rank_distinct_and_restorable(tmp_path):
    import glob

    import jax

    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import device_cache as dc

    spill_dir = str(tmp_path / "spill")
    set_config(
        chunk_cache="on", chunk_cache_host_bytes=1,
        chunk_cache_spill_dir=spill_dir,
    )
    try:
        cache = dc.ChunkCache()

        def src():
            for i in range(3):
                yield (np.full((50, 4), i, np.float64), None)

        first = [np.array(x[0]) for x in cache.stream(("sp",), src)]
        files = glob.glob(os.path.join(spill_dir, "*.spill"))
        assert len(files) == 3
        # filenames embed the process index (+ pid): two pod ranks
        # sharing one spill dir can never clobber each other
        prefix = f"srmt-chunk-p{jax.process_index()}-{os.getpid()}-"
        assert all(os.path.basename(f).startswith(prefix) for f in files)
        # file-backed blobs leave the host budget entirely
        assert cache._host_total == 0 and cache._spill_disk_b > 0
        replay = [np.array(x[0]) for x in cache.stream(("sp",), src)]
        for a, b in zip(first, replay):
            np.testing.assert_array_equal(a, b)
        cache.clear()
        assert glob.glob(os.path.join(spill_dir, "*.spill")) == []
        assert cache._spill_disk_b == 0
    finally:
        set_config(
            chunk_cache="off", chunk_cache_host_bytes=2 * 1024**3,
            chunk_cache_spill_dir="",
        )


def test_spill_file_vanishing_degrades_to_source_replay(tmp_path):
    import glob

    from spark_rapids_ml_tpu.config import set_config
    from spark_rapids_ml_tpu.parallel import device_cache as dc

    spill_dir = str(tmp_path / "spill2")
    set_config(
        chunk_cache="on", chunk_cache_host_bytes=1,
        chunk_cache_spill_dir=spill_dir,
    )
    try:
        cache = dc.ChunkCache()

        def src():
            yield (np.full((50, 4), 3.0, np.float64), None)

        list(cache.stream(("gone",), src))
        for f in glob.glob(os.path.join(spill_dir, "*.spill")):
            os.unlink(f)
        # a vanished spill file is noted as a checksum failure and the
        # stream falls back to the source — data stays correct
        before = dc.CHUNK_METRICS["checksum_failures"]
        out = [np.array(x[0]) for x in cache.stream(("gone",), src)]
        assert len(out) == 1
        np.testing.assert_array_equal(out[0], np.full((50, 4), 3.0))
        assert dc.CHUNK_METRICS["checksum_failures"] == before + 1
    finally:
        set_config(
            chunk_cache="off", chunk_cache_host_bytes=2 * 1024**3,
            chunk_cache_spill_dir="",
        )


def test_baseline_builder_wire_roundtrip():
    from spark_rapids_ml_tpu.monitor.fingerprint import (
        BaselineBuilder,
        builder_from_bytes,
        builder_to_bytes,
    )

    rng = np.random.default_rng(5)
    X = rng.integers(0, 16, size=(300, 4)).astype(np.float64)
    b = BaselineBuilder(4)
    b.update(X)
    blob = builder_to_bytes(b)
    back = builder_from_bytes(blob)
    # the round trip is exact: re-serializing yields identical bytes
    assert builder_to_bytes(back) == blob
    assert back.n == b.n
    with pytest.raises(ValueError):
        builder_from_bytes(b"XXXX" + blob[4:])


def test_sketch_wire_roundtrip_bit_exact():
    from spark_rapids_ml_tpu.stats.sketches import (
        quantile_init,
        quantile_merge,
        quantile_update,
        sketch_from_bytes,
        sketch_to_bytes,
    )

    rng = np.random.default_rng(6)
    X = rng.integers(0, 100, size=(200, 3)).astype(np.float64)
    valid = np.ones(200, bool)
    k = 64
    st = quantile_update(quantile_init(3, k), X, valid, k)
    blob = sketch_to_bytes("quantile", st)
    kind, back = sketch_from_bytes(blob)
    assert kind == "quantile"
    assert sketch_to_bytes("quantile", back) == blob
    # merging a deserialized state is bit-identical to merging the live one
    other = quantile_update(quantile_init(3, k), X[:50], valid[:50], k)
    m1 = quantile_merge(st, other, k)
    m2 = quantile_merge(back, other, k)
    for key in m1:
        np.testing.assert_array_equal(m1[key], m2[key])


# ---------------------------------------------------------------------------
# 2-rank workers (coordination service only — no XLA collectives)
# ---------------------------------------------------------------------------


def _launch(script_body: str, nproc: int, tmp_path, args=(), timeout=600):
    script = tmp_path / "worker.py"
    script.write_text(script_body)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    outfile = tmp_path / f"out_{nproc}.json"
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["SRMT_REPO"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nproc), str(port),
             str(outfile), *[str(a) for a in args]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(nproc)
    ]
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                try:
                    q.communicate(timeout=10)
                except Exception:
                    pass
            raise
        errs.append((p.returncode, err))
    for rc, err in errs:
        assert rc == 0, err[-6000:]
    with open(outfile) as f:
        return json.load(f)


_SEAM_WORKER = textwrap.dedent(
    """
    import json, os, sys
    pid, nproc, port, outfile = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config
    set_config(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
        process_id=pid, multiproc_reduce="wire",
        multiproc_reduce_timeout_s=120.0,
    )
    assert init_distributed()
    import jax
    assert jax.process_count() == nproc

    from spark_rapids_ml_tpu.parallel.context import (
        RankDivergenceError, allgather_bytes, broadcast_bytes,
        check_rank_agreement, content_fingerprint, reduce_blob_list,
        reduce_host_arrays, resolve_reduce_backend,
    )
    assert resolve_reduce_backend() == "wire"

    # allgather: every rank sees every payload, in rank order
    got = allgather_bytes("hello", f"rank{pid}".encode())
    assert got == [f"rank{r}".encode() for r in range(nproc)], got

    # broadcast: non-root passes None and still receives root's payload
    bc = broadcast_bytes("uid", b"the-uid" if pid == 0 else None)
    assert bc == b"the-uid", bc

    # wire reduce: rank-ordered f64 fold, exact for integer partials
    part = {
        "s1": (np.arange(5, dtype=np.float64) + 1) * (pid + 1),
        "n": np.int64(100 + pid),
    }
    out = reduce_host_arrays(dict(part), "seam")
    want_s1 = sum(
        (np.arange(5, dtype=np.float64) + 1) * (r + 1) for r in range(nproc)
    )
    assert out["s1"].tobytes() == want_s1.tobytes()
    assert int(out["n"]) == sum(100 + r for r in range(nproc))
    assert out["n"].dtype == np.int64, out["n"].dtype

    # sketch states allgathered and merged in rank order: every rank
    # computes the identical merged bytes
    from spark_rapids_ml_tpu.stats.sketches import (
        quantile_init, quantile_merge, quantile_update, sketch_from_bytes,
        sketch_to_bytes,
    )
    k = 128
    rows = np.arange(200, dtype=np.float64).reshape(100, 2)
    lo, hi = (0, 50) if pid == 0 else (50, 100)
    mine = quantile_update(
        quantile_init(2, k), rows[lo:hi], np.ones(hi - lo, bool), k
    )
    blobs = reduce_blob_list("sk", sketch_to_bytes("quantile", mine))
    assert len(blobs) == nproc
    states = [sketch_from_bytes(b)[1] for b in blobs]
    merged = states[0]
    for s in states[1:]:
        merged = quantile_merge(merged, s, k)
    # no compaction at n <= k: the rank-ordered merge reproduces the
    # sequential single-stream fold byte-for-byte
    ref = quantile_update(
        quantile_init(2, k), rows, np.ones(100, bool), k
    )
    for key in ref:
        assert np.asarray(merged[key]).tobytes() == np.asarray(
            ref[key]
        ).tobytes(), key
    merged_hex = sketch_to_bytes("quantile", merged).hex()
    hexes = {
        b.decode() for b in allgather_bytes("mh", merged_hex.encode())
    }
    assert len(hexes) == 1, "ranks merged to different sketch bytes"

    # divergence MUST fail loudly: ranks present different layouts
    bad = {"s1": np.zeros(5 + pid)}
    try:
        check_rank_agreement("bad", content_fingerprint("bad", bad))
        raise SystemExit("divergence check did not fire")
    except RankDivergenceError as e:
        assert "bad" in str(e) and len(e.fingerprints) == nproc

    # ...and a matching layout passes right after on the same tag space
    check_rank_agreement("good", content_fingerprint("good", {"x": np.ones(3)}))

    if pid == 0:
        with open(outfile, "w") as f:
            json.dump({"ok": True, "merged_hex": merged_hex}, f)
    """
)


def test_two_rank_wire_seam(tmp_path):
    out = _launch(_SEAM_WORKER, 2, tmp_path, timeout=420)
    assert out["ok"] is True


_PARITY_WORKER = textwrap.dedent(
    """
    import json, os, sys
    pid, nproc, port, outfile, ppath = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
        sys.argv[5],
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={4 // nproc}"
    )
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config
    set_config(
        multiproc_reduce="wire", pca_solver="full",
        summarizer_sketch_k=1024, summarizer_frequent_k=32,
        fused_parquet_readers=1,
    )
    if nproc > 1:
        set_config(
            coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
            process_id=pid,
        )
        assert init_distributed()
    import jax
    assert jax.process_count() == nproc
    assert len(jax.local_devices()) == 4 // nproc

    def hexd(a):
        return np.ascontiguousarray(np.asarray(a, np.float64)).tobytes().hex()

    out = {}
    d = 6
    CHUNK = 128  # divisible by both local device counts (4 and 2)

    # --- parallel ingest coverage: each rank decodes ONLY its share ----
    from spark_rapids_ml_tpu.fused import (
        iter_parquet_chunks, process_row_group_shares,
    )
    rows_seen = 0
    for cX, cy, cw in iter_parquet_chunks(
        ppath, "features", (), None, None, CHUNK, np.float64
    ):
        rows_seen += int(cX.shape[0]) if cw is None else int((cw > 0).sum())
    if nproc > 1:
        from spark_rapids_ml_tpu.parallel.context import allgather_bytes
        counts = [
            int.from_bytes(b, "little")
            for b in allgather_bytes(
                "cov", int(rows_seen).to_bytes(8, "little")
            )
        ]
        assert sum(counts) == 500, counts
        assert all(c > 0 for c in counts), counts  # real decode scaling
        shares = process_row_group_shares(ppath, nproc)
        assert shares is not None and len(shares) == nproc
    else:
        assert rows_seen == 500, rows_seen
    out["rows_seen_local"] = rows_seen

    # --- fused linreg: one pass, one pass_complete reduction ----------
    from spark_rapids_ml_tpu.fused import fused_linreg_stats, fused_pca_stats

    def producer(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), "label", None, CHUNK, np.float64,
                prep=prep,
            ),
            prep,
        )

    lin = fused_linreg_stats(producer, d, np.float64)
    out["linreg"] = {k: hexd(v) for k, v in sorted(lin.items())}

    def producer_x(n_dev):
        prep = {"s": 0.0, "iv": []}
        return (
            iter_parquet_chunks(
                ppath, "features", (), None, None, CHUNK, np.float64,
                prep=prep,
            ),
            prep,
        )

    pca = fused_pca_stats(producer_x, d, 2, np.float64)
    assert pca.pop("kind") == "moments"
    out["pca"] = {k: hexd(v) for k, v in sorted(pca.items())}

    # --- Summarizer.describe(): engine pass + sketch wire merge -------
    from spark_rapids_ml_tpu.stats.summarizer import Summarizer
    df = Summarizer.describe(ppath, features_col="features")
    out["describe_index"] = [str(i) for i in df.index]
    out["describe"] = hexd(df.to_numpy())

    # --- host/min-max/int device programs through the same seam -------
    from spark_rapids_ml_tpu.stats.engine import run_programs
    r = run_programs(
        ["frequent_items", "distinct_count"], ppath,
        features_col="features", dtype=np.float64,
    )
    st = r["frequent_items"]["state"]
    out["frequent"] = {k: hexd(st[k]) for k in sorted(st)}
    out["distinct"] = [float(x) for x in np.atleast_1d(
        r["distinct_count"]["distinct"]
    )]

    # --- kmeans_sample reservoir through the sharded engine ingest ----
    # each rank decodes only its row-group share; chunks carry GLOBAL
    # first-row offsets (iter_parquet_chunks with_offsets), so every
    # rank fills the same reservoir slots a single-process scan fills
    ks = run_programs(
        ["kmeans_sample"], ppath, features_col="features",
        dtype=np.float64,
        opts={"kmeans_sample": {"stride": 7, "cap": (500 - 1) // 7 + 1}},
    )["kmeans_sample"]
    out["kmeans_sample"] = {
        "X": hexd(ks["X"]), "w": hexd(ks["w"]), "count": int(ks["count"]),
    }

    # --- streaming k-means fit: global-slot seeding, merged sample ----
    # integer-valued f64 rows keep the Lloyd sums/counts exact, so the
    # centers must come out byte-identical at any process count; cost
    # accumulates in f32 chunk order and is NOT compared
    from spark_rapids_ml_tpu.streaming import kmeans_streaming_fit
    km = kmeans_streaming_fit(
        ppath, "features", (), None, k=4, seed=7, max_iter=8,
        dtype=np.float64, chunk_rows=CHUNK, init_rows=150,
    )
    out["kmeans_centers"] = hexd(km["centers"])
    out["kmeans_n_iter"] = int(km["n_iter"])

    if pid == 0:
        with open(outfile, "w") as f:
            json.dump(out, f)
    """
)


def test_two_process_fused_parity_byte_identical(tmp_path):
    """THE pod-parity contract: 2-process parallel ingest + wire-reduced
    fused PCA / linreg / describe() must be byte-identical to the
    single-process fit.  Integer-valued float64 data makes every partial
    sum exactly representable, so any difference is a real data-path
    divergence, never float noise."""
    import pandas as pd

    rng = np.random.default_rng(17)
    X = rng.integers(0, 16, size=(500, 6)).astype(np.float64)
    beta = np.array([1.0, 0.0, -1.0, 2.0, 0.0, 1.0])
    y = X @ beta  # integer-valued
    ppath = str(tmp_path / "parity.parquet")
    pd.DataFrame({"features": list(X), "label": y}).to_parquet(
        ppath, row_group_size=80  # 7 groups >= 2 processes
    )

    single = _launch(_PARITY_WORKER, 1, tmp_path, args=(ppath,))
    multi = _launch(_PARITY_WORKER, 2, tmp_path, args=(ppath,))

    assert single["rows_seen_local"] == 500
    assert multi["rows_seen_local"] < 500  # rank 0 decoded only its share
    assert multi["linreg"] == single["linreg"]
    assert multi["pca"] == single["pca"]
    assert multi["describe_index"] == single["describe_index"]
    assert multi["describe"] == single["describe"]
    assert multi["frequent"] == single["frequent"]
    assert multi["distinct"] == single["distinct"]
    assert multi["kmeans_sample"] == single["kmeans_sample"]
    assert multi["kmeans_centers"] == single["kmeans_centers"]
    assert multi["kmeans_n_iter"] == single["kmeans_n_iter"]
