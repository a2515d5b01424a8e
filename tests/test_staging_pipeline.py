#
# Pipelined per-device staging engine (parallel/mesh.py) — byte-exact
# parity with the legacy serial path for every RowStager layout, the
# depth=1 serial fallback, engine eligibility (single-process row-sharded
# targets only), the stage_parquet ingest wiring, and what the engine's
# win rests on (prep on its own thread, every piece put once), read from
# the spans it records on the multi-device CPU mesh.
#
import numpy as np
import pytest

import jax

import spark_rapids_ml_tpu.parallel.mesh as mesh_mod
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.parallel.mesh import (
    RowStager,
    ShardedRowWriter,
    _writer_devices,
    assemble_rows_chunked,
    get_mesh,
)


def _staged() -> dict:
    """The last staging's own record: its `staging` fact, read from this
    thread's trace buffer."""
    from spark_rapids_ml_tpu.tracing import last_fact

    return last_fact("staging")


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


@pytest.fixture
def force_pipelined(monkeypatch):
    """Route even tiny test arrays through the engine (production gates on
    _PIPELINED_MIN_BYTES)."""
    monkeypatch.setattr(mesh_mod, "_FORCE_PIPELINED", True)


def _host(arr) -> np.ndarray:
    return np.asarray(jax.device_get(arr))


# ---------------------------------------------------------------------------
# byte-exact parity with the serial path
# ---------------------------------------------------------------------------


class _Applied:
    """A piece's `applied` token that remembers having been waited for."""

    def __init__(self, token, waited: list) -> None:
        self._token, self._waited = token, waited

    def block_until_ready(self):
        self._waited.append(self)
        return self._token.block_until_ready()


def _rows_as(X: np.ndarray, stored: str) -> np.ndarray:
    """The same rows as the caller might hold them: C-ordered, F-ordered,
    or every other row and column of a wider array (a strided view)."""
    if stored == "F":
        return np.asfortranarray(X)
    if stored == "strided":
        wide = np.zeros((2 * X.shape[0], 2 * X.shape[1]), X.dtype)
        wide[::2, ::2] = X
        return wide[::2, ::2]
    return np.ascontiguousarray(X)


@pytest.mark.parametrize("stored", ["C", "F", "strided"])
@pytest.mark.parametrize("n,d,src_dt,out_dt", [
    (10_000, 37, np.float64, np.float32),   # cast fused into the gather
    (10_000, 37, np.float32, np.float32),
    (4_096, 16, np.float64, np.float64),    # f64 end-to-end
    (999, 5, np.float32, np.float32),       # ragged tail vs shard grid
    (256, 3, np.float32, np.float32),       # minimum bucket
])
def test_stage_parity_all_layouts(n, d, src_dt, out_dt, stored, num_workers,
                                  force_pipelined):
    """Pipelined staging is byte-identical to the serial path for the
    interleaved AND contiguous layouts at every mesh size, whether a
    piece is a view of the caller's rows (C order, the target dtype,
    consecutive rows) or a gathered copy (everything else)."""
    rng = np.random.default_rng(n + d)
    X = _rows_as(rng.standard_normal((n, d)).astype(src_dt), stored)
    m = get_mesh(num_workers)
    for interleave in (None, False):
        st = RowStager(n, m, interleave=interleave)
        serial = _host(st._stage_serial(X, np.dtype(out_dt)))
        staged = st.stage(X, out_dt)
        assert np.array_equal(serial, _host(staged))
        # the staged array must land row-sharded like the serial path
        from jax.sharding import NamedSharding

        from spark_rapids_ml_tpu.parallel.mesh import data_pspec

        want = NamedSharding(m, data_pspec(2))
        assert staged.sharding.is_equivalent_to(want, 2)
        # round trip through the layout: original rows in original order
        assert np.array_equal(
            st.fetch(staged), X.astype(out_dt)[: st.n_valid]
        )


@pytest.mark.parametrize("interleave", [None, False])
@pytest.mark.parametrize("stored", ["C", "F", "strided"])
@pytest.mark.parametrize("src_dt", [np.float32, np.float64])
@pytest.mark.parametrize("n_dev", [1, 8])
def test_piece_is_a_view_exactly_when_stored_as_needed(
    n_dev, src_dt, stored, interleave, force_pipelined, monkeypatch
):
    """The producer hands over a view of the caller's rows exactly when
    they are C-contiguous, of the target dtype and consecutive (one
    device, or the contiguous layout), and a new array otherwise;
    `pieces_viewed` counts them."""
    set_config(staging_chunk_bytes=16 * 1024)  # several pieces a device
    n, d = 6_000, 16
    X = _rows_as(
        np.random.default_rng(7).standard_normal((n, d)).astype(src_dt),
        stored,
    )
    st = RowStager(n, get_mesh(n_dev), interleave=interleave)
    pieces = []
    real = mesh_mod.run_staging_pipeline

    def recording(writer, producer, **kw):
        def tee():
            for item in producer:
                pieces.append(item[2])
                yield item

        return real(writer, tee(), **kw)

    monkeypatch.setattr(mesh_mod, "run_staging_pipeline", recording)
    staged = st.stage(X, np.float32)
    consecutive = n_dev == 1 or not st._interleave
    viewed = src_dt == np.float32 and stored == "C" and consecutive
    assert len(pieces) == _staged()["pieces"] > n_dev
    assert [np.shares_memory(p, X) for p in pieces] == [viewed] * len(pieces)
    assert _staged()["pieces_viewed"] == (len(pieces) if viewed else 0)
    assert np.array_equal(st.fetch(staged), X.astype(np.float32))
    if not viewed:
        # copied pieces share a few buffers, however many pieces there are
        assert all(p.base is not None for p in pieces)
        buffers = {id(p.base) for p in pieces}
        assert len(buffers) <= 2 + mesh_mod._MAX_INFLIGHT_PIECES + 2
        assert len(buffers) < len(pieces)


def test_piece_pool_waits_for_the_piece_last_put_from_a_buffer():
    """A pooled buffer is written again only after the token of the
    piece last put from it was waited for; a buffer handed out and never
    reported put is not reused (the piece gets an array of its own)."""
    waited = []
    pool = mesh_mod._PiecePool(2, (4, 3), np.float32)
    n = len(pool._bufs)
    assert n == 2 + mesh_mod._MAX_INFLIGHT_PIECES + 2
    X = np.arange(300, dtype=np.float64).reshape(100, 3)
    first = []
    for k in range(n):
        piece = pool.gather(X, 4 * k, 1, 4)
        assert np.array_equal(piece, X[4 * k : 4 * k + 4])
        first.append(piece)
        pool.note_put(piece, _Applied(jax.numpy.zeros(()), waited))
    assert not waited  # every buffer was new: nothing to wait for
    again = pool.gather(X, 50, 2, 3)  # the first buffer's turn, 3 rows
    assert len(waited) == 1 and np.shares_memory(again, first[0])
    assert np.array_equal(again, X[50:56:2])
    assert again.dtype == np.float32 and again.flags.c_contiguous
    # the second buffer's piece is reported put; the third's is not
    for k in range(1, n):
        pool.note_put(first[k], _Applied(jax.numpy.zeros(()), waited))
    pool._state[2] = pool._OUT
    second = pool.gather(X, 0, 1, 4)
    own = pool.gather(X, 8, 1, 4)
    assert np.shares_memory(second, first[1]) and len(waited) == 2
    assert not any(np.shares_memory(own, b) for b in pool._bufs)
    assert np.array_equal(own, X[8:12])


def _as_one_of_two_processes(st: RowStager) -> RowStager:
    """Send `st.stage` down the multi-process path in this one process:
    rank 0 owning every shard of the mesh (one block, no bucketing)."""
    st.n_proc = 2
    st.block_sizes = np.array([st.local_padded], np.int64)
    return st


@pytest.mark.parametrize("multi_process", [False, True])
def test_caller_may_overwrite_rows_once_stage_returns(
    multi_process, force_pipelined, monkeypatch
):
    """Pieces are views of the caller's array, which the transfers read:
    `stage` returns only after the last piece has been applied, so NaNs
    written over `X` the moment it returns reach no staged row.  (On the
    CPU the last transfer loses that race once in a few runs, so the
    wait itself is checked too: every piece's token was waited for.)"""
    set_config(staging_chunk_bytes=256 * 1024)
    n, d = 40_000, 32
    X = np.random.default_rng(11).standard_normal((n, d)).astype(np.float32)
    want = X.copy()
    st = RowStager(n, get_mesh(1), bucketing=False)
    if multi_process:
        st = _as_one_of_two_processes(st)
    tokens, waited = [], []
    real = mesh_mod._shard_update_fns

    def spied(shape, dtype_str, device):
        mk, upd = real(shape, dtype_str, device)

        def upd_spied(buf, piece, lo):
            buf, applied = upd(buf, piece, lo)
            tokens.append(_Applied(applied, waited))
            return buf, tokens[-1]

        return mk, upd_spied

    monkeypatch.setattr(mesh_mod, "_shard_update_fns", spied)
    staged = st.stage(X, np.float32)
    X[:] = np.nan
    assert _staged()["label"] == ("stage_mp" if multi_process else "stage")
    assert _staged()["pieces"] > 4 * mesh_mod._MAX_INFLIGHT_PIECES
    assert _staged()["pieces_viewed"] == _staged()["pieces"]
    assert len(tokens) == _staged()["pieces"]
    assert all(any(t is w for w in waited) for t in tokens)
    assert np.array_equal(_host(staged)[:n], want)


def test_stage_parity_1d_labels_f64(num_workers, force_pipelined):
    """f64 label vectors (float32_inputs=False) stage byte-identically."""
    rng = np.random.default_rng(0)
    y = rng.standard_normal(10_000)
    m = get_mesh(num_workers)
    st = RowStager(10_000, m)
    serial = _host(st._stage_serial(y, np.dtype(np.float64)))
    assert np.array_equal(serial, _host(st.stage(y, np.float64)))


@pytest.mark.parametrize("n,d,src_dt,out_dt", [
    (1, 8, np.float32, np.float32),       # one serving request row
    (13, 8, np.float64, np.float32),      # cast fused into the slice
    (500, 16, np.float32, np.float32),    # bucketed + interleaved
    (300, 6, np.float64, np.float64),     # f64 end-to-end
])
def test_small_direct_parity(n, d, src_dt, out_dt, num_workers):
    """The small-batch direct fast path (`_stage_small_direct` — per-
    device slices + one device_put per shard, no padded host copy, no
    jitted update programs) is byte-identical to the serial path for
    both layouts; `staging_small_direct=off` restores the legacy path.
    The serving layer's micro-batches depend on this gate."""
    rng = np.random.default_rng(n * d)
    X = rng.standard_normal((n, d)).astype(src_dt)
    m = get_mesh(num_workers)
    for interleave in (None, False):
        st = RowStager(n, m, interleave=interleave)
        assert X.nbytes < mesh_mod._PIPELINED_MIN_BYTES  # gate actually hit
        serial = _host(st._stage_serial(X, np.dtype(out_dt)))
        direct = st._stage_small_direct(
            X, np.dtype(out_dt),
            mesh_mod.NamedSharding(m, mesh_mod.data_pspec(2)),
            _writer_devices(
                mesh_mod.NamedSharding(m, mesh_mod.data_pspec(2)),
                (st.local_padded, d),
            ),
        )
        assert np.array_equal(serial, _host(direct))
        # the production gate routes stage() through the fast path...
        staged = st.stage(X, out_dt)
        assert np.array_equal(serial, _host(staged))
        assert np.array_equal(st.fetch(staged), X.astype(out_dt)[:n])
        # ...and the conf turns it back off (parity must hold regardless)
        set_config(staging_small_direct=False)
        try:
            assert np.array_equal(serial, _host(st.stage(X, out_dt)))
        finally:
            set_config(staging_small_direct=True)


def test_small_direct_1d_mask_parity(num_workers):
    """1-D companions (masks/labels/fold ids) take the fast path too."""
    rng = np.random.default_rng(3)
    y = rng.standard_normal(700)
    m = get_mesh(num_workers)
    st = RowStager(700, m)
    serial = _host(st._stage_serial(y, np.dtype(np.float32)))
    assert np.array_equal(serial, _host(st.stage(y, np.float32)))


def test_depth_one_serial_fallback(force_pipelined):
    """staging_pipeline_depth=1 runs the engine without the producer
    thread — identical bytes, no overlap accounting."""
    set_config(staging_pipeline_depth=1)
    rng = np.random.default_rng(1)
    X = rng.standard_normal((5_000, 24)).astype(np.float32)
    m = get_mesh(4)
    st = RowStager(5_000, m)
    serial = _host(st._stage_serial(X, np.dtype(np.float32)))
    assert np.array_equal(serial, _host(st.stage(X, np.float32)))
    assert _staged()["depth"] == 1
    assert _staged()["overlap_ratio"] == 0.0


def test_stage_metrics_populated(force_pipelined):
    rng = np.random.default_rng(2)
    X = rng.standard_normal((8_192, 8)).astype(np.float32)
    st = RowStager(8_192, get_mesh(8))
    from spark_rapids_ml_tpu import tracing

    with tracing.run_context(prefix="stage-test") as run_id:
        st.stage(X, np.float32)
    staged = tracing.last_fact("staging", run_id=run_id)
    for key in ("bytes", "seconds", "mb_per_s", "overlap_ratio", "pieces",
                "pieces_viewed", "depth", "n_dev", "label"):
        assert key in staged, key
    # the prep and put seconds are the run's spans, one of each per piece
    spans = [e.name for e in tracing.get_all_trace_events(run_id)]
    assert spans.count("stage_prep") == spans.count("stage_put") == (
        staged["pieces"]
    )
    # padding never travels: transferred bytes == valid rows only
    assert _staged()["bytes"] == X.size * 4
    assert _staged()["n_dev"] == 8


def test_chunked_pieces_respect_budget(force_pipelined):
    """staging_chunk_bytes bounds one prepared host piece, so a shard
    stages in multiple pieces when the budget is small."""
    set_config(staging_chunk_bytes=64 * 1024)  # 64 KiB -> many pieces
    rng = np.random.default_rng(3)
    X = rng.standard_normal((16_384, 32)).astype(np.float32)
    m = get_mesh(4)
    st = RowStager(16_384, m)
    serial = _host(st._stage_serial(X, np.dtype(np.float32)))
    assert np.array_equal(serial, _host(st.stage(X, np.float32)))
    assert _staged()["pieces"] > 4  # more than one piece per device


# ---------------------------------------------------------------------------
# sparse chunked densify + assemble_dense_chunks routing
# ---------------------------------------------------------------------------


def test_sparse_chunked_densify_parity(num_workers):
    sp = pytest.importorskip("scipy.sparse")
    from jax.sharding import NamedSharding, PartitionSpec

    from spark_rapids_ml_tpu.data import assemble_dense_chunks

    X = sp.random(5_000, 64, density=0.05, format="csr",
                  dtype=np.float32, random_state=1)
    m = get_mesh(num_workers)
    n_pad = 5_120
    sh = NamedSharding(m, PartitionSpec("data", None))
    out = assemble_dense_chunks(X, n_pad, np.float32, 512,
                                out_shardings=sh)
    ref = np.zeros((n_pad, 64), np.float32)
    ref[:5_000] = X.toarray()
    assert np.array_equal(_host(out), ref)


def test_stage_sparse_matches_dense_stage(force_pipelined):
    sp = pytest.importorskip("scipy.sparse")

    X = sp.random(3_000, 48, density=0.08, format="csr",
                  dtype=np.float32, random_state=2)
    m = get_mesh(4)
    st = RowStager(3_000, m, interleave=False)
    dense_staged = _host(st.stage(X.toarray(), np.float32))
    sparse_staged = _host(st.stage_sparse(X, np.float32))
    assert np.array_equal(dense_staged, sparse_staged)


# ---------------------------------------------------------------------------
# eligibility: the engine only takes targets it can decompose
# ---------------------------------------------------------------------------


def test_writer_multi_process_row_sharded_stays_eligible(monkeypatch):
    """Multi-process staging is first-class now (PR 17): a row-sharded
    target keeps its GLOBAL writer device list (one owner per shard in
    row order — ShardedRowWriter materializes buffers only for the
    addressable ones), while an UNSHARDED target — which has no
    meaningful multi-process assembly — still falls back to serial."""
    from jax.sharding import NamedSharding, PartitionSpec

    m = get_mesh(4)
    sh = NamedSharding(m, PartitionSpec("data", None))
    assert _writer_devices(sh, (512, 8)) is not None
    monkeypatch.setattr(mesh_mod.jax, "process_count", lambda: 2)
    devs = _writer_devices(sh, (512, 8))
    assert devs is not None and len(devs) == 4  # global, row-ordered
    assert _writer_devices(None, (512, 8)) is None  # unsharded: serial
    # the writer itself assembles correctly with the count patched (all
    # four devices are addressable in this single-process test run)
    w = ShardedRowWriter((512, 8), np.float32, sh)
    w.write(0, np.ones((512, 8), np.float32))
    assert np.array_equal(_host(w.finish()), np.ones((512, 8), np.float32))
    pieces = [(0, np.ones((512, 8), np.float32))]
    out = assemble_rows_chunked((512, 8), np.float32, iter(pieces),
                                out_shardings=sh)
    assert np.array_equal(_host(out), np.ones((512, 8), np.float32))


def test_writer_rejects_replicated_sharding():
    from jax.sharding import NamedSharding, PartitionSpec

    m = get_mesh(4)
    repl = NamedSharding(m, PartitionSpec())
    assert _writer_devices(repl, (512, 8)) is None
    # column sharding is not row-decomposable either
    col = NamedSharding(m, PartitionSpec(None, "data"))
    assert _writer_devices(col, (512, 8)) is None


def test_multiprocess_stage_branch_unchanged(monkeypatch):
    """RowStager.stage with n_proc > 1 must go through
    make_array_from_process_local_data, never the engine (its per-device
    buffers are process-local)."""
    m = get_mesh(4)
    st = RowStager(1_024, m)
    called = {}

    def fake_mafpld(sharding, padded, shape):
        called["shape"] = shape
        import jax as _jax

        return _jax.device_put(padded, sharding)

    monkeypatch.setattr(st, "n_proc", 2)
    monkeypatch.setattr(jax, "make_array_from_process_local_data",
                        fake_mafpld)
    X = np.ones((1_024, 4), np.float32)
    st.stage(X, np.float32)
    assert called["shape"] == (st.n_padded, 4)


# ---------------------------------------------------------------------------
# producer-thread error propagation
# ---------------------------------------------------------------------------


def test_producer_error_surfaces(force_pipelined):
    def bad_pieces():
        yield 0, np.ones((64, 4), np.float32)
        raise RuntimeError("decode exploded")

    from jax.sharding import NamedSharding, PartitionSpec

    m = get_mesh(4)
    sh = NamedSharding(m, PartitionSpec("data", None))
    with pytest.raises(RuntimeError, match="decode exploded"):
        assemble_rows_chunked((512, 4), np.float32, bad_pieces(),
                              out_shardings=sh)


# ---------------------------------------------------------------------------
# stage_parquet ingest wiring
# ---------------------------------------------------------------------------


def test_stage_parquet_per_device_engine(tmp_path):
    pd = pytest.importorskip("pandas")
    from spark_rapids_ml_tpu.streaming import stage_parquet

    rng = np.random.default_rng(4)
    n, d = 20_000, 24
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float64)
    w = rng.uniform(0.5, 1.5, n)
    path = str(tmp_path / "a.parquet")
    pd.DataFrame(
        {"features": list(X), "label": y, "w": w}
    ).to_parquet(path)
    ds = stage_parquet(path, label_col="label", weight_col="w",
                       chunk_rows=4_096, num_workers=8,
                       label_dtype=np.float64)
    assert _staged()["engine"] == "per-device"
    assert _staged()["bytes_transferred"] > 0
    hX, hy, hw = _host(ds.X), _host(ds.y), _host(ds.weight)
    assert np.array_equal(hX[:n], X)
    assert np.array_equal(hy[:n], y)
    assert np.allclose(hw[:n], w.astype(np.float32))
    # buffer tail padding stays zero (it never travelled)
    assert not hX[n:].any() and not hy[n:].any() and not hw[n:].any()


# ---------------------------------------------------------------------------
# the win: per-device assembly + overlap, byte-identical to the serial path
# ---------------------------------------------------------------------------


def test_pipelined_beats_serial_on_multi_device_mesh():
    """On the 8-device CPU mesh the serial path pays the n_dev x GSPMD
    replication per chunk plus two full host copies; the engine transfers
    each byte once with prep overlapped.  The speedup itself is a number
    for the chip, not for a CPU wall clock shared with five other test
    workers; what is checked here is
    what makes the win possible and needs no race against a clock: the
    same bytes land, prep ran on another thread than the puts, and every
    piece was put exactly once."""
    import threading

    from spark_rapids_ml_tpu import tracing

    rng = np.random.default_rng(5)
    n, d = 120_000, 64  # ~30 MB f32 -> above _PIPELINED_MIN_BYTES
    X = rng.standard_normal((n, d))  # f64 source: real cast work
    m = get_mesh(8)
    st = RowStager(n, m)
    assert st._interleave  # the bucketed layout the engine must fuse

    serial = _host(st._stage_serial(X, np.dtype(np.float32)))
    with tracing.run_context(prefix="stage-test") as run_id:
        piped = _host(st.stage(X, np.float32))
    assert np.array_equal(serial, piped)

    spans = tracing.get_all_trace_events(run_id)
    prep = [e for e in spans if e.name == "stage_prep"]
    put = [e for e in spans if e.name == "stage_put"]
    assert prep and {e.thread_id for e in prep} != {threading.get_ident()}, (
        "host prep did not run on the prefetch thread"
    )
    assert {e.thread_id for e in put} == {threading.get_ident()}
    assert len(put) == len(prep) == _staged()["pieces"] >= 8


@pytest.mark.parametrize("src_dt", [np.float32, np.float64])
def test_fit_report_says_how_many_pieces_were_views(src_dt):
    """A fit from host rows on one device reports `pieces_viewed` beside
    `pieces` (all of them for float32 rows, none where the cast to
    float32 has to copy) and the host's MemAvailable at its two ends;
    a fit from a DeviceDataset reads none."""
    from spark_rapids_ml_tpu.classification import LogisticRegression

    set_config(staging_chunk_bytes=1024 * 1024)
    rng = np.random.default_rng(13)
    X = rng.standard_normal((40_000, 32)).astype(src_dt)
    assert X.nbytes >= mesh_mod._PIPELINED_MIN_BYTES  # the labels stay serial
    y = (X[:, 0] > 0).astype(np.float64)
    model = LogisticRegression(maxIter=3, num_workers=1).fit((X, y))
    rep = model.fit_report()
    staging = rep["staging"]
    assert staging["pieces"] >= 4
    assert staging["pieces_viewed"] == (
        staging["pieces"] if src_dt == np.float32 else 0
    )
    host = rep["memory"]["host_available_bytes"]
    assert host["start"] > 0 and host["end"] > 0
    # a fit from rows already on the devices reads no host memory
    from spark_rapids_ml_tpu.data import DeviceDataset

    cached = LogisticRegression(maxIter=3, num_workers=1).fit(
        DeviceDataset.from_host(X, y, num_workers=1)
    )
    assert "host_available_bytes" not in cached.fit_report()["memory"]
