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
    STAGE_METRICS,
    RowStager,
    ShardedRowWriter,
    _writer_devices,
    assemble_rows_chunked,
    get_mesh,
)


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


@pytest.mark.parametrize("n,d,src_dt,out_dt", [
    (10_000, 37, np.float64, np.float32),   # cast fused into the gather
    (10_000, 37, np.float32, np.float32),
    (4_096, 16, np.float64, np.float64),    # f64 end-to-end
    (999, 5, np.float32, np.float32),       # ragged tail vs shard grid
    (256, 3, np.float32, np.float32),       # minimum bucket
])
def test_stage_parity_all_layouts(n, d, src_dt, out_dt, num_workers,
                                  force_pipelined):
    """Pipelined staging is byte-identical to the serial path for the
    interleaved AND contiguous layouts at every mesh size."""
    rng = np.random.default_rng(n + d)
    X = rng.standard_normal((n, d)).astype(src_dt)
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
    assert STAGE_METRICS["depth"] == 1
    assert STAGE_METRICS["overlap_ratio"] == 0.0


def test_stage_metrics_populated(force_pipelined):
    rng = np.random.default_rng(2)
    X = rng.standard_normal((8_192, 8)).astype(np.float32)
    st = RowStager(8_192, get_mesh(8))
    st.stage(X, np.float32)
    for key in ("bytes", "seconds", "mb_per_s", "host_prep_s",
                "device_put_s", "overlap_ratio", "pieces", "depth",
                "n_dev"):
        assert key in STAGE_METRICS, key
    # padding never travels: transferred bytes == valid rows only
    assert STAGE_METRICS["bytes"] == X.size * 4
    assert STAGE_METRICS["n_dev"] == 8


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
    assert STAGE_METRICS["pieces"] > 4  # more than one piece per device


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
    from spark_rapids_ml_tpu.streaming import LAST_STAGE, stage_parquet

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
    assert LAST_STAGE["engine"] == "per-device"
    assert LAST_STAGE["bytes_transferred"] > 0
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
    for the chip (and bench.py's `staging` section), not for a CPU wall
    clock shared with five other test workers; what is checked here is
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
    assert len(put) == len(prep) == STAGE_METRICS["pieces"] >= 8
