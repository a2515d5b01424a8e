#
# Out-of-core / streaming ingest — the analog of the reference's
# reserved-memory loader (`_concat_with_reserved_gpu_mem` utils.py:403-522:
# reserve a fraction of free GPU memory, stream Arrow batches straight into
# it) and of Spark-partitioned ingest scaling.  Two mechanisms:
#
#   A. `stage_parquet` — stream parquet record batches host->HBM into a
#      PREALLOCATED sharded device buffer via one compiled
#      dynamic-update-slice step with buffer donation (in-place).  The full
#      dataset is never materialized in one host allocation; host memory is
#      one chunk (`host_batch_bytes`).  Result: a DeviceDataset, so every
#      estimator's normal device-resident fit path runs unchanged.
#      Multi-process: each process reads only its row slice of the dataset
#      (per-partition loading; host memory = dataset / n_processes).
#
#   B. `linreg_streaming_stats` / `pca_streaming_stats` — TRUE multi-pass
#      streaming for sufficient-statistics algorithms: chunks are staged,
#      reduced into (d,d)-sized accumulators on device, and discarded.
#      Dataset size is bounded by neither host RAM nor HBM.
#
from __future__ import annotations

import os
import time
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from .config import get_config
from .tracing import fact
from .utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.streaming")


def is_parquet_path(dataset) -> bool:
    return isinstance(dataset, str) and (
        os.path.isdir(dataset) or dataset.endswith(".parquet")
    )


def parquet_row_count(path: str) -> int:
    import pyarrow.dataset as ds

    return ds.dataset(path, format="parquet").count_rows()


_PROBE_CACHE: dict = {}


def _path_stamp(path: str):
    """Change-detection stamp for the probe cache: (mtime_ns, size) of the
    file, or the sorted per-entry stamps of a dataset directory (an
    in-place fragment rewrite changes its file's mtime even when the
    directory's own mtime is unchanged)."""
    import zlib

    try:
        st = os.stat(path)
        if not os.path.isdir(path):
            return (st.st_mtime_ns, st.st_size)
        # recurse (hive-partitioned layouts nest fragments), folding every
        # fragment's (relpath, mtime, size) into one running crc so memory
        # stays O(1) no matter how many files the dataset holds
        h = 0
        count = 0
        total = 0
        for root, _dirs, files in os.walk(path):
            for f in files:
                full = os.path.join(root, f)
                s = os.stat(full)
                h = zlib.crc32(
                    f"{os.path.relpath(full, path)}|{s.st_mtime_ns}|"
                    f"{s.st_size}".encode(), h,
                )
                count += 1
                total += s.st_size
        return (h, count, total)
    except OSError:
        return None


def probe_num_features(
    path: str, features_col: Optional[str], features_cols: Sequence[str]
) -> int:
    """Feature dimension from the schema (fixed_size_list) or the first
    record batch (the analog of the reference's `df.first()` dimension
    probe, core.py:467-568).  Cached per (path, col): epoch-streaming
    solvers stream the same file once per L-BFGS evaluation, and a probe
    that re-decodes the first row group each epoch was measured at 10 s
    on a 500k-row file (batch_size=1 forces a full row-group decode)."""
    if features_cols:
        return len(features_cols)
    stamp = _path_stamp(path)
    # no stamp (os.stat failed, e.g. an object-store URI pyarrow can still
    # read): re-probe every call rather than cache under a key that would
    # go stale if the remote dataset is rewritten in-place
    key = None if stamp is None else (path, features_col, stamp)
    hit = _PROBE_CACHE.get(key) if key is not None else None
    if hit is not None:
        return hit
    import pyarrow as pa
    import pyarrow.dataset as ds

    dataset = ds.dataset(path, format="parquet")
    d = None
    field = dataset.schema.field(features_col) if (
        features_col in dataset.schema.names
    ) else None
    if field is not None and pa.types.is_fixed_size_list(field.type):
        if dataset.count_rows() == 0:  # metadata-only, cheap
            raise ValueError("Dataset is empty: nothing to fit/transform")
        d = field.type.list_size
    else:
        # default batch size: the scanner hands back a whole decoded page
        # cheaply instead of slicing the row group into 1-row batches
        for batch in dataset.to_batches(columns=[features_col]):
            if batch.num_rows == 0:
                continue
            first = batch.column(0)[0].as_py()
            d = 1 if np.isscalar(first) else len(first)
            break
        if d is None:
            raise ValueError("Dataset is empty: nothing to fit/transform")
    if key is not None:
        if len(_PROBE_CACHE) >= 64:
            _PROBE_CACHE.pop(next(iter(_PROBE_CACHE)))
        _PROBE_CACHE[key] = d
    return d


def chunk_rows_for(d: int, itemsize: int = 4) -> int:
    """Rows per streamed chunk from the `host_batch_bytes` budget."""
    budget = int(get_config("host_batch_bytes"))
    return max(1024, budget // max(d * itemsize, 1))


def _batch_to_arrays(
    pdf,
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: Optional[str],
    weight_col: Optional[str],
    dtype: np.dtype,
):
    from .data import _features_from_pandas

    X = _features_from_pandas(pdf, features_col, list(features_cols), dtype)
    y = pdf[label_col].to_numpy() if label_col else None
    w = pdf[weight_col].to_numpy() if weight_col else None
    return X, y, w


def _decode_batch(
    batch,
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: Optional[str],
    weight_col: Optional[str],
    dtype: np.dtype,
):
    """Arrow RecordBatch -> (X, y, w) numpy arrays WITHOUT pandas.

    The hot ingest path: a list<float> feature column decodes by
    flattening the Arrow child buffer and reshaping — zero-copy when the
    storage dtype matches — instead of materializing one numpy object per
    row and re-packing (measured 45x on the 1-core bench host: 24k ->
    1.09M rows/s at 64 cols).  Falls back to the pandas path for nulls,
    ragged rows, or exotic types.  Analog of the reference's Arrow-batch
    fast path into reserved GPU memory (utils.py:403-522)."""
    import pyarrow as pa

    names = batch.schema.names

    def _col(name: str):
        return batch.column(names.index(name))

    def _np1d(arr, want=None):
        out = arr.to_numpy(zero_copy_only=False)
        if want is not None:
            out = np.asarray(out, want)
        return out

    try:
        if features_cols:
            cols = [_np1d(_col(c)) for c in features_cols]
            X = np.empty((batch.num_rows, len(cols)), dtype)
            for j, c in enumerate(cols):
                X[:, j] = c
        else:
            assert features_col is not None
            c = _col(features_col)
            t = c.type
            if pa.types.is_list(t) or pa.types.is_large_list(t) or (
                pa.types.is_fixed_size_list(t)
            ):
                if c.null_count:
                    raise ValueError("nulls in feature column")
                n = len(c)
                if n == 0:
                    raise ValueError("empty batch")
                if pa.types.is_fixed_size_list(t):
                    d = t.list_size
                else:
                    # exact per-row lengths from the offsets: a ragged
                    # batch whose total count divides n must NOT silently
                    # reshape values across row boundaries
                    offs = np.asarray(c.offsets)
                    lens = np.diff(offs)
                    d = int(lens[0])
                    if not (lens == d).all():
                        raise ValueError("ragged feature rows")
                vals = c.flatten().to_numpy(zero_copy_only=False)
                if vals.shape[0] != n * d:
                    raise ValueError("ragged feature rows")
                X = np.asarray(vals, dtype).reshape(n, d)
            else:
                X = _np1d(c, dtype).reshape(-1, 1)
        y = _np1d(_col(label_col), np.float64) if label_col else None
        w = _np1d(_col(weight_col), np.float64) if weight_col else None
        return X, y, w
    except (ValueError, KeyError, pa.ArrowInvalid, NotImplementedError):
        return _batch_to_arrays(
            batch.to_pandas(), features_col, features_cols, label_col,
            weight_col, dtype,
        )


def _scan_columns(
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: Optional[str],
    weight_col: Optional[str],
) -> list:
    columns = (
        list(features_cols) if features_cols else [features_col]
    )
    if label_col:
        columns.append(label_col)
    if weight_col:
        columns.append(weight_col)
    return columns


def _chunk_stream_key(
    path: str,
    features_col,
    features_cols,
    label_col,
    weight_col,
    chunk_rows: int,
    dtype,
    row_range,
    tag: str = "iter_chunks",
    topology=None,
):
    """Chunk-cache stream key: the path's content stamp plus every scan
    parameter that shapes the yielded chunks.  None (cache bypass) when
    the path cannot be stat'd — a remote dataset rewritten in place must
    never replay stale chunks.  The key also carries the rank and the
    process-group SIZE: each host caches (and spills) only its own
    slice's chunks, two ranks replaying the SAME parquet path through a
    shared `chunk_cache_spill_dir` must never collide on a spill
    filename, and a stream decoded under one partition layout must never
    be replayed under another (the share boundaries moved).  `topology`
    overrides the (size, rank) pair — how a rank-loss recovery pass
    (resilience/pod.py) reconstructs a pre-loss stream key so the
    survivor's own share replays from cache byte-for-byte."""
    stamp = _path_stamp(path)
    if stamp is None:
        return None
    if topology is not None:
        nranks, rank = int(topology[0]), int(topology[1])
    else:
        # the topology view (identical to the jax view until a pod
        # recovery installs an override): a stream decoded under one
        # ingest layout must never serve another
        from .parallel.context import process_topology

        nranks, rank = process_topology()
    return (
        tag, path, stamp, rank, features_col,
        tuple(features_cols or ()), label_col, weight_col,
        int(chunk_rows), np.dtype(dtype).str, row_range, nranks,
    )


def chunk_stream_key(
    path, features_col, features_cols, label_col, weight_col,
    chunk_rows, dtype, row_range=None,
):
    """Public form of the `iter_chunks` cache key (the epoch solvers use
    it to ask `chunk_stream_complete` whether sampling may engage)."""
    return _chunk_stream_key(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, row_range,
    )


def _dev_chunk(c, dtype):
    """Chunk feature block -> device array of `dtype`.  A cache-served
    DEVICE-RESIDENT chunk passes straight through (no host round trip —
    the device tier's whole point); host chunks take the usual
    cast-and-put."""
    import jax
    import jax.numpy as jnp

    want = np.dtype(dtype)
    if isinstance(c, jax.Array):
        return c if c.dtype == want else c.astype(want)
    return jnp.asarray(np.asarray(c, want))


def iter_chunks(
    path: str,
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: Optional[str],
    weight_col: Optional[str],
    chunk_rows: int,
    dtype: np.dtype,
    row_range: Optional[Tuple[int, int]] = None,
    device_ok: bool = False,
    select_chunks=None,
    cache_ok: bool = True,
) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray], int]]:
    """Stream `(X, y, w, n_valid)` chunks of EXACTLY `chunk_rows` rows
    (zero-padded tail on the last chunk) — fixed shapes keep the device
    staging step at one compilation.  `row_range=(lo, hi)` restricts to a
    global row slice (multi-process per-partition reads).

    Each yielded chunk owns its arrays (no buffer reuse): an exactly-full
    Arrow batch is yielded as a zero-copy reshape of the Arrow child
    buffer; partial batches accumulate into a freshly allocated chunk.

    The stream runs through the chunk cache (`chunk_cache` conf,
    parallel/device_cache.py): the first identical scan decodes parquet
    and records the chunks (served arrays are READ-ONLY from then on);
    later identical scans replay them byte-for-byte without touching
    disk.  `device_ok=True` consumers (the epoch solvers, whose chunks
    go straight into jitted device steps) may receive the feature block
    as a device-resident jax array; everyone else always sees numpy.
    `select_chunks` (a position set) replays only those chunks of a
    fully cached stream — skipped chunks never decompress or transfer
    (the DuHL sampling path).  `cache_ok=False` bypasses the cache
    entirely — the one-shot staging scans (`stage_parquet`) would
    otherwise retain chunks they never replay AND could LRU-evict the
    epoch solvers' streams, the consumers the cache exists for."""

    def _source():
        import pyarrow.dataset as ds

        columns = _scan_columns(
            features_col, features_cols, label_col, weight_col
        )
        dataset = ds.dataset(path, format="parquet")
        return chunks_from_batches(
            dataset.to_batches(columns=columns, batch_size=chunk_rows),
            features_col, features_cols, label_col, weight_col,
            chunk_rows, dtype, row_range=row_range,
        )

    from .parallel.device_cache import cached_chunk_stream

    key = None if not cache_ok else _chunk_stream_key(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, row_range,
    )
    yield from cached_chunk_stream(
        key, _source,
        device_elem=0 if device_ok else None,
        serve_device=device_ok,
        select=select_chunks,
    )


def chunks_from_batches(
    batches,
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: Optional[str],
    weight_col: Optional[str],
    chunk_rows: int,
    dtype: np.dtype,
    row_range: Optional[Tuple[int, int]] = None,
) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray], int]]:
    """The chunk-assembly half of `iter_chunks`, decoupled from the Arrow
    scanner so alternative batch sources — the fused engine's
    row-group-pruned parallel range readers (fused.py) — reuse the exact
    decode + fixed-shape chunking semantics.  `row_range` counts rows
    from the start of THIS batch stream."""
    d = None  # derived from the first decoded batch (no separate probe)
    bufX = bufy = bufw = None
    fill = 0
    seen = 0  # global rows consumed so far
    lo, hi = row_range if row_range is not None else (0, None)

    for batch in batches:
        nb = batch.num_rows
        if nb == 0:
            continue
        b_lo, b_hi = seen, seen + nb
        seen = b_hi
        # intersect with the requested row range
        s = max(b_lo, lo)
        e = b_hi if hi is None else min(b_hi, hi)
        if s >= e:
            if hi is not None and b_lo >= hi:
                break
            continue
        X, y, w = _decode_batch(
            batch.slice(s - b_lo, e - s), features_col, features_cols,
            label_col, weight_col, dtype,
        )
        if d is None:
            d = X.shape[1]
        if fill == 0 and X.shape[0] == chunk_rows:
            # exactly-full batch: hand the decoded arrays over directly
            yield X, y, w, chunk_rows
            continue
        pos = 0
        while pos < X.shape[0]:
            if bufX is None:
                bufX = np.zeros((chunk_rows, d), dtype)
                bufy = np.zeros((chunk_rows,), np.float64) if label_col else None
                bufw = np.zeros((chunk_rows,), np.float64) if weight_col else None
            take = min(chunk_rows - fill, X.shape[0] - pos)
            bufX[fill : fill + take] = X[pos : pos + take]
            if bufy is not None:
                bufy[fill : fill + take] = y[pos : pos + take]
            if bufw is not None:
                bufw[fill : fill + take] = w[pos : pos + take]
            fill += take
            pos += take
            if fill == chunk_rows:
                yield bufX, bufy, bufw, fill
                bufX = bufy = bufw = None
                fill = 0
    if fill:
        yield bufX, bufy, bufw, fill


def iter_chunks_prefetch(*args, **kwargs) -> Iterator:
    """`iter_chunks` with the parquet decode running on a background
    thread ahead of the consumer: the device consumes chunk i while the
    host reads chunk i+1 (the streaming analog of the reference's
    overlapped reserved-memory copies, utils.py:403-522).  `iter_chunks`
    yields owned chunks, so the queue holds `streaming_prefetch_depth`-1
    chunks of extra host memory and no copy is needed.  Disable via the
    `streaming_prefetch` conf (or depth <= 1)."""
    from .utils import prefetch_iter

    depth = max(1, int(get_config("streaming_prefetch_depth")))
    if not get_config("streaming_prefetch") or depth <= 1:
        yield from iter_chunks(*args, **kwargs)
        return
    yield from prefetch_iter(iter_chunks(*args, **kwargs), depth=depth)



_ONES_CACHE: dict = {}


def _weights_host(cw, n_c: int, chunk_rows: int, dtype) -> np.ndarray:
    """Per-chunk weight vector (zero past n_c).  The common case — no
    weight column, full chunk — returns a cached read-only ones array, so
    the hot ingest loop allocates nothing."""
    dtype = np.dtype(dtype)
    if cw is None and n_c == chunk_rows:
        key = (chunk_rows, dtype.str)
        a = _ONES_CACHE.get(key)
        if a is None:
            a = np.ones((chunk_rows,), dtype)
            a.setflags(write=False)
            _ONES_CACHE[key] = a
        return a
    w = np.zeros((chunk_rows,), dtype)
    w[:n_c] = 1.0 if cw is None else np.asarray(cw[:n_c], dtype)
    return w


# ---------------------------------------------------------------------------
# Mechanism A: stream-stage into a sharded HBM buffer
# ---------------------------------------------------------------------------


def _parquet_share_offsets(path: str, readers: int) -> Optional[list]:
    """[(row_group_indices, global_start_row)] shares for the PARALLEL
    staging readers: the fused engine's row-balanced contiguous
    row-group partition (fused._partition_row_groups) annotated with
    each share's global starting row, so out-of-order decoded chunks
    still land at their exact global offsets in the ShardedRowWriters.
    None = not splittable (directory dataset / too few groups /
    readers<=1): the caller keeps the single in-order scan."""
    from .fused import _partition_row_groups

    shares = _partition_row_groups(path, readers)
    if shares is None:
        return None
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    sizes = [md.row_group(i).num_rows for i in range(md.num_row_groups)]
    starts = np.concatenate([[0], np.cumsum(sizes)])
    return [(groups, int(starts[groups[0]])) for groups in shares]


def _share_chunks(
    path: str,
    features_col,
    features_cols,
    label_col,
    weight_col,
    chunk_rows: int,
    dtype: np.dtype,
    groups,
) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray], int]]:
    """One staging reader's share: the iter_chunks decode + fixed-shape
    chunking over ONLY its row groups (fused._reader_batches prunes the
    scan).  Deliberately NOT chunk-cached: a staging scan runs once per
    dataset-cache miss and would only burn host budget the epoch
    solvers' streams need."""
    from .fused import _reader_batches

    columns = _scan_columns(
        features_col, features_cols, label_col, weight_col
    )
    yield from chunks_from_batches(
        _reader_batches(path, columns, chunk_rows, groups),
        features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype,
    )


def stage_parquet(
    path: str,
    features_col: Optional[str] = "features",
    features_cols: Sequence[str] = (),
    label_col: Optional[str] = None,
    weight_col: Optional[str] = None,
    num_workers: Optional[int] = None,
    dtype=np.float32,
    label_dtype=None,
    chunk_rows: Optional[int] = None,
):
    """Stream a parquet dataset into a row-sharded DeviceDataset without a
    full-dataset host allocation (single-process), or from this process's
    row slice only (multi-process)."""
    import jax

    from .data import DeviceDataset
    from .parallel.mesh import _ensure_distributed, get_mesh

    _ensure_distributed()
    t_stage0 = time.perf_counter()
    dtype = np.dtype(dtype)
    n_total = parquet_row_count(path)
    if n_total == 0:
        raise ValueError("Dataset is empty: nothing to fit/transform")
    d = probe_num_features(path, features_col, features_cols)
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)

    from .parallel.context import process_topology

    if process_topology()[0] > 1:
        # per-partition read: every host decodes ONLY its contiguous row
        # share (host memory = dataset / n_processes, decode throughput
        # scales with host count), then the standard RowStager layout —
        # whose large-array path now runs the per-device writer over the
        # addressable shards — assembles the ONE global sharded array.
        # The share partition is pure arithmetic on (n_total, rank):
        # deterministic on every rank, and coverage-asserted to tile
        # [0, n_total) exactly, so no row is decoded twice or dropped.
        # Topology view: a post-rank-loss survivor group re-partitions
        # over the survivors, not the boot process count.
        n_proc, pid = process_topology()
        ranges = process_ingest_ranges(n_total, n_proc)
        lo, hi = ranges[pid]
        n_local = hi - lo
        X = np.zeros((n_local, d), dtype)
        y = np.zeros((n_local,), np.float64) if label_col else None
        w = np.zeros((n_local,), np.float64) if weight_col else None
        at = 0
        for cX, cy, cw, n_c in iter_chunks_prefetch(
            path, features_col, features_cols, label_col, weight_col,
            chunk_rows, dtype, row_range=(lo, hi), cache_ok=False,
        ):
            X[at : at + n_c] = cX[:n_c]
            if y is not None:
                y[at : at + n_c] = cy[:n_c]
            if w is not None:
                w[at : at + n_c] = cw[:n_c]
            at += n_c
        if at != n_local:
            raise RuntimeError(
                f"parallel ingest coverage: rank {pid} decoded {at} rows "
                f"of its share [{lo}, {hi}) — expected {n_local}"
            )
        return DeviceDataset.from_host(
            X, y=y, weight=w, num_workers=num_workers, dtype=dtype,
            label_dtype=label_dtype,
        )

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from .parallel.mesh import (
        DATA_AXIS, ShardedRowWriter, _writer_devices, ensure_x64,
    )

    ensure_x64(dtype)
    mesh = get_mesh(num_workers)
    n_dev = mesh.devices.size
    # chunk-aligned AND device-aligned buffer size, so every
    # dynamic-update-slice lands fully inside the buffer; the chunk never
    # exceeds the (device-aligned) dataset, or a small dataset would stage
    # into a full-chunk buffer of mostly padding (30k rows in a 512 MB
    # chunk = a 2.1M-row device buffer, 70x wasted compute per fit)
    chunk_rows = min(chunk_rows, max(n_total, 1))
    chunk_rows = -(-chunk_rows // n_dev) * n_dev
    n_padded = -(-n_total // chunk_rows) * chunk_rows
    ldt = np.dtype(label_dtype) if label_dtype is not None else dtype
    if label_col:
        ensure_x64(ldt)

    row_spec = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
    mat_spec = NamedSharding(mesh, PartitionSpec(DATA_AXIS, None))

    # per-device staging engine (parallel/mesh.py): each decoded chunk is
    # split at device-shard boundaries and transferred to exactly ONE
    # device — the legacy jitted global fill let GSPMD replicate every
    # chunk to all devices (n_dev x the minimal ingest traffic).  The
    # parquet decode already runs one chunk ahead on the prefetch thread
    # (iter_chunks_prefetch), so host prep overlaps the transfers here
    # the same way the staging pipeline's producer thread does.
    use_writer = _writer_devices(mat_spec, (n_padded, d)) is not None
    if use_writer:
        wX = ShardedRowWriter((n_padded, d), dtype, mat_spec)
        wy = (
            ShardedRowWriter((n_padded,), ldt, row_spec)
            if label_col else None
        )
        ww = ShardedRowWriter((n_padded,), dtype, row_spec)
    else:  # legacy global-update path (non-decomposable placements)
        def _alloc():
            return (
                jnp.zeros((n_padded, d), dtype),
                jnp.zeros((n_padded,), ldt) if label_col else None,
                jnp.zeros((n_padded,), dtype),
            )

        bufX, bufy, bufw = jax.jit(
            _alloc,
            out_shardings=(
                mat_spec, row_spec if label_col else None, row_spec
            ),
        )()

        def _fill(bX, bY, bW, cX, cY, cW, off):
            # explicit int32 zero: a Python literal would trace as int64
            # when a prior fit enabled x64, and dus requires uniform
            # index types
            bX = jax.lax.dynamic_update_slice(
                bX, cX, (off, jnp.zeros((), jnp.int32))
            )
            if bY is not None:
                bY = jax.lax.dynamic_update_slice(bY, cY, (off,))
            bW = jax.lax.dynamic_update_slice(bW, cW, (off,))
            return bX, bY, bW

        fill = jax.jit(
            _fill,
            donate_argnums=(0, 1, 2),
            out_shardings=(
                mat_spec, row_spec if label_col else None, row_spec
            ),
        )

    off = 0
    n_chunks = 0
    shares = None
    if use_writer:
        from .fused import resolve_parquet_readers

        readers = resolve_parquet_readers(path)
        if readers > 1:
            shares = _parquet_share_offsets(path, readers)
    if shares is not None:
        # PARALLEL ingest (multi-core hosts): each range reader decodes
        # ONLY its row-group share and feeds the per-device writers
        # DIRECTLY from its own thread — decode, compress/spill
        # (chunk-cache inserts) and device transfer all overlap.  The
        # share's global start row keeps every chunk at its exact
        # global offset, so the staged buffer is byte-identical to the
        # single-reader scan (asserted by tests/test_chunk_cache.py).
        import threading

        from .tracing import adopt_trace_context

        # every reader holds its own decode buffers and one chunk while
        # it waits for the device, so N readers at the full chunk size
        # hold N x (row batch + levels + chunk) — measured 2.8 GB per
        # reader at 1M x 3000, which 13 readers do not fit in a 45 GB
        # host.  The readers SHARE the `host_batch_bytes` budget instead:
        # the buffer's shape keeps the full chunk size, the pieces shrink
        piece_rows = max(1024, chunk_rows // len(shares))
        errors: list = []
        counted = {"chunks": 0}
        cmu = threading.Lock()
        # reader threads decode AND dispatch device writes: adopt the
        # fit's trace context so their compile events and any fault
        # markers land in the fit's report, not an anonymous thread
        adopt = adopt_trace_context()

        def _stage_share(groups, start: int) -> None:
            adopt()
            try:
                at = start
                for cX, cy, cw, n_c in _share_chunks(
                    path, features_col, features_cols, label_col,
                    weight_col, piece_rows, dtype, groups,
                ):
                    wX.write(at, np.asarray(cX[:n_c], dtype))
                    if wy is not None:
                        wy.write(at, np.asarray(np.asarray(cy)[:n_c], ldt))
                    ww.write(
                        at, _weights_host(cw, n_c, piece_rows, dtype)[:n_c]
                    )
                    at += n_c
                    with cmu:
                        counted["chunks"] += 1
            except BaseException as e:
                errors.append(e)

        threads = [
            threading.Thread(target=_stage_share, args=s, daemon=True)
            for s in shares
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        n_chunks = counted["chunks"]
    else:
        # cache_ok=False: a one-shot staging scan must neither retain
        # chunks it never replays nor evict the epoch solvers' streams
        for cX, cy, cw, n_c in iter_chunks_prefetch(
            path, features_col, features_cols, label_col, weight_col,
            chunk_rows, dtype, cache_ok=False,
        ):
            if use_writer:
                # only the valid rows travel: chunk tail padding (and the
                # buffer tail) stays in the zeros the shard buffers started
                # with, so a short final chunk transfers no padding bytes
                wX.write(off, np.asarray(cX[:n_c], dtype))
                if wy is not None:
                    wy.write(off, np.asarray(np.asarray(cy)[:n_c], ldt))
                # sliced to the valid rows so tail padding never travels;
                # the chunk_rows arg keeps _ONES_CACHE keyed to the one
                # full-chunk size (a per-tail-size key would grow the
                # cache unboundedly across fits)
                ww.write(off, _weights_host(cw, n_c, chunk_rows, dtype)[:n_c])
            else:
                w_host = _weights_host(cw, n_c, chunk_rows, dtype)
                cY = (
                    jnp.asarray(np.asarray(cy, ldt)) if label_col else None
                )
                bufX, bufy, bufw = fill(
                    bufX, bufy, bufw,
                    jnp.asarray(cX), cY, jnp.asarray(w_host),
                    jnp.asarray(off, jnp.int32),
                )
            off += chunk_rows
            n_chunks += 1
    if use_writer:
        bufX = wX.finish()
        bufy = wy.finish() if wy is not None else None
        bufw = ww.finish()
    # block so the recorded staging time covers the actual host->device
    # transfer, not just async dispatch
    jax.block_until_ready(bufX)
    el = time.perf_counter() - t_stage0
    mb = n_padded * d * dtype.itemsize / 1e6
    staged = {
        "label": "parquet",
        "engine": (
            "per-device-parallel" if shares is not None
            else "per-device" if use_writer else "global-update"
        ),
        "seconds": round(el, 2),
        "mb": round(mb, 1),
        "mb_per_s": round(mb / max(el, 1e-9), 1),
    }
    if shares is not None:
        staged["readers"] = len(shares)
    if use_writer:
        # what travelled (padding never does) and in how many pieces
        writers = [w for w in (wX, ww, wy) if w is not None]
        staged["bytes_transferred"] = sum(w.bytes_written for w in writers)
        staged["pieces"] = sum(w.pieces for w in writers)
    fact("staging", **staged)
    logger.info(
        f"Streamed {n_total} rows x {d} cols from {path} in {n_chunks} "
        f"chunks of {chunk_rows} rows onto {mesh} "
        f"({el:.1f}s, {mb / max(el, 1e-9):.0f} MB/s)"
    )
    return DeviceDataset(mesh, bufX, n_total, y=bufy, weight=bufw)


# ---------------------------------------------------------------------------
# Mechanism B: multi-pass streaming sufficient statistics (beyond HBM)
# ---------------------------------------------------------------------------


def process_ingest_ranges(n_total: int, n_proc: int) -> list:
    """The deterministic per-process ingest partition: contiguous
    `[lo, hi)` row ranges, one per rank, balanced to within one row.
    Pure arithmetic on the inputs (every rank computes the identical
    table with no exchange) and coverage-asserted: the ranges tile
    `[0, n_total)` exactly — the contract that makes 'each host decodes
    only its slice' safe to reduce over."""
    base, rem = divmod(int(n_total), int(n_proc))
    ranges = []
    lo = 0
    for p in range(int(n_proc)):
        hi = lo + base + (1 if p < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    # coverage assertion (cheap, and the failure mode — double-decoded
    # or dropped rows silently skewing the reduced statistics — is the
    # worst kind): ranges must tile [0, n_total) with no gaps/overlaps
    if ranges[0][0] != 0 or ranges[-1][1] != int(n_total) or any(
        a[1] != b[0] for a, b in zip(ranges, ranges[1:])
    ):  # pragma: no cover - arithmetic invariant
        raise AssertionError(
            f"process ingest ranges do not tile [0, {n_total}): {ranges}"
        )
    return ranges


def _process_row_range(n_total: int) -> Tuple[int, int]:
    from .parallel.context import process_topology

    n_proc, pid = process_topology()
    if n_proc == 1:
        return 0, n_total
    return process_ingest_ranges(n_total, n_proc)[pid]


def _sum_across_processes(host_stats: dict) -> dict:
    """Sum per-process partial statistics (host side) through the
    cross-process reduce seam (parallel/context.py): one jitted psum on
    collective-capable backends, the coordination-service wire fold on
    CPU builds — with the rank-agreement check either way.  Topology-
    gated: a post-rank-loss survivor group of one skips the reduce."""
    from .parallel.context import process_topology

    if process_topology()[0] == 1:
        return host_stats
    from .parallel.context import reduce_host_arrays

    arrays = {k: np.asarray(v) for k, v in host_stats.items()}
    return reduce_host_arrays(arrays, "streaming_stats")


def _linreg_acc(d: int, dtype):
    """(initial accumulator, donated jitted step) for the weighted
    Gram/moment/cross statistics (ops/linear.py `linreg_sufficient_stats`)
    — shared by the parquet-streaming and blocked-CSR fits.  The spec
    resolves through the statistic-program registry (stats/programs.py
    `linreg` — the migrated ops/stats.py spec, incl. the optional Kahan
    compensation under `stats_precision="high_compensated"`), the same
    one the fused stage-and-solve engine accumulates through."""
    import jax

    from .stats.programs import get_program

    p = get_program("linreg")
    dtype = np.dtype(dtype)
    step, _unw = p.make_step(d, dtype, {})
    return p.init(d, dtype, {}), jax.jit(step, donate_argnums=0)


def _pca_acc(d: int, dtype):
    """(initial accumulator, donated jitted step) for the PCA second
    moments (S = sum w x x^T, s1, sw) — the registered `pca_moments`
    program, see `_linreg_acc`."""
    import jax

    from .stats.programs import get_program

    p = get_program("pca_moments")
    dtype = np.dtype(dtype)
    step, _unw = p.make_step(d, dtype, {})
    return p.init(d, dtype, {}), jax.jit(step, donate_argnums=0)


def iter_csr_chunks(
    csr,
    y: Optional[np.ndarray],
    w: Optional[np.ndarray],
    chunk_rows: int,
    dtype: np.dtype,
) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray], int]]:
    """Blocked densify of a host CSR matrix: yields dense `(X, y, w,
    n_valid)` row blocks of at most `chunk_rows` rows (native
    `densify_csr` per block), so peak host memory is one dense block —
    the TPU answer to the reference's CSR staging for datasets whose
    dense form doesn't fit (reference core.py:220-265,
    classification.py:960-966)."""
    from .native import densify_csr

    n = csr.shape[0]
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        rows = hi - lo
        Xb = densify_csr(csr[lo:hi], rows, dtype)
        wb = (
            np.ones((rows,), dtype)
            if w is None
            else np.asarray(w[lo:hi], dtype)
        )
        yield Xb, None if y is None else y[lo:hi], wb, rows


def linreg_streaming_stats(
    path: str,
    features_col: Optional[str],
    features_cols: Sequence[str],
    label_col: str,
    weight_col: Optional[str],
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
) -> dict:
    """Weighted Gram/moment/cross statistics accumulated chunk-by-chunk:
    the dataset is bounded by neither host RAM nor HBM.  Returns host-side
    float64 stats summed across processes."""
    import jax
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    d = probe_num_features(path, features_col, features_cols)
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)
    n_total = parquet_row_count(path)
    lo, hi = _process_row_range(n_total)

    # accumulate in f32 on device (MXU matmuls); final sums come back f64
    # (drift-baseline capture rides the same decoded chunks — zero extra
    # passes; replayed device-resident chunks are skipped host-side)
    from .monitor import baseline as _baseline

    acc, step = _linreg_acc(d, dtype)
    _baseline.begin_pass()
    for cX, cy, cw, n_c in iter_chunks_prefetch(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
    ):
        w_host = _weights_host(cw, n_c, chunk_rows, dtype)
        _baseline.fold_chunk(cX, w_host)
        acc = step(
            acc, _dev_chunk(cX, dtype), jnp.asarray(w_host),
            jnp.asarray(np.asarray(cy, dtype)),
        )
    _baseline.pass_complete()
    return _acc_to_host_f64(acc)


def _acc_to_host_f64(acc) -> dict:
    """Device accumulator -> float64 host dict (Kahan carries folded —
    ops/stats.py `acc_to_host_f64`), summed across processes
    (multi-process batches hold only local rows, like the parquet path)."""
    from .ops.stats import acc_to_host_f64

    return _sum_across_processes(acc_to_host_f64(acc))


def linreg_stats_from_csr(
    csr,
    y: np.ndarray,
    weight: Optional[np.ndarray],
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
) -> dict:
    """`linreg_streaming_stats` over a host CSR matrix via blocked
    densify: exact sparse sufficient statistics with one dense block of
    host memory and a (d,d) device accumulator."""
    import jax
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    d = int(csr.shape[1])
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)
    acc, step = _linreg_acc(d, dtype)
    for Xb, yb, wb, _rows in iter_csr_chunks(csr, y, weight, chunk_rows, dtype):
        acc = step(
            acc, jnp.asarray(Xb), jnp.asarray(wb),
            jnp.asarray(np.asarray(yb, dtype)),
        )
    return _acc_to_host_f64(acc)


def pca_streaming_stats(
    path: str,
    features_col: Optional[str],
    features_cols: Sequence[str],
    weight_col: Optional[str],
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
) -> dict:
    """Second-moment statistics for PCA (S = sum w x x^T, s1 = sum w x,
    sw = sum w), accumulated chunk-by-chunk."""
    import jax
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    d = probe_num_features(path, features_col, features_cols)
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)
    n_total = parquet_row_count(path)
    lo, hi = _process_row_range(n_total)

    from .monitor import baseline as _baseline

    acc, step = _pca_acc(d, dtype)
    _baseline.begin_pass()
    for cX, _, cw, n_c in iter_chunks_prefetch(
        path, features_col, features_cols, None, weight_col,
        chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
    ):
        w_host = _weights_host(cw, n_c, chunk_rows, dtype)
        _baseline.fold_chunk(cX, w_host)
        acc = step(acc, _dev_chunk(cX, dtype), jnp.asarray(w_host))
    _baseline.pass_complete()
    return _acc_to_host_f64(acc)


def pca_stats_from_csr(
    csr,
    weight: Optional[np.ndarray],
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
) -> dict:
    """`pca_streaming_stats` over a host CSR matrix via blocked densify."""
    import jax
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    d = int(csr.shape[1])
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)
    acc, step = _pca_acc(d, dtype)
    for Xb, _, wb, _rows in iter_csr_chunks(csr, None, weight, chunk_rows, dtype):
        acc = step(acc, jnp.asarray(Xb), jnp.asarray(wb))
    return _acc_to_host_f64(acc)


# ---------------------------------------------------------------------------
# Mechanism C: EPOCH-STREAMING fits for iterative solvers (beyond HBM).
# Sufficient statistics don't exist for LogReg/KMeans; instead every solver
# iteration re-streams the dataset through a donated device accumulator
# (loss+gradient for L-BFGS, per-cluster sums for Lloyd).  Dataset size is
# bounded by DISK — the TPU answer to the reference's ingest scaling with
# cluster GPU memory (reference utils.py:403-522, core.py:771-812), where
# the 1B-row BASELINE workloads live.
# ---------------------------------------------------------------------------


def partial_jit_donate(fn):
    """jit with the two leading accumulator args donated (in-place)."""
    import jax

    return jax.jit(fn, donate_argnums=(0, 1))


def _label_moments_scan(
    path: str,
    features_col,
    features_cols,
    label_col,
    weight_col,
    dtype,
    chunk_rows: int,
    need_moments: bool,
) -> dict:
    """One cheap host-side pass: weight sum, label range/integrality, and
    (optionally) weighted feature moments for standardization."""
    d = probe_num_features(path, features_col, features_cols)
    n_total = parquet_row_count(path)
    lo, hi = _process_row_range(n_total)
    wsum = 0.0
    n_valid = 0
    y_min, y_max = np.inf, -np.inf
    integral = 1.0
    s1 = np.zeros((d,), np.float64)
    s2 = np.zeros((d,), np.float64)
    for cX, cy, cw, n_c in iter_chunks(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, row_range=(lo, hi),
    ):
        w = (
            np.ones((n_c,), np.float64)
            if cw is None
            else cw[:n_c].astype(np.float64)
        )
        wsum += w.sum()
        n_valid += n_c
        if label_col is not None:
            yc = cy[:n_c]
            pos = w > 0
            if pos.any():
                y_min = min(y_min, float(yc[pos].min()))
                y_max = max(y_max, float(yc[pos].max()))
                if not np.all(yc[pos] == np.round(yc[pos])):
                    integral = 0.0
        if need_moments:
            Xc = cX[:n_c].astype(np.float64)
            s1 += (Xc * w[:, None]).sum(axis=0)
            s2 += (Xc * Xc * w[:, None]).sum(axis=0)
    agg = _sum_across_processes(
        {"wsum": wsum, "n_valid": n_valid, "s1": s1, "s2": s2,
         "not_integral": 1.0 - integral}
    )
    # min/max need min/max-reduction, not sum: gather explicitly
    from .parallel.context import process_topology, topology_overridden

    if process_topology()[0] > 1:
        rng = np.asarray([y_min, -y_max], np.float64)
        if topology_overridden():
            # post-rank-loss survivor group: the jax collective spans
            # the (stale) boot process set and would park on the dead —
            # gather over the bounded KV wire path instead
            from .parallel.context import allgather_bytes

            rng_all = np.stack([
                np.frombuffer(b, np.float64)
                for b in allgather_bytes(rng.tobytes(), "label_range")
            ]).reshape(-1, 2)
        else:
            from jax.experimental import multihost_utils

            rng_all = np.asarray(
                multihost_utils.process_allgather(rng)
            ).reshape(-1, 2)
        y_min = float(rng_all[:, 0].min())
        y_max = float(-rng_all[:, 1].min())
    return {
        "d": d,
        "n_total": n_total,
        "wsum": float(agg["wsum"]),
        "n_valid": int(agg["n_valid"]),
        "y_min": y_min,
        "y_max": y_max,
        "integral": float(agg["not_integral"]) == 0.0,
        "s1": np.asarray(agg["s1"]),
        "s2": np.asarray(agg["s2"]),
    }


# the checkpoint contract (content-tag naming, atomic tmp + os.replace,
# rank-0 writer, in-file tag check) moved to resilience/checkpoint.py so
# every iterative solver shares it; re-exported here for back-compat
from .resilience.checkpoint import checkpoint_file_for  # noqa: F401, E402


# ---------------------------------------------------------------------------
# DuHL-style chunk importance sampling (`streaming_chunk_sampling=duhl`).
# "Large-Scale Stochastic Learning using GPUs" (DuHL, PAPERS.md) keeps
# the coordinates with the largest duality-gap contribution in fast
# memory and streams only those; the chunk-granularity analog here:
# once the chunk cache holds the full stream, an epoch revisits only
# the chunks whose contribution to the solver's own statistics is
# still MOVING (per-chunk scores), and every unvisited chunk
# contributes its last-computed statistics (stale-compensation — the
# SAG-style trick that keeps the objective estimate unbiased-in-the-
# limit as the iterates settle).  Skipped chunks never decompress or
# transfer.  Guard rails: a chunk is force-revisited after MAX_AGE
# epochs, and every FULL_EVERY-th evaluation runs a full refresh pass,
# so no stale contribution can survive convergence checking.
# ---------------------------------------------------------------------------


def chunk_sampling_mode() -> str:
    mode = str(get_config("streaming_chunk_sampling")).lower()
    if mode not in ("off", "duhl"):
        raise ValueError(
            f"streaming_chunk_sampling must be off|duhl, got {mode!r}"
        )
    return mode


class DuhlChunkSampler:
    """Per-chunk contribution bookkeeping for sampled epochs.  The
    solver feeds `visited(idx, score)` after recomputing a chunk and
    asks `select()` for the next epoch's chunk set (None = run a full
    pass: not primed yet, periodic refresh due, or the selection would
    cover everything anyway).

    The selection is FROZEN between full refreshes: within a refresh
    cycle every evaluation revisits the SAME chunk set, so the
    stale-compensated objective is a consistent (smoothly varying)
    function of the iterate — an L-BFGS line search backtracking over a
    selection that changed per evaluation would see the compensation
    offsets jump discontinuously and stall.  The periodic full pass
    refreshes every stale contribution and re-scores the next cycle's
    selection; `MAX_AGE` additionally force-includes any chunk whose
    contribution somehow outlived a cycle (a guard, not the steady
    state)."""

    MAX_AGE = 12  # no chunk's contribution may go staler than this
    FULL_EVERY = 8  # full refresh every Nth evaluation (cycle length)
    WARM_EVALS = 8  # full passes before sampling engages: the early
    # L-BFGS phase takes large steps whose line searches need the exact
    # objective; sampling pays off in the bulk-descent phase after it
    TAIL_EPS = 0.02  # once the iterate moves less than this (relative)
    # between full refreshes, sampling hands back to exact passes for
    # good: the stale-compensation bias would otherwise floor the
    # achievable tolerance, and there is nothing left to save — the
    # endgame's convergence checks must run on the exact objective

    def __init__(self, fraction: float, warm_evals: Optional[int] = None,
                 full_every: Optional[int] = None) -> None:
        self.fraction = min(max(float(fraction), 0.1), 1.0)
        if warm_evals is not None:
            self.WARM_EVALS = int(warm_evals)
        if full_every is not None:
            self.FULL_EVERY = max(2, int(full_every))
        self.n_chunks: Optional[int] = None
        self.score: Optional[np.ndarray] = None
        self.age: Optional[np.ndarray] = None
        self.sampled_epochs = 0
        self.chunk_visits_saved = 0
        self._evals = 0
        self._sel: Optional[list] = None  # frozen within the cycle
        self._ref: Optional[np.ndarray] = None  # iterate at last refresh
        self._exact = False  # tail reached: full passes from here on

    def ready(self) -> bool:
        return self.n_chunks is not None

    def start(self, n_chunks: int) -> None:
        self.n_chunks = int(n_chunks)
        self.score = np.full((n_chunks,), np.inf)
        self.age = np.zeros((n_chunks,), np.int64)

    def _pick(self) -> Optional[list]:
        n = self.n_chunks
        want = max(1, int(np.ceil(n * self.fraction)))
        order = np.argsort(-self.score, kind="stable")
        sel = set(int(i) for i in order[:want])
        sel |= set(int(i) for i in np.flatnonzero(self.age + 1 >= self.MAX_AGE))
        if len(sel) >= n:
            return None
        return sorted(sel)

    def select(self) -> Optional[list]:
        """Chunk positions for the next epoch; None = full pass."""
        self._evals += 1
        if (
            self._exact
            or not self.ready()
            or self._evals <= self.WARM_EVALS
            or (self._evals - 1) % self.FULL_EVERY == 0
        ):
            self._sel = None  # full refresh re-scores the next cycle
            return None
        if self._sel is None:
            self._sel = self._pick()
        if self._sel is None:
            return None
        self.sampled_epochs += 1
        self.chunk_visits_saved += self.n_chunks - len(self._sel)
        return self._sel

    def note_refresh(self, iterate: np.ndarray) -> None:
        """Called after every FULL pass with the solver's current
        iterate (flattened): detects the convergence tail — relative
        movement below TAIL_EPS since the previous full refresh — and
        switches to exact mode permanently."""
        it = np.asarray(iterate, np.float64).ravel()
        if self._ref is not None and self._ref.shape == it.shape:
            denom = max(float(np.linalg.norm(self._ref)), 1.0)
            if float(np.linalg.norm(it - self._ref)) / denom < self.TAIL_EPS:
                self._exact = True
        self._ref = it.copy()

    def visited(self, idx: int, score: float) -> None:
        self.score[idx] = float(score)
        self.age[idx] = 0

    def epoch_done(self, visited_idx) -> None:
        mask = np.ones((self.n_chunks,), bool)
        mask[list(visited_idx)] = False
        self.age[mask] += 1

    def summary(self) -> dict:
        return {
            "sampled_epochs": int(self.sampled_epochs),
            "chunk_visits_saved": int(self.chunk_visits_saved),
        }


def logreg_streaming_fit(
    path: str,
    features_col,
    features_cols,
    label_col: str,
    weight_col,
    family: str = "auto",
    l2: float = 0.0,
    l1: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = False,
    tol: float = 1e-6,
    max_iter: int = 100,
    history: int = 10,
    ls_max: int = 20,
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
    checkpoint_path: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
) -> dict:
    """Epoch-streaming logistic regression: host L-BFGS/OWL-QN
    (`ops/lbfgs.py lbfgs_minimize_host`) whose every evaluation streams the
    parquet chunks through one jitted loss+gradient accumulator step.
    Matches the in-memory `ops/logistic.py` objective exactly (Spark
    binomial/multinomial forms, unpenalized intercepts, standardization
    as scale-only without intercept)."""
    import jax
    import jax.numpy as jnp

    from .ops.lbfgs import lbfgs_minimize_host

    dtype = np.dtype(dtype)
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(
            probe_num_features(path, features_col, features_cols),
            dtype.itemsize,
        )
    scan = _label_moments_scan(
        path, features_col, features_cols, label_col, weight_col, dtype,
        chunk_rows, need_moments=standardization,
    )
    d, wsum = scan["d"], scan["wsum"]
    if not scan["integral"] or scan["y_min"] < 0:
        raise RuntimeError("Labels MUST be non-negative Integers")
    y_min, y_max = int(scan["y_min"]), int(scan["y_max"])
    if y_min == y_max:
        return {"degenerate_label": float(y_min), "d": d}
    n_classes = y_max + 1
    binomial = n_classes == 2 and family in ("auto", "binomial")

    mean = std = None
    inv_std_dev = mean_dev = None
    if standardization:
        mu = scan["s1"] / wsum
        var = np.maximum(scan["s2"] / wsum - mu * mu, 0.0)
        std = np.sqrt(var)
        inv_std = np.where(std > 0, 1.0 / np.where(std > 0, std, 1.0), 1.0)
        if fit_intercept:
            mean = mu
            mean_dev = jnp.asarray(mu.astype(dtype))
        inv_std_dev = jnp.asarray(inv_std.astype(dtype))

    C = n_classes
    n_coef = d if binomial else C * d
    n_param = n_coef + ((1 if binomial else C) if fit_intercept else 0)

    def chunk_obj(theta, X, w, y):
        if inv_std_dev is not None:
            X = (X - mean_dev) * inv_std_dev if mean_dev is not None else (
                X * inv_std_dev
            )
        if binomial:
            beta = theta[:d]
            b = theta[d] if fit_intercept else jnp.asarray(0.0, theta.dtype)
            margin = X @ beta + b
            sgn = 2.0 * y - 1.0
            return (jax.nn.softplus(-sgn * margin) * w).sum()
        Wm = theta[:n_coef].reshape(C, d)
        b = theta[n_coef:] if fit_intercept else jnp.zeros((C,), theta.dtype)
        logits = X @ Wm.T + b
        logp = jax.nn.log_softmax(logits, axis=-1)
        y1h = jax.nn.one_hot(y.astype(jnp.int32), C, dtype=theta.dtype)
        nll = -(y1h * logp).sum(axis=1)
        return (nll * w).sum()

    vg = jax.value_and_grad(chunk_obj)

    @partial_jit_donate
    def step(acc_l, acc_g, theta, X, w, y):
        loss, g = vg(theta, X, w, y)
        return acc_l + loss, acc_g + g

    lo, hi = _process_row_range(scan["n_total"])
    coef_mask = np.zeros((n_param,), np.float64)
    coef_mask[:n_coef] = 1.0
    epochs = {"n": 0}

    duhl = chunk_sampling_mode() == "duhl"
    sampler = stale_l = stale_g = None
    if duhl:
        sampler = DuhlChunkSampler(
            get_config("streaming_chunk_sample_fraction")
        )
        # per-chunk (loss, grad) — NOT donated/accumulated: the sampled
        # epochs need each chunk's own contribution to compensate the
        # unvisited ones and to score "is this chunk still moving"
        lg = jax.jit(vg)
    stream_key = chunk_stream_key(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, (lo, hi),
    )

    def _chunk_iter(sel):
        kw = dict(row_range=(lo, hi), device_ok=True)
        if sel is None:
            return enumerate(iter_chunks_prefetch(
                path, features_col, features_cols, label_col, weight_col,
                chunk_rows, dtype, **kw,
            ))
        return zip(sel, iter_chunks_prefetch(
            path, features_col, features_cols, label_col, weight_col,
            chunk_rows, dtype, select_chunks=frozenset(sel), **kw,
        ))

    def _duhl_eval(theta, theta_np):
        """One (possibly sampled) epoch: fresh per-chunk contributions
        for the selected chunks, last-computed (stale) contributions for
        the rest.  Selection engages only once the chunk cache replays
        the full stream — skipping chunks of a stream that still reads
        parquet would skip-scan the file for no win."""
        nonlocal stale_l, stale_g
        from .parallel.device_cache import chunk_stream_complete

        sel = None
        if (
            sampler.ready()
            and chunk_stream_complete(stream_key) == sampler.n_chunks
        ):
            sel = sampler.select()
        idxs, dev_l, dev_g = [], [], []
        host_l, host_g = [], []

        def _flush():
            # BOUNDED batched fetches (not one per epoch): per-chunk
            # contributions held on device until epoch end would grow
            # O(n_chunks x n_param) of device memory on a fit whose
            # whole point is bounded-memory epochs; per-chunk syncs
            # would serialize the prefetch pipeline away.  64 in-flight
            # chunks keeps both properties
            if dev_l:
                hl, hg = jax.device_get((dev_l, dev_g))
                host_l.extend(hl)
                host_g.extend(hg)
                dev_l.clear()
                dev_g.clear()

        for idx, (cX, cy, cw, n_c) in _chunk_iter(sel):
            w_host = _weights_host(cw, n_c, chunk_rows, np.float32)
            l, g = lg(
                theta, _dev_chunk(cX, np.float32), jnp.asarray(w_host),
                jnp.asarray(np.asarray(cy, np.float32)),
            )
            idxs.append(idx)
            dev_l.append(l)
            dev_g.append(g)
            if len(dev_l) >= 64:
                _flush()
        _flush()
        if not sampler.ready():
            sampler.start(len(idxs))
            stale_l = np.zeros((len(idxs),), np.float64)
            stale_g = np.zeros((len(idxs), n_param), np.float64)
        for i, idx in enumerate(idxs):
            g_new = np.asarray(host_g[i], np.float64)
            sampler.visited(idx, float(np.linalg.norm(g_new - stale_g[idx])))
            stale_l[idx] = float(host_l[i])
            stale_g[idx] = g_new
        sampler.epoch_done(idxs)
        if sel is None:
            sampler.note_refresh(theta_np)
        return float(stale_l.sum()), stale_g.sum(axis=0)

    def oracle(theta_np: np.ndarray):
        theta = jnp.asarray(theta_np.astype(np.float32))
        if duhl:
            tot_l, tot_g = _duhl_eval(theta, theta_np)
            agg = _sum_across_processes(
                {"l": np.asarray(tot_l, np.float64), "g": tot_g}
            )
        else:
            acc_l = jnp.zeros((), jnp.float32)
            acc_g = jnp.zeros((n_param,), jnp.float32)
            for cX, cy, cw, n_c in iter_chunks_prefetch(
                path, features_col, features_cols, label_col, weight_col,
                chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
            ):
                w_host = _weights_host(cw, n_c, chunk_rows, np.float32)
                acc_l, acc_g = step(
                    acc_l, acc_g, theta,
                    _dev_chunk(cX, np.float32),
                    jnp.asarray(w_host),
                    jnp.asarray(np.asarray(cy, np.float32)),
                )
            host_l, host_g = jax.device_get((acc_l, acc_g))
            agg = _sum_across_processes(
                {"l": np.asarray(host_l, np.float64),
                 "g": np.asarray(host_g, np.float64)}
            )
        epochs["n"] += 1
        beta = theta_np * coef_mask
        f = float(agg["l"]) / wsum + 0.5 * l2 * float(beta @ beta)
        grad = np.asarray(agg["g"], np.float64) / wsum + l2 * beta
        return f, grad

    # m (history) is shape-critical: the checkpointed S/Y buffers are
    # (m, n), so a resume under a different memory size must tag-mismatch
    ckpt_tag = (
        f"logreg|{path}|n={scan['n_total']}|d={d}|C={n_classes}|"
        f"l2={l2}|l1={l1}|int={fit_intercept}|std={standardization}|"
        f"m={int(history)}|ls={int(ls_max)}"
    )
    if checkpoint_path is None and checkpoint_dir:
        checkpoint_path = checkpoint_file_for(checkpoint_dir, ckpt_tag)
    theta, n_iter, converged, hist = lbfgs_minimize_host(
        oracle,
        np.zeros((n_param,), np.float64),
        max_iter=max_iter,
        tol=tol,
        history=history,
        l1=l1,
        l1_mask=coef_mask,
        ls_max=ls_max,
        checkpoint_path=checkpoint_path,
        checkpoint_tag=ckpt_tag,
    )
    logger.info(
        f"Epoch-streaming logreg: {n_iter} iterations, {epochs['n']} data "
        f"epochs over {scan['n_total']} rows"
    )
    if binomial:
        coef = theta[:d].reshape(1, d)
        intercept = np.asarray([theta[d] if fit_intercept else 0.0])
    else:
        coef = theta[:n_coef].reshape(C, d)
        intercept = (
            theta[n_coef:] if fit_intercept else np.zeros((C,))
        )
    return {
        "coef": coef,
        "intercept": intercept,
        "n_classes": n_classes,
        "d": d,
        "n_iter": n_iter,
        "converged": converged,
        "history": hist,
        "mean": mean,
        "std": std,
        "binomial": binomial,
        # TRUE dataset passes (accepted iterates + line-search backtracks)
        "epochs": epochs["n"],
        # DuHL sampling accounting (0s when streaming_chunk_sampling=off)
        **(sampler.summary() if sampler is not None else {}),
    }


def kmeans_streaming_fit(
    path: str,
    features_col,
    features_cols,
    weight_col,
    k: int,
    seed: int,
    max_iter: int = 300,
    tol: float = 1e-4,
    init: str = "scalable-k-means++",
    init_steps: int = 2,
    oversample: float = 2.0,
    dtype=np.float32,
    chunk_rows: Optional[int] = None,
    init_rows: int = 262_144,
    checkpoint_path: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
) -> dict:
    """Epoch-streaming Lloyd: centers are seeded from a strided global
    subsample (k-means|| on device), then each iteration streams the
    chunks through a jitted assign+accumulate step (per-cluster sums /
    counts / cost in a donated accumulator) and updates centers on host.
    Convergence matches `ops/kmeans.py kmeans_fit` (max center shift).
    `checkpoint_path`: per-iteration center checkpoint for preemption
    recovery (same contract as `lbfgs_minimize_host`)."""
    import jax
    import jax.numpy as jnp

    from .ops.kmeans import (
        kmeans_init,
        kmeans_parallel_init,
        lloyd_partials,
        seed_sample_stride,
    )

    dtype = np.dtype(dtype)
    d = probe_num_features(path, features_col, features_cols)
    if chunk_rows is None:
        chunk_rows = chunk_rows_for(d, dtype.itemsize)
    n_total = parquet_row_count(path)
    if n_total < k:
        raise ValueError(f"k={k} exceeds the dataset row count {n_total}")
    lo, hi = _process_row_range(n_total)

    # ---- strided global subsample for seeding (every process fills the
    # GLOBAL reservoir slots of its ingest range, then the slot-disjoint
    # accumulators wire-merge in rank order on every rank).  The
    # collection runs as the registered `kmeans_sample` statistic
    # program (stats/programs.py): slot-disjoint per-chunk folds, so any
    # chunking assembles the identical sample (byte parity with the
    # pre-migration inline loop asserted by tests/test_stat_programs.py)
    stride = seed_sample_stride(n_total, init_rows)
    cap = (n_total - 1) // stride + 1
    from .stats.engine import iter_chunk_accs
    from .stats.programs import get_program

    prog = get_program("kmeans_sample")
    ks_opts = {"stride": stride, "cap": cap}
    acc = iter_chunk_accs(
        "kmeans_sample",
        iter_chunks(
            path, features_col, features_cols, None, weight_col,
            chunk_rows, dtype, row_range=(lo, hi),
        ),
        d, dtype,
        opts=ks_opts,
        offset0=lo,
    )
    from .parallel.context import process_topology as _ptopo

    if _ptopo()[0] > 1:
        # merge the slot-disjoint per-rank reservoirs (each rank filled
        # only the GLOBAL slots of its ingest range) in ascending rank
        # order: every rank assembles the identical global sample,
        # byte-for-byte the single-process fill.  The padded-allgather
        # concatenation this replaces changed the sample SHAPE (and
        # zero-row layout) with process count, which perturbed the
        # seeding draws — 1p vs Np centers diverged (ROADMAP item-1
        # leftover; parity asserted by tests/test_multihost_datapath)
        import io

        from .parallel.context import reduce_blob_list
        from .stats.programs import merge_accs

        buf = io.BytesIO()
        np.savez(buf, **{f: np.asarray(v) for f, v in acc.items()})
        states = []
        for blob in reduce_blob_list("kmeans_seed_sample", buf.getvalue()):
            with np.load(io.BytesIO(blob)) as z:
                states.append({f: np.array(z[f]) for f in z.files})
        acc = states[0]
        for s in states[1:]:
            acc = merge_accs(prog, acc, s, ks_opts)
    sample = prog.finalize(acc, {})
    Xs_host = np.asarray(sample["X"], dtype)
    ws_host = np.asarray(sample["w"], np.float64)
    valid_s = ws_host > 0
    if valid_s.sum() < k:
        raise ValueError(
            f"Seeding subsample holds {int(valid_s.sum())} weighted rows < k={k}"
        )
    Xs = jnp.asarray(Xs_host.astype(dtype))
    ws = jnp.asarray(ws_host.astype(dtype))
    if init in ("scalable-k-means++", "k-means||"):
        m = max(
            int(round(oversample * k)),
            -(-(k - 1) // max(init_steps, 1)),
            1,
        )
        m = min(m, int(Xs.shape[0]))
        centers = kmeans_parallel_init(
            Xs, ws, k, seed, rounds=max(init_steps, 1), m=m
        )
    else:
        centers = kmeans_init(Xs, ws, k, seed, init)

    # ---- streamed Lloyd ----
    @partial_jit_donate
    def assign_step(acc, counts, C, X, w):
        sums, cost = acc
        part = lloyd_partials(C, X, w, k)
        return (sums + part[0], cost + part[2]), counts + part[1]

    duhl = chunk_sampling_mode() == "duhl"
    sampler = None
    stale = {"sums": None, "counts": None, "cost": None}
    if duhl:
        # Lloyd has no line search and tolerates stale assign stats far
        # better than L-BFGS tolerates a stale objective: engage after
        # 3 exact passes, refresh every 4th
        sampler = DuhlChunkSampler(
            get_config("streaming_chunk_sample_fraction"),
            warm_evals=3, full_every=4,
        )

        # per-chunk assign stats (NOT accumulated): the sampled Lloyd
        # passes need each chunk's own (sums, counts, cost) so
        # unvisited chunks can contribute their last-computed stats
        chunk_stats = jax.jit(lambda C, X, w: lloyd_partials(C, X, w, k))
    stream_key = chunk_stream_key(
        path, features_col, features_cols, None, weight_col,
        chunk_rows, dtype, (lo, hi),
    )

    def one_pass(C_host: np.ndarray):
        C_dev = jnp.asarray(C_host.astype(dtype))
        acc = (jnp.zeros((k, d), jnp.float32), jnp.zeros((), jnp.float32))
        counts = jnp.zeros((k,), jnp.float32)
        for cX, _, cw, n_c in iter_chunks_prefetch(
            path, features_col, features_cols, None, weight_col,
            chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
        ):
            w_host = _weights_host(cw, n_c, chunk_rows, np.float32)
            acc, counts = assign_step(
                acc, counts, C_dev,
                _dev_chunk(cX, np.float32), jnp.asarray(w_host),
            )
        host = jax.device_get({"sums": acc[0], "counts": counts, "cost": acc[1]})
        agg = _sum_across_processes(
            {kk: np.asarray(v, np.float64) for kk, v in host.items()}
        )
        return agg["sums"], agg["counts"], float(agg["cost"])

    def one_pass_duhl(C_host: np.ndarray):
        """DuHL-sampled Lloyd pass: chunks with the largest cost
        contribution (points far from their centers — the ones that
        still move centers) recompute under the current centers; the
        rest contribute their last-computed assign statistics."""
        from .parallel.device_cache import chunk_stream_complete

        C_dev = jnp.asarray(C_host.astype(dtype))
        sel = None
        if (
            sampler.ready()
            and chunk_stream_complete(stream_key) == sampler.n_chunks
        ):
            sel = sampler.select()
        if sel is None:
            it = enumerate(iter_chunks_prefetch(
                path, features_col, features_cols, None, weight_col,
                chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
            ))
        else:
            it = zip(sel, iter_chunks_prefetch(
                path, features_col, features_cols, None, weight_col,
                chunk_rows, dtype, row_range=(lo, hi), device_ok=True,
                select_chunks=frozenset(sel),
            ))
        idxs, dev_stats, host_stats = [], [], []

        def _flush():
            # bounded batched fetches: per-chunk (k, d) assign stats on
            # device until epoch end would be O(n_chunks x k x d) HBM
            if dev_stats:
                host_stats.extend(jax.device_get(dev_stats))
                dev_stats.clear()

        for idx, (cX, _, cw, n_c) in it:
            w_host = _weights_host(cw, n_c, chunk_rows, np.float32)
            dev_stats.append(chunk_stats(
                C_dev, _dev_chunk(cX, np.float32), jnp.asarray(w_host)
            ))
            idxs.append(idx)
            if len(dev_stats) >= 16:
                _flush()
        _flush()
        if not sampler.ready():
            n_ch = len(idxs)
            sampler.start(n_ch)
            stale["sums"] = np.zeros((n_ch, k, d), np.float64)
            stale["counts"] = np.zeros((n_ch, k), np.float64)
            stale["cost"] = np.zeros((n_ch,), np.float64)
        for i, idx in enumerate(idxs):
            s, c, co = host_stats[i]
            stale["sums"][idx] = np.asarray(s, np.float64)
            stale["counts"][idx] = np.asarray(c, np.float64)
            stale["cost"][idx] = float(co)
            sampler.visited(idx, float(co))
        sampler.epoch_done(idxs)
        if sel is None:
            sampler.note_refresh(np.asarray(C_host, np.float64).ravel())
        agg = _sum_across_processes({
            "sums": stale["sums"].sum(axis=0),
            "counts": stale["counts"].sum(axis=0),
            "cost": np.asarray(stale["cost"].sum(), np.float64),
        })
        return agg["sums"], agg["counts"], float(agg["cost"])

    from .resilience import maybe_inject
    from .resilience.checkpoint import (
        clear_checkpoint,
        load_checkpoint,
        save_checkpoint,
    )

    ckpt_tag = f"kmeans|{path}|n={n_total}|d={d}|k={k}|seed={seed}"
    if checkpoint_path is None and checkpoint_dir:
        checkpoint_path = checkpoint_file_for(checkpoint_dir, ckpt_tag)

    C_host = np.asarray(jax.device_get(centers), np.float64)
    start_it = 0
    resumed = (
        load_checkpoint(checkpoint_path, ckpt_tag) if checkpoint_path else None
    )
    if resumed is not None:
        C_host = np.asarray(resumed["centers"], np.float64)
        start_it = int(resumed["it"])
        from .tracing import event

        event("kmeans_resume", detail=f"it={start_it}", log=logger)
        logger.info(
            f"Resuming epoch-streaming kmeans at iteration {start_it}"
        )
    from .telemetry import Heartbeat

    hb = Heartbeat("kmeans_streaming", total=max_iter, log=logger)
    n_iter = start_it
    cost = 0.0
    for n_iter in range(start_it + 1, max_iter + 1):
        maybe_inject("kmeans_lloyd")
        sums, counts, cost = (
            one_pass_duhl(C_host) if duhl else one_pass(C_host)
        )
        hb.beat(n_iter, loss=cost)
        new_C = np.where(
            counts[:, None] > 0,
            sums / np.where(counts > 0, counts, 1.0)[:, None],
            C_host,
        )
        shift2 = float(((new_C - C_host) ** 2).sum(axis=1).max())
        C_host = new_C
        if checkpoint_path:
            save_checkpoint(
                checkpoint_path, ckpt_tag, {"centers": C_host, "it": n_iter}
            )
        if shift2 <= tol * tol:
            break
    # final cost under the final centers
    _, _, cost = one_pass(C_host)
    # end-mark on normal completion (Heartbeat.close) AFTER the final
    # cost pass: a death before the result exists keeps the solver
    # gauges visible for the flight recorder's post-mortem
    hb.close()
    if checkpoint_path:
        clear_checkpoint(checkpoint_path)
    logger.info(
        f"Epoch-streaming kmeans: {n_iter} Lloyd passes over {n_total} rows"
    )
    return {
        "centers": C_host, "cost": cost, "n_iter": n_iter, "d": d,
        **(sampler.summary() if sampler is not None else {}),
    }
