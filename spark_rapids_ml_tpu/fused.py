#
# Fused stage-and-solve engine — the one-pass sufficient-statistics
# estimators (PCA, LinearRegression) solve WHILE they stage.  The
# two-phase path pays stage + solve strictly additively; here each host
# chunk's Gram/moment/cross contribution is folded into a donated device
# accumulator the moment the chunk lands on the mesh, with the host
# producer thread (utils.prefetch_iter — the PR-2 staging pipeline's
# overlap primitive) prepping chunk N+1 while the mesh accumulates chunk
# N.  The full staged array never exists: HBM holds one sharded chunk +
# the (d,d)-class accumulator, and wall time collapses toward
# max(stage, solve).  The "Parallel-and-stream accelerator" overlap
# pattern and Snap ML's chunk-local host/accelerator accumulate
# (PAPERS.md) are the templates.
#
# Routing lives in core.py (`fused_stage_solve` conf: auto|on|off);
# the chunk update math lives in ops/stats.py (shared with the
# multi-pass streaming fits, incl. the Kahan-compensated
# `stats_precision="high_compensated"` level); the randomized PCA
# range-finder (ops/pca.py) composes: each of its tall-skinny passes is
# one stage-overlapped accumulation here.
#
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import numpy as np

from .config import get_config
from .telemetry.utilization import (
    interval_overlap_s as _interval_overlap_s,
    merge_intervals as _merge_intervals,
)
from .tracing import current_run_id, event, fact
from .utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.fused")

# `fused_stage_solve="auto"` fuses once the estimated staged bytes reach
# this floor: below it one plain staging beats the per-chunk dispatch
# overhead and the two-phase path keeps its exact single-matmul stats
_AUTO_MIN_BYTES = 64 * 1024 * 1024

# aim for at least this many chunks per pass so the producer thread has
# something to run ahead on (one-chunk passes cannot overlap)
_MIN_CHUNKS = 8
_MIN_CHUNK_ROWS = 1024


def fused_mode() -> str:
    mode = str(get_config("fused_stage_solve")).lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"fused_stage_solve must be auto|on|off, got {mode!r}"
        )
    return mode


def fused_enabled(est_bytes: float) -> bool:
    """Whether the conf routes an ELIGIBLE fit (dense, statistics-capable
    — the caller checks those) through the fused engine: "on" always,
    "auto" once the staged-bytes estimate clears `_AUTO_MIN_BYTES`,
    "off" never.  Multi-process fits run fused too: each rank folds its
    ingest share on its LOCAL devices and the partials meet in one
    reduction at pass_complete (parallel/context.py) — the gate only
    drops to the two-phase paths when no reduce seam is available
    (jax.distributed not initialized)."""
    mode = fused_mode()
    if mode == "off":
        return False
    import jax

    if jax.process_count() > 1:
        from .parallel.context import cross_process_reduce_ready

        if not cross_process_reduce_ready():
            return False
    if mode == "on":
        return True
    return float(est_bytes) >= _AUTO_MIN_BYTES


@functools.lru_cache(maxsize=32)
def _jitted_steps(
    kind: str, d: int, l: int, dtype_str: str,
    precision: str, compensated: bool,
):
    """(weighted, unweighted) donated jitted accumulator steps per
    (kind, shape, dtype, precision) — repeated fused fits at the same
    shape reuse the compiled programs instead of re-tracing a fresh
    closure every fit (measured ~80 ms/fit of re-lowering on the CPU
    mesh).  The unweighted variant skips the `X * w` chunk-sized
    materialization for full chunks of weightless fits (ops/stats.py).
    `precision`/`compensated` key the conf values baked in at trace
    time; the initial zeros accumulator is built FRESH per fit (it is
    donated into the first step and must never be reused).

    The specs resolve through the statistic-program registry
    (stats/programs.py STAT_PROGRAMS) — `kind` IS the registered
    program name, so the fused estimators and any other registry
    consumer share one owner for the update math (the PR-8 specs,
    migrated)."""
    import jax

    from .stats.programs import get_program

    dtype = np.dtype(dtype_str)
    step, unw = get_program(kind).make_step(d, dtype, {"l": l})
    return (
        jax.jit(step, donate_argnums=0),
        jax.jit(unw, donate_argnums=0),
    )


def _acc_spec(kind: str, d: int, l: int, dtype):
    """(fresh initial accumulator, cached (weighted, unweighted) jitted
    steps) for the registered statistic program `kind`."""
    from .ops.precision import stats_compensated
    from .stats.programs import get_program

    dtype = np.dtype(dtype)
    acc = get_program(kind).init(d, dtype, {"l": l})
    steps = _jitted_steps(
        kind, d, l, dtype.str,
        str(get_config("stats_precision")).lower(), stats_compensated(),
    )
    return acc, steps


def fused_chunk_rows(n: int, d: int, itemsize: int, n_dev: int) -> int:
    """Rows per fused chunk: bounded by `staging_chunk_bytes` clamped to
    the single-transfer ceiling (the same sizing rule as the staging
    pipeline's pieces — mesh._staging_chunk_rows), floored so a pass
    still yields >= `_MIN_CHUNKS` chunks to overlap, and device-aligned
    so every chunk shards evenly over the mesh."""
    from .parallel.mesh import _MAX_PUT_BYTES

    row_bytes = max(d * itemsize, 1)
    budget = max(
        1,
        min(int(get_config("staging_chunk_bytes")), _MAX_PUT_BYTES)
        // row_bytes,
    )
    rows = min(budget, max(-(-n // _MIN_CHUNKS), _MIN_CHUNK_ROWS))
    rows = min(rows, max(n, 1))
    return -(-rows // n_dev) * n_dev


def iter_host_chunks(
    X: np.ndarray,
    y: Optional[np.ndarray],
    weight: Optional[np.ndarray],
    chunk_rows: int,
    dtype: np.dtype,
    label_dtype: Optional[np.dtype] = None,
) -> Iterable[Tuple[np.ndarray, Optional[np.ndarray], np.ndarray]]:
    """Fixed-shape `(X_chunk, y_chunk, w_chunk)` host chunks of an
    in-memory batch, fully PREPARED (cast + zero-padded tail + validity
    weights) inside `__next__` — on the fused pipeline this runs on the
    producer thread, overlapped with the device accumulate.  Mirrors
    `streaming.iter_chunks` semantics: padding rows carry weight 0, so
    they are mathematically absent from every statistic."""
    dtype = np.dtype(dtype)
    ldt = np.dtype(label_dtype) if label_dtype is not None else dtype
    n = int(X.shape[0])
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        rows = hi - lo
        if rows == chunk_rows:
            cX = np.ascontiguousarray(X[lo:hi], dtype=dtype)
            # None = full unweighted chunk: the engine dispatches the
            # unweighted step (skips the X*w chunk copy entirely)
            cw = (
                None
                if weight is None
                else np.asarray(weight[lo:hi], dtype)
            )
            cy = (
                None if y is None
                else np.ascontiguousarray(
                    np.asarray(y[lo:hi]).reshape(-1), dtype=ldt
                )
            )
        else:  # zero-padded tail chunk (padding weight stays 0)
            cX = np.zeros((chunk_rows,) + X.shape[1:], dtype)
            cX[:rows] = X[lo:hi]
            cw = np.zeros((chunk_rows,), dtype)
            cw[:rows] = 1.0 if weight is None else np.asarray(
                weight[lo:hi], dtype
            )
            cy = None
            if y is not None:
                cy = np.zeros((chunk_rows,), ldt)
                cy[:rows] = np.asarray(y[lo:hi]).reshape(-1)
        yield cX, cy, cw


# measured single-reader decode throughput (updated by `_range_chunks`
# after every un-cached single-reader pass): the `auto` reader count is
# sink-bounded by it — decode only needs to outrun the device transfer
_DECODE_RATE: dict = {}

_MAX_AUTO_READERS = 16


def resolve_parquet_readers(path: Optional[str] = None) -> int:
    """Effective parallel-reader count from the `fused_parquet_readers`
    conf.  Explicit ints pin the count (back-compat); "auto" probes the
    host: os.cpu_count() capped at `_MAX_AUTO_READERS`, then bounded by
    the measured decode-vs-sink rates when both are on record (readers
    beyond sink_rate/decode_rate + 1 only contend for memory
    bandwidth).  Row-group availability clamps later, in
    `_partition_row_groups`.  The decision (mode, count, reason) is the
    run's `parquet_readers` fact: "why did this fit decode with N
    readers" is answered by the fit report's `solver_decision`."""
    import os

    raw = get_config("fused_parquet_readers")
    mode = str(raw).strip().lower()
    if mode == "auto":
        cores = os.cpu_count() or 1
        readers = max(1, min(int(cores), _MAX_AUTO_READERS))
        reason = f"cpu_count={cores}"
        decode_mbs = _DECODE_RATE.get("mb_per_s")
        if decode_mbs:
            reason += f", measured_decode={decode_mbs:.0f}MB/s"
            from .parallel.mesh import last_put_rate_mb_per_s

            sink_mbs = last_put_rate_mb_per_s()
            if sink_mbs:
                need = int(np.ceil(
                    float(sink_mbs) / max(float(decode_mbs), 1e-9)
                )) + 1
                if need < readers:
                    readers = max(1, need)
                    reason += f", sink-bounded at {sink_mbs:.0f}MB/s put"
    else:
        readers = max(1, int(raw))
        mode = "explicit"
        reason = "pinned by conf"
    fact(
        "parquet_readers",
        parquet_readers=int(readers),
        parquet_readers_mode=mode,
        parquet_readers_reason=reason,
    )
    return readers


def _partition_row_groups(path: str, readers: int) -> Optional[list]:
    """Split a single parquet FILE's row groups into `readers`
    row-balanced contiguous shares.  None when the path is a dataset
    directory or has too few groups to split — the caller then runs one
    in-order reader."""
    import os

    if readers <= 1 or os.path.isdir(path):
        return None
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    sizes = [md.row_group(i).num_rows for i in range(md.num_row_groups)]
    if len(sizes) < 2:
        return None
    readers = min(readers, len(sizes))
    total = sum(sizes)
    shares, cur, acc = [], [], 0
    per = -(-total // readers)
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        if acc >= per and len(shares) < readers - 1:
            shares.append(cur)
            cur, acc = [], 0
    if cur:
        shares.append(cur)
    return shares if len(shares) > 1 else None


def process_row_group_shares(path: str, n_proc: int) -> Optional[list]:
    """Partition a parquet FILE's row groups into exactly `n_proc`
    contiguous row-balanced shares — the per-PROCESS ingest split of the
    fused producer (each host decodes only its share; the commutative
    accumulators make arrival order irrelevant).  Deterministic: pure
    arithmetic over the file metadata, identical on every rank.
    Coverage-asserted: the shares concatenate to every row group exactly
    once.  None when the path is a dataset directory or has fewer groups
    than processes — the caller then falls back to the chunk-index
    modulo split."""
    import os

    if n_proc <= 1 or os.path.isdir(path):
        return None
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    sizes = [md.row_group(i).num_rows for i in range(md.num_row_groups)]
    if len(sizes) < n_proc:
        return None
    total = sum(sizes)
    per = -(-total // n_proc)
    shares, cur, acc = [], [], 0
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        if acc >= per and len(shares) < n_proc - 1:
            shares.append(cur)
            cur, acc = [], 0
    if cur:
        shares.append(cur)
    while len(shares) < n_proc:
        shares.append([])
    flat = [g for sh in shares for g in sh]
    if flat != list(range(len(sizes))):  # pragma: no cover - invariant
        raise AssertionError(
            f"process row-group shares do not cover {path} exactly once: "
            f"{shares}"
        )
    return shares


def _share_row_starts(path: str, shares: list) -> list:
    """Global first-row offset of each contiguous row-group share (the
    `_partition_row_groups` / `process_row_group_shares` output): prefix
    sums over the file's row-group sizes — pure metadata arithmetic,
    identical on every rank, same determinism contract as the split
    itself.  Empty shares get 0 (they yield no chunks anyway)."""
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    sizes = [md.row_group(i).num_rows for i in range(md.num_row_groups)]
    starts = np.concatenate(([0], np.cumsum(sizes)))
    return [int(starts[sh[0]]) if sh else 0 for sh in shares]


def _reader_batches(path: str, columns, chunk_rows: int, groups=None):
    """Arrow record batches for the fused producer: a row-group-pruned
    `ParquetFile` reader for single files (measurably leaner than the
    dataset scanner on this path, and `groups` lets a parallel range
    reader decode ONLY its share — never scan-and-skip), with the
    dataset-scanner fallback for directory datasets."""
    import os

    if not os.path.isdir(path):
        import pyarrow.parquet as pq

        pf = pq.ParquetFile(path)
        kw = {} if groups is None else {"row_groups": list(groups)}
        yield from pf.iter_batches(
            batch_size=chunk_rows, columns=columns, **kw
        )
        return
    import pyarrow.dataset as pads

    yield from pads.dataset(path, format="parquet").to_batches(
        columns=columns, batch_size=chunk_rows
    )


def _range_chunks(
    path: str,
    features_col,
    features_cols,
    label_col,
    weight_col,
    chunk_rows: int,
    dtype: np.dtype,
    ldt: np.dtype,
    groups,
    base_offset: Optional[int] = None,
) -> Iterable[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]]:
    """One reader's share of the fused parquet producer: decode + prepare
    `(X, y, w)` chunks of its row-group share
    (`streaming.chunks_from_batches` — the exact iter_chunks decode and
    fixed-shape chunking).  `w` is None for full unweighted chunks (the
    engine's fast step) and the zero-weighted padding vector on the
    share's tail chunk.

    With `base_offset` (the GLOBAL row index of this share's first row),
    chunks yield as 4-tuples `(X, y, w, global_offset)` — the exact
    first-row offset of each chunk in the whole FILE, tracked through
    valid-row counts so a partial tail chunk cannot skew later offsets.
    Offset-addressed accumulators (the kmeans_sample reservoir) need
    this to place rows identically no matter which rank decodes them."""
    from .streaming import _scan_columns, _weights_host, chunks_from_batches

    columns = _scan_columns(features_col, features_cols, label_col, weight_col)
    it = iter(chunks_from_batches(
        _reader_batches(path, columns, chunk_rows, groups),
        features_col, features_cols, label_col, weight_col,
        chunk_rows, np.dtype(dtype),
    ))
    off = None if base_offset is None else int(base_offset)
    decode_s = 0.0
    rows = 0
    nbytes = 0
    while True:
        t0 = time.perf_counter()
        try:
            cX, cy, cw, n_c = next(it)
        except StopIteration:
            break
        decode_s += time.perf_counter() - t0
        rows += int(n_c)
        nbytes += cX.nbytes
        if cw is None and n_c == chunk_rows:
            w_host = None  # full unweighted chunk -> unweighted step
        else:
            w_host = np.asarray(_weights_host(cw, n_c, chunk_rows, dtype))
        cy_out = None
        if cy is not None:
            cy_out = np.zeros((chunk_rows,), ldt)
            cy_out[:n_c] = np.asarray(cy[:n_c]).reshape(-1)
        if off is None:
            yield cX, cy_out, w_host
        else:
            yield cX, cy_out, w_host, off
            off += int(n_c)
    # single-reader decode rate feeds resolve_parquet_readers("auto");
    # too-short passes are scheduler noise, not a measurement
    if groups is None and decode_s > 0.02 and rows:
        _DECODE_RATE.update(
            rows_per_s=rows / decode_s, mb_per_s=nbytes / decode_s / 1e6,
        )


def iter_parquet_chunks(
    path: str,
    features_col,
    features_cols,
    label_col,
    weight_col,
    chunk_rows: int,
    dtype: np.dtype,
    label_dtype: Optional[np.dtype] = None,
    readers: Optional[int] = None,
    prep: Optional[Dict[str, Any]] = None,
    with_offsets: bool = False,
) -> Iterable[Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]]:
    """Parquet producer for the fused engine: the chunk decode (the
    dominant host cost of the refconfig fits) runs through a row-group-
    pruned reader, optionally split across `readers` PARALLEL range-
    reader threads (`fused_parquet_readers` conf), each decoding ONLY
    its own row-group share.  Chunk ARRIVAL ORDER is then arbitrary —
    which is exactly why this lives on the fused path only: the
    statistics accumulators are commutative sums, so order is
    irrelevant, while the two-phase staging path must place rows at
    their global offsets and keeps its single in-order scan.  Parallel
    readers pay off when the scan has idle time to recover (real IO, a
    multi-core host — the parallel-sharded-reader direction of ROADMAP
    item 4); the 1-core CI box measured the Arrow scan CPU-bound with
    readers=2 ~= readers=1, hence the conservative default of 1.

    When `prep` is given, each reader's decode time and wall intervals
    accumulate there ({"s": float, "iv": [(t0, t1)]}) — the engine's
    overlap measurement; interval lists from concurrent readers overlap
    and are union-merged by the consumer.

    The whole producer runs through the chunk cache: the first pass of
    a (path-stamp, scan-params) stream decodes parquet and records the
    prepared chunks; every later identical pass — the randomized PCA
    range-finder re-streaming the SAME file 2+power_iters times within
    one fit is the headline consumer — replays them without touching
    disk or the reader pool.  Replayed feature blocks may arrive
    device-resident (the engine's `device_put` reshards them in place);
    on a replayed pass the serve time is what lands in `prep`.

    `with_offsets=True` yields 4-tuples `(X, y, w, global_offset)`:
    each chunk carries the GLOBAL first-row index of its rows in the
    file, exact under every split mode (row-group shares, chunk-modulo
    fallback, parallel range readers) — what lets offset-addressed
    accumulators (the kmeans_sample reservoir) place rows identically
    at any process count.  The offset variant keys a DISTINCT cache
    stream: its cached tuples have four parts."""
    ldt = np.dtype(label_dtype) if label_dtype is not None else np.dtype(dtype)
    if readers is None:
        readers = resolve_parquet_readers(path)

    from .parallel.device_cache import (
        cached_chunk_stream,
        chunk_stream_complete,
    )
    from .streaming import _chunk_stream_key

    tag = ("fused+goff:" if with_offsets else "fused:") + ldt.str
    key = _chunk_stream_key(
        path, features_col, features_cols, label_col, weight_col,
        chunk_rows, dtype, None, tag=tag,
    )

    def _timed(it):
        if prep is None:
            return it
        from .parallel.mesh import timed_iter

        return timed_iter(it, prep)

    from .parallel.context import process_topology
    from .resilience.pod import active_recovery_plan, record_pass_manifest

    # the TOPOLOGY view, not jax.process_count(): after a rank loss the
    # pod layer shrinks the reduce group without tearing down the jax
    # backend, and the ingest partition must follow the survivors
    n_proc, pid = process_topology()

    plan = active_recovery_plan()
    plan_shares = (
        process_row_group_shares(path, plan.share_n)
        if plan is not None else None
    )
    if plan is not None and plan_shares is not None:
        # RESUME under a rank-loss recovery plan: this survivor decodes
        # the ORIGINAL share_n-way layout's shares the plan assigned it —
        # its own pre-loss share (same stream key as the interrupted
        # pass, so it replays from the chunk cache at epoch-2 cost) plus
        # any share inherited from a dead rank (cache miss on first
        # post-loss pass: parquet decode, cached for later passes).
        # Every row of the file is covered exactly once across the
        # survivors, which is all the commutative accumulators need for
        # byte parity with a fault-free fit.
        plan_starts = (
            _share_row_starts(path, plan_shares) if with_offsets else None
        )
        entries = plan.assignments.get(pid, ())
        record_pass_manifest(
            path=str(path), tag=tag, share_n=plan.share_n,
            generation=plan.generation,
            assignments={
                str(r): [list(e) for e in v]
                for r, v in plan.assignments.items()
            },
        )

        def _share_stream(share_idx: int, owner_boot: int):
            # keyed by the ORIGINAL topology slot (share_n, owner boot
            # rank): the survivor's own share reuses its pre-loss cache
            # entries byte-for-byte
            skey = _chunk_stream_key(
                path, features_col, features_cols, label_col,
                weight_col, chunk_rows, dtype, None, tag=tag,
                topology=(plan.share_n, owner_boot),
            )
            groups = plan_shares[share_idx]

            def _ssource():
                if not groups:
                    return iter(())
                base = (
                    plan_starts[share_idx] if with_offsets else None
                )
                return _range_chunks(
                    path, features_col, features_cols, label_col,
                    weight_col, chunk_rows, dtype, ldt, groups,
                    base_offset=base,
                )

            # ordered=True: a vanished spill blob mid-serve degrades to
            # source replay at the failed position instead of forcing a
            # restart of an already-part-folded recovery pass
            return cached_chunk_stream(
                skey, _ssource, device_elem=0, serve_device=True,
                ordered=True,
            )

        def _plan_chained():
            for share_idx, owner_boot in entries:
                yield from _share_stream(int(share_idx), int(owner_boot))

        yield from _timed(_plan_chained())
        return

    if n_proc > 1:
        # multi-host ingest partition: this process decodes ONLY its
        # deterministic row-group share (coverage-asserted); the
        # commutative accumulators make the resulting arbitrary global
        # chunk order irrelevant, and the per-rank chunk-stream key
        # keeps each host's cache holding only its own slice
        record_pass_manifest(
            path=str(path), tag=tag, share_n=n_proc, generation=None,
            assignments={str(pid): [[pid, pid]]},
        )
        shares = process_row_group_shares(path, n_proc)

        def _source():
            if shares is not None:
                if not shares[pid]:
                    return iter(())
                # global offset of the share's first row: prefix sum of
                # the row-group sizes ahead of it — every rank's chunks
                # land at the same indices a single-process scan gives
                base = (
                    _share_row_starts(path, shares)[pid]
                    if with_offsets else None
                )
                return _timed(_range_chunks(
                    path, features_col, features_cols, label_col,
                    weight_col, chunk_rows, dtype, ldt, shares[pid],
                    base_offset=base,
                ))

            # no row groups to split (directory dataset / single
            # group): every rank decodes the scan but FOLDS only
            # chunks congruent to its rank — disjoint exact cover,
            # no decode scaling.  The serial scan's own offset
            # tracking (base 0) is already global here.
            def _mod_filter():
                for i, item in enumerate(_range_chunks(
                    path, features_col, features_cols, label_col,
                    weight_col, chunk_rows, dtype, ldt, None,
                    base_offset=0 if with_offsets else None,
                )):
                    if i % n_proc == pid:
                        yield item

            return _timed(_mod_filter())

    else:
        def _source():
            return _parquet_reader_pool(
                path, features_col, features_cols, label_col, weight_col,
                chunk_rows, dtype, ldt, readers, _timed,
                with_offsets=with_offsets,
            )

    # NOTE: checked before iterating (benign race: a stream completed by
    # a concurrent fit in this window serves untimed; a mid-serve source
    # fallback would double-time the remainder — both observability-only
    # skews on rare interleavings, never data errors).  ordered=False:
    # the reader pool's merge order is nondeterministic, so a mid-serve
    # cache failure must restart the pass rather than position-resume
    served_from_cache = chunk_stream_complete(key) is not None
    stream = cached_chunk_stream(
        key, _source, device_elem=0, serve_device=True, ordered=False,
    )
    if served_from_cache:
        # replay: no reader threads run, so the serve cost is the prep
        stream = _timed(stream)
    yield from stream


def _parquet_reader_pool(
    path, features_col, features_cols, label_col, weight_col,
    chunk_rows, dtype, ldt, readers, _timed,
    with_offsets: bool = False,
):
    """The live (non-cached) fused producer: one in-order pruned reader,
    or `readers` parallel range-reader threads merged through a bounded
    queue.  With `with_offsets`, every reader carries its share's global
    first-row base, so the merged (arbitrary-order) stream still labels
    each chunk with its exact position in the file."""
    shares = _partition_row_groups(path, readers)
    if shares is None:
        yield from _timed(
            _range_chunks(
                path, features_col, features_cols, label_col, weight_col,
                chunk_rows, dtype, ldt, None,
                base_offset=0 if with_offsets else None,
            )
        )
        return

    import queue
    import threading
    q: "queue.Queue" = queue.Queue(maxsize=len(shares) + 1)
    _DONE = object()
    stop = threading.Event()

    def _put(item) -> bool:
        # bounded puts (the utils.prefetch_iter discipline): an abandoned
        # consumer must not pin reader threads + chunk copies forever
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _run(groups, base) -> None:
        try:
            # per-reader interval tracking shares the one `prep` dict:
            # "s" additions race benignly under the GIL (a lost update
            # drops a timing sample, never chunk data); list.append is
            # atomic
            for item in _timed(
                _range_chunks(
                    path, features_col, features_cols, label_col,
                    weight_col, chunk_rows, dtype, ldt, groups,
                    base_offset=base,
                )
            ):
                if not _put(item):
                    return
            _put(_DONE)
        except BaseException as e:  # surface reader errors on the consumer
            _put(e)

    starts = (
        _share_row_starts(path, shares) if with_offsets
        else [None] * len(shares)
    )
    threads = [
        threading.Thread(target=_run, args=(g, b), daemon=True)
        for g, b in zip(shares, starts)
    ]
    for t in threads:
        t.start()
    try:
        done = 0
        while done < len(threads):
            item = q.get()
            if item is _DONE:
                done += 1
                continue
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


# The interval math this engine introduced is now owned by
# telemetry/utilization.py (the whole-run idle-gap attribution surface);
# these aliases keep the engine's (and stats/engine.py's) call sites —
# the overlap measure is unchanged: chunk-prep intervals (producer
# thread) intersected with device-busy intervals, so 'the solve ran
# inside the stage window' is read off the clock directly instead of
# inferred from duration sums (which a time-sliced single-core host
# systematically under-attributes).



def accumulate_chunks(
    acc: Dict[str, Any],
    step: Callable,
    chunks: Iterable,
    mesh,
    *,
    has_y: bool = False,
    extra_args: Tuple = (),
    prep: Optional[Dict[str, Any]] = None,
) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """Drive one fused pass: fold every prepared host chunk into the
    donated device accumulator as it lands, with chunk prep running
    `staging_pipeline_depth` items ahead on a producer thread.

    `acc`/`step` come from `_acc_spec` (`step` is the CACHED
    (weighted, unweighted) jitted donated step pair — `_jitted_steps`);
    the accumulator replicates over
    `mesh`, each chunk is `device_put` row-SHARDED (one transfer per
    device — no GSPMD replication: the put happens outside any jitted
    program), and the jitted step's matmuls psum over the mesh.
    `extra_args` (e.g. the randomized range-finder's Omega) replicate
    once up front.

    Returns (host float64 stats with Kahan carries folded, pass metrics:
    wall_s/host_prep_s/device_acc_s/chunks/bytes)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from .ops.stats import acc_to_host_f64
    from .parallel.mesh import DATA_AXIS, _staging_depth, data_pspec, timed_iter
    from .resilience import maybe_inject
    from .telemetry.compile import compile_label
    from .utils import prefetch_iter

    if jax.process_count() > 1:
        # multi-process: fold on the LOCAL devices only — chunks and the
        # accumulator never leave this host, every collective in the
        # jitted step stays intra-process, and the per-rank partials
        # meet in ONE cross-process reduction at pass_complete below
        # (psum on collective-capable backends, the coordination-service
        # wire on CPU builds)
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.local_devices()), (DATA_AXIS,))

    mat_sh = NamedSharding(mesh, data_pspec(2))
    row_sh = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
    rep_sh = NamedSharding(mesh, PartitionSpec())

    acc = jax.device_put(acc, rep_sh)
    extra_dev = tuple(jax.device_put(a, rep_sh) for a in extra_args)
    step_w, step_unw = step if isinstance(step, tuple) else (step, None)

    # drift-baseline capture (monitor/baseline.py): when a collector is
    # armed for this fit, the decoded host chunks ALSO fold into the
    # baseline fingerprint — zero extra data passes, host tier only.
    # begin_pass resets a half-folded retried pass; pass_complete after
    # the loop freezes the capture so the later passes of a multi-pass
    # fit (randomized PCA re-streams) fold nothing
    from .monitor import baseline as _baseline
    from .stats.engine import _device_step_lock

    _baseline.begin_pass()
    # pod observatory (telemetry/fleet.py): one pod-global pass id per
    # accumulate pass — rank 0 mints, the broadcast seam distributes,
    # every rank's spans and reduce-wait intervals carry it until the
    # pass report closes below.  SPMD site, like begin_pass itself
    from .telemetry import fleet as _fleet

    _fleet.begin_pod_pass()

    t0 = time.perf_counter()
    # a producer that tracks its own prep (the parallel parquet readers)
    # passes the shared dict in; otherwise the chunk iterator is wrapped
    # here and prep time is measured on the consumer's pull
    self_timed = prep is not None
    if prep is None:
        prep = {"s": 0.0, "iv": []}
        chunks = timed_iter(chunks, prep)

    depth = _staging_depth()
    acc_s = 0.0
    acc_iv = []
    n_chunks = 0
    nbytes = 0
    # the accumulate is synced per chunk: the donated accumulator
    # serializes steps on device anyway, and the sync (a) bounds
    # in-flight device memory to one chunk + the accumulator and (b)
    # keeps device_acc_s honest — the producer thread keeps decoding the
    # NEXT chunks through the whole blocked window, which is exactly the
    # overlap the engine exists to create
    with compile_label("fused_stats"):
        for cX, cy, cw in prefetch_iter(chunks, depth):
            # the fused-path fault site: an injected OOM/device_lost here
            # fails the WHOLE pass, and the retry (core.py fused_fit
            # dispatch) restarts it with FRESH accumulators — re-creatable
            # state, never resumed mid-pass, so chunks cannot double-count
            maybe_inject("fused_accumulate")
            ta = time.perf_counter()
            # dispatch-to-sync under the shared one-pass statistics
            # device lock (stats/engine.py _device_step_lock):
            # concurrent mesh-sharded accumulator dispatches — a fused
            # fit racing another fused fit or a Summarizer pass — can
            # interleave per-device executions into a runtime deadlock;
            # the baseline fold rides inside the held region like the
            # engine's host sketches, overlapped with the async device
            # execution
            with _device_step_lock:
                args = [jax.device_put(cX, mat_sh)]
                if cw is not None:
                    args.append(jax.device_put(cw, row_sh))
                if has_y:
                    args.append(jax.device_put(cy, row_sh))
                args.extend(extra_dev)
                step_j = step_w if cw is not None else (step_unw or step_w)
                acc = step_j(acc, *args)
                _baseline.fold_chunk(cX, cw)
                jax.block_until_ready(acc)
            tb = time.perf_counter()
            acc_s += tb - ta
            acc_iv.append((ta, tb))
            n_chunks += 1
            nbytes += (
                cX.nbytes
                + (cw.nbytes if cw is not None else 0)
                + (cy.nbytes if has_y else 0)
            )
    _baseline.pass_complete()
    host = acc_to_host_f64(acc)
    from .parallel.context import process_topology

    if process_topology()[0] > 1:
        # the pass_complete reduction: one global fold of the per-rank
        # f64 partials (rank-agreement-checked); everything downstream —
        # finalize, the solve — sees the same global statistics a
        # single-process pass over the full data would produce.  Gated
        # on the TOPOLOGY view so a post-rank-loss survivor group of one
        # skips the reduce instead of waiting on the dead
        from .parallel.context import reduce_host_arrays

        host = reduce_host_arrays(host, "fused_pass")
    wall = time.perf_counter() - t0
    prep_iv = _merge_intervals(prep["iv"]) if self_timed else prep["iv"]
    # feed the run's utilization timeline (telemetry/utilization.py):
    # the same intervals the overlap fraction is computed from become
    # the fit report's device-busy / gap-attribution evidence
    from .telemetry import utilization

    utilization.note_intervals("device", acc_iv, cause="fused_accumulate")
    utilization.note_intervals("host_prep", prep_iv, cause="chunk_prep")
    # close the pod pass AFTER the intervals land: the straggler blob
    # is computed from the timeline, and its reduce_blob_list exchange
    # is the pass's last SPMD site (every rank reaches it after the
    # fold above succeeded)
    _fleet.complete_pod_pass(run_id=current_run_id())
    return host, {
        "wall_s": wall,
        "host_prep_s": prep["s"],
        "device_acc_s": acc_s,
        "overlap_s": _interval_overlap_s(prep_iv, acc_iv),
        "chunks": n_chunks,
        "bytes": nbytes,
    }


def _record_metrics(
    label: str, kind: str, passes: int, totals: Dict[str, float],
    solver: Optional[str] = None,
) -> None:
    """Fold one fused fit's (possibly multi-pass) totals into the run's
    `fused` fact + a trace event.  host_prep_s is the chunk
    decode/cast/slice time on the reader thread(s), device_acc_s the
    device_put + accumulate time on the consumer thread; overlap_s is
    the measured wall-clock intersection of the chunk-prep intervals
    with the device-busy intervals (`_interval_overlap_s`);
    overlap_fraction normalizes it by the smaller phase (1.0 = the
    cheaper phase ran entirely inside the other's window)."""
    wall = totals.get("wall_s", 0.0)
    prep_s = totals.get("host_prep_s", 0.0)
    acc_s = totals.get("device_acc_s", 0.0)
    overlap_s = max(totals.get("overlap_s", 0.0), 0.0)
    overlap = 0.0
    if min(prep_s, acc_s) > 1e-9:
        overlap = max(0.0, min(overlap_s / min(prep_s, acc_s), 1.0))
    fact(
        "fused",
        label=label,
        kind=kind,
        **({"solver": solver} if solver is not None else {}),
        passes=int(passes),
        chunks=int(totals.get("chunks", 0)),
        bytes=int(totals.get("bytes", 0)),
        wall_s=round(wall, 4),
        host_prep_s=round(prep_s, 4),
        device_acc_s=round(acc_s, 4),
        overlap_s=round(overlap_s, 4),
        overlap_fraction=round(overlap, 4),
    )
    event(
        f"fused_stats[{label}]",
        detail=(
            f"{kind} passes={passes} chunks={totals.get('chunks', 0)} "
            f"{totals.get('bytes', 0) / 1e6:.1f}MB wall={wall:.2f}s "
            f"overlap={overlap:.2f}"
        ),
    )


def _merge_totals(totals: Dict[str, float], m: Dict[str, float]) -> None:
    for k, v in m.items():
        totals[k] = totals.get(k, 0.0) + v


def _resolve_producer(produced):
    """A producer factory returns either a plain chunk iterable (the
    engine times prep on its pull) or `(iterable, prep_dict)` when the
    producer tracks its own decode time (the parallel parquet
    readers)."""
    if isinstance(produced, tuple):
        return produced
    return produced, None


def fused_linreg_stats(
    producer_factory: Callable[[int], Iterable],
    d: int,
    dtype,
    label: str = "linreg",
) -> Dict[str, Any]:
    """One fused pass of the weighted Gram/moment/cross statistics
    (ops/stats.py `linreg_acc`).  `producer_factory(n_dev)` yields
    prepared `(X, y, w)` chunks.  Returns host float64 stats in the
    exact shape `LinearRegression._attrs_from_stats` consumes."""
    from .parallel.mesh import get_mesh

    dtype = np.dtype(dtype)
    mesh = get_mesh()
    acc, step = _acc_spec("linreg", d, 0, dtype)
    chunks, prep = _resolve_producer(producer_factory(mesh.devices.size))
    host, m = accumulate_chunks(
        acc, step, chunks, mesh, has_y=True, prep=prep,
    )
    _record_metrics(label, "linreg", 1, m)
    return host


def fused_pca_stats(
    producer_factory: Callable[[int], Iterable],
    d: int,
    k: int,
    dtype,
    label: str = "pca",
) -> Dict[str, Any]:
    """Fused PCA statistics with solver dispatch (ops/pca.py
    `resolve_pca_solver`):

    - "full": one pass of the exact second moments ->
      {"kind": "moments", "S", "s1", "sw"} (the shape
      `PCA._attrs_from_moments` consumes).
    - "randomized": the Halko range-finder run STAGE-OVERLAPPED — each
      tall-skinny product (sketch, power iterations, final projection)
      is one fused O(n d l) pass re-streamed through
      `producer_factory` -> {"kind": "projected", "Q", "SQ", "s1",
      "ssq", "sw"} for `ops.pca.pca_attrs_from_projected`.

    `producer_factory(n_dev)` must return a FRESH chunk iterator per
    call (multi-pass re-reads the source)."""
    from .ops.pca import resolve_pca_solver
    from .parallel.mesh import get_mesh

    dtype = np.dtype(dtype)
    mesh = get_mesh()
    n_dev = mesh.devices.size
    solver, l, power_iters, _reason = resolve_pca_solver(d, k, streamed=True)
    if solver == "full":
        acc, step = _acc_spec("pca_moments", d, 0, dtype)
        chunks, prep = _resolve_producer(producer_factory(n_dev))
        host, m = accumulate_chunks(acc, step, chunks, mesh, prep=prep)
        _record_metrics(label, "pca_moments", 1, m, solver="full")
        host["kind"] = "moments"
        return host

    totals: Dict[str, float] = {}

    def projected_pass(omega: np.ndarray) -> Dict[str, Any]:
        acc, step = _acc_spec("pca_projected", d, l, dtype)
        chunks, prep = _resolve_producer(producer_factory(n_dev))
        host, m = accumulate_chunks(
            acc, step, chunks, mesh,
            extra_args=(np.asarray(omega, dtype),), prep=prep,
        )
        _merge_totals(totals, m)
        return host

    # deterministic sketch (same data -> same components across refits)
    omega = np.random.default_rng(0).standard_normal((d, l)).astype(dtype)
    st = projected_pass(omega)
    sw = float(st["sw"])
    mean = st["s1"] / sw

    def centered(SOm: np.ndarray, om: np.ndarray) -> np.ndarray:
        # (A^T A) om from the raw projected moments: Σ w x (xᵀom) −
        # sw·mean·(meanᵀom)
        return np.asarray(SOm, np.float64) - sw * np.outer(mean, mean @ om)

    Y = centered(st["SOm"], omega)
    for _ in range(power_iters):
        Q, _r = np.linalg.qr(Y)
        Y = centered(projected_pass(Q.astype(dtype))["SOm"], Q)
    Q, _r = np.linalg.qr(Y)
    final = projected_pass(Q.astype(dtype))
    passes = 2 + power_iters
    _record_metrics(label, "pca_projected", passes, totals, solver="randomized")
    return {
        "kind": "projected",
        "Q": Q,
        "SQ": final["SOm"],
        "s1": final["s1"],
        "ssq": final["ssq"],
        "sw": final["sw"],
        "k": k,
    }
