#
# Device mesh + row-sharding helpers — the TPU-native replacement for the
# reference's partition->GPU placement (`_get_gpu_id` utils.py:138-170,
# `_CumlCommon._set_gpu_device` core.py:366-411) and the data-parallel rank
# layout.  One 1-D mesh axis "data" carries the reference's row-sharded
# data parallelism (SURVEY.md §2.12 strategy 1); a second axis name is
# reserved for model/feature sharding extensions.
#
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Iterable, Iterator, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def ensure_x64(dtype) -> None:
    """Enable jax x64 on demand when the user requests float64
    (`float32_inputs=False`, reference core.py:514-537 keeps f64 inputs in
    f64).  Scoped to the explicit request rather than an import-time global
    flip so importing this library never changes the numerics of unrelated
    JAX code in the process."""
    if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
        from ..utils import get_logger

        get_logger("spark_rapids_ml_tpu").info(
            "Enabling jax_enable_x64 for float64 inputs (float32_inputs=False)."
        )
        jax.config.update("jax_enable_x64", True)

DATA_AXIS = "data"
MODEL_AXIS = "model"

_mesh_cache = {}

# Device ids an elastic recovery (resilience/elastic.py) has removed from
# service: every future mesh is built from the survivors only.  Lives
# here — not in the resilience layer — because get_mesh is the single
# choke point every staging/fit path resolves devices through.
_excluded_device_ids: set = set()


def active_devices() -> list:
    """The devices meshes may be built from: the visible set minus any
    the elastic recovery layer has excluded after a device loss."""
    devices = jax.devices()
    if not _excluded_device_ids:
        return list(devices)
    return [d for d in devices if d.id not in _excluded_device_ids]


def excluded_device_ids() -> frozenset:
    return frozenset(_excluded_device_ids)


def exclude_devices(ids) -> None:
    """Remove devices from every FUTURE mesh (elastic mesh recovery:
    the survivors of a device loss form the degraded mesh).  Cached
    meshes containing an excluded device are dropped so the next
    `get_mesh` rebuilds from the survivors; arrays already sharded over
    a lost device stay untouched — their consumers re-stage."""
    _excluded_device_ids.update(int(i) for i in ids)
    for key in list(_mesh_cache):
        if any(d in _excluded_device_ids for d in key[1]):
            del _mesh_cache[key]


def restore_devices() -> None:
    """Clear every elastic exclusion (tests; operator reset after the
    lost hardware came back — the next fit sees the full device set)."""
    _excluded_device_ids.clear()


def drop_staging_programs(reason: str = "elastic_shrink") -> None:
    """Forget the compiled staging programs: the donated single-device
    updaters and the global bounded-upload pair bind CONCRETE devices,
    so after a mesh rebuild they must re-lower for the surviving device
    set instead of dispatching to a dead chip.  Counted on
    `recompiles_total{fn="staging_programs"}` with a `recompile[...]`
    marker in the active run's span tree (telemetry/compile.py), so an
    elastic recovery's re-lowering storm is visible inside the fit it
    interrupted."""
    _shard_update_fns.cache_clear()
    _chunked_upload_fns.cache_clear()
    from ..telemetry.compile import note_recompile

    # one re-lower EVENT per drop (not per cached program): the counter
    # answers "how many recompile storms", the compile_seconds histogram
    # answers how much each one cost
    note_recompile("staging_programs", reason)


def bucket_rows(n: int) -> int:
    """Smallest {1, 1.5} x 2^k >= n (min 256): the shape-bucketing grid.

    Kernels jit-compile per padded shape; padding row counts to a coarse
    grid lets k-fold CV folds, fitMultiple re-fits, and transform tail
    chunks of nearby sizes reuse one compilation (the round-1 finding: an
    87.8s cold compile re-paid per (shape, static-arg) combo).  Padding
    rows carry zero weight, so they are masked out of every kernel."""
    if n <= 256:
        return 256
    p = 1 << (int(n - 1).bit_length() - 1)  # largest power of two < n... or ==
    # candidates around n: p, 1.5p, 2p
    for c in (p, p + p // 2, 2 * p):
        if c >= n:
            return c
    return 2 * p


def bucket_rows_floor(n: int) -> int:
    """Largest bucket grid point <= n (min 256).  Chunked drivers size
    their FULL chunks with this so no chunk carries bucket padding; only
    the tail chunk buckets up."""
    if n <= 256:
        return 256
    b = bucket_rows(n)
    if b == n:
        return n
    # previous grid point: 1.5*2^k points are divisible by 3, 2^k never is
    return (2 * b) // 3 if b % 3 == 0 else (3 * b) // 4


def get_mesh(num_workers: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the first `num_workers` ACTIVE devices (visible
    minus elastic exclusions).  `num_workers` is the analog of the
    reference's `num_workers` (= #GPUs = #barrier tasks, reference
    params.py:556-588); on TPU it is the number of chips participating
    in the SPMD fit."""
    devices = active_devices()
    if not devices:
        raise RuntimeError(
            "no devices left after elastic exclusions "
            f"({sorted(_excluded_device_ids)}); call "
            "parallel.mesh.restore_devices() once the hardware is back"
        )
    n = num_workers or len(devices)
    if n > len(devices):
        if _excluded_device_ids:
            # elastic degraded mode: the requested width counts devices a
            # recovery removed from service — shrink to the survivors
            # rather than failing a fit the recovery just salvaged
            from ..utils import get_logger

            get_logger("mesh").warning(
                f"num_workers={n} exceeds the {len(devices)} surviving "
                f"device(s) (excluded: {sorted(_excluded_device_ids)}); "
                "running on the degraded mesh"
            )
            n = len(devices)
        else:
            raise ValueError(
                f"num_workers={n} exceeds the {len(devices)} visible devices. "
                f"On multi-host pods initialize jax.distributed first."
            )
    key = (n, tuple(d.id for d in devices[:n]))
    if key not in _mesh_cache:
        _mesh_cache[key] = Mesh(np.array(devices[:n]), (DATA_AXIS,))
    return _mesh_cache[key]


def data_pspec(ndim: int = 2) -> PartitionSpec:
    """Rows sharded over the data axis, features replicated."""
    return PartitionSpec(DATA_AXIS, *([None] * (ndim - 1)))


def replicated_pspec() -> PartitionSpec:
    return PartitionSpec()


# the largest single host<->device transfer: bigger arrays move in row
# pieces of at most this size (`_chunked_device_put` / `_chunked_device_get`
# and the staging piece sizing).  512 MiB matches the streaming path's
# chunk sizing.  The ceiling was set for a development link that failed
# any transfer it could not finish in a minute; that link is gone and the
# bound is inherited, to be re-justified on the chip or deleted (ROADMAP
# Design 2).
_MAX_PUT_BYTES = 512 * 1024 * 1024


def _dus_rows(b, c, lo):
    """Write rows `c` into buffer `b` at row offset `lo` (any ndim)."""
    import jax.numpy as jnp

    idx = (lo,) + tuple(jnp.zeros((), jnp.int32) for _ in range(b.ndim - 1))
    return jax.lax.dynamic_update_slice(b, c, idx)


@functools.lru_cache(maxsize=64)
def _chunked_upload_fns(shape, dtype, out_shardings):
    """Jitted (zeros-maker, donated-updater) pair for the bounded-upload
    loop, cached so repeated stagings of the same shape/sharding reuse
    the compiled programs instead of re-tracing per call."""
    import jax.numpy as jnp

    if out_shardings is not None:
        mk = jax.jit(
            lambda: jnp.zeros(shape, dtype), out_shardings=out_shardings
        )
        upd = jax.jit(_dus_rows, donate_argnums=0,
                      out_shardings=out_shardings)
    else:
        mk = jax.jit(lambda: jnp.zeros(shape, dtype))
        upd = jax.jit(_dus_rows, donate_argnums=0)
    return mk, upd


def assemble_rows_serial(shape, dtype, pieces, out_shardings=None):
    """LEGACY bounded-upload assembly loop: a zero device buffer of
    `shape` (optionally sharded) receives host row-pieces via donated
    in-place dynamic_update_slice writes — compiles are cached per
    (shape, dtype, sharding).  `pieces` yields (row_offset, np_chunk).

    Each host piece enters the jitted update unsharded, so GSPMD
    replicates it to every device of a row-sharded target — n_dev x the
    minimal traffic (the factor the pipelined per-device engine below
    removes).  Kept as the fallback for shardings the per-device writer
    cannot decompose, and as the byte-parity reference for the engine
    (tests/test_staging_pipeline.py)."""
    import jax.numpy as jnp

    dtype = np.dtype(dtype)
    ensure_x64(dtype)  # the zeros buffer must not truncate f64/i64
    mk, upd = _chunked_upload_fns(tuple(shape), dtype, out_shardings)
    buf = mk()
    for lo, piece in pieces:
        buf = upd(buf, piece, jnp.asarray(lo, jnp.int32))
    return buf


def assemble_rows_chunked(shape, dtype, pieces, out_shardings=None,
                          label: str = "assemble"):
    """The shared bounded-upload assembly entry point (used by
    `data.assemble_dense_chunks` — the CSR densify path): host row-pieces
    land in a device buffer of `shape` (optionally sharded).  `pieces` yields (row_offset, np_chunk); the
    chunk PREPARATION (densify/cast/slice) is expected to happen lazily
    inside the iterator, because on the pipelined path the iterator runs
    on a background host thread, overlapped with the device transfers
    (`staging_pipeline_depth`).

    Row-shardable targets at engine-worthy sizes route through the
    per-device staging engine (`ShardedRowWriter`): each piece is split
    at shard boundaries and transferred to exactly ONE device,
    eliminating the GSPMD replication factor of the legacy jitted global
    update (`assemble_rows_serial`).  Below `_PIPELINED_MIN_BYTES` the
    per-device buffers + producer thread cost more than they save (the
    same gate `RowStager.stage` applies), so small assemblies stay
    serial."""
    dtype = np.dtype(dtype)
    ensure_x64(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    if (
        (_FORCE_PIPELINED or nbytes >= _PIPELINED_MIN_BYTES)
        and _writer_devices(out_shardings, tuple(shape)) is not None
    ):
        writer = ShardedRowWriter(shape, dtype, out_shardings)
        return run_staging_pipeline(
            writer, ((None, lo, piece) for lo, piece in pieces), label=label
        )
    return assemble_rows_serial(shape, dtype, pieces,
                                out_shardings=out_shardings)


# ---------------------------------------------------------------------------
# Pipelined per-device staging engine
# ---------------------------------------------------------------------------
#
# The serial staging path paid three avoidable costs on the hot
# host->device edge:
#
#   1. `pad_cast` materialized a FULL padded host copy, then `_to_layout`
#      materialized a SECOND full copy for the interleave permutation;
#   2. `_chunked_device_put`'s jitted global dynamic_update_slice let
#      GSPMD replicate every host chunk to ALL devices of a row-sharded
#      target — n_dev x the minimal traffic;
#   3. host prep (pad/cast/densify/decode) and the device transfer ran
#      strictly serially.
#
# The engine below removes all three: host rows are sliced PER DEVICE
# SHARD straight from the caller's array (the interleave permutation is
# fused into a strided gather — no full-array copy ever exists), each
# piece is `device_put` to exactly one device and written into a
# per-device zeros buffer by a donated single-device update program, the
# global array assembles via `jax.make_array_from_single_device_arrays`,
# and a bounded background thread (`staging_pipeline_depth`) prepares the
# next piece while the current one rides the wire.  Padding rows are
# never transferred at all — the zeros buffers already hold them.

from ..telemetry.locks import named_lock
from ..telemetry.registry import dict_view as _dict_view
from ..tracing import fact, record_span, trace

# the put rate (MB/s) of the last staging-engine run: a measured value
# its owner keeps for a decision, as `fused._DECODE_RATE` is kept
# (`fused.resolve_parquet_readers` sizes its reader pool so decode just
# outruns it).  One accessor, `last_put_rate_mb_per_s`.
_PUT_RATE: dict = {}


def last_put_rate_mb_per_s() -> Optional[float]:
    """MB/s the last staging-engine run moved, None before the first."""
    return _PUT_RATE.get("mb_per_s")


# CUMULATIVE process-wide staging/cache counters (never cleared by a
# staging run): `dataset_stagings` counts EVERY
# 2-D host->device staging through RowStager.stage/stage_sparse — fit
# feature matrices AND per-chunk transform/eval inputs (which is why a
# legacy k-fold CV measures >= 2k+1: k train stagings + one eval staging
# per (fold, model) + the refit).  The `cache_*` keys mirror the
# device-cache registry's hit/miss/evict events
# (parallel/device_cache.py).  The cache tests read deltas of these to
# assert the stagings-per-CV-run contract (2k+1-and-more -> 1).
STAGE_COUNTS = _dict_view(
    "staging_counts",
    "Cumulative staging/cache counters (dataset_stagings, cache_*)",
    initial={
        "dataset_stagings": 0,
        "cache_hits": 0,
        "cache_misses": 0,
        "cache_evictions": 0,
        "cache_inserts": 0,
    },
)


def note_dataset_staging() -> None:
    """Record one full host->device staging of a 2-D feature block."""
    STAGE_COUNTS["dataset_stagings"] += 1

# tests: route even tiny arrays through the engine
_FORCE_PIPELINED = False

# below this, one plain device_put beats per-device assembly overheads
_PIPELINED_MIN_BYTES = 4 * 1024 * 1024


def _staging_chunk_rows(row_bytes: int) -> int:
    """Rows per prepared host piece from the `staging_chunk_bytes` budget,
    clamped to the single-transfer ceiling."""
    from ..config import get_config

    budget = min(int(get_config("staging_chunk_bytes")), _MAX_PUT_BYTES)
    return max(1, budget // max(int(row_bytes), 1))


def _staging_depth() -> int:
    from ..config import get_config

    return max(1, int(get_config("staging_pipeline_depth")))


def _writer_devices(sharding, shape) -> Optional[list]:
    """Device list, ordered by owned row range, for a target the
    per-device writer can assemble: a row-sharded (or unsharded)
    placement whose equal shards tile axis 0.  Multi-process the list is
    GLOBAL — it names every shard's owner in row order, and
    `ShardedRowWriter` materializes buffers only for the addressable
    ones (each host assembles its own slice of the one global array).
    None means the caller must use the serial path."""
    if not shape or shape[0] <= 0:
        return None
    if sharding is None:
        # an unsharded (default-device) target has no meaningful
        # multi-process assembly — that caller holds the full array
        if jax.process_count() != 1:
            return None
        return [jax.devices()[0]]
    try:
        imap = sharding.devices_indices_map(tuple(shape))
    except Exception:
        return None
    starts = {}
    for dev, idx in imap.items():
        # only axis-0 sharding: every other axis must be the full slice
        for ax, sl in enumerate(idx[1:], start=1):
            if (sl.start or 0) != 0 or (
                sl.stop is not None and sl.stop != shape[ax]
            ):
                return None
        lo = idx[0].start or 0
        if lo in starts:  # replication over the row axis
            return None
        starts[lo] = dev
    n_dev = len(starts)
    if shape[0] % n_dev != 0:
        return None
    s = shape[0] // n_dev
    if sorted(starts) != [i * s for i in range(n_dev)]:
        return None
    return [starts[i * s] for i in range(n_dev)]


# host pieces one writer lets sit un-applied on ONE device: one riding
# the wire while one is written into the shard.  A piece occupies device
# memory from its device_put until its update has run, so without the
# bound N parallel range readers (streaming.stage_parquet) park N
# `host_batch_bytes` pieces beside the shard they fill — 13 readers x
# 512 MB beside a 12.3 GB shard does not fit a 16 GB chip.
_MAX_INFLIGHT_PIECES = 2


def _dus_rows_done(b, c, lo):
    """`_dus_rows` plus a scalar that becomes ready only when the update
    has run: the buffer itself is donated into the next update, so it
    cannot be waited on."""
    # the scope names the staged chunk's write in a profile (metadata only)
    with jax.named_scope("stage_chunk"):
        return _dus_rows(b, c, lo), lo + 1


@functools.lru_cache(maxsize=256)
def _shard_update_fns(shape, dtype_str, device):
    """Jitted (zeros-maker, donated updater) pair committed to ONE
    device: single-device programs see no GSPMD, so a host piece is
    transferred to its target device and nowhere else."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sds = SingleDeviceSharding(device)
    dtype = np.dtype(dtype_str)
    mk = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sds)
    upd = jax.jit(_dus_rows_done, donate_argnums=0, out_shardings=sds)
    return mk, upd


class ShardedRowWriter:
    """Per-device row staging: one zeros buffer per device shard receives
    host pieces via donated single-device dynamic_update_slice programs;
    `finish` assembles the global array with
    `jax.make_array_from_single_device_arrays`.  Rows the caller never
    writes stay zero (padding is not transferred).

    Multi-process: the shard map stays GLOBAL (shard index = global row
    range), but buffers exist only for this process's ADDRESSABLE
    devices — each host writes its own slice, `finish` passes the local
    shard arrays, and jax assembles the ONE global array from every
    process's pieces.  `write` silently skips spans owned by remote
    hosts (a decode chunk straddling a process boundary writes only its
    local part; `rows_skipped_remote` counts the rest), while the
    explicit `write_shard` refuses remote shards loudly."""

    def __init__(self, shape, dtype, sharding=None) -> None:
        self.shape = tuple(int(x) for x in shape)
        self.dtype = np.dtype(dtype)
        ensure_x64(self.dtype)
        self.sharding = sharding
        devices = _writer_devices(sharding, self.shape)
        if devices is None:
            raise ValueError(
                "ShardedRowWriter requires a row-sharded (or single-"
                f"process unsharded) target; got {sharding} for {self.shape}"
            )
        self._devices = devices
        self._n_dev = len(devices)
        self._s = self.shape[0] // self._n_dev
        shard_shape = (self._s,) + self.shape[1:]
        pid = jax.process_index()
        # shard index -> live buffer, addressable shards only
        self._bufs = {}
        with trace("stage_alloc"):
            for d, dev in enumerate(devices):
                if getattr(dev, "process_index", pid) != pid:
                    continue
                mk, _ = _shard_update_fns(shard_shape, self.dtype.str, dev)
                self._bufs[d] = mk()
        if not self._bufs:
            raise ValueError(
                "ShardedRowWriter: this process owns none of the target's "
                "shards (mesh/process mismatch)"
            )
        self.bytes_written = 0
        self.put_seconds = 0.0  # dispatch-side time (transfers are async)
        self.pieces = 0
        # pieces a producer handed over as views of the caller's rows (no
        # host copy was made for them); the producer counts, on its thread
        self.pieces_viewed = 0
        self.rows_skipped_remote = 0
        # the parallel parquet range readers (streaming.stage_parquet)
        # call write() from their own threads at disjoint row offsets.
        # One lock per device orders that device's put -> update pairs
        # and holds them to `_MAX_INFLIGHT_PIECES`; `_mu` protects the
        # metrics the devices share
        self._mu = named_lock("staging_writer")
        self._dev_locks = {d: threading.Lock() for d in self._bufs}
        self._done = {d: collections.deque() for d in self._bufs}

    @property
    def shard_rows(self) -> int:
        return self._s

    @property
    def n_dev(self) -> int:
        return self._n_dev

    def write(self, lo: int, rows: np.ndarray) -> None:
        """Write host `rows` at GLOBAL row offset `lo`, splitting at
        device-shard boundaries (each split lands on exactly one
        device).  Spans owned by a remote process's devices are skipped
        (and counted) — multi-process callers write their whole decode
        chunk and only the addressable part transfers."""
        n = int(rows.shape[0])
        pos = 0
        while pos < n:
            g = lo + pos
            d = g // self._s
            take = min(n - pos, (d + 1) * self._s - g)
            if d in self._bufs:
                self.write_shard(d, g - d * self._s, rows[pos : pos + take])
            else:
                with self._mu:
                    self.rows_skipped_remote += int(take)
            pos += take

    def write_shard(self, d: int, lo: int, rows: np.ndarray):
        """Write host `rows` at offset `lo` WITHIN device `d`'s shard.
        Thread-safe: concurrent range readers writing disjoint offsets
        serialize only the (fast) update dispatch.  Returns the piece's
        `applied` token: once it is ready the transfer has read `rows`
        for the last time."""
        import jax.numpy as jnp

        if d not in self._bufs:
            dev = self._devices[d]
            raise ValueError(
                f"shard {d} is owned by process "
                f"{getattr(dev, 'process_index', '?')}; rank "
                f"{jax.process_index()} writes only its addressable shards"
            )
        dev = self._devices[d]
        t0 = time.perf_counter()
        piece = np.ascontiguousarray(rows, dtype=self.dtype)
        _, upd = _shard_update_fns(
            (self._s,) + self.shape[1:], self.dtype.str, dev
        )
        # host prep is timed before the device lock and the put + update
        # after it is taken — put_seconds must never include another
        # reader's lock hold, or N contending range readers would read
        # as an Nx device-transfer bottleneck that is actually
        # serialization.  Waiting for an older piece to be applied IS
        # transfer time and counts.
        prep_s = time.perf_counter() - t0
        # one `stage_put` span per piece, and its clock is `put_seconds`'
        # too.  Beneath it the host waits for an older piece's update
        # (a span only where one is waited for), works in the runtime's
        # two `device_put` calls, and dispatches the update
        with self._dev_locks[d], trace("stage_put") as put:
            done = self._done[d]
            if len(done) >= _MAX_INFLIGHT_PIECES:
                with trace("stage_put_wait", detail="wait"):
                    done.popleft().block_until_ready()
            with trace("stage_put_call", detail="work"):
                pj = jax.device_put(piece, dev)
                off = jax.device_put(np.asarray(lo, np.int32), dev)
            with trace("stage_put_update", detail="work"):
                self._bufs[d], applied = upd(self._bufs[d], pj, off)
            done.append(applied)
        with self._mu:
            self.put_seconds += prep_s + put.seconds
            self.bytes_written += piece.nbytes
            self.pieces += 1
        return applied

    def finish(self) -> "jax.Array":
        # a piece may be a view of the caller's rows, and the transfer
        # reads them until its update has run: no piece is in flight once
        # this returns, so the caller may overwrite its array
        for done in self._done.values():
            while done:
                with trace("stage_put_wait", detail="wait"):
                    done.popleft().block_until_ready()
        if self.sharding is None:
            out = self._bufs[0]
        else:
            # addressable shards only, in shard order: multi-process,
            # every process passes ITS pieces and jax stitches the one
            # global array (remote shards come from their own hosts)
            out = jax.make_array_from_single_device_arrays(
                self.shape, self.sharding,
                [self._bufs[d] for d in sorted(self._bufs)],
            )
        self._bufs = {}  # the writer must not pin the shard buffers
        return out


class _PiecePool:
    """Host buffers for the pieces a producer has to COPY (a cast, the
    interleave, rows that are no contiguous block), reused in turn
    instead of allocated anew for each piece.  On a v5e host a staging
    through new 256 MB pieces left 3-7 GB of host memory in use after it,
    outside the process and for good, and paid the first touch of every
    page (4.0 s of gathering for 12 GB where reused buffers take 0.55 s;
    PERF.md, PR 30): what the TPU runtime keeps for a host address it
    has transferred from is found again only when the address comes
    back.

    One producer thread calls `gather`; the thread that puts calls
    `note_put`.  A buffer is written again only once the token of the
    piece last put from it is ready.  The producer runs at most `depth`
    pieces ahead of the puts, so with `depth` + `_MAX_INFLIGHT_PIECES` +
    2 buffers that token is always known by then; should it ever not
    be, the piece gets an array of its own."""

    _OUT = object()  # handed to the producer's consumer, not yet put

    def __init__(self, depth: int, shape, dtype) -> None:
        self.shape, self.dtype = tuple(shape), np.dtype(dtype)
        self._bufs: list = [None] * (depth + _MAX_INFLIGHT_PIECES + 2)
        self._state: list = [None] * len(self._bufs)
        self._turn = 0

    def gather(
        self, arr: np.ndarray, start: int, step: int, count: int
    ) -> np.ndarray:
        """`native.gather_rows_strided` into the buffer whose turn it is."""
        from ..native import gather_rows_strided

        i = self._turn % len(self._bufs)
        self._turn += 1
        state = self._state[i]
        if state is self._OUT:
            return gather_rows_strided(arr, start, step, count, self.dtype)
        if state is not None:
            state.block_until_ready()
        if self._bufs[i] is None:
            self._bufs[i] = np.empty(self.shape, self.dtype)
        self._state[i] = self._OUT
        return gather_rows_strided(
            arr, start, step, count, self.dtype, out=self._bufs[i][:count]
        )

    def note_put(self, piece: np.ndarray, applied) -> None:
        for i, buf in enumerate(self._bufs):
            if buf is not None and piece.base is buf:
                self._state[i] = applied
                return


def timed_iter(producer: Iterable, prep: dict) -> Iterator:
    """Wrap `producer` so each item's production time (the host prep the
    pipeline overlaps: slice/cast/densify/decode) accumulates into
    `prep["s"]`.  When `prep` carries an `"iv"` list, each item's
    (start, end) wall interval is appended too — the fused engine
    (fused.py) intersects those with its device-busy intervals to
    measure the stage/solve overlap directly.  Shared by the staging
    pipeline below and the fused engine — one owner for the prep-side
    of every overlap measurement."""
    it = iter(producer)
    iv = prep.get("iv")
    while True:
        t_abs = time.time()
        t = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        t1 = time.perf_counter()
        prep["s"] += t1 - t
        if iv is not None:
            iv.append((t, t1))
        # the same interval as a `stage_prep` span of the run: on the
        # prefetch thread where there is one (it adopted the caller's
        # trace context), so prep shows beside the puts it overlaps
        record_span("stage_prep", t_abs, t_abs + (t1 - t))
        yield item


def run_staging_pipeline(
    writer: ShardedRowWriter, producer: Iterable, label: str = "stage",
    on_put=None,
) -> "jax.Array":
    """Drive `producer` — an iterator of `(dev_or_None, lo, host_rows)`
    whose per-item PREP work (slice/cast/densify) happens inside its
    `__next__` — through `writer`, with the prep running `depth` items
    ahead on a background thread (`staging_pipeline_depth`; depth 1 =
    serial, no thread).  Every put is dispatched from the calling
    thread; `on_put(host_rows, applied)` tells a producer that reuses
    its buffers (`_PiecePool`) which token frees which.
    Records throughput + overlap as the run's `staging` fact
    (`tracing.fact`); the run's trace holds one `stage_prep` and one
    `stage_put` span per piece (their sums are the prep and put seconds)
    and a `stage_finish` span for the assembly and the bookkeeping after
    the last put."""
    depth = _staging_depth()
    t0 = time.perf_counter()
    prep = {"s": 0.0, "iv": []}

    def timed() -> Iterator:
        return timed_iter(producer, prep)

    from ..telemetry.compile import compile_label
    from ..utils import prefetch_iter

    # first use of a (shape, device) pair lowers the donated updater
    # here: attribute those compiles to the engine, not the estimator
    with compile_label("staging"):
        for dev, lo, rows in prefetch_iter(timed(), depth):
            if dev is None:
                writer.write(int(lo), rows)
                continue
            applied = writer.write_shard(int(dev), int(lo), rows)
            if on_put is not None:
                on_put(rows, applied)
        # the drain of the last pieces (a `stage_put_wait` each), the
        # assembly and the bookkeeping after the last put
        with trace("stage_finish"):
            out = writer.finish()
            wall = time.perf_counter() - t0
            mb = writer.bytes_written / 1e6
            busy = prep["s"] + writer.put_seconds
            overlap = 0.0
            if depth > 1 and min(prep["s"], writer.put_seconds) > 1e-9:
                overlap = max(0.0, min(
                    (busy - wall) / min(prep["s"], writer.put_seconds), 1.0
                ))
            _PUT_RATE["mb_per_s"] = round(mb / max(wall, 1e-9), 1)
            fact(
                "staging",
                label=label,
                bytes=writer.bytes_written,
                seconds=round(wall, 4),
                mb_per_s=_PUT_RATE["mb_per_s"],
                overlap_ratio=round(overlap, 4),
                pieces=writer.pieces,
                pieces_viewed=writer.pieces_viewed,
                depth=depth,
                n_dev=writer.n_dev,
            )
            # the staging engine's prep + wall windows feed the run's
            # utilization timeline: host->device transfer time is "stage"
            # activity (gap evidence), chunk prep is "host_prep"
            from ..telemetry import utilization

            utilization.note_intervals("host_prep", prep["iv"], cause="stage_prep")
            utilization.note_interval("stage", t0, t0 + wall, cause=label)
    return out


def _chunked_device_get(arr) -> np.ndarray:
    """Mirror of `_chunked_device_put` for device->host fetches: rows
    fetch in slices of at most `_MAX_PUT_BYTES`."""
    nbytes = arr.size * arr.dtype.itemsize
    if nbytes <= _MAX_PUT_BYTES or arr.ndim == 0 or arr.shape[0] <= 1:
        if nbytes > _MAX_PUT_BYTES:
            # unsplittable on the row axis: same attribution warning as
            # the put-side mirror
            from ..utils import get_logger

            get_logger("mesh").warning(
                f"one-shot device fetch of {nbytes/2**20:.0f} MiB (single "
                "row over the transfer ceiling)"
            )
        return np.asarray(arr)
    row_bytes = max(nbytes // arr.shape[0], 1)
    if row_bytes > _MAX_PUT_BYTES:
        # the chunked loop degenerates to one row per fetch and EACH of
        # those still exceeds the ceiling — same attribution warning as
        # the single-row branch, or the hang class would be silent here
        from ..utils import get_logger

        get_logger("mesh").warning(
            f"chunked device fetch rows are {row_bytes/2**20:.0f} MiB each "
            "(single row over the transfer ceiling)"
        )
    rows = max(1, int(_MAX_PUT_BYTES // row_bytes))
    out = np.empty(arr.shape, arr.dtype)
    for lo in range(0, arr.shape[0], rows):
        out[lo : lo + rows] = np.asarray(arr[lo : lo + rows])
    return out


def _chunked_device_put(arr: np.ndarray, sharding=None) -> "jax.Array":
    """device_put for arrays beyond _MAX_PUT_BYTES: bounded row pieces
    assembled on device instead of one transfer.  sharding=None targets
    the default device.  Deliberately uses the LEGACY global-update loop
    (`assemble_rows_serial`), never the per-device engine:
    `RowStager._stage_serial` is the byte-parity reference the engine is
    compared with (routed through the engine at large sizes, the
    reference would be the engine itself), and the other callers
    (ops/ivf.py, models/knn.py index uploads) are unsharded
    default-device puts the per-device writer could not improve."""
    ensure_x64(arr.dtype)
    if arr.nbytes <= _MAX_PUT_BYTES or arr.ndim == 0 or arr.shape[0] <= 1:
        if arr.nbytes > _MAX_PUT_BYTES:
            # a single row past the ceiling cannot be split on the row
            # axis; make the hang class attributable instead of silent
            from ..utils import get_logger

            get_logger("mesh").warning(
                f"one-shot device_put of {arr.nbytes/2**20:.0f} MiB "
                "(single row over the transfer ceiling)"
            )
        return (jax.device_put(arr, sharding) if sharding is not None
                else jax.device_put(arr))
    row_bytes = max(arr.nbytes // arr.shape[0], 1)
    if row_bytes > _MAX_PUT_BYTES:
        # mirror of the fetch-side chunked-loop warning: one-row pieces
        # are still over the ceiling and cannot be split further
        from ..utils import get_logger

        get_logger("mesh").warning(
            f"chunked device_put pieces are {row_bytes/2**20:.0f} MiB each "
            "(single row over the transfer ceiling)"
        )
    chunk = max(1, int(_MAX_PUT_BYTES // row_bytes))
    pieces = (
        (lo, np.ascontiguousarray(arr[lo : lo + chunk]))
        for lo in range(0, arr.shape[0], chunk)
    )
    return assemble_rows_serial(arr.shape, arr.dtype, pieces,
                                out_shardings=sharding)


def _allgather_i64(value: int, tag: str = "i64") -> np.ndarray:
    """Every process's int64 scalar, in rank order — the XLA collective
    where the backend supports cross-process collectives, the
    coordination-service wire where it doesn't (CPU builds).  The tiny
    exchange every multi-process layout negotiation starts from."""
    if jax.process_count() == 1:
        return np.asarray([int(value)], np.int64)
    from .context import allgather_bytes, psum_capable

    if not psum_capable():
        blobs = allgather_bytes(
            f"i64/{tag}", int(value).to_bytes(8, "little", signed=True)
        )
        return np.asarray(
            [int.from_bytes(b, "little", signed=True) for b in blobs],
            np.int64,
        )
    from jax.experimental import multihost_utils

    return np.asarray(
        multihost_utils.process_allgather(np.asarray(int(value), np.int64))
    ).reshape(-1)


class RowStager:
    """Stages host arrays onto the mesh with one consistent padded row
    layout, so X / y / weights / masks / row-ids always line up.

    Single-process (the common case): the caller holds the full dataset.
    With small (exact-shape) padding rows stay contiguous with the zero
    padding at the global tail; once bucket padding could unbalance the
    per-device split, rows interleave round-robin over devices (see
    `_to_layout`) so every device holds an even share of valid rows.

    Multi-process (pods): every process holds only its LOCAL rows — the
    analog of the reference's per-partition data loading (each Spark barrier
    task stages its partition, core.py:886-957).  Each process pads its
    local block to a common per-process size (so shards stay equal and
    static-shaped) and `jax.make_array_from_process_local_data` assembles
    the global array without any process ever materializing the full
    dataset.  Padding is therefore *interleaved* at each process-block tail,
    which is why masks/labels must be staged through the same object.
    """

    def __init__(
        self, n_local_rows: int, mesh: Mesh,
        bucketing: Optional[bool] = None,
        interleave: Optional[bool] = None,
        telemetry: bool = True,
    ) -> None:
        """`bucketing` pads the row count to the shape-bucket grid for
        compile sharing; `interleave` round-robins rows over devices so
        bucketed padding doesn't starve the tail devices of valid rows.
        Pass `interleave=False` for order-sensitive consumers (top-k tie
        breaking): the contiguous layout keeps original row order on the
        devices while bucketed padding still shares compiles.
        `telemetry=False` skips the per-staging instrumentation
        (dataset-staging counter, byte-model prediction, device-memory
        census) — for request-rate consumers like the serving
        dispatcher, where a ~ms `jax.live_arrays()` census per 1-row
        micro-batch would eat the latency SLO and a fit-scale
        `dataset_stagings` bump per request would skew a counter defined
        as one full feature-block staging."""
        _ensure_distributed()
        self.mesh = mesh
        self.n_proc = jax.process_count()
        self._replicated_input = False
        self._interleave = False
        self._telemetry = bool(telemetry)
        if self.n_proc == 1:
            from ..config import get_config

            if bucketing is None:
                bucketing = bool(get_config("shape_bucketing"))
            n_dev = mesh.devices.size
            self.n_local = int(n_local_rows)
            self.n_valid = self.n_local
            target = bucket_rows(self.n_local) if bucketing else self.n_local
            self.local_padded = target + ((-target) % n_dev)
            self.n_padded = self.local_padded
            self._n_dev = n_dev
            # interleave only when padding is big enough to unbalance the
            # contiguous per-device split (bucketed padding); exact-shape
            # staging keeps the copy-free contiguous layout
            if interleave is None:
                interleave = (
                    self.local_padded - self.n_local
                ) >= n_dev
            self._interleave = n_dev > 1 and interleave
        else:
            counts = _allgather_i64(int(n_local_rows), "stager_counts")
            self._init_layout(counts, mesh)

    def _init_layout(self, counts: np.ndarray, mesh: Mesh) -> None:
        """Multi-process padded layout from the per-process row counts.

        The shard size `s` (rows per DEVICE) is the max over processes of
        ceil(count_p / ldc_p), so every process's rows fit on its own
        devices even when processes own different device counts; every
        quantity here is computed identically on all processes from the
        globally-visible mesh + allgathered counts."""
        pid = jax.process_index()
        n_dev = mesh.devices.size
        if n_dev != len(jax.devices()):
            raise ValueError(
                "multi-process staging must use the full device set: "
                f"mesh has {n_dev} devices, global count is "
                f"{len(jax.devices())} (set num_workers=None)"
            )
        pidx = [d.process_index for d in mesh.devices.flat]
        if any(a > b for a, b in zip(pidx, pidx[1:])):
            raise ValueError(
                "mesh device order must group processes contiguously in "
                "ascending process_index order (the global row order "
                "contract); got process indices " + str(pidx)
            )
        ldc_all = np.bincount(pidx, minlength=self.n_proc)
        if (ldc_all == 0).any():
            raise ValueError("every process must own >=1 device in the mesh")
        # rows per device shard, agreed globally
        s = max(
            int(-(-int(c) // int(l)))
            for c, l in zip(counts, ldc_all)
        )
        s = max(s, 1)
        # NOTE: no shape bucketing here — multi-process blocks shard
        # contiguously per device, so bucket padding could leave whole
        # devices holding only padding (per-device work like the RF
        # ensemble would silently starve); per-process loading already
        # bounds padding to < one device share
        self.counts = counts
        self.n_local = int(counts[pid])
        self.n_valid = int(counts.sum())
        self.block_sizes = (ldc_all * s).astype(np.int64)  # padded rows/process
        self.local_padded = int(self.block_sizes[pid])
        self.n_padded = s * n_dev

    @classmethod
    def for_replicated(
        cls, n_rows: int, mesh: Mesh, bucketing: Optional[bool] = None,
        interleave: Optional[bool] = None, telemetry: bool = True,
    ) -> "RowStager":
        """Stager for host arrays REPLICATED on every process (model
        attributes, transform inputs the caller holds in full).  Each
        process stages only its even block of the global rows, so the
        device layout matches a per-process-loaded fit and no rows
        duplicate.  Single-process this is identical to RowStager."""
        _ensure_distributed()
        if jax.process_count() == 1:
            return cls(n_rows, mesh, bucketing=bucketing,
                       interleave=interleave, telemetry=telemetry)
        pid, n_proc = jax.process_index(), jax.process_count()
        # one scalar allgather VALIDATES the replication contract — a caller
        # passing process-local rows here (fit-style input) would otherwise
        # stage mismatched global shapes and deadlock in the next collective
        seen = _allgather_i64(int(n_rows), "replicated_rows")
        if not (seen == seen[0]).all():
            raise ValueError(
                "RowStager.for_replicated requires the SAME row count on "
                f"every process (saw {seen.tolist()}); pass process-local "
                "rows through RowStager(...) instead"
            )
        base, rem = divmod(int(n_rows), n_proc)
        counts = np.array(
            [base + (1 if p < rem else 0) for p in range(n_proc)], np.int64
        )
        st = object.__new__(cls)
        st.mesh = mesh
        st.n_proc = n_proc
        st._replicated_input = True
        st._interleave = False  # multi-process blocks stay contiguous
        st._telemetry = bool(telemetry)
        st._lo = int(counts[:pid].sum())
        st._init_layout(counts, mesh)
        # n_valid for a replicated stager is the full input length the
        # caller passes to stage() (== counts.sum() here)
        return st

    def stage(
        self, arr: np.ndarray, dtype: Optional[np.dtype] = None
    ) -> jax.Array:
        """Stage a (n_local, ...) host array -> (n_padded, ...) global
        sharded jax.Array, zero-padded per the layout.  For `for_replicated`
        stagers, pass the FULL (n_valid, ...) array; the local block is
        sliced out here."""
        if self._replicated_input:
            if arr.shape[0] != self.n_valid:
                raise ValueError(
                    f"replicated array has {arr.shape[0]} rows, expected "
                    f"{self.n_valid}"
                )
            arr = arr[self._lo : self._lo + self.n_local]
        dtype = np.dtype(dtype) if dtype is not None else arr.dtype
        ensure_x64(dtype)
        if arr.shape[0] != self.n_local:
            raise ValueError(
                f"array has {arr.shape[0]} rows, stager expects {self.n_local}"
            )
        if arr.ndim == 2 and self._telemetry:
            # 1-D companions (labels/weights/masks/fold-ids) ride along a
            # dataset staging; only the feature block counts as one
            note_dataset_staging()
            # the byte model's prediction for this staging (padded rows x
            # row bytes) — the measured-peak watermark checks it
            # (telemetry/memory.py budget_drift_ratio)
            from ..telemetry.memory import note_host_staging, record_prediction

            note_host_staging()
            record_prediction(
                "staged",
                float(self.local_padded)
                * int(np.prod(arr.shape[1:], dtype=np.int64))
                * np.dtype(dtype).itemsize,
            )
        sharding = NamedSharding(self.mesh, data_pspec(arr.ndim))
        try:
            if self.n_proc == 1:
                if (
                    _FORCE_PIPELINED or arr.nbytes >= _PIPELINED_MIN_BYTES
                ) and _writer_devices(
                    sharding, (self.local_padded,) + arr.shape[1:]
                ) is not None:
                    return self._stage_pipelined(arr, dtype, sharding)
                if not _FORCE_PIPELINED and self._small_direct_eligible():
                    devices = _writer_devices(
                        sharding, (self.local_padded,) + arr.shape[1:]
                    )
                    if devices is not None:
                        return self._stage_small_direct(
                            arr, dtype, sharding, devices
                        )
                return self._stage_serial(arr, dtype)
            if (
                _FORCE_PIPELINED or arr.nbytes >= _PIPELINED_MIN_BYTES
            ) and _writer_devices(
                sharding, (self.n_padded,) + arr.shape[1:]
            ) is not None:
                return self._stage_pipelined_multi(arr, dtype, sharding)
            padded = self._pad_host(arr, dtype)
            return jax.make_array_from_process_local_data(
                sharding, padded, (self.n_padded,) + padded.shape[1:]
            )
        finally:
            if arr.ndim == 2 and self._telemetry:
                # a staging is exactly where resident bytes step up:
                # sample so per-fit peak watermarks see the new level
                from ..telemetry.memory import sample_devices

                sample_devices()

    def _pad_host(self, arr: np.ndarray, dtype: np.dtype) -> np.ndarray:
        """Zero-padded dtype-cast host copy in the ORIGINAL row order (the
        serial path's first copy; also the multi-process block layout)."""
        if arr.shape[0] == self.local_padded and arr.dtype == dtype:
            return arr
        if arr.ndim == 2:
            # single host copy fusing the dtype cast and the zero-padding;
            # OpenMP-parallel via the native staging library when large
            from ..native import pad_cast

            return pad_cast(arr, self.local_padded, dtype)
        padded = np.zeros((self.local_padded,) + arr.shape[1:], dtype)
        padded[: arr.shape[0]] = arr
        return padded

    def _stage_serial(self, arr: np.ndarray, dtype: np.dtype) -> jax.Array:
        """LEGACY single-process staging: full padded host copy ->
        interleave permutation copy -> (chunked) device_put.  Kept for
        small arrays (one plain device_put beats per-device assembly
        overheads) and as the byte-parity reference for the pipelined
        engine (tests/test_staging_pipeline.py)."""
        padded = self._pad_host(arr, dtype)
        sharding = NamedSharding(self.mesh, data_pspec(padded.ndim))
        return _chunked_device_put(self._to_layout(padded), sharding)

    def _small_direct_eligible(self) -> bool:
        from ..config import get_config

        return bool(get_config("staging_small_direct"))

    def _stage_small_direct(
        self, arr: np.ndarray, dtype: np.dtype, sharding, devices
    ) -> jax.Array:
        """Small-batch fast path (sub-`_PIPELINED_MIN_BYTES` arrays): the
        serial path pays a full padded host copy (`_pad_host`), a second
        full copy for the interleave permutation (`_to_layout`) and a
        global sharded device_put — machinery sized for dataset stagings,
        not for the 1-row.. few-row micro-batches the serving layer
        (serving/) dispatches at request rate.  Here each device shard's
        rows slice straight out of the caller's array (the interleave
        permutation fused into a strided basic slice, the cast fused
        into the assignment), land in one small zero-padded shard
        buffer, and ONE batched `jax.device_put` moves every buffer to
        exactly its device (the runtime overlaps the per-device
        transfers; per-device calls would serialize n_dev round trips
        on the serving dispatch path) — no jitted update programs, no
        GSPMD, no full-array copy.  Byte-identical to `_stage_serial`
        for every layout (asserted by tests/test_staging_pipeline.py);
        gated by the `staging_small_direct` conf."""
        n_dev = len(devices)
        s = self.local_padded // n_dev
        shard_shape = (s,) + arr.shape[1:]
        n_local = self.n_local
        pieces = []
        for d_i in range(n_dev):
            if self._interleave:
                # laid-out shard row p holds original row p*n_dev + d_i
                start, step = d_i, n_dev
                cnt = max(0, -(-(n_local - d_i) // n_dev))
            else:
                start, step = d_i * s, 1
                cnt = min(max(n_local - d_i * s, 0), s)
            piece = np.zeros(shard_shape, dtype)
            if cnt:
                piece[:cnt] = arr[start : start + cnt * step : step]
            pieces.append(piece)
        shards = jax.device_put(pieces, list(devices))
        return jax.make_array_from_single_device_arrays(
            (self.local_padded,) + arr.shape[1:], sharding, shards
        )

    def _stage_pipelined(
        self, arr: np.ndarray, dtype: np.dtype, sharding
    ) -> jax.Array:
        """Pipelined per-device staging: each device shard's rows go
        straight from `arr` to exactly ONE device, with the next piece
        prepared on a background thread while the current one transfers.
        Where the rows of a piece already lie in `arr` as the device
        needs them (C-contiguous, the target dtype, consecutive rows) the
        piece is a VIEW of them and nothing is copied on the host;
        otherwise they are gathered and cast into one of a few reused
        piece buffers (`_PiecePool`; the interleave permutation fused
        into a strided slice — no full-array host copy).  Padding rows
        are never transferred (the shard buffers start zero).
        Byte-identical to `_stage_serial` for every layout."""
        writer = ShardedRowWriter(
            (self.local_padded,) + arr.shape[1:], dtype, sharding
        )
        s = writer.shard_rows
        n_dev = writer.n_dev
        row_bytes = int(
            np.prod(arr.shape[1:], dtype=np.int64)
        ) * np.dtype(dtype).itemsize if arr.ndim > 1 else np.dtype(dtype).itemsize
        chunk = _staging_chunk_rows(row_bytes)
        interleave = self._interleave
        n_local = self.n_local
        # a device's rows are consecutive (step 1) unless they interleave
        views = (
            arr.flags.c_contiguous and arr.dtype == dtype and not interleave
        )
        pool = None if views else _PiecePool(
            _staging_depth(), (min(chunk, s),) + arr.shape[1:], dtype
        )

        def producer() -> Iterator:
            for d_i in range(n_dev):
                if interleave:
                    # laid-out shard row p holds original row p*n_dev + d_i
                    start, step = d_i, n_dev
                    total = max(0, -(-(n_local - d_i) // n_dev))
                else:
                    start, step = d_i * s, 1
                    total = min(max(n_local - d_i * s, 0), s)
                for lo in range(0, total, chunk):
                    cnt = min(chunk, total - lo)
                    if views:
                        piece = arr[start + lo : start + lo + cnt]
                        writer.pieces_viewed += 1
                    else:
                        piece = pool.gather(arr, start + lo * step, step, cnt)
                    yield d_i, lo, piece

        return run_staging_pipeline(
            writer, producer(), label="stage",
            on_put=None if views else pool.note_put,
        )

    def _stage_pipelined_multi(
        self, arr: np.ndarray, dtype: np.dtype, sharding
    ) -> jax.Array:
        """Multi-process per-device staging: a writer over the GLOBAL
        padded shape whose buffers exist only for this process's
        addressable shards; local rows stream in at this process's
        global block offset and `finish` assembles the one global array
        from every host's pieces.  Byte-identical placement to the
        `make_array_from_process_local_data` path (contiguous process
        blocks, zero padding at each block tail) without materializing
        the padded host copy."""
        writer = ShardedRowWriter(
            (self.n_padded,) + arr.shape[1:], dtype, sharding
        )
        block_lo = int(self.block_sizes[: jax.process_index()].sum())
        row_bytes = (
            int(np.prod(arr.shape[1:], dtype=np.int64))
            * np.dtype(dtype).itemsize
            if arr.ndim > 1
            else np.dtype(dtype).itemsize
        )
        chunk = _staging_chunk_rows(row_bytes)
        n_local = self.n_local

        def producer() -> Iterator:
            # multi-process blocks are contiguous (never interleaved), so
            # pieces are plain slices; the writer routes each to its shard
            for lo in range(0, n_local, chunk):
                cnt = min(chunk, n_local - lo)
                rows = arr[lo : lo + cnt]
                piece = np.ascontiguousarray(rows, dtype=dtype)
                writer.pieces_viewed += piece is rows
                yield None, block_lo + lo, piece

        return run_staging_pipeline(writer, producer(), label="stage_mp")

    def stage_sparse(
        self,
        X,
        dtype: Optional[np.dtype] = None,
        row_transform=None,
    ) -> jax.Array:
        """Stage a host CSR matrix as the DENSE padded sharded device array
        `stage` would produce for its densification — without ever holding
        more than one `host_batch_bytes` dense chunk in host memory
        (single-process), or more than this process's local block
        (multi-process, where the block is already the bounded working
        set).  TPU kernels take dense operands; this bounds the HOST peak,
        the analog of the reference keeping CSR end-to-end through staging
        (core.py:183-265).

        `row_transform` is applied per dense host chunk before transfer
        (metric row preprocessing).  Requires a non-interleaved layout —
        build the stager with ``interleave=False`` for sparse staging
        (bucketed padding is fine; only the round-robin permutation is
        incompatible with chunkwise assembly)."""
        from ..native import densify_csr
        from ..streaming import chunk_rows_for

        if self._interleave:
            raise ValueError(
                "sparse chunked staging requires the contiguous row layout; "
                "construct the RowStager with interleave=False"
            )
        X = X.tocsr()
        if self._replicated_input:
            if X.shape[0] != self.n_valid:
                raise ValueError(
                    f"replicated matrix has {X.shape[0]} rows, expected "
                    f"{self.n_valid}"
                )
            X = X[self._lo : self._lo + self.n_local]
        if X.shape[0] != self.n_local:
            raise ValueError(
                f"matrix has {X.shape[0]} rows, stager expects {self.n_local}"
            )
        d = int(X.shape[1])
        dtype = np.dtype(dtype) if dtype is not None else np.dtype(X.dtype)
        ensure_x64(dtype)
        note_dataset_staging()
        chunk = max(1, int(chunk_rows_for(d, dtype.itemsize)))
        sharding = NamedSharding(self.mesh, data_pspec(2))

        def _chunk(lo: int, hi: int) -> np.ndarray:
            dense = densify_csr(X[lo:hi], hi - lo, dtype)
            if row_transform is not None:
                dense = np.asarray(row_transform(dense), dtype=dtype)
            return dense

        if self.n_proc > 1:
            # per-process block assembly: peak host memory is the local
            # padded block (< 1/n_proc of the data + <1 device share of
            # padding), the same bound the dense multi-process path has
            padded = np.zeros((self.local_padded, d), dtype)
            for lo in range(0, self.n_local, chunk):
                hi = min(lo + chunk, self.n_local)
                padded[lo:hi] = _chunk(lo, hi)
            return jax.make_array_from_process_local_data(
                sharding, padded, (self.n_padded, d)
            )

        from ..data import assemble_dense_chunks

        return assemble_dense_chunks(
            X, self.n_padded, dtype, chunk, row_transform,
            out_shardings=sharding,
        )

    # -- single-process round-robin device layout ---------------------------
    #
    # Sharding splits axis 0 into contiguous per-device blocks.  With
    # tail padding (especially bucketed padding, which can exceed n/n_dev
    # rows) contiguous blocks would leave the LAST devices mostly or
    # entirely padding — fatal for per-device work like the RF ensemble
    # (a device with no valid rows grows an empty tree).  Host rows are
    # therefore interleaved round-robin: row j lands on device j % n_dev,
    # so every device holds an even share of valid rows no matter how much
    # padding the bucket adds.  The transform is one reshape+transpose copy.

    def _to_layout(self, padded: np.ndarray) -> np.ndarray:
        if not self._interleave:
            return padded
        n_dev = self._n_dev
        s = self.local_padded // n_dev
        return np.ascontiguousarray(
            padded.reshape((s, n_dev) + padded.shape[1:])
            .swapaxes(0, 1)
            .reshape(padded.shape)
        )

    def _from_layout(self, laid_out: np.ndarray) -> np.ndarray:
        if not self._interleave:
            return laid_out
        n_dev = self._n_dev
        s = self.local_padded // n_dev
        return (
            laid_out.reshape((n_dev, s) + laid_out.shape[1:])
            .swapaxes(0, 1)
            .reshape(laid_out.shape)
        )

    def trim_host(self, host: np.ndarray) -> np.ndarray:
        """Valid rows, in input order, of a HOST array shaped like the
        staged layout (the host-side sibling of `fetch`).  Arrays NOT in
        the staged layout (length != local_padded — e.g. already-trimmed
        host outputs in original order) are head-trimmed untouched.
        Multi-process stagers fall back to a plain head-trim — only
        constant-per-row host outputs (degenerate-model paths) take that
        branch."""
        host = np.asarray(host)
        if self.n_proc == 1 and host.shape[0] == self.local_padded:
            return self._from_layout(host)[: self.n_valid]
        return host[: self.n_valid]

    def mask(self, dtype=np.float32, weights: Optional[np.ndarray] = None) -> jax.Array:
        """Validity weights (weight for real rows, 0 for padding), staged
        with the same layout as the data."""
        n = self.n_valid if self._replicated_input else self.n_local
        w = np.zeros((n,), np.dtype(dtype))
        w[:] = 1.0 if weights is None else np.asarray(weights, dtype)
        return self.stage(w, dtype)

    def fetch(self, arr: jax.Array) -> np.ndarray:
        """Device (n_padded, ...) row-sharded array -> host (n_valid, ...)
        valid rows in global order.  Single-process: a plain device_get +
        tail trim.  Multi-process: device_get only the LOCAL shards (no
        device-side replication of the full array — that would put the
        whole dataset in every device's HBM), drop this block's tail
        padding, then allgather the host blocks."""
        if self.n_proc == 1:
            host = np.asarray(jax.device_get(arr))
            return self._from_layout(host)[: self.n_valid]
        if arr.is_fully_replicated:
            host = np.asarray(jax.device_get(arr))
            offs = np.concatenate([[0], np.cumsum(self.block_sizes)])
            return np.concatenate(
                [
                    host[int(offs[p]) : int(offs[p]) + int(c)]
                    for p, c in enumerate(self.counts)
                ],
                axis=0,
            )
        local = _local_rows(arr)[: self.n_local]
        return allgather_host_rows(local)

    def row_ids(self, base: int = 0) -> jax.Array:
        """Global row ids (int32; -1 on padding), staged with the layout.
        In multi-process mode ids are offset by the preceding processes'
        valid counts, so they match the single-process numbering."""
        if self.n_proc > 1:
            base += int(self.counts[: jax.process_index()].sum())
        ids = np.arange(base, base + self.n_local, dtype=np.int32)
        padded = np.full((self.local_padded,), -1, np.int32)
        padded[: self.n_local] = ids
        sharding = NamedSharding(self.mesh, data_pspec(1))
        if self.n_proc == 1:
            return jax.device_put(self._to_layout(padded), sharding)
        return jax.make_array_from_process_local_data(
            sharding, padded, (self.n_padded,)
        )


def _ensure_distributed() -> None:
    """Lazy config-tier multi-host bootstrap before the first
    process_count()-dependent staging decision, so
    `set_config(coordinator_address=...)` works without an explicit
    `init_distributed()` call.  Raises loudly (from jax) if the backend was
    already initialized single-process — silent degradation would fit a
    different model on every host."""
    from ..config import get_config

    if get_config("coordinator_address") is not None:
        from .context import init_distributed

        init_distributed()


def _local_rows(arr: "jax.Array") -> np.ndarray:
    """This process's rows of an axis-0-sharded global array, in global
    order, as one host block (device_get of only the addressable shards)."""
    seen = {}
    for sh in arr.addressable_shards:
        start = sh.index[0].start or 0
        seen.setdefault(start, sh)
    shards = [seen[k] for k in sorted(seen)]
    return np.concatenate([np.asarray(sh.data) for sh in shards], axis=0)


def allgather_host_rows(arr: np.ndarray) -> np.ndarray:
    """Concatenate per-process host row blocks into the full array on EVERY
    process (process-major order — the same global order RowStager.fetch
    produces).  No-op single-process.  Used by fits whose model must hold
    replicated host state (kNN item sets, UMAP raw data — the analog of the
    reference broadcasting model data for distributed transform,
    umap.py:1407-1450)."""
    _ensure_distributed()
    if jax.process_count() == 1:
        return arr
    from .context import psum_capable

    if not psum_capable():
        # CPU builds can't run the XLA collective: ship the blocks over
        # the coordination-service wire instead (same process-major
        # concatenation order)
        import io

        from .context import allgather_bytes

        buf = io.BytesIO()
        np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
        blobs = allgather_bytes("host_rows", buf.getvalue())
        return np.concatenate(
            [np.load(io.BytesIO(b), allow_pickle=False) for b in blobs],
            axis=0,
        )
    from jax.experimental import multihost_utils

    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray(arr.shape[0], np.int64))
    ).reshape(-1)
    m = int(counts.max())
    padded = np.zeros((m,) + arr.shape[1:], arr.dtype)
    padded[: arr.shape[0]] = arr
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    return np.concatenate(
        [gathered[p, : int(c)] for p, c in enumerate(counts)], axis=0
    )


def allgather_host_csr(X):
    """`allgather_host_rows` for scipy CSR matrices: concatenate per-process
    CSR row blocks into the full CSR matrix on EVERY process WITHOUT any
    process densifying — the three component arrays (data, indices, per-row
    counts) gather as ragged 1-D blocks and the global indptr rebuilds from
    the counts.  No-op single-process."""
    _ensure_distributed()
    X = X.tocsr()
    if jax.process_count() == 1:
        return X
    import scipy.sparse as sp

    n_cols = int(X.shape[1])
    data = allgather_host_rows(np.asarray(X.data))
    indices = allgather_host_rows(np.asarray(X.indices, np.int64))
    row_nnz = allgather_host_rows(np.diff(X.indptr).astype(np.int64))
    indptr = np.concatenate([[0], np.cumsum(row_nnz)])
    return sp.csr_matrix(
        (data, indices, indptr), shape=(len(row_nnz), n_cols)
    )


def fetch_replicated(arr: "jax.Array", mesh: Mesh) -> np.ndarray:
    """device_get that also works for non-fully-addressable (multi-process)
    axis-0-sharded arrays.  Returns the full padded global array.  The
    gather happens on the HOST (local shards -> process allgather), never
    by replicating the array into every device's memory."""
    if jax.process_count() == 1 or arr.is_fully_replicated:
        return np.asarray(jax.device_get(arr))
    return allgather_host_rows(_local_rows(arr))


def shard_rows(
    arr: np.ndarray,
    mesh: Mesh,
    dtype: Optional[np.dtype] = None,
) -> Tuple[jax.Array, int]:
    """Stage a host array onto the mesh with rows sharded over DATA_AXIS.

    This is the host->device staging hot loop of the reference
    (core.py:886-957 pandas->cupy conversion + `_concat_and_free`); here a
    single `jax.device_put` with a NamedSharding splits rows across chips
    (multi-process: `jax.make_array_from_process_local_data` of each
    process's local rows).  Returns (global sharded jax.Array, true GLOBAL
    row count before padding).  Callers that also need masks/labels/ids in
    multi-process mode should use `RowStager` directly so layouts line up.

    This thin wrapper keeps the ORIGINAL contiguous-tail-padding contract
    (no bucketing, no interleave): its return value exposes no stager, so
    `device_get(...)[:n]` must stay a valid way to recover the rows.
    Bucketed/interleaved staging is RowStager-only.
    """
    st = RowStager(arr.shape[0], mesh, bucketing=False)
    return st.stage(arr, dtype), st.n_valid


def replicate(arr: Union[np.ndarray, jax.Array], mesh: Mesh) -> jax.Array:
    """Replicate an array on every device of the mesh (model/centroid
    arrays — the analog of NCCL-broadcast model state)."""
    sharding = NamedSharding(mesh, PartitionSpec())
    return jax.device_put(arr, sharding)
