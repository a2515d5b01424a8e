#
# Device-resident dataset cache — the "stage once, fit/evaluate many"
# layer (the Snap ML hierarchical-accelerator-cache lesson from PAPERS.md
# applied to the JAX runtime).  Before this layer `CrossValidator.fit`
# paid `2k+1` full host->device stagings of overlapping rows per run: k
# fold-train stagings in `fitMultiple`, k fold-eval stagings in
# `_transformEvaluate`, plus the best-model refit.  Here the full dataset
# is staged onto the mesh ONCE (through the PR-2 pipelined engine inside
# `RowStager.stage`) and every consumer gets a VIEW of the resident
# sharded arrays:
#
#   - fold TRAIN selection happens on device: a per-row fold-id array is
#     staged with the data's layout, and a weight-capable kernel sees
#     `w * (fold_id != fold)` (zero-weight rows are mathematically absent
#     — the contract the ops kernels declare via SUPPORTS_ZERO_WEIGHT_ROWS);
#   - estimators whose fit is row-COUNT sensitive (seeded inits draw one
#     Gumbel per padded row) instead get an on-device gather/compaction
#     view shaped exactly like a fresh staging of the fold's host slice,
#     so trajectories match the legacy path;
#   - fold EVAL runs each model's `_transform_device` over the resident
#     rows and selects the fold's rows host-side — no eval restaging;
#   - the best-params refit fits the resident full dataset directly.
#
# Entries are fingerprint-keyed (content hash of the host arrays + layout
# metadata), accounted against the same device-memory model as the
# staging decisions (`device_data_budget_bytes`, the `_over_device_budget`
# formula in core.py), LRU-evicted under the `device_cache_bytes` conf,
# and the whole layer degrades to the legacy per-fold host-slicing path
# when disabled (`device_cache=off`) or over budget.  Hit/miss/evict
# counters mirror into `mesh.STAGE_COUNTS` and emit trace events.
#
from __future__ import annotations

import functools
import hashlib
import itertools
import os
from typing import Any, Dict, List, Optional

import numpy as np

from ..data import DeviceDataset
from .mesh import (
    STAGE_COUNTS,
    RowStager,
    NamedSharding,
    data_pspec,
    get_mesh,
)

# cumulative cache metrics (also mirrored into mesh.STAGE_COUNTS): read
# by tests and operators debugging residency.
# Now a VIEW over the telemetry registry (the `device_cache{key=...}`
# Prometheus family) — the mapping surface is unchanged.
from ..telemetry.registry import dict_view as _dict_view
from ..telemetry.locks import named_lock

CACHE_METRICS = _dict_view(
    "device_cache",
    "Device-resident dataset cache counters (hits/misses/evictions/...)",
    initial={
        "hits": 0,
        "misses": 0,
        "evictions": 0,
        "inserts": 0,
        "resident_bytes": 0,
        "resident_entries": 0,
    },
)

_lock = named_lock("device_cache")


def _note(kind: str, detail: str = "") -> None:
    with _lock:
        CACHE_METRICS.bump(kind)
        # the STAGE_COUNTS mirror used to be gated on the key already
        # existing, which silently dropped any kind whose mirror was
        # missing (`inserts` drifted unrecorded); bump() creates-at-zero,
        # and tests/test_telemetry.py asserts the two stay equal
        STAGE_COUNTS.bump("cache_" + kind)
    from ..tracing import event

    event(f"device_cache_{kind}", detail=detail)


def device_hbm_bytes(device) -> int:
    """Bytes of memory ONE device offers the byte model: what its
    allocator reports (`memory_stats()["bytes_limit"]` — on a v5e less
    than the 16 GiB on the data sheet), unless the `hbm_bytes` conf was
    set explicitly.  Backends that report nothing (the CPU test mesh)
    get the conf's default; a TPU that reports nothing is an error — a
    guessed limit there routes a fit that fits to the streaming path, or
    one that does not into an OOM."""
    from ..config import get_config, is_explicit

    if is_explicit("hbm_bytes"):
        return int(get_config("hbm_bytes"))
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit)
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']; set the "
            "hbm_bytes conf to its per-device memory explicitly"
        )
    return int(get_config("hbm_bytes"))


def bytes_beside(X) -> int:
    """Bytes one device has left beside its shard of the resident rows
    `X` (`device_hbm_bytes` less the shard): what a solver's temporaries
    may be sized against."""
    shard = X.addressable_shards[0]
    return max(0, device_hbm_bytes(shard.device) - shard.data.nbytes)


def fused_program_fits(X, temp_bytes: int = 0, rows_in_place: bool = False) -> bool:
    """Whether one device can hold what a solver fused into one
    `while_loop` program holds beside its shard of the resident rows `X`:
    `temp_bytes` of the program's own temporaries and, unless the loop
    reads the rows in place, the shard a SECOND time.  XLA copies the
    loop-invariant operands of a `while_loop` out of the read-only entry
    parameters into the loop's own state, so an autodiff or matmul
    program carries a second resident copy of the features as a temp.
    Measured on a v5e at the reference's 1M x 3000 (the fused L-BFGS with
    autodiff): 11.78 GB of HLO temp beside 11.51 GB of arguments, 23.3 GB
    asked of a 15.75 GB chip, a compile-time RESOURCE_EXHAUSTED.
    `rows_in_place`: the loop reads the rows through a kernel from a
    bitcast of the resident buffer (the one-pass logistic evaluation,
    `ops/pallas_logistic.py`), and no copy is made: compiled for a v5e at
    that shape the fused program holds 14.7 MB of temp beside 12.01 GB of
    arguments (`tests/test_pallas_logistic.py` keeps it).  A
    host-dispatched program has no loop and no copy.  The ONE memory test
    every fused-vs-host-dispatched router reads (logistic L-BFGS, KMeans
    Lloyd)."""
    copy = 0 if rows_in_place else X.addressable_shards[0].data.nbytes
    return copy + temp_bytes <= bytes_beside(X)


def device_data_budget_bytes() -> float:
    """The device-memory budget staged training data is accounted
    against: mem_ratio_for_data x the sum of `device_hbm_bytes` over the
    devices — ONE formula shared with `_TpuCaller._over_device_budget`
    (core.py) so the cache can never believe in more memory than the
    staging decisions do.
    Counts ACTIVE devices only: after an elastic mesh shrink the lost
    chips' HBM is gone with them.  Multi-process, each rank stages and
    caches only its ADDRESSABLE shards (mesh.ShardedRowWriter), so the
    budget counts this process's devices alone — a rank can never book
    bytes against a remote host's HBM."""
    import jax

    from ..config import get_config
    from .mesh import active_devices

    devices = active_devices()
    if jax.process_count() > 1:
        pid = jax.process_index()
        devices = [d for d in devices if d.process_index == pid]
    return float(get_config("mem_ratio_for_data")) * sum(
        device_hbm_bytes(d) for d in devices
    )


def cache_enabled() -> bool:
    from ..config import get_config

    return str(get_config("device_cache")).lower() == "on"


def cache_budget_bytes() -> float:
    from ..config import get_config

    explicit = int(get_config("device_cache_bytes"))
    return float(explicit) if explicit > 0 else device_data_budget_bytes()


# ---------------------------------------------------------------------------
# Fingerprinting
# ---------------------------------------------------------------------------

# above this, a 2-D array hashes a strided row sample + a per-row
# random-projection digest instead of every byte (hashing 5 GB would
# cost seconds; staging it costs minutes — but the fingerprint must stay
# cheap enough to run on every fit).  1-D arrays (labels/weights) always
# hash in full: they are a few bytes per row.
_FULL_HASH_MAX_BYTES = 64 * 1024 * 1024
_SAMPLE_ROWS = 1024


def _hash_array(h: "hashlib._Hash", arr: Optional[np.ndarray]) -> None:
    if arr is None:
        h.update(b"<none>")
        return
    arr = np.ascontiguousarray(arr)
    h.update(str(arr.shape).encode())
    h.update(str(arr.dtype).encode())
    if arr.ndim != 2 or arr.nbytes <= _FULL_HASH_MAX_BYTES:
        h.update(arr.tobytes())
        return
    # strided row sample + a per-row random-projection digest (one
    # O(n*d) matvec pass against a shape-seeded fixed vector): the (n,)
    # projection sequence is ORDER-sensitive — swapping any two distinct
    # rows changes it — so permutations of non-sampled rows cannot
    # silently collide with the resident entry (an order-invariant
    # column sum could)
    n = arr.shape[0]
    stride = max(1, n // _SAMPLE_ROWS)
    h.update(np.ascontiguousarray(arr[::stride]).tobytes())
    v = np.random.default_rng(arr.shape[1]).standard_normal(arr.shape[1])
    h.update(np.asarray(arr @ v, np.float64).tobytes())


def dataset_fingerprint(
    X: np.ndarray,
    y: Optional[np.ndarray],
    weight: Optional[np.ndarray],
    dtype: np.dtype,
    label_dtype: Optional[np.dtype],
    mesh,
) -> str:
    """Content fingerprint binding a cache entry to the DATA and its
    staged layout: host array contents, staged dtypes, and the mesh's
    device set (a different mesh shards differently).  Shape-bucketing is
    part of the layout, so its conf value keys too."""
    from ..config import get_config

    h = hashlib.blake2b(digest_size=20)
    _hash_array(h, X)
    _hash_array(h, y)
    _hash_array(h, weight)
    h.update(str(np.dtype(dtype)).encode())
    h.update(str(np.dtype(label_dtype) if label_dtype else None).encode())
    h.update(str(bool(get_config("shape_bucketing"))).encode())
    h.update(",".join(str(d.id) for d in mesh.devices.flat).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# On-device fold programs
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _masked_weight_fn(sharding):
    """Jitted `w * (fold_ids != fold)` — ONE compile serves every fold
    (the fold index is a traced scalar)."""
    import jax

    def mask(w, fold_ids, fold):
        return w * (fold_ids != fold).astype(w.dtype)

    return jax.jit(mask, out_shardings=sharding)


@functools.lru_cache(maxsize=64)
def _gather_masked_fn(sharding):
    """Jitted resident-array row gather + validity mask:
    `out[i] = arr[idx[i]] * valid[i]`, with the view's row sharding.  The
    only bytes that cross the HOST->device edge for a gather view are the
    (4 bytes/row) index and validity arrays; the data rows move between
    devices — but NOTE that XLA lowers the arbitrary cross-shard take to
    an all-gather, so the program transiently materializes the FULL
    source array per device (~n_dev x the dataset, cluster-wide).  The
    reservation for gather-path consumers sizes that transient
    (`_cached_fit_entry`'s working_factor).  The mask matters because
    padding slots of the view have no source row to read (their `idx`
    points at an arbitrary valid slot); re-zeroing them reproduces
    EXACTLY the zero padding a fresh host staging of the fold slice
    would carry (byte parity with the legacy path, asserted by
    tests/test_device_cache.py)."""
    import jax
    import jax.numpy as jnp

    def gather(arr, idx, valid):
        g = jnp.take(arr, idx, axis=0)
        v = valid.astype(arr.dtype)
        return g * (v[:, None] if g.ndim == 2 else v)

    return jax.jit(gather, out_shardings=sharding)


# ---------------------------------------------------------------------------
# Cache entry: one resident dataset + its fold views
# ---------------------------------------------------------------------------


class CacheEntry:
    """A dataset resident on the mesh plus the machinery to derive fold
    views from it without restaging.  `dataset` is a `DeviceDataset`
    (with its staging `RowStager`, so layouts always line up).  Fold
    state lives in per-run `FoldSet` objects (`fold_set`), NOT on the
    entry: concurrent CV runs sharing one resident entry must not swap
    each other's fold assignments."""

    def __init__(self, fingerprint: str, dataset: DeviceDataset,
                 nbytes: int, base_bytes: Optional[int] = None) -> None:
        self.fingerprint = fingerprint
        self.dataset = dataset
        # nbytes = the RESERVED accounting size (base + any gather-path
        # working headroom); base_bytes = the resident arrays alone
        self.nbytes = int(nbytes)
        self.base_bytes = int(base_bytes if base_bytes is not None
                              else nbytes)
        self.last_used = 0
        self._src_slot: Optional[np.ndarray] = None  # orig row -> staged slot

    @property
    def stager(self) -> RowStager:
        return self.dataset._stager

    @property
    def mesh(self):
        return self.dataset.mesh

    # -- fold registration ---------------------------------------------------

    def fold_set(self, folds: np.ndarray) -> "FoldSet":
        """Stage a per-row fold-id array (int32, entry layout) and
        return the RUN-owned handle the fold views hang off.  Padding
        rows get fold id -1 — they carry zero weight already, but the
        sentinel keeps them out of any `== fold` eval selection too."""
        folds = np.ascontiguousarray(np.asarray(folds, np.int32))
        st = self.stager
        if folds.shape[0] != st.n_valid:
            raise ValueError(
                f"fold array has {folds.shape[0]} rows, dataset has "
                f"{st.n_valid}"
            )
        import jax

        padded = np.full((st.local_padded,), -1, np.int32)
        padded[: st.n_valid] = folds
        sharding = NamedSharding(self.mesh, data_pspec(1))
        fold_dev = jax.device_put(st._to_layout(padded), sharding)
        return FoldSet(self, folds, fold_dev)

    def _slot_of_row(self) -> np.ndarray:
        """original row id -> staged slot index, for the entry layout."""
        if self._src_slot is None:
            st = self.stager
            laid = np.full((st.local_padded,), -1, np.int64)
            laid[: st.n_valid] = np.arange(st.n_valid, dtype=np.int64)
            laid = st._to_layout(laid)  # slot -> orig row (or -1)
            slot = np.empty((st.n_valid,), np.int64)
            valid = laid >= 0
            slot[laid[valid]] = np.flatnonzero(valid)
            self._src_slot = slot
        return self._src_slot

    def _gather_view(self, sel: np.ndarray, what: str) -> DeviceDataset:
        """On-device gather/compaction of the rows selected by boolean
        `sel` into a fresh sharded view laid out EXACTLY like a legacy
        staging of the selected host slice (same RowStager layout
        decisions).  Only the int32 slot-index + validity arrays cross
        the host->device edge; the data rows move device-to-device."""
        import jax

        ds = self.dataset
        rows = np.flatnonzero(sel)
        if rows.size == 0:
            raise ValueError(f"{what} selects no rows")
        src_slot = self._slot_of_row()[rows]
        view_st = RowStager(rows.size, self.mesh)
        idx = np.zeros((view_st.local_padded,), np.int64)
        idx[: rows.size] = src_slot
        idx = view_st._to_layout(idx).astype(np.int32)
        sharding1 = NamedSharding(self.mesh, data_pspec(1))
        idx_dev = jax.device_put(idx, sharding1)
        valid = np.zeros((view_st.local_padded,), np.float32)
        valid[: rows.size] = 1.0
        valid_dev = jax.device_put(
            view_st._to_layout(valid).astype(np.dtype(ds.weight.dtype)),
            sharding1,
        )
        sharding2 = NamedSharding(self.mesh, data_pspec(2))
        Xv = _gather_masked_fn(sharding2)(ds.X, idx_dev, valid_dev)
        wv = _gather_masked_fn(sharding1)(ds.weight, idx_dev, valid_dev)
        yv = None
        if ds.y is not None:
            yv = _gather_masked_fn(sharding1)(ds.y, idx_dev, valid_dev)
        return DeviceDataset(
            self.mesh, Xv, rows.size, y=yv, weight=wv, stager=view_st
        )


class FoldSet:
    """One CV run's fold assignment staged against a cache entry's
    layout.  Owned by the RUN, not the entry: two concurrent consumers
    of the same resident entry each hold their own FoldSet, so neither
    can silently evaluate against the other's train/eval split."""

    def __init__(self, entry: CacheEntry, folds: np.ndarray,
                 fold_dev) -> None:
        self.entry = entry
        self.folds = folds  # host (n_valid,) int32, original row order
        self.fold_dev = fold_dev  # staged fold ids, entry layout

    def train_view(self, fold: int) -> DeviceDataset:
        """Weight-mask train view: the resident X/y plus
        `w * (fold_id != fold)`.  Zero host->device traffic.  Correct for
        kernels that honor the zero-weight-row contract
        (ops SUPPORTS_ZERO_WEIGHT_ROWS; `_supports_fold_weights`)."""
        import jax.numpy as jnp

        entry = self.entry
        ds = entry.dataset
        sharding = NamedSharding(entry.mesh, data_pspec(1))
        w = _masked_weight_fn(sharding)(
            ds.weight, self.fold_dev, jnp.asarray(int(fold), jnp.int32)
        )
        return DeviceDataset(
            entry.mesh, ds.X, ds.n_valid, y=ds.y, weight=w,
            stager=entry.stager,
        )

    def gather_train_view(self, fold: int) -> DeviceDataset:
        """Gather/compaction train view for estimators whose fit is
        row-count sensitive (seeded inits draw one variate per padded
        row): byte-identical to a fresh staging of the fold's host
        slice, so fits match the uncached path's trajectory."""
        return self.entry._gather_view(self.folds != fold,
                                       f"train fold {fold}")

    def eval_view(self, fold: int, eval_df) -> "CachedEvalView":
        """Fold-eval view: the fold's rows are gather/compacted on
        device ONCE and every model scores only them (`eval_df` holds
        the fold's host rows for the evaluator's label/weight
        columns)."""
        sel = np.asarray(self.folds == fold)
        if not sel.any():
            raise ValueError(f"fold {fold} has no validation rows")
        return CachedEvalView(self.entry, fold, sel, eval_df)


class CachedEvalView:
    """`_transformEvaluate` input backed by a cache entry: the fold's
    eval rows are gather/compacted on device once per fold (transforms
    run over n/k rows, not n — row-wise transforms make the compaction
    exact), each model's `_transform_device` runs over them (compile
    shared across folds and param maps via shape bucketing), and the
    trimmed outputs come back in the eval frame's row order — zero eval
    restaging.  Models without a device transform fall back to their
    normal host transform of the fold's rows.

    Unlike `_transform_mesh`, the fold transform is NOT re-chunked by
    `host_batch_bytes`: its input rows are already resident (no staged
    copy to bound) and its outputs are O(n/k x n_output_cols) — small
    next to the (n/k, d) view for every current model family.  A future
    model with very wide outputs would want chunking here too."""

    def __init__(self, entry: CacheEntry, fold: int, sel: np.ndarray,
                 eval_df) -> None:
        self.entry = entry
        self.fold = int(fold)
        self.sel = sel  # bool (n_valid,) in original row order
        self.eval_df = eval_df
        self._view: Optional[DeviceDataset] = None  # built on first use

    def _eval_rows(self) -> DeviceDataset:
        if self._view is None:
            self._view = self.entry._gather_view(
                self.sel, f"eval fold {self.fold}"
            )
        return self._view

    def evaluate(self, models: List[Any], evaluator: Any) -> List[float]:
        return [self._evaluate_one(m, evaluator) for m in models]

    def _evaluate_one(self, model: Any, evaluator: Any) -> float:
        from ..core import _TpuModel

        if type(model)._transform_device is _TpuModel._transform_device:
            # no device transform (DBSCAN/UMAP/kNN manage their own
            # staging): the fold's host rows go through the normal path
            return evaluator.evaluate(model.transform(self.eval_df))
        import jax
        import pandas as pd

        view = self._eval_rows()
        st = view._stager
        dev = model._transform_device(view.X)
        cols: Dict[str, Any] = {}
        for col, v in dev.items():
            # fetch trims padding and restores the eval frame's row order
            host = (
                st.fetch(v)
                if isinstance(v, jax.Array)
                else st.trim_host(np.asarray(v))
            )
            cols[col] = list(host) if host.ndim == 2 else host
        base = self.eval_df
        overlap = [c for c in cols if c in base.columns]
        if overlap:
            base = base.drop(columns=overlap)
        out_df = pd.concat(
            [
                base.reset_index(drop=True),
                pd.DataFrame(cols),
            ],
            axis=1,
        )
        return evaluator.evaluate(out_df)


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------


class DeviceDatasetCache:
    """Fingerprint-keyed LRU registry of resident datasets, accounted
    against `cache_budget_bytes()`.  Registry mutations hold `_mu`; the
    module `_lock` (metrics) is never taken while `_mu` is held in a way
    that nests the other direction, so the two cannot deadlock."""

    def __init__(self) -> None:
        self._entries: Dict[str, CacheEntry] = {}
        self._clock = 0
        self._mu = named_lock("dataset_cache", kind="rlock")
        # bytes reserve()d but not yet insert()ed (staging in flight):
        # without this ledger two concurrent misses could both pass
        # reserve() against the same headroom and overcommit the budget
        self._pending = 0
        # long-lived NON-dataset residency booked against the same
        # budget (the serving model registry's pinned weights,
        # serving/registry.py): tag -> bytes.  Counted by every budget
        # comparison but never LRU-evicted from here — the owning layer
        # decides what to drop and releases the claim itself.
        self._external: Dict[str, int] = {}

    def lookup(self, fingerprint: str) -> Optional[CacheEntry]:
        with self._mu:
            entry = self._entries.get(fingerprint)
            if entry is None:
                return None
            self._clock += 1
            entry.last_used = self._clock
        _note("hits", detail=f"fp={fingerprint[:12]} bytes={entry.nbytes}")
        return entry

    def resident_bytes(self) -> int:
        with self._mu:
            return sum(e.nbytes for e in self._entries.values())

    def claimed_bytes(self) -> int:
        """Resident bytes PLUS in-flight reservations PLUS external
        (non-dataset) residency claims — what every budget comparison
        must see."""
        with self._mu:
            return (
                self.resident_bytes()
                + self._pending
                + sum(self._external.values())
            )

    def _evict_lru(self) -> bool:
        with self._mu:
            if not self._entries:
                return False
            fp = min(self._entries,
                     key=lambda k: self._entries[k].last_used)
            self.evict(fp)
            return True

    def evict(self, fingerprint: str) -> None:
        with self._mu:
            entry = self._entries.pop(fingerprint, None)
        if entry is None:
            return
        # deliberately do NOT null the entry's device references: an
        # in-flight CV run may still hold this entry and its views, and
        # they stay valid — eviction only removes the REGISTRY's claim,
        # and the buffers free (async, via jax) when the last consumer
        # reference dies
        _note("evictions",
              detail=f"fp={fingerprint[:12]} bytes={entry.nbytes}")
        self._sync_metrics()

    def reserve(self, need_bytes: int) -> bool:
        """Claim room for `need_bytes` of new residency, LRU-evicting
        entries as needed.  On True the bytes are held as an in-flight
        claim until `insert` (which converts it to the entry) or
        `release` (staging failed); False when they cannot fit even with
        the cache empty (the caller then degrades to the uncached
        path)."""
        budget = cache_budget_bytes()
        if need_bytes > budget:
            return False
        with self._mu:
            while self.claimed_bytes() + need_bytes > budget:
                if not self._evict_lru():
                    break
            if self.claimed_bytes() + need_bytes > budget:
                return False
            self._pending += int(need_bytes)
            return True

    def release(self, need_bytes: int) -> None:
        """Drop an in-flight reservation whose staging failed."""
        with self._mu:
            self._pending = max(0, self._pending - int(need_bytes))

    def top_up(self, entry: CacheEntry, extra: int) -> bool:
        """Grow an existing (just-looked-up, hence MRU) entry's
        reservation by `extra` bytes, LRU-evicting OTHER entries as
        needed — never the entry itself (the `len > 1` guard keeps the
        MRU entry out of reach of `_evict_lru`).  False when the extra
        headroom cannot fit."""
        budget = cache_budget_bytes()
        with self._mu:
            while (
                self.claimed_bytes() + extra > budget
                and len(self._entries) > 1
            ):
                if not self._evict_lru():
                    break
            if entry.fingerprint not in self._entries:
                return False
            if self.claimed_bytes() + extra > budget:
                return False
            entry.nbytes += int(extra)
        self._sync_metrics()
        return True

    def reserve_external(
        self, tag: str, need_bytes: int, evict: bool = True
    ) -> bool:
        """Book `need_bytes` of budget-accounted residency for a
        non-dataset consumer (keyed by `tag`; a repeat reservation for
        the same tag REPLACES the old claim), LRU-evicting dataset
        entries to make room — residency is re-creatable, a pinned
        serving model is not re-creatable cheaply mid-request.  On False
        nothing is claimed (the old claim for `tag`, if any, stays) and
        the caller degrades: the serving registry evicts its own LRU
        pins and retries.  External claims are visible to every budget
        comparison (`claimed_bytes`, hence `cache_resident_bytes()` and
        core's `_over_device_budget`) but are never evicted from this
        side — only `release_external` drops them.

        `evict=False` claims only FREE headroom: the chunk cache's
        device tier is opportunistic residency (re-creatable from its
        own host/spill copies), so it must never push a dataset entry
        or make a later staging decision degrade on its behalf."""
        budget = cache_budget_bytes()
        need_bytes = int(need_bytes)
        with self._mu:
            old = self._external.get(tag, 0)
            extra = need_bytes - old
            if extra > budget:
                return False
            while evict and self.claimed_bytes() + extra > budget:
                if not self._evict_lru():
                    break
            if self.claimed_bytes() + extra > budget:
                return False
            self._external[tag] = need_bytes
        _note("external_reserves", detail=f"tag={tag} bytes={need_bytes}")
        return True

    def release_external(self, tag: str) -> int:
        """Drop an external residency claim; returns the bytes freed
        (0 for an unknown tag).  Idempotent."""
        with self._mu:
            freed = self._external.pop(tag, 0)
        if freed:
            _note("external_releases", detail=f"tag={tag} bytes={freed}")
        return freed

    def release_external_many(self, tags) -> int:
        """Drop a BATCH of external claims under ONE lock acquisition
        and emit ONE ledger note; returns total bytes freed.  The
        serving registry's batched LRU eviction uses this: under pin
        churn at hundreds of models, per-victim `release_external`
        calls pay a lock round-trip and a tracing event each, and the
        ledger lock is shared with every staging reserve."""
        dropped = 0
        freed = 0
        with self._mu:
            for tag in tags:
                b = self._external.pop(tag, 0)
                if b:
                    dropped += 1
                    freed += b
        if freed:
            _note(
                "external_releases",
                detail=f"tags={dropped} bytes={freed}",
            )
        return freed

    def external_shortfall(self, tag: str, need_bytes: int) -> int:
        """Bytes that must be freed elsewhere before
        `reserve_external(tag, need_bytes)` can succeed with the cache
        as it stands (0 = it already fits).  Pure read: the caller
        (serving registry) sizes ONE batched eviction pass instead of
        probing reserve/evict per victim."""
        budget = cache_budget_bytes()
        with self._mu:
            old = self._external.get(tag, 0)
            extra = int(need_bytes) - old
            return max(0, self.claimed_bytes() + extra - budget)

    def external_bytes(self) -> int:
        with self._mu:
            return sum(self._external.values())

    def insert(self, entry: CacheEntry) -> None:
        with self._mu:
            self._clock += 1
            entry.last_used = self._clock
            self._entries[entry.fingerprint] = entry
            # the staging this entry came from ran under a reserve()
            # claim; the entry now carries those bytes itself
            self._pending = max(0, self._pending - entry.nbytes)
        # through _note so the STAGE_COUNTS cache_inserts mirror moves
        # with it (the drift test pins the pair equal)
        _note("inserts")
        self._sync_metrics()

    def clear(self) -> None:
        with self._mu:
            fps = list(self._entries)
        for fp in fps:
            self.evict(fp)

    def _sync_metrics(self) -> None:
        resident, count = self.resident_bytes(), len(self._entries)
        with _lock:
            CACHE_METRICS["resident_bytes"] = resident
            CACHE_METRICS["resident_entries"] = count


_global_cache: Optional[DeviceDatasetCache] = None


def get_device_cache() -> DeviceDatasetCache:
    global _global_cache
    if _global_cache is None:
        _global_cache = DeviceDatasetCache()
    return _global_cache


def clear_device_cache() -> None:
    """Release every resident DATASET entry (tests; explicit operator
    reset; the OOM-recovery paths in core.py call this so resident
    entries cannot starve a retried fit).  External claims (pinned
    serving models) survive: they are not re-creatable mid-request and
    their owner (serving/registry.py) runs its own eviction."""
    if _global_cache is not None:
        _global_cache.clear()


def reserve_external(tag: str, need_bytes: int) -> bool:
    """Module-level facade over `DeviceDatasetCache.reserve_external`
    on the global cache (the serving registry's entry point)."""
    return get_device_cache().reserve_external(tag, need_bytes)


def release_external(tag: str) -> int:
    if _global_cache is None:
        return 0
    return _global_cache.release_external(tag)


def release_external_many(tags) -> int:
    if _global_cache is None:
        return 0
    return _global_cache.release_external_many(tags)


def external_shortfall(tag: str, need_bytes: int) -> int:
    return get_device_cache().external_shortfall(tag, need_bytes)


def cache_resident_bytes() -> int:
    """Bytes the cache holds or has claimed (resident entries plus
    in-flight reservations) — added to every `_over_device_budget`
    estimate (core.py) so staging decisions see the HBM the cache
    occupies."""
    return _global_cache.claimed_bytes() if _global_cache is not None else 0


def invalidate_for_devices(ids) -> int:
    """Evict every resident entry whose mesh contains one of the given
    device ids — the elastic mesh recovery hook (resilience/elastic.py):
    an entry sharded over a lost device is unreadable, so its registry
    claim is dropped and the next consumer re-stages onto the shrunken
    mesh through the pipelined engine (a cache MISS — the new mesh's
    device set keys a different fingerprint anyway).  The chunk cache's
    device tier invalidates on the same signal (host-spilled chunks
    survive — `ChunkCache.invalidate_devices`).  Returns the number of
    dataset entries invalidated."""
    ids = {int(i) for i in ids}
    if _chunk_cache is not None:
        _chunk_cache.invalidate_devices(ids)
    if _global_cache is None:
        return 0
    cache = _global_cache
    with cache._mu:
        doomed = [
            fp
            for fp, e in cache._entries.items()
            if any(int(d.id) in ids for d in e.mesh.devices.flat)
        ]
    for fp in doomed:
        cache.evict(fp)
    return len(doomed)


def evict_to_fit(need_bytes: float, budget: float) -> None:
    """LRU-evict resident entries until `need_bytes` fits under `budget`
    alongside the remaining residency (no-op when it already fits).
    Residency is re-creatable; a staging decision must not degrade to
    the much slower streamed-statistics path while droppable entries
    hold the room (in-flight consumers of an evicted entry keep their
    views — only the registry's claim is released)."""
    if _global_cache is None:
        return
    cache = _global_cache
    while (
        cache.resident_bytes()
        and need_bytes + cache.claimed_bytes() > budget
    ):
        if not cache._evict_lru():
            break


def get_or_stage(
    X: np.ndarray,
    y: Optional[np.ndarray],
    weight: Optional[np.ndarray],
    dtype,
    label_dtype=None,
    num_workers: Optional[int] = None,
    logger=None,
    working_factor: float = 1.0,
) -> Optional[CacheEntry]:
    """The one staging entry point of the cache: return the resident
    entry for this dataset, staging it (once, through the pipelined
    engine) on a miss.  None when the entry would not fit the budget —
    the caller falls back to the legacy uncached path.  `working_factor`
    scales the RESERVATION for consumers whose fold views need transient
    device memory beyond the resident entry — the gather/compaction
    path's cross-shard take lowers to an all-gather that transiently
    replicates the full array per device (~n_dev x), plus the compacted
    view itself: the headroom must exist up front or the per-fold gather
    OOMs after reserve() said yes.  A cache HIT tops the existing
    entry's reservation up to this consumer's factor (a gather-path run
    may hit an entry a mask-path run inserted at factor 1)."""
    dtype = np.dtype(dtype)
    mesh = get_mesh(num_workers)
    fp = dataset_fingerprint(X, y, weight, dtype, label_dtype, mesh)
    cache = get_device_cache()
    entry = cache.lookup(fp)
    if entry is not None:
        want = int(entry.base_bytes * max(working_factor, 1.0))
        if want > entry.nbytes and not cache.top_up(
            entry, want - entry.nbytes
        ):
            _note(
                "misses",
                detail=f"fp={fp[:12]} hit lacks gather headroom "
                f"(+{want - entry.nbytes} over budget)",
            )
            return None
        return entry
    st = RowStager(X.shape[0], mesh)
    ldt = np.dtype(label_dtype) if label_dtype is not None else dtype
    row_bytes = int(X.shape[1]) * dtype.itemsize + dtype.itemsize
    if y is not None:
        row_bytes += ldt.itemsize
    need = st.local_padded * row_bytes
    reserved = int(need * max(working_factor, 1.0))
    # the reservation IS a byte-model prediction (base bytes x the
    # n_dev+2 gather factor): record it so the measured watermark can
    # report how much of that headroom real fits actually touch
    from ..telemetry.memory import record_budget_decision

    ok = cache.reserve(reserved)
    record_budget_decision("device_cache", reserved, not ok)
    if not ok:
        _note(
            "misses",
            detail=f"fp={fp[:12]} over-budget need={need} "
            f"budget={cache_budget_bytes():.0f}",
        )
        if logger is not None:
            logger.info(
                f"device cache: dataset (~{need/2**20:.0f} MiB) exceeds "
                "the cache budget; falling back to uncached staging"
            )
        return None
    _note("misses", detail=f"fp={fp[:12]} staging {need} bytes")
    # pre-staging census: the insert-time drift below measures what THIS
    # staging added, not whatever else already sits on the chips
    from ..telemetry.memory import note_measured_drift, sample_devices

    baseline = sum(sample_devices().values())
    try:
        Xs = st.stage(X, dtype)
        w = st.mask(dtype, weights=weight)
        yd = None
        if y is not None:
            yd = st.stage(np.asarray(y).reshape(-1).astype(ldt), ldt)
    except Exception as e:
        # the byte model cannot see fragmentation or non-dataset HBM
        # (model attributes, solver state): a real staging OOM degrades
        # to the legacy uncached path like every other ineligibility —
        # drop the partial buffers first, they hold the exhausted HBM
        from ..resilience import is_oom

        Xs = w = yd = None  # noqa: F841
        cache.release(reserved)
        if not is_oom(e):
            raise
        if logger is not None:
            logger.warning(
                "device cache: staging exhausted HBM; falling back to "
                "uncached staging"
            )
        return None
    ds = DeviceDataset(mesh, Xs, st.n_valid, y=yd, weight=w, stager=st)
    # the entry records the full reservation (base + gather headroom):
    # it must survive later inserts, or an interleaved get_or_stage
    # could reclaim the room the per-fold gathers need (overstating
    # residency costs cache capacity, never correctness)
    entry = CacheEntry(fp, ds, reserved, base_bytes=need)
    cache.insert(entry)
    # point-in-time drift at the moment residency lands: bytes this
    # staging ADDED vs the entry's reservation (telemetry/memory.py)
    note_measured_drift("device_cache", reserved, baseline_bytes=baseline)
    return entry


# ---------------------------------------------------------------------------
# Chunk-granularity cache — the out-of-core EPOCH engine's fast tier.
#
# The dataset cache above holds whole staged datasets; the epoch-
# streaming solvers (streaming.py mechanism B/C) never stage — they
# re-read and re-decode the same parquet once per L-BFGS evaluation /
# Lloyd pass, and the decode is the measured bottleneck of every
# beyond-HBM fit (BENCH ingest_rows_per_sec caps the epoch rate).  The
# ChunkCache records the DECODED fixed-shape chunks of a scan the first
# time it runs (epoch 1) and replays them for every later identical
# scan (epochs 2..n), so only epoch 1 pays parquet.  Snap ML's
# hierarchical host/accelerator split (PAPERS.md) is the template:
#
#   device tier   the chunk's feature block lives on-device (jax array)
#                 while free headroom under the SAME budget ledger the
#                 dataset cache and serving pins use allows
#                 (`reserve_external(evict=False)` — opportunistic
#                 residency may never displace a dataset entry);
#   host tier     decoded numpy arrays (the pinned-host stand-in on the
#                 CPU mesh), bounded by `chunk_cache_host_bytes`;
#   spill tier    LRU chunks compressed through a pluggable codec
#                 (parallel/chunk_codec.py: none/zlib, lz4/zstd where
#                 the wheels exist) and crc32-checksummed — a corrupt
#                 blob is detected at re-serve and the stream falls
#                 back to the parquet source instead of corrupting an
#                 epoch.
#
# Streams are keyed by the caller (path content stamp + scan
# parameters); chunks are stored as the exact tuples the source
# iterator yielded (ndarray elements read-only, scalars verbatim), so
# replay is byte-identical.  `select` serves only the chunk positions
# an importance-sampling epoch asks for — skipped chunks never
# decompress or transfer (the DuHL win, streaming.py).
# ---------------------------------------------------------------------------

CHUNK_METRICS = _dict_view(
    "chunk_cache",
    "Chunk cache counters (hits/misses/spills/restores/bytes by tier)",
    initial={
        "hits": 0,
        "misses": 0,
        "inserts": 0,
        "spills": 0,
        "restores": 0,
        "evictions": 0,
        "invalidations": 0,
        "checksum_failures": 0,
        "hit_bytes": 0,
        "host_bytes": 0,
        "spilled_bytes": 0,
        "device_bytes": 0,
        "streams_complete": 0,
    },
)

_CHUNK_TAG = "chunk_cache"


class ChunkIntegrityError(RuntimeError):
    """A spilled chunk's crc32 did not match at re-serve time."""


def chunk_cache_enabled() -> bool:
    from ..config import get_config

    return str(get_config("chunk_cache")).lower() == "on"


def chunk_cache_host_budget() -> int:
    from ..config import get_config

    return int(get_config("chunk_cache_host_bytes"))


def _chunk_note(kind: str, amount: int = 1) -> None:
    with _lock:
        CHUNK_METRICS.bump(kind, amount)


_spill_seq = itertools.count()


def _spill_file_path(spill_dir: str, crc: int) -> str:
    """Collision-free spill filename under a SHARED spill dir: multiple
    pod processes may point `chunk_cache_spill_dir` at one filesystem
    (local emulation, NFS scratch), so the name embeds the process
    index and pid alongside the per-process sequence and the content
    crc — two ranks spilling the same content-stamped stream can never
    clobber each other's blobs."""
    import jax

    os.makedirs(spill_dir, exist_ok=True)
    fname = (
        f"srmt-chunk-p{jax.process_index()}-{os.getpid()}-"
        f"{next(_spill_seq)}-{crc & 0xFFFFFFFF:08x}.spill"
    )
    return os.path.join(spill_dir, fname)


class _SpilledArray:
    """One ndarray serialized into the spill tier: an in-memory
    compressed blob by default, or a file under `chunk_cache_spill_dir`
    (`blob is None`, `path` set) when the conf points at a directory —
    the blob bytes then leave the host budget entirely."""

    __slots__ = (
        "codec", "blob", "path", "nbytes", "dtype_str", "shape", "crc",
        "raw_nbytes",
    )

    def __init__(self, codec, blob, dtype_str, shape, crc, raw_nbytes,
                 path=None, nbytes=None):
        self.codec = codec
        self.blob = blob
        self.path = path
        self.nbytes = len(blob) if blob is not None else int(nbytes)
        self.dtype_str = dtype_str
        self.shape = shape
        self.crc = crc
        self.raw_nbytes = int(raw_nbytes)


class _ChunkArray:
    """One ndarray element of a cached chunk: host (numpy) and/or
    device (jax array — a MIRROR of the host copy, feature blocks
    only), or spilled (codec blob + checksum).  The device tier caches
    the host tier rather than replacing it: device consumers skip the
    H2D put every epoch while host consumers (staging writers, host
    moment scans, pure replays) keep zero-copy serves — and a device
    loss costs only the mirror, never the data."""

    __slots__ = ("host", "dev", "spill")

    def __init__(self, host) -> None:
        self.host = host
        self.dev = None
        self.spill = None

    def host_nbytes(self) -> int:
        return int(self.host.nbytes) if self.host is not None else 0

    def spill_nbytes(self) -> int:
        return self.spill.nbytes if self.spill is not None else 0

    def dev_nbytes(self) -> int:
        return int(self.dev.nbytes) if self.dev is not None else 0


class CachedChunk:
    """One yielded tuple of a cached stream: `layout` interleaves
    ("v", scalar-or-None) pass-through elements with ("a", _ChunkArray)
    array elements, preserving tuple order exactly."""

    __slots__ = ("layout", "last_used")

    def __init__(self, layout) -> None:
        self.layout = layout
        self.last_used = 0

    def arrays(self):
        return [v for kind, v in self.layout if kind == "a"]


class _ChunkStream:
    __slots__ = ("key", "chunks", "complete", "dropped", "serving")

    def __init__(self, key) -> None:
        self.key = key
        self.chunks: List[CachedChunk] = []
        self.complete = False
        self.dropped = False
        self.serving = 0  # active serve iterations (eviction pin)


class ChunkCache:
    """Registry of cached chunk streams with tiered residency.  All
    registry state is guarded by `_mu`; the dataset cache's lock is
    only ever taken AFTER `_mu` (via the external-reservation ledger),
    never the other way, so the two cannot deadlock.  Tier byte totals
    are maintained INCREMENTALLY on every transition (a rescan of all
    cached arrays per insert would be O(total_chunks^2) per epoch under
    the lock at small-chunk configurations)."""

    def __init__(self) -> None:
        self._mu = named_lock("chunk_cache", kind="rlock")
        self._streams: Dict[Any, _ChunkStream] = {}
        self._clock = 0
        self._host_b = 0  # host-resident array bytes
        self._spill_b = 0  # compressed spill blob bytes (in-memory)
        self._spill_disk_b = 0  # file-backed spill bytes (spill dir)
        self._dev_total = 0  # bytes booked under _CHUNK_TAG

    # -- accounting ----------------------------------------------------------

    @property
    def _host_total(self) -> int:
        """Bytes counted against `chunk_cache_host_bytes` (host arrays
        plus IN-MEMORY spill blobs).  File-backed spills
        (`chunk_cache_spill_dir`) live on disk and leave the host
        budget entirely — that is the point of configuring a dir."""
        return self._host_b + self._spill_b

    def _touch_locked(self, chunk: CachedChunk) -> None:
        self._clock += 1
        chunk.last_used = self._clock

    def _account_locked(self, host_delta: int = 0, spill_delta: int = 0,
                        disk_delta: int = 0) -> None:
        self._host_b = max(0, self._host_b + int(host_delta))
        self._spill_b = max(0, self._spill_b + int(spill_delta))
        self._spill_disk_b = max(0, self._spill_disk_b + int(disk_delta))
        self._sync_bytes_locked()

    def _sync_bytes_locked(self) -> None:
        with _lock:
            CHUNK_METRICS["host_bytes"] = self._host_b
            CHUNK_METRICS["spilled_bytes"] = self._spill_b + self._spill_disk_b
            CHUNK_METRICS["device_bytes"] = self._dev_total

    def _book_dev_locked(self, delta: int) -> bool:
        """Grow/shrink the chunk cache's claim in the device-budget
        ledger (the same one serving pins and dataset residency use).
        Growth claims FREE headroom only (`evict=False`)."""
        new = self._dev_total + int(delta)
        ledger = get_device_cache()
        if delta > 0:
            if not ledger.reserve_external(_CHUNK_TAG, new, evict=False):
                return False
        elif new <= 0:
            ledger.release_external(_CHUNK_TAG)
            new = 0
        else:
            ledger.reserve_external(_CHUNK_TAG, new)  # shrink always fits
        self._dev_total = new
        return True

    # -- tier transitions ----------------------------------------------------

    def _spill_chunk_locked(self, chunk: CachedChunk) -> None:
        """Move every array of `chunk` into the spill tier (compress +
        checksum).  The `chunk_cache_spill` fault site fires here: an
        injected fault propagates into the consuming epoch iteration,
        whose fit-level retry restarts the pass with fresh accumulators
        (re-creatable state — chunks can never double-count)."""
        from ..config import get_config
        from ..resilience import maybe_inject
        from .chunk_codec import checksum, resolve_codec

        maybe_inject("chunk_cache_spill")
        name, compress, _ = resolve_codec(get_config("chunk_cache_codec"))
        spill_dir = str(get_config("chunk_cache_spill_dir") or "")
        freed_dev = 0
        host_delta = 0
        spill_delta = 0
        disk_delta = 0
        for a in chunk.arrays():
            if a.spill is not None:
                continue
            arr = a.host if a.host is not None else np.asarray(a.dev)
            arr = np.ascontiguousarray(arr)
            raw = arr.tobytes()
            blob = compress(raw)
            crc = checksum(raw)
            if spill_dir:
                path = _spill_file_path(spill_dir, crc)
                with open(path, "wb") as f:
                    f.write(blob)
                a.spill = _SpilledArray(
                    name, None, arr.dtype.str, arr.shape, crc, len(raw),
                    path=path, nbytes=len(blob),
                )
                disk_delta += a.spill.nbytes
            else:
                a.spill = _SpilledArray(
                    name, blob, arr.dtype.str, arr.shape, crc, len(raw),
                )
                spill_delta += a.spill.nbytes
            if a.dev is not None:
                freed_dev += a.dev_nbytes()
                a.dev = None
            host_delta -= a.host_nbytes()
            a.host = None
        if freed_dev:
            self._book_dev_locked(-freed_dev)
        self._account_locked(host_delta, spill_delta, disk_delta)
        _chunk_note("spills")
        from ..tracing import event

        event(
            "chunk_cache_spill",
            detail=f"codec={name}" + (" tier=disk" if spill_dir else ""),
        )

    def _restore_array_locked(self, a: _ChunkArray) -> np.ndarray:
        """Spill blob -> read-only ndarray, crc-verified.  The restored
        view is NOT re-warmed into the host tier: a working set larger
        than the host budget would otherwise thrash (restore chunk i,
        spill chunk j, every epoch)."""
        from .chunk_codec import checksum, resolve_codec

        sp = a.spill
        _, _, decompress = resolve_codec(sp.codec)
        blob = sp.blob
        if blob is None:
            try:
                with open(sp.path, "rb") as f:
                    blob = f.read()
            except OSError as e:
                # a vanished/unreadable spill file is an integrity loss,
                # same verdict as a torn in-memory blob
                _chunk_note("checksum_failures")
                raise ChunkIntegrityError(
                    f"spill file unreadable ({sp.path}): {e}"
                ) from e
        try:
            raw = decompress(blob)
        except Exception as e:
            # a torn blob can fail the codec before the crc ever runs —
            # same integrity verdict either way
            _chunk_note("checksum_failures")
            raise ChunkIntegrityError(
                f"spilled chunk failed to decompress (codec={sp.codec}): "
                f"{e}"
            ) from e
        if checksum(raw) != sp.crc:
            _chunk_note("checksum_failures")
            raise ChunkIntegrityError(
                f"spilled chunk failed crc32 (codec={sp.codec}, "
                f"{len(raw)} bytes)"
            )
        _chunk_note("restores")
        return np.frombuffer(raw, dtype=np.dtype(sp.dtype_str)).reshape(
            sp.shape
        )

    def _drop_stream_locked(self, st: _ChunkStream, reason: str) -> None:
        if st.dropped:
            return
        st.dropped = True
        freed_dev = host_delta = spill_delta = disk_delta = 0
        for c in st.chunks:
            for a in c.arrays():
                freed_dev += a.dev_nbytes()
                host_delta -= a.host_nbytes()
                if a.spill is not None and a.spill.path is not None:
                    disk_delta -= a.spill.nbytes
                    try:
                        os.unlink(a.spill.path)
                    except OSError:
                        pass  # best-effort: orphans are rank-distinct files
                else:
                    spill_delta -= a.spill_nbytes()
        st.chunks = []
        self._streams.pop(st.key, None)
        if freed_dev:
            self._book_dev_locked(-freed_dev)
        self._account_locked(host_delta, spill_delta, disk_delta)
        _chunk_note("evictions")
        from ..tracing import event

        event("chunk_cache_evict", detail=reason)

    def _shrink_locked(self, protect: Optional[_ChunkStream]) -> None:
        """Enforce the host budget: spill LRU chunks first (compression
        may shrink them), then evict LRU streams outright.  `protect`
        is the stream currently FILLING — evicted only as the last
        resort (a single stream larger than the whole budget)."""
        budget = chunk_cache_host_budget()
        spills_help = True  # flips off when the codec frees nothing
        while self._host_total > budget:
            victim = None
            if spills_help:
                # host-resident chunks only: device-tier chunks cost no
                # host bytes, and spilling one would GROW the host total
                for st in self._streams.values():
                    for c in st.chunks:
                        if any(a.host is not None for a in c.arrays()):
                            if (victim is None
                                    or c.last_used < victim.last_used):
                                victim = c
            if victim is not None:
                before = self._host_total
                self._spill_chunk_locked(victim)
                if self._host_total < before:
                    continue
                # codec="none" spills byte-for-byte: stop burning CPU
                # on no-gain spills and move straight to eviction
                spills_help = False
            # nothing (usefully) spillable left: drop whole LRU streams.
            # Streams with an ACTIVE serve iteration are pinned — an
            # eviction mid-serve would force the position-based source
            # fallback, which is only sound for in-order sources
            streams = [
                s for s in self._streams.values()
                if s is not protect and s.serving == 0
            ]
            if not streams and protect is not None and protect.serving == 0:
                streams = [protect]
            if not streams:
                return  # everything pinned: transiently over budget
            lru = min(
                streams,
                key=lambda s: min(
                    (c.last_used for c in s.chunks), default=0
                ),
            )
            self._drop_stream_locked(lru, "host_budget")

    # -- insert / serve ------------------------------------------------------

    def _insert(self, st: _ChunkStream, item: tuple,
                device_elem: Optional[int], serve_device: bool):
        """Record one yielded tuple; returns the tuple to hand the
        consumer (same host arrays, marked read-only — a mutating
        consumer must fail loudly, not corrupt later epochs).  A
        device-capable consumer receives the freshly created device
        MIRROR for the promoted element: its own `device_put` of the
        same bytes would double the fill epoch's H2D traffic."""
        layout = []
        served = []
        host_bytes = 0
        for part in item:
            if isinstance(part, np.ndarray):
                a = np.ascontiguousarray(part)
                a.setflags(write=False)
                layout.append(("a", _ChunkArray(a)))
                served.append(a)
                host_bytes += a.nbytes
            else:
                layout.append(("v", part))
                served.append(part)
        chunk = CachedChunk(tuple(layout))
        with self._mu:
            if st.dropped:
                return tuple(served)
            st.chunks.append(chunk)
            self._touch_locked(chunk)
            if device_elem is not None:
                kind, ca = chunk.layout[device_elem]
                if kind == "a" and self._book_dev_locked(ca.host_nbytes()):
                    try:
                        import jax

                        ca.dev = jax.device_put(ca.host)
                        if serve_device:
                            served[device_elem] = ca.dev
                    except Exception:
                        # opportunistic residency must never fail the
                        # consumer OR leak its booked claim: release
                        # and keep serving from the host tier
                        self._book_dev_locked(-ca.host_nbytes())
            self._account_locked(host_delta=host_bytes)
            self._shrink_locked(protect=st)
        _chunk_note("inserts")
        return tuple(served)

    def _serve_chunk_locked(self, chunk: CachedChunk,
                            serve_device: bool) -> tuple:
        out = []
        nbytes = 0
        first_arr = True
        for kind, v in chunk.layout:
            if kind == "v":
                out.append(v)
                continue
            if (
                serve_device and first_arr and v.dev is None
                and v.host is not None
                and self._book_dev_locked(v.host_nbytes())
            ):
                # serve-time promotion: a stream first filled by a
                # host-only consumer (label-moments scan, k-means
                # seeding) mirrors its feature blocks on device the
                # first time a device consumer replays it, while ledger
                # headroom allows
                try:
                    import jax

                    v.dev = jax.device_put(v.host)
                except Exception:
                    # failed mirror: release the booked claim and keep
                    # serving host bytes — a device OOM here must
                    # degrade, not abort the consuming epoch
                    self._book_dev_locked(-v.host_nbytes())
                self._sync_bytes_locked()
            first_arr = False
            if serve_device and v.dev is not None:
                out.append(v.dev)
                nbytes += v.dev_nbytes()
            elif v.host is not None:
                out.append(v.host)
                nbytes += v.host_nbytes()
            elif v.dev is not None:
                out.append(np.asarray(v.dev))
                nbytes += v.dev_nbytes()
            else:
                arr = self._restore_array_locked(v)
                out.append(arr)
                nbytes += arr.nbytes
        self._touch_locked(chunk)
        _chunk_note("hit_bytes", nbytes)
        return tuple(out)

    def stream_complete(self, key) -> Optional[int]:
        """Chunk count of a fully cached stream, None otherwise — the
        gate importance-sampling epochs check before selecting."""
        with self._mu:
            st = self._streams.get(key)
            if st is not None and st.complete and not st.dropped:
                return len(st.chunks)
            return None

    def stream(self, key, source_factory, device_elem: Optional[int] = None,
               serve_device: bool = False, select=None,
               ordered: bool = True):
        """Serve the chunk stream for `key` from cache when complete,
        else run `source_factory()` and record it in passing.  A stream
        another iteration is still filling is bypassed (read the source
        directly, cache untouched).  `select` (position set) filters
        the served chunks; it only applies to fully cached streams —
        callers gate on `stream_complete` first.  `ordered=False`
        declares the SOURCE's chunk order nondeterministic (the fused
        parallel reader pool): a mid-serve failure then cannot resume
        from the source by position, so it raises instead of silently
        mixing two orderings (actively-served streams are eviction-
        pinned, making that path corruption-only)."""
        with self._mu:
            st = self._streams.get(key)
            if st is not None and st.complete and not st.dropped:
                mode = "serve"
                st.serving += 1  # pins the stream against eviction
            elif st is None:
                st = _ChunkStream(key)
                self._streams[key] = st
                mode = "fill"
            else:
                mode = "bypass"
        if mode == "bypass":
            yield from _select_iter(source_factory(), select)
            return
        if mode == "serve":
            _chunk_note("hits")
            try:
                yield from self._serve(
                    st, source_factory, serve_device, select, ordered
                )
            finally:
                with self._mu:
                    st.serving = max(0, st.serving - 1)
            return
        _chunk_note("misses")
        done = False
        try:
            for item in _select_iter(source_factory(), select):
                try:
                    out = self._insert(st, item, device_elem, serve_device)
                except Exception:
                    # insert failed (injected spill fault, codec error):
                    # the cache must not keep a half-recorded stream —
                    # the error itself propagates into the consuming
                    # iteration (fit-level retry restarts the pass)
                    with self._mu:
                        self._drop_stream_locked(st, "insert_failed")
                    raise
                yield out
            done = True
        finally:
            with self._mu:
                if done and not st.dropped and select is None:
                    st.complete = True
                    _chunk_note("streams_complete")
                else:
                    self._drop_stream_locked(st, "abandoned")

    def _serve(self, st: _ChunkStream, source_factory, serve_device: bool,
               select, ordered: bool):
        n = len(st.chunks)
        pos = 0
        while pos < n:
            if select is not None and pos not in select:
                pos += 1
                continue
            try:
                with self._mu:
                    if st.dropped or pos >= len(st.chunks):
                        raise LookupError("chunk evicted mid-serve")
                    item = self._serve_chunk_locked(
                        st.chunks[pos], serve_device
                    )
            except (LookupError, ChunkIntegrityError, ImportError,
                    ValueError) as e:
                with self._mu:
                    self._drop_stream_locked(st, "serve_fallback")
                if not ordered:
                    # the recorded order came from a nondeterministic
                    # reader pool: position-resume against a fresh pool
                    # run would double-count some chunks and drop
                    # others.  Fail LOUDLY — the consuming pass's
                    # accumulators are re-creatable and its fit-level
                    # retry re-reads the (now uncached) source
                    raise ChunkIntegrityError(
                        "cached chunk unusable mid-serve of an "
                        f"order-free stream ({e}); restart the pass"
                    ) from e
                # in-order source: drop the stream and finish from the
                # parquet source at the same position — the consumer
                # sees an uninterrupted, byte-identical stream
                for i, fresh in enumerate(source_factory()):
                    if i < pos:
                        continue
                    if select is None or i in select:
                        yield fresh
                return
            yield item
            pos += 1

    # -- maintenance ---------------------------------------------------------

    def invalidate_devices(self, ids) -> int:
        """Drop the device tier for chunks resident on the given (lost)
        device ids.  A chunk with a host/spill copy survives and keeps
        serving; a device-only chunk is gone with its chip, so its
        whole stream drops (the next scan is a miss that re-reads
        parquet — exactly the dataset cache's recovery contract)."""
        ids = {int(i) for i in ids}
        n = 0
        with self._mu:
            for st in list(self._streams.values()):
                doomed = False
                for c in st.chunks:
                    for a in c.arrays():
                        if a.dev is None:
                            continue
                        try:
                            on_lost = any(
                                int(d.id) in ids for d in a.dev.devices()
                            )
                        except Exception:
                            on_lost = True
                        if not on_lost:
                            continue
                        self._book_dev_locked(-a.dev_nbytes())
                        a.dev = None
                        n += 1
                        if a.host is None and a.spill is None:
                            doomed = True
                if doomed:
                    self._drop_stream_locked(st, "device_lost")
            self._sync_bytes_locked()
        if n:
            _chunk_note("invalidations", n)
        return n

    def clear(self) -> None:
        with self._mu:
            for st in list(self._streams.values()):
                self._drop_stream_locked(st, "clear")


def _select_iter(it, select):
    if select is None:
        yield from it
        return
    for i, item in enumerate(it):
        if i in select:
            yield item


_chunk_cache: Optional[ChunkCache] = None


def get_chunk_cache() -> ChunkCache:
    global _chunk_cache
    if _chunk_cache is None:
        _chunk_cache = ChunkCache()
    return _chunk_cache


def clear_chunk_cache() -> None:
    """Drop every cached chunk stream and release the device-ledger
    claim (tests; explicit operator reset)."""
    if _chunk_cache is not None:
        _chunk_cache.clear()


def cached_chunk_stream(key, source_factory, device_elem: Optional[int] = None,
                        serve_device: bool = False, select=None,
                        ordered: bool = True):
    """The one consumer entry point: wrap a chunk iterator in the chunk
    cache.  `key=None` (source not content-stampable) or
    `chunk_cache=off` bypasses entirely.  `ordered=False` marks a
    source whose chunk order is nondeterministic (see
    `ChunkCache.stream`)."""
    if key is None or not chunk_cache_enabled():
        yield from _select_iter(source_factory(), select)
        return
    yield from get_chunk_cache().stream(
        key, source_factory, device_elem=device_elem,
        serve_device=serve_device, select=select, ordered=ordered,
    )


def chunk_stream_complete(key) -> Optional[int]:
    if key is None or _chunk_cache is None or not chunk_cache_enabled():
        return None
    return _chunk_cache.stream_complete(key)


__all__ = [
    "CACHE_METRICS",
    "CHUNK_METRICS",
    "CacheEntry",
    "CachedEvalView",
    "ChunkCache",
    "ChunkIntegrityError",
    "DeviceDatasetCache",
    "FoldSet",
    "cache_budget_bytes",
    "cache_enabled",
    "cache_resident_bytes",
    "cached_chunk_stream",
    "chunk_cache_enabled",
    "chunk_cache_host_budget",
    "chunk_stream_complete",
    "clear_chunk_cache",
    "clear_device_cache",
    "dataset_fingerprint",
    "device_data_budget_bytes",
    "get_chunk_cache",
    "get_device_cache",
    "get_or_stage",
    "external_shortfall",
    "invalidate_for_devices",
    "release_external",
    "release_external_many",
    "reserve_external",
]
