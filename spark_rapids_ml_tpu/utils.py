#
# Utilities — the analog of reference utils.py (982 LoC): logging
# (utils.py:555-576), PartitionDescriptor (utils.py:300-355),
# memory-efficient concat (utils.py:358-400), and small array helpers.
# The GPU-id / RMM pieces have no TPU analog (XLA owns HBM); host staging
# helpers live in data.py.
#
from __future__ import annotations

import logging
import sys
from dataclasses import dataclass
from typing import Any, List, Optional, Type, Union

import numpy as np

_logger_initialized = set()


def get_logger(cls: Union[Type, str], level: int = logging.INFO) -> logging.Logger:
    """Per-class stderr logger (reference utils.py:555-576)."""
    name = cls if isinstance(cls, str) else f"spark_rapids_ml_tpu.{cls.__name__}"
    logger = logging.getLogger(name)
    if name not in _logger_initialized:
        logger.setLevel(level)
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
        logger.addHandler(handler)
        logger.propagate = False
        _logger_initialized.add(name)
    return logger


@dataclass
class PartitionDescriptor:
    """Global partition layout for a distributed fit (reference
    `PartitionDescriptor`, utils.py:300-355, built there via barrier
    allGather; here computed by the single controller that shards rows).

    m: total number of rows
    n: number of features (data dim)
    parts_rank_size: (rank, row-count) per shard
    rank: this process's rank (always 0 single-controller)
    """

    m: int
    n: int
    parts_rank_size: List[tuple]
    rank: int = 0
    max_nnz: int = 0

    @classmethod
    def build(cls, partition_rows: List[int], total_cols: int, rank: int = 0,
              max_nnz: int = 0) -> "PartitionDescriptor":
        return cls(
            m=int(sum(partition_rows)),
            n=int(total_cols),
            parts_rank_size=[(i, int(r)) for i, r in enumerate(partition_rows)],
            rank=rank,
            max_nnz=max_nnz,
        )


def _concat_and_free(arrays: List[np.ndarray], order: str = "C") -> np.ndarray:
    """Concatenate row blocks into a preallocated output, freeing inputs as
    we go to halve peak host memory (reference `_concat_and_free`,
    utils.py:358-400)."""
    if len(arrays) == 1:
        return np.ascontiguousarray(arrays[0]) if order == "C" else np.asfortranarray(arrays[0])
    rows = sum(a.shape[0] for a in arrays)
    if arrays[0].ndim == 1:
        out = np.empty((rows,), dtype=arrays[0].dtype)
    else:
        out = np.empty((rows, arrays[0].shape[1]), dtype=arrays[0].dtype, order=order)  # type: ignore[call-overload]
    offset = 0
    while arrays:
        a = arrays.pop(0)
        out[offset : offset + a.shape[0]] = a
        offset += a.shape[0]
        del a
    return out


def _standardize_stats(X: np.ndarray, sample_weight: Optional[np.ndarray] = None):
    """Weighted column mean/std matching Spark's summarizer semantics
    (ddof=1-style scaling, reference `_standardize_dataset` utils.py:876-982).
    Host-side helper for the CPU path; the distributed version is
    ops/stats.py."""
    if sample_weight is None:
        mean = X.mean(axis=0)
        std = X.std(axis=0, ddof=1)
    else:
        w = sample_weight / sample_weight.sum()
        mean = (X * w[:, None]).sum(axis=0)
        var = (w[:, None] * (X - mean) ** 2).sum(axis=0) * (
            sample_weight.sum() / max(sample_weight.sum() - 1, 1)
        )
        std = np.sqrt(var)
    std = np.where(std == 0.0, 1.0, std)
    return mean, std


def array_equal_tol(
    a: Any, b: Any, unit_tol: float = 1e-4, total_tol: float = 0.0
) -> bool:
    """Tolerant array comparison used throughout tests (reference
    tests/utils.py:150-165)."""
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        return False
    close = np.isclose(a, b, atol=unit_tol, rtol=0)
    return bool((~close).sum() <= total_tol * close.size)


@dataclass
class _ArrayBatch:
    """A staged host batch: features plus optional label/weight/id columns."""

    X: np.ndarray
    y: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    row_id: Optional[np.ndarray] = None


def prefetch_iter(it, depth: int):
    """Run iterator `it` on a daemon thread up to `depth` items ahead of
    the consumer (bounded queue of depth-1 + the one in the producer's
    hand) — the shared overlap primitive behind
    `streaming.iter_chunks_prefetch` (parquet decode ahead of the device)
    and the staging pipeline's producer (`mesh.run_staging_pipeline`).
    Bounded puts so an abandoned consumer (exception/GC closes the
    generator) cannot pin the producer thread + item copies forever;
    producer exceptions re-raise on the consumer.  depth <= 1: plain
    iteration, no thread."""
    if depth <= 1:
        yield from it
        return
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth - 1)
    _DONE = object()
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    # the producer runs the caller's iterator (chunk-cache inserts emit
    # spill/evict trace events; device_puts emit compile events): adopt
    # the caller's trace buffer + run context so they attribute to the
    # fit that is consuming, not to an anonymous worker thread
    from .tracing import adopt_trace_context

    adopt = adopt_trace_context()

    def producer() -> None:
        adopt()
        try:
            for item in it:
                if not _put(item):
                    return
            _put(_DONE)
        except BaseException as e:  # surface producer errors on the consumer
            _put(e)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _DONE:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
