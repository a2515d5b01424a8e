#
# Baseline fingerprints — the distribution summary the drift monitor
# compares serving traffic against.  A fingerprint is ONE pass of
# host-side mergeable state per feature column:
#
#   moments        count / NaN count / sum / sum-of-squares / min / max
#   quantiles      the mergeable KLL-style sketch (stats/sketches.py)
#   frequent items Misra-Gries table (categorical-coded columns)
#   distinct       HyperLogLog registers (host fold, same hashing as the
#                  device `distinct_count` program)
#
# All state folds NUMPY-ONLY on the host tier: capturing a baseline
# during a fused fit costs the chunks the fit already decoded (zero
# extra data passes, zero device work — the Snap ML host/accelerator
# split from PAPERS.md applied to monitoring), and the serving-side
# sliding windows (monitor/monitor.py) reuse the same builder.
#
# Weights are a VALIDITY mask (w > 0 participates once), matching the
# sketch discipline documented in docs/statistics.md.  NaN values are
# excluded from the moments and the frequency table (their rate is
# tracked as the `null_rate` statistic — a null-rate SHIFT is itself a
# drift signal), count as a single distinct value in the HLL (np.nan's
# canonical bit pattern, same as the device `distinct_count` program),
# and for the quantile sketch are imputed to the chunk's column mean so
# the sketch stays all-column vectorized without NaN poisoning the
# sorted buffers.
#
from __future__ import annotations

import io
import struct
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..config import get_config
from ..stats.sketches import (
    frequent_init,
    frequent_merge,
    hll_estimate,
    hll_init,
    hll_update,
    quantile_init,
    quantile_merge,
    quantile_query,
    quantile_update,
)

FINGERPRINT_MAGIC = b"SRFP"
FINGERPRINT_VERSION = 1

# builder-state wire (cross-process baseline reduction): the LIVE
# mergeable state, as opposed to the finalized SRFP fingerprint — ranks
# exchange builders so the merged result is exactly what one builder
# folding all slices would hold
BUILDER_MAGIC = b"SRBB"
BUILDER_VERSION = 1

# rows buffered before the sketches fold: per-row serving requests must
# not pay a per-row np.unique per column — buffered folds amortize the
# sketch cost over the buffer
_FOLD_BATCH_ROWS = 2048

# decile edges the PSI comparison bins on (monitor/compare.py)
PSI_QUANTILES = tuple(np.linspace(0.1, 0.9, 9))


class BaselineBuilder:
    """One-pass mergeable distribution state over (rows, d) chunks.
    `update(X, valid)` buffers rows and folds in batches; `finalize()`
    returns an immutable `Fingerprint`.  The geometry (sketch k,
    frequent-items cap, HLL bits) comes from the summarizer confs, read
    once at construction so a builder is internally consistent even if
    the confs change mid-capture."""

    def __init__(self, d: int) -> None:
        self.d = int(d)
        self.k = int(get_config("summarizer_sketch_k"))
        self.cap = int(get_config("summarizer_frequent_k"))
        self.bits = int(get_config("summarizer_hll_bits"))
        self.n = 0  # valid rows folded (incl. buffered)
        self.nan = np.zeros((d,), np.int64)
        self.s1 = np.zeros((d,), np.float64)
        self.s2 = np.zeros((d,), np.float64)
        self.vmin = np.full((d,), np.inf)
        self.vmax = np.full((d,), -np.inf)
        self.q = quantile_init(d, self.k)
        self.f = frequent_init(d, self.cap)
        self.h = hll_init(d, self.bits)
        self._pending: List[np.ndarray] = []
        self._pending_rows = 0
        # frequent-items folding deactivates per column once the column
        # proves CONTINUOUS (two consecutive flushes mostly-unique): the
        # Misra-Gries dict fold is the dominant sketch cost (~10 us/row
        # measured), and the comparator's churn statistic never consults
        # a table whose coverage is negligible — exactly the tables a
        # continuous column produces.  Categorical-coded columns stay
        # active forever.
        self._mg_active = np.ones(d, bool)
        self._mg_streak = np.zeros(d, np.int32)

    def update(self, X: np.ndarray, valid: Optional[np.ndarray] = None):
        """Fold one chunk; `valid` masks padding rows (None = all
        valid).  Cheap per call — small blocks buffer and fold per
        `_FOLD_BATCH_ROWS`; large blocks (fit-time chunks) fold
        directly in bounded slices, so a multi-hundred-MB staged chunk
        never gets a full-width float64 twin."""
        X = np.asarray(X)
        if X.ndim == 1:
            X = X[None, :]
        if valid is not None:
            v = np.asarray(valid).reshape(-1) > 0
            if not v.all():
                X = X[v]
        if X.shape[0] == 0:
            return self
        if X.shape[1] != self.d:
            raise ValueError(
                f"baseline expects {self.d} columns, got {X.shape[1]}"
            )
        self.n += int(X.shape[0])
        if X.shape[0] >= _FOLD_BATCH_ROWS:
            self._flush()
            for lo in range(0, X.shape[0], _FOLD_BATCH_ROWS):
                self._fold_block(
                    np.array(X[lo:lo + _FOLD_BATCH_ROWS], np.float64)
                )
        else:
            self._pending.append(np.array(X, np.float64))
            self._pending_rows += int(X.shape[0])
            if self._pending_rows >= _FOLD_BATCH_ROWS:
                self._flush()
        return self

    def _flush(self) -> None:
        if not self._pending:
            return
        X = (
            self._pending[0]
            if len(self._pending) == 1
            else np.concatenate(self._pending, axis=0)
        )
        self._pending = []
        self._pending_rows = 0
        self._fold_block(X)

    def _fold_block(self, X: np.ndarray) -> None:
        nan = np.isnan(X)
        has_nan = bool(nan.any())
        if has_nan:
            self.nan += nan.sum(axis=0)
            Xs = np.where(nan, 0.0, X)
            cnt = np.maximum((~nan).sum(axis=0), 1)
            self.s1 += Xs.sum(axis=0)
            self.s2 += (Xs * Xs).sum(axis=0)
            self.vmin = np.minimum(
                self.vmin, np.where(nan, np.inf, X).min(axis=0)
            )
            self.vmax = np.maximum(
                self.vmax, np.where(nan, -np.inf, X).max(axis=0)
            )
            # quantile sketch: impute NaN to the chunk column mean so the
            # all-column vectorized fold stays NaN-free (null-rate drift
            # is tracked separately)
            Xq = np.where(nan, (Xs.sum(axis=0) / cnt)[None, :], X)
        else:
            self.s1 += X.sum(axis=0)
            self.s2 += (X * X).sum(axis=0)
            self.vmin = np.minimum(self.vmin, X.min(axis=0))
            self.vmax = np.maximum(self.vmax, X.max(axis=0))
            Xq = X
        ones = np.ones((X.shape[0],), bool)
        quantile_update(self.q, Xq, ones, self.k)
        self._mg_fold(X)
        # RAW values into the HLL (np.nan canonicalizes to one quiet-NaN
        # bit pattern, so missing values count as a single distinct —
        # exactly what the device `distinct_count` program does; the
        # imputed Xq would mint a fresh chunk-mean distinct per flush)
        hll_update(self.h, X, ones, self.bits)

    # columns with at least this many non-NaN rows in a flush may be
    # judged continuous; mostly-unique = uniques > half the rows
    _MG_JUDGE_ROWS = 512

    def _mg_fold(self, X: np.ndarray) -> None:
        """Per-column Misra-Gries fold over the still-active columns
        (see `_mg_active`) — the body of `sketches.frequent_update` with
        the continuous-column opt-out."""
        from ..stats.sketches import _mg_fold_column

        self.f["n"] = self.f["n"] + X.shape[0]
        for j in np.flatnonzero(self._mg_active):
            col = X[:, j]
            col = col[~np.isnan(col)]
            if col.size == 0:
                continue
            uniq, cnts = np.unique(col, return_counts=True)
            if (
                col.size >= self._MG_JUDGE_ROWS
                and uniq.size > col.size // 2
            ):
                self._mg_streak[j] += 1
                if self._mg_streak[j] >= 2:
                    self._mg_active[j] = False
                    continue
            else:
                self._mg_streak[j] = 0
            self.f["keys"][j], self.f["counts"][j], e = _mg_fold_column(
                self.f["keys"][j], self.f["counts"][j],
                int(self.f["err"][j]), uniq, cnts, self.cap,
            )
            self.f["err"][j] = e

    def merge(self, other: "BaselineBuilder") -> "BaselineBuilder":
        """Fold `other`'s state into a NEW builder (both inputs stay
        usable) — the tumbling-window pair the serving monitor scores
        (last closed window + current)."""
        if (self.d, self.k, self.cap, self.bits) != (
            other.d, other.k, other.cap, other.bits
        ):
            raise ValueError("cannot merge builders of differing geometry")
        self._flush()
        other._flush()
        out = BaselineBuilder.__new__(BaselineBuilder)
        out.d, out.k, out.cap, out.bits = self.d, self.k, self.cap, self.bits
        out.n = self.n + other.n
        out.nan = self.nan + other.nan
        out.s1 = self.s1 + other.s1
        out.s2 = self.s2 + other.s2
        out.vmin = np.minimum(self.vmin, other.vmin)
        out.vmax = np.maximum(self.vmax, other.vmax)
        out.q = quantile_merge(self.q, other.q, self.k)
        out.f = frequent_merge(self.f, other.f, self.cap)
        out.h = {"regs": np.maximum(self.h["regs"], other.h["regs"])}
        out._pending = []
        out._pending_rows = 0
        out._mg_active = self._mg_active & other._mg_active
        out._mg_streak = np.maximum(self._mg_streak, other._mg_streak)
        return out

    def finalize(
        self, column_names: Optional[List[str]] = None
    ) -> Optional["Fingerprint"]:
        """The immutable fingerprint, or None when nothing folded (a
        pass served entirely device-resident has no host rows — the fit
        then simply carries no baseline)."""
        self._flush()
        if self.n == 0:
            return None
        return Fingerprint(
            d=self.d,
            n=self.n,
            nan=self.nan.copy(),
            s1=self.s1.copy(),
            s2=self.s2.copy(),
            vmin=self.vmin.copy(),
            vmax=self.vmax.copy(),
            quantile={k: np.array(v) for k, v in self.q.items()},
            frequent={k: np.array(v) for k, v in self.f.items()},
            hll={"regs": np.array(self.h["regs"])},
            columns=list(column_names or ()),
            created=time.time(),
        )


class Fingerprint:
    """An immutable captured distribution summary: the fit-time baseline
    a model carries (`model._drift_baseline`, persisted as
    `drift_baseline.bin` next to the model arrays) and the shape the
    serving windows finalize into for comparison."""

    __slots__ = (
        "d", "n", "nan", "s1", "s2", "vmin", "vmax",
        "quantile", "frequent", "hll", "columns", "created",
    )

    def __init__(self, d, n, nan, s1, s2, vmin, vmax, quantile,
                 frequent, hll, columns, created) -> None:
        self.d = int(d)
        self.n = int(n)
        self.nan = nan
        self.s1 = s1
        self.s2 = s2
        self.vmin = vmin
        self.vmax = vmax
        self.quantile = quantile
        self.frequent = frequent
        self.hll = hll
        self.columns = list(columns or ())
        self.created = float(created)

    # -- derived statistics --------------------------------------------------

    def mean(self) -> np.ndarray:
        denom = np.maximum(self.n - self.nan, 1)
        return self.s1 / denom

    def std(self) -> np.ndarray:
        denom = np.maximum(self.n - self.nan, 1)
        mean = self.s1 / denom
        var = np.maximum(self.s2 / denom - mean * mean, 0.0)
        return np.sqrt(var)

    def null_rate(self) -> np.ndarray:
        return self.nan / max(self.n, 1)

    def distinct(self) -> np.ndarray:
        return hll_estimate(self.hll["regs"])

    def quantiles(self, qs) -> np.ndarray:
        return quantile_query(self.quantile, qs)

    def column_name(self, j: int) -> str:
        if j < len(self.columns):
            return str(self.columns[j])
        return f"x{j}"

    def summary(self) -> Dict[str, Any]:
        """JSON-friendly per-column summary — what the post-mortem
        bundle's drift attachment and `server.report()` carry (the full
        sketch state stays in the binary form)."""
        deciles = self.quantiles(PSI_QUANTILES)
        return {
            "rows": self.n,
            "created": round(self.created, 3),
            "columns": [self.column_name(j) for j in range(self.d)],
            "mean": [round(float(v), 6) for v in self.mean()],
            "std": [round(float(v), 6) for v in self.std()],
            "min": [round(float(v), 6) for v in self.vmin],
            "max": [round(float(v), 6) for v in self.vmax],
            "null_rate": [round(float(v), 6) for v in self.null_rate()],
            "distinct": [round(float(v), 1) for v in self.distinct()],
            "deciles": [
                [round(float(v), 6) for v in deciles[j]]
                for j in range(self.d)
            ],
        }

    # -- wire format ---------------------------------------------------------

    def to_bytes(self) -> bytes:
        """Versioned serialized form (compressed; the sketch buffers are
        mostly zeros).  `from_bytes` REJECTS other wire versions — a
        baseline from a different layout must be re-captured."""
        import json

        meta = {
            "d": self.d, "n": self.n, "created": self.created,
            "columns": self.columns,
        }
        buf = io.BytesIO()
        np.savez_compressed(
            buf,
            nan=self.nan, s1=self.s1, s2=self.s2,
            vmin=self.vmin, vmax=self.vmax,
            q__items=self.quantile["items"],
            q__sizes=self.quantile["sizes"],
            q__n=self.quantile["n"],
            f__keys=self.frequent["keys"],
            f__counts=self.frequent["counts"],
            f__err=self.frequent["err"],
            f__n=self.frequent["n"],
            h__regs=self.hll["regs"],
        )
        meta_b = json.dumps(meta).encode()
        return (
            FINGERPRINT_MAGIC
            + struct.pack("<HI", FINGERPRINT_VERSION, len(meta_b))
            + meta_b
            + buf.getvalue()
        )

    @classmethod
    def from_bytes(cls, blob: bytes) -> "Fingerprint":
        import json

        if blob[:4] != FINGERPRINT_MAGIC:
            raise ValueError("not a serialized fingerprint (bad magic)")
        version, mlen = struct.unpack("<HI", blob[4:10])
        if version != FINGERPRINT_VERSION:
            raise ValueError(
                f"fingerprint wire version {version} unsupported (this "
                f"build speaks {FINGERPRINT_VERSION}); re-fit to "
                "re-capture the baseline"
            )
        meta = json.loads(blob[10:10 + mlen].decode())
        with np.load(io.BytesIO(blob[10 + mlen:]), allow_pickle=False) as z:
            arr = {k: z[k] for k in z.files}
        return cls(
            d=meta["d"], n=meta["n"],
            nan=arr["nan"], s1=arr["s1"], s2=arr["s2"],
            vmin=arr["vmin"], vmax=arr["vmax"],
            quantile={
                "items": arr["q__items"], "sizes": arr["q__sizes"],
                "n": arr["q__n"],
            },
            frequent={
                "keys": arr["f__keys"], "counts": arr["f__counts"],
                "err": arr["f__err"], "n": arr["f__n"],
            },
            hll={"regs": arr["h__regs"]},
            columns=meta.get("columns", []),
            created=meta.get("created", 0.0),
        )


def builder_to_bytes(b: BaselineBuilder) -> bytes:
    """Versioned wire form of a builder's LIVE mergeable state — the
    payload each rank ships at the cross-process baseline reduction
    (parallel/context.py reduce_blob_list).  The three sketches travel
    in their own versioned `sketch_to_bytes` wire (the existing
    cross-version contract); moments and Misra-Gries control state ride
    one compressed npz."""
    import json

    from ..stats.sketches import sketch_to_bytes

    b._flush()
    meta = {"d": b.d, "k": b.k, "cap": b.cap, "bits": b.bits, "n": b.n}
    buf = io.BytesIO()
    np.savez_compressed(
        buf,
        meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
        nan=b.nan, s1=b.s1, s2=b.s2, vmin=b.vmin, vmax=b.vmax,
        q=np.frombuffer(sketch_to_bytes("quantile", b.q), np.uint8),
        f=np.frombuffer(sketch_to_bytes("frequent", b.f), np.uint8),
        h=np.frombuffer(sketch_to_bytes("hll", b.h), np.uint8),
        mg_active=b._mg_active, mg_streak=b._mg_streak,
    )
    payload = buf.getvalue()
    return BUILDER_MAGIC + struct.pack("<H", BUILDER_VERSION) + payload


def builder_from_bytes(blob: bytes) -> BaselineBuilder:
    """Inverse of `builder_to_bytes`; refuses unknown magic/version
    loudly (a mixed-version pod must not silently mis-merge)."""
    import json

    from ..stats.sketches import sketch_from_bytes

    if blob[:4] != BUILDER_MAGIC:
        raise ValueError("not a baseline-builder wire blob (bad magic)")
    (version,) = struct.unpack("<H", blob[4:6])
    if version != BUILDER_VERSION:
        raise ValueError(
            f"baseline-builder wire version {version} unsupported (this "
            f"build speaks {BUILDER_VERSION}); align library versions "
            "across the pod"
        )
    with np.load(io.BytesIO(blob[6:]), allow_pickle=False) as z:
        meta = json.loads(bytes(z["meta"]).decode())
        b = BaselineBuilder.__new__(BaselineBuilder)
        b.d = int(meta["d"])
        b.k = int(meta["k"])
        b.cap = int(meta["cap"])
        b.bits = int(meta["bits"])
        b.n = int(meta["n"])
        b.nan = np.array(z["nan"])
        b.s1 = np.array(z["s1"])
        b.s2 = np.array(z["s2"])
        b.vmin = np.array(z["vmin"])
        b.vmax = np.array(z["vmax"])
        for name, attr in (("q", "q"), ("f", "f"), ("h", "h")):
            kind, state = sketch_from_bytes(bytes(z[name]))
            setattr(b, attr, state)
        b._pending = []
        b._pending_rows = 0
        b._mg_active = np.array(z["mg_active"])
        b._mg_streak = np.array(z["mg_streak"])
    return b


__all__ = [
    "BaselineBuilder",
    "Fingerprint",
    "PSI_QUANTILES",
    "builder_from_bytes",
    "builder_to_bytes",
]
