#
# Statistic-program engine — run ANY set of registered programs
# (stats/programs.py STAT_PROGRAMS) in ONE pass over the data, on every
# chunk path the package already has:
#
#   - in-memory batches chunk through `fused.iter_host_chunks` (the
#     fused engine's prepared fixed-shape chunks),
#   - parquet paths stream through `fused.iter_parquet_chunks` — the
#     row-group-pruned parallel range readers AND the chunk cache, so a
#     second summarize of the same file replays from memory,
#   - chunk prep runs `staging_pipeline_depth` ahead on the producer
#     thread while the mesh folds the previous chunk (the PR-8 overlap).
#
# Device programs fold through ONE jitted combined step with the whole
# accumulator dict donated; host (sketch) programs fold on the consumer
# thread from the same decoded chunk — still one pass, no extra IO.
#
# Resilience: the per-chunk `stat_program_step` fault site fails the
# WHOLE pass, and the retry restarts it with FRESH accumulators
# (re-creatable state, never resumed mid-pass), so a retried chunk can
# never double-count — the `fused_accumulate` contract, inherited.
#
from __future__ import annotations

import functools

from ..telemetry.locks import named_lock
import time
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from ..config import get_config
from ..telemetry.registry import counter, histogram
from ..utils import get_logger

logger = get_logger("spark_rapids_ml_tpu.stats")

_runs_total = counter(
    "stat_program_runs_total",
    "Statistic-program executions by program name",
)
_pass_seconds = histogram(
    "stat_program_pass_seconds",
    "Wall seconds per fused statistic pass by run label",
)

# Each pass records its own `stats` fact on its run (`tracing.fact`).
# A pass that overlapped another — in EITHER direction: every live pass
# is marked when a new one starts, so the first starter finishing last
# still knows — records `concurrent_passes`, so readers know the engine
# counters around it are process-level (the PR-5 concurrent-fits report
# guard, mirrored).  The lock guards the list of live passes.
_stat_metrics_lock = named_lock("stat_metrics")
_PASS_STATE: Dict[str, Any] = {"live": []}  # per-pass mutable tokens

# CONCURRENT one-pass statistics folds serialize their DEVICE step on
# this lock: two threads dispatching multi-device (mesh-sharded) jitted
# accumulator steps simultaneously can interleave their per-device
# executions into a resource-ordering deadlock inside the runtime
# (observed wedging the full CPU-mesh suite at the concurrent-describe
# test — both threads frozen inside the jitted call, zero CPU).  The
# lock is SHARED with the fused stage-and-solve engine
# (fused.accumulate_chunks — the other mesh-sharded accumulator
# dispatch site), so a describe() racing a fused fit serializes too.
# Chunk prep and the prefetch producers still interleave freely; the
# host sketch folds run INSIDE the held region, between the async
# dispatch and the sync, so a lone pass keeps its device/host overlap
# and pays one uncontended acquire per chunk.
_device_step_lock = named_lock("device_step")


def _chunk_rows_for(n: int, d: int, itemsize: int, n_dev: int) -> int:
    from ..fused import fused_chunk_rows

    return fused_chunk_rows(n, d, itemsize, n_dev)


@functools.lru_cache(maxsize=32)
def _combined_step(
    names: Tuple[str, ...], d: int, dtype_str: str, has_y: bool,
    weighted: bool, opts_token: Tuple, precision: str, compensated: bool,
):
    """One donated jitted step folding EVERY requested device program's
    chunk contribution — repeated runs at the same (programs, shape,
    dtype, precision) reuse the compiled program (the fused engine's
    `_jitted_steps` discipline).  `precision`/`compensated` key the
    conf values baked in at trace time, and `opts_token` carries the
    RESOLVED per-program options (sketch/bin geometry included), so a
    conf change between runs re-traces instead of reusing a step built
    for the old shapes.  The `weighted=False` variant dispatches each
    program's unweighted fast step where it has one (full unweighted
    chunks skip the X*w chunk-sized copy and the weight transfer —
    ops/stats.py's unweighted-variant rationale)."""
    import jax

    from .programs import get_program

    opts = {name: dict(o) for name, o in opts_token}
    dtype = np.dtype(dtype_str)
    steps: Dict[str, Tuple[Callable, Optional[Callable], bool]] = {}
    for name in names:
        p = get_program(name)
        step_w, unw = p.make_step(d, dtype, opts.get(name, {}))
        steps[name] = (step_w, unw, p.needs_y)

    def _one(name, fn_w, unw, ny, acc, X, w, y):
        if w is None and unw is not None and not ny:
            return unw(acc[name], X)
        import jax.numpy as jnp

        wv = jnp.ones((X.shape[0],), X.dtype) if w is None else w
        if ny:
            return fn_w(acc[name], X, wv, y)
        return fn_w(acc[name], X, wv)

    if has_y:
        if weighted:
            def combined(acc, X, w, y):
                return {
                    name: _one(name, fw, unw, ny, acc, X, w, y)
                    for name, (fw, unw, ny) in steps.items()
                }
        else:
            def combined(acc, X, y):
                return {
                    name: _one(name, fw, unw, ny, acc, X, None, y)
                    for name, (fw, unw, ny) in steps.items()
                }
    else:
        if weighted:
            def combined(acc, X, w):
                return {
                    name: _one(name, fw, unw, ny, acc, X, w, None)
                    for name, (fw, unw, ny) in steps.items()
                }
        else:
            def combined(acc, X):
                return {
                    name: _one(name, fw, unw, ny, acc, X, None, None)
                    for name, (fw, unw, ny) in steps.items()
                }

    return jax.jit(combined, donate_argnums=0)


def _normalize_source(
    source, features_col, features_cols, label_col, weight_col, dtype,
    needs_y: bool,
):
    """(producer_factory, d, n_or_None, dtype): producer_factory(n_dev)
    yields prepared `(X, y, w)` fixed-shape chunks (fused.py contract;
    `w` None = full unweighted chunk)."""
    from ..streaming import is_parquet_path

    dtype = np.dtype(dtype or np.float32)
    if is_parquet_path(source):
        from ..streaming import (
            chunk_rows_for,
            parquet_row_count,
            probe_num_features,
        )

        d = probe_num_features(source, features_col, features_cols)
        n = parquet_row_count(source)
        if n == 0:
            raise ValueError("Dataset is empty: nothing to summarize")
        chunk_rows = min(chunk_rows_for(d, dtype.itemsize), max(n, 1))

        def factory(n_dev: int):
            from ..fused import iter_parquet_chunks

            rows = -(-min(chunk_rows, n) // n_dev) * n_dev
            prep: Dict[str, Any] = {"s": 0.0, "iv": []}
            return (
                # with_offsets: each chunk carries its GLOBAL first-row
                # index, so offset-addressed host programs (the
                # kmeans_sample reservoir) fill the same slots from the
                # same rows at any process/reader count
                iter_parquet_chunks(
                    source, features_col, features_cols,
                    label_col if needs_y else None, weight_col,
                    rows, dtype, prep=prep, with_offsets=True,
                ),
                prep,
            )

        return factory, d, n, dtype

    from ..data import _is_sparse, extract_arrays

    batch = extract_arrays(
        source,
        features_col=features_col,
        features_cols=features_cols,
        label_col=label_col if needs_y else None,
        weight_col=weight_col,
        dtype=None,
        supervised=needs_y,
    )
    X = batch.X
    if _is_sparse(X):
        X = np.asarray(X.todense())
    X = np.asarray(X, dtype)
    if X.ndim == 1:
        X = X.reshape(-1, 1)
    n, d = int(X.shape[0]), int(X.shape[1])
    if n == 0:
        raise ValueError("Dataset is empty: nothing to summarize")
    y, w = batch.y, batch.weight

    def factory(n_dev: int):
        from ..fused import iter_host_chunks

        rows = _chunk_rows_for(n, d, dtype.itemsize, n_dev)
        return iter_host_chunks(X, y, w, rows, dtype)

    return factory, d, n, dtype


def run_programs(
    names: Sequence[str],
    source,
    *,
    features_col: Optional[str] = "features",
    features_cols: Sequence[str] = (),
    label_col: Optional[str] = None,
    weight_col: Optional[str] = None,
    dtype=None,
    opts: Optional[Dict[str, Dict[str, Any]]] = None,
    quantiles: Optional[Sequence[float]] = None,
    label: str = "summarize",
) -> Dict[str, Dict[str, Any]]:
    """Run the named registered programs in ONE fused pass over
    `source` (in-memory batch, pandas frame, or parquet path).  Returns
    `{program_name: finalized statistics}`.

    The pass runs under the standard retry policy with the accumulators
    treated as re-creatable state: a mid-pass OOM/device-loss (the
    `stat_program_step` fault site) restarts the whole pass fresh on
    the (possibly shrunken) mesh — never resuming half-folded sums, so
    a retried chunk cannot double-count."""
    from ..resilience import retry_call

    names = tuple(dict.fromkeys(names))  # preserve order, drop dups
    if not names:
        raise ValueError("no statistic programs requested")
    from .programs import get_program

    progs = [get_program(n) for n in names]
    for p in progs:
        if p.extra_args:
            raise ValueError(
                f"program {p.name!r} requires extra step arguments "
                f"{p.extra_args} and runs only through its dedicated "
                "caller (the fused estimator path), not the generic "
                "engine dispatch"
            )
    needs_y = any(p.needs_y for p in progs)
    if needs_y and label_col is None and not _has_label(source):
        raise ValueError(
            "programs "
            + ", ".join(p.name for p in progs if p.needs_y)
            + " need a label column (label_col=...)"
        )
    factory, d, n, dtype = _normalize_source(
        source, features_col, features_cols, label_col, weight_col,
        dtype, needs_y,
    )
    return retry_call(
        lambda: _one_pass(
            progs, factory, d, dtype, needs_y,
            dict(opts or {}), quantiles, label,
        ),
        label="stat_programs",
        log=logger,
    )


def run_program(name: str, source, **kwargs) -> Dict[str, Any]:
    """Single-program convenience over `run_programs`."""
    return run_programs([name], source, **kwargs)[name]


def _has_label(source) -> bool:
    return isinstance(source, (tuple, list)) and len(source) == 2


def _one_pass(
    progs, factory, d: int, dtype, needs_y: bool,
    opts: Dict[str, Dict[str, Any]], quantiles, label: str,
) -> Dict[str, Dict[str, Any]]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..fused import _interval_overlap_s, _merge_intervals, _resolve_producer
    from ..ops.precision import stats_compensated
    from ..ops.stats import acc_to_host_f64
    from ..parallel.mesh import (
        DATA_AXIS, _staging_depth, data_pspec, get_mesh, timed_iter,
    )
    from ..resilience import maybe_inject
    from ..telemetry.compile import compile_label
    from ..telemetry.heartbeat import Heartbeat
    from ..telemetry.memory import record_prediction
    from ..tracing import (
        current_run_id, event, fact, mint_run_id, run_context,
    )
    from ..utils import prefetch_iter

    from .programs import resolve_opts

    dtype = np.dtype(dtype)
    device_progs = [p for p in progs if p.kind == "device"]
    host_progs = [p for p in progs if p.kind == "host"]
    mesh = get_mesh()
    if jax.process_count() > 1:
        # multi-process: fold on the LOCAL devices only — chunks and the
        # accumulators never leave this host; the per-rank partials meet
        # in ONE cross-process reduction after the chunk loop (psum on
        # collective-capable backends, the coordination-service wire on
        # CPU builds) — see _reduce_pass_across_processes
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.local_devices()), (DATA_AXIS,))
    n_dev = mesh.devices.size

    popts = {p.name: resolve_opts(p, opts.get(p.name)) for p in progs}
    dev_acc = {
        p.name: p.init(d, dtype, popts[p.name]) for p in device_progs
    }
    host_acc = {
        p.name: p.init(d, dtype, popts[p.name]) for p in host_progs
    }
    host_steps = {
        p.name: p.make_step(d, dtype, popts[p.name]) for p in host_progs
    }
    step_for = None
    if device_progs:
        dev_names = tuple(p.name for p in device_progs)
        opts_token = tuple(
            (p.name, tuple(sorted(popts[p.name].items())))
            for p in device_progs
        )
        precision = str(get_config("stats_precision")).lower()
        comp = stats_compensated()

        def step_for(weighted: bool):
            return _combined_step(
                dev_names, d, dtype.str, needs_y, weighted, opts_token,
                precision, comp,
            )
    # budget accounting: the pass holds one sharded chunk + the
    # accumulators — record the prediction so the drift watermarks see it
    acc_bytes = sum(
        int(np.asarray(v).nbytes)
        for acc in dev_acc.values()
        for v in jax.tree_util.tree_leaves(acc)
    )
    record_prediction("stat_programs", float(acc_bytes))

    mat_sh = NamedSharding(mesh, data_pspec(2))
    row_sh = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
    rep_sh = NamedSharding(mesh, PartitionSpec())
    if device_progs:
        dev_acc = jax.device_put(dev_acc, rep_sh)

    chunks, prep = _resolve_producer(factory(n_dev))
    self_timed = prep is not None
    if prep is None:
        prep = {"s": 0.0, "iv": []}
        chunks = timed_iter(chunks, prep)

    t0 = time.perf_counter()
    acc_s = 0.0
    acc_iv = []
    n_chunks = 0
    nbytes = 0
    offset = 0
    # ad-hoc describe()/summarize() calls must not leave live solver
    # series behind: beats run under a minted run id and the gauges are
    # end-marked on NORMAL completion (Heartbeat.close); a pass that
    # dies mid-loop deliberately leaves its last state visible for the
    # flight recorder
    rid = current_run_id() or mint_run_id("summarize")
    # pod observatory (telemetry/fleet.py): pod-global pass id for this
    # statistics pass — SPMD site, every rank mints/receives here
    from ..telemetry import fleet as _fleet

    _fleet.begin_pod_pass()
    pass_token = {"overlapped": False}
    with _stat_metrics_lock:
        if _PASS_STATE["live"]:
            pass_token["overlapped"] = True
            for t in _PASS_STATE["live"]:
                t["overlapped"] = True
        _PASS_STATE["live"].append(pass_token)
    try:
        with run_context(rid), compile_label("stat_programs"):
            hb = Heartbeat("stat_programs")
            for item in prefetch_iter(chunks, _staging_depth()):
                # the engine's fault site: a failure here fails the WHOLE
                # pass; the retry restarts with fresh accumulators
                maybe_inject("stat_program_step")
                # parquet producers yield 4-tuples carrying the chunk's
                # GLOBAL first-row offset (iter_parquet_chunks
                # with_offsets); in-memory producers yield 3-tuples and
                # the rank-local running offset is already global there
                cX, cy, cw = item[0], item[1], item[2]
                goff = item[3] if len(item) > 3 else None
                chunk_rows = int(cX.shape[0])
                ta = time.perf_counter()

                def _fold_host() -> None:
                    if not host_progs:
                        return
                    from ..streaming import _weights_host

                    # cached read-only ones for the common full-
                    # unweighted chunk: the validity mask allocates
                    # nothing
                    w_host = cw if cw is not None else _weights_host(
                        None, chunk_rows, chunk_rows, dtype
                    )
                    ctx = {
                        "offset": offset if goff is None else goff,
                        "n_valid": int(np.count_nonzero(w_host > 0)),
                    }
                    for p in host_progs:
                        host_acc[p.name] = host_steps[p.name](
                            host_acc[p.name], cX, w_host, cy, ctx
                        )

                if step_for is not None:
                    # full unweighted chunks (cw None) dispatch the
                    # unweighted fast variant: no weight transfer, no
                    # X*w chunk copy for programs that declare an unw
                    # step.  Dispatch-to-sync holds _device_step_lock
                    # (see the lock's comment); the host folds run
                    # between dispatch and sync so the async device
                    # execution still overlaps them
                    with _device_step_lock:
                        args = [jax.device_put(cX, mat_sh)]
                        if cw is not None:
                            args.append(jax.device_put(cw, row_sh))
                        if needs_y:
                            args.append(jax.device_put(cy, row_sh))
                        dev_acc = step_for(cw is not None)(dev_acc, *args)
                        _fold_host()
                        jax.block_until_ready(dev_acc)
                else:
                    _fold_host()
                tb = time.perf_counter()
                acc_s += tb - ta
                acc_iv.append((ta, tb))
                offset += chunk_rows
                n_chunks += 1
                nbytes += cX.nbytes + (
                    cw.nbytes if cw is not None else 0
                ) + (cy.nbytes if needs_y and cy is not None else 0)
                hb.beat(n_chunks)
            hb.close()

        folded: Dict[str, Dict[str, Any]] = {}
        for p in device_progs:
            folded[p.name] = acc_to_host_f64(dev_acc[p.name])
        folded.update(host_acc)
        # topology view (parallel/context.py): a post-rank-loss survivor
        # group of one skips the reduce instead of waiting on the dead
        from ..parallel.context import process_topology

        if process_topology()[0] > 1:
            folded, offset = _reduce_pass_across_processes(
                progs, popts, d, folded, offset
            )
        wall = time.perf_counter() - t0

        ctx = {"d": d, "rows": offset, "quantiles": tuple(quantiles or ())}
        results = {p.name: p.finalize(folded[p.name], ctx) for p in progs}

        prep_iv = _merge_intervals(prep["iv"]) if self_timed else prep["iv"]
        # the pass's device/prep windows feed the run's utilization
        # timeline (telemetry/utilization.py) — same evidence the
        # overlap fraction below is computed from
        from ..telemetry import utilization

        utilization.note_intervals("device", acc_iv, cause="stat_programs")
        utilization.note_intervals("host_prep", prep_iv, cause="chunk_prep")
        # close the pod pass after the intervals land (the straggler
        # blob reads the timeline); its exchange is the pass's last
        # SPMD site
        _fleet.complete_pod_pass(run_id=rid)
        overlap_s = _interval_overlap_s(prep_iv, acc_iv)
        overlap = 0.0
        if min(prep["s"], acc_s) > 1e-9:
            overlap = max(0.0, min(overlap_s / min(prep["s"], acc_s), 1.0))
        for p in progs:
            _runs_total.inc(program=p.name)
        _pass_seconds.observe(wall, label=label)
        with _stat_metrics_lock:
            overlapped = pass_token["overlapped"]
        fact(
            "stats",
            label=label,
            programs=len(progs),
            passes=1,
            chunks=n_chunks,
            bytes=int(nbytes),
            wall_s=round(wall, 4),
            host_prep_s=round(prep["s"], 4),
            device_acc_s=round(acc_s, 4),
            overlap_s=round(overlap_s, 4),
            overlap_fraction=round(overlap, 4),
            **({"concurrent_passes": True} if overlapped else {}),
        )
    finally:
        with _stat_metrics_lock:
            _PASS_STATE["live"].remove(pass_token)
    event(
        f"stat_programs[{label}]",
        detail=(
            f"programs={len(progs)} chunks={n_chunks} "
            f"{nbytes / 1e6:.1f}MB wall={wall:.2f}s overlap={overlap:.2f}"
        ),
    )
    return results


def _reduce_pass_across_processes(progs, popts, d, folded, rows):
    """Cross-process reduction at pass completion: every rank folded
    only its ingest share (streaming.process_ingest_ranges /
    fused.process_row_group_shares), so the per-rank partials combine
    here into the GLOBAL accumulators every rank then finalizes
    identically.

    Pure-sum device fields — plus the pass row count — collapse through
    ONE reduce_host_arrays call (a single jitted psum when the backend
    supports cross-process collectives, the deterministic rank-ordered
    wire fold otherwise).  min/max device fields and the host sketch
    programs (KLL quantiles, Misra-Gries, k-means sample) travel as one
    wire blob per rank and merge with each program's own merge
    (stats.programs.merge_accs) in ascending rank order, so every rank
    computes byte-identical results — the 2-process parity suite
    asserts describe() equality against a single-process run.

    Host-step `ctx["offset"]` is GLOBAL under sharded ingest (the
    parquet producer labels every chunk with its first-row index in the
    file — iter_parquet_chunks with_offsets), so offset-addressed slot
    programs (kmeans_sample) fill the same reservoir slots from the
    same rows at any process count and their merge is byte-identical
    to the single-process fill (the 2-process parity suite asserts a
    k-means fit equal against a 1-process run)."""
    import io

    from ..parallel.context import reduce_blob_list, reduce_host_arrays

    sums: Dict[str, Any] = {"__rows__": np.asarray(float(rows))}
    wire: Dict[str, Any] = {}
    modes: Dict[str, str] = {}
    for p in progs:
        if p.kind == "host":
            for f, v in folded[p.name].items():
                wire[f"{p.name}:{f}"] = np.asarray(v)
            continue
        declared = p.shapes(d, popts[p.name])
        for f, v in folded[p.name].items():
            if declared[f].merge == "sum":
                sums[f"{p.name}:{f}"] = np.asarray(v)
            else:
                wire[f"{p.name}:{f}"] = np.asarray(v)
                modes[f"{p.name}:{f}"] = declared[f].merge

    summed = reduce_host_arrays(sums, "stat_pass")
    rows_global = int(round(float(summed.pop("__rows__"))))
    for key, v in summed.items():
        name, f = key.split(":", 1)
        folded[name][f] = v

    if wire:
        from .programs import merge_accs

        buf = io.BytesIO()
        np.savez(buf, **wire)
        blobs = reduce_blob_list("stat_sketches", buf.getvalue())
        states = []
        for blob in blobs:
            with np.load(io.BytesIO(blob)) as z:
                states.append({k: np.array(z[k]) for k in z.files})
        for key, mode in modes.items():
            out = states[0][key]
            for s in states[1:]:
                out = (
                    np.minimum(out, s[key]) if mode == "min"
                    else np.maximum(out, s[key])
                )
            name, f = key.split(":", 1)
            folded[name][f] = out
        for p in progs:
            if p.kind != "host":
                continue
            fields = list(folded[p.name])
            acc = {f: states[0][f"{p.name}:{f}"] for f in fields}
            for s in states[1:]:
                acc = merge_accs(
                    p, acc,
                    {f: s[f"{p.name}:{f}"] for f in fields},
                    popts[p.name],
                )
            folded[p.name] = acc
    return folded, rows_global


def iter_chunk_accs(
    name: str,
    chunks: Iterable,
    d: int,
    dtype=np.float32,
    opts: Optional[Dict[str, Any]] = None,
    offset0: int = 0,
) -> Dict[str, Any]:
    """Fold an explicit in-order `(X, y, w, n_valid)` chunk iterator
    (streaming.iter_chunks contract) through ONE program and return the
    HOST accumulator — the light entry the epoch-streaming paths use
    (e.g. the k-means|| seeding sample), where the caller owns the
    chunk loop and row range.  `offset0` is the GLOBAL row index of the
    stream's first row (multi-process per-partition reads)."""
    import jax

    from ..ops.stats import acc_to_host_f64
    from ..streaming import _weights_host
    from .programs import get_program

    from .programs import resolve_opts

    p = get_program(name)
    dtype = np.dtype(dtype)
    popts = resolve_opts(p, opts)
    acc = p.init(d, dtype, popts)
    if p.kind == "host":
        step = p.make_step(d, dtype, popts)
        offset = int(offset0)
        for cX, cy, cw, n_c in chunks:
            chunk_rows = int(cX.shape[0])
            w_host = np.asarray(
                _weights_host(cw, n_c, chunk_rows, dtype)
            )
            acc = step(
                acc, np.asarray(cX), w_host, cy,
                {"offset": offset, "n_valid": int(n_c)},
            )
            offset += n_c
        return acc
    import jax.numpy as jnp

    step_w, _unw = p.make_step(d, dtype, popts)
    step_j = jax.jit(step_w, donate_argnums=0)
    for cX, cy, cw, n_c in chunks:
        chunk_rows = int(cX.shape[0])
        w_host = _weights_host(cw, n_c, chunk_rows, dtype)
        args = [jnp.asarray(np.asarray(cX, dtype)), jnp.asarray(w_host)]
        if p.needs_y:
            args.append(jnp.asarray(np.asarray(cy, dtype)))
        acc = step_j(acc, *args)
    return acc_to_host_f64(acc)
