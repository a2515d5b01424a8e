#!/usr/bin/env python3
#
# chip_smoke.py — the quickest proof that the system still starts on the chip.
#
#   python chip_smoke.py            # the reference width and rows: 1M x 3000 f32
#   python chip_smoke.py --rows N   # fewer rows (says so); the 3000 is never cut
#   python chip_smoke.py --from-memory   # no parquet: fit((X, y)) of the same rows
#
# One process, every visible device.  It drives the flagship path through
# the entry points a user calls — seeded parquet (benchmark/gen_data.py) ->
# LogisticRegression.fit(path) -> core._stage_or_stream ->
# streaming.stage_parquet -> ShardedRowWriter -> L-BFGS -> model.transform
# -> save / load -> transform — and checks what came out.  Weights are what
# a few solver steps on seeded data give; this is not a timing.  The 12 GB
# file goes inside the checkout, else to the system's temporary directory
# (never a tmpfs); a place that cannot keep it is named and passed over,
# and with none left the same array is fitted from memory
# (fit((X, y)) -> core._stage_fit_input -> RowStager), which the output says.
#
# Output: `smoke: ...` lines with the facts (device, versions, per-phase
# cold wall time, compile seconds, staging engine, L-BFGS route, peak HBM,
# measured numeric gap), every failed check as `smoke: FAILED ...` (on
# stderr as well, for a caller that keeps only its end), and on success, as
# the LAST line of stdout, one JSON object
#   {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
# Exit code 0 only if jax.devices()[0].platform == "tpu" and every check
# held.  No TPU: exit 1 at once with a one-line reason and no result line.
# Nothing here sets JAX_PLATFORMS.
#
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

REF_ROWS, REF_COLS = 1_000_000, 3_000  # BASELINE.md's LogisticRegression row
TRANSFORM_ROWS = 100_000
SAMPLE_ROWS = 10_000
SEED = 21
# rows per parquet row group.  A range reader holds about two row groups
# of decode state, so 16 readers over 600 MB groups (50k rows) hold 20 GB
# of host memory and over 120 MB groups hold 4
SLAB_ROWS = 10_000
GEN_WORKERS = min(8, os.cpu_count() or 1)  # threads generating slabs

# The reference's run_benchmark.sh row fits with standardization off;
# with it on, ops/stats.standardize materialises a second copy of X, which
# at 12 GB cannot exist beside the first on one 16 GB chip.
FIT_PARAMS = dict(maxIter=5, regParam=1e-4, standardization=False)

# |probability(on chip) - sigmoid(X @ coef + b) in float64 numpy|, max over
# the sample.  ops/logistic.py sets no matmul precision and this script
# does not change that (it would change speed); it measures what the
# precision is.  Measured on a v5e (jax 0.9.0, libtpu 0.0.34): 1.1e-6 —
# XLA lowers the f32 (N, d) @ (d,) matvec to an exact f32 multiply-reduce,
# not to a bf16 pass on the MXU, so "default TPU matmul precision, no
# conf" is f32 for the binomial path.  One bf16 pass would land near
# 1e-3..1e-2 (8-bit products, 3000 of them per margin, d(sigmoid)/d(margin)
# <= 1/4).  1e-4 leaves two orders of room for summation order and fails,
# on purpose, the day a compiler or a code change drops the matvec to bf16.
PROB_TOL = 1e-4
# theta0 = 0, so the first objective is ln 2 up to f32 rounding of the mean
LN2_TOL = 1e-5
# staged bytes per device vs N*d*4/n_dev: chunk-aligned row padding (2.9 %
# at 1M rows) plus the label and weight vectors stay far inside this, and
# "everything on the first device" is n_dev times outside it
PER_DEVICE_BYTES_TOL = 0.10

DATA_DIR = os.path.join(REPO, ".chip_smoke_data")
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


def say(msg: str, err: bool = False) -> None:
    """One line of the report; `err` copies it to stderr (phase ends and
    failures), so a caller that keeps only the end of stderr still learns
    how far the run came and why it stopped."""
    print(f"smoke: {msg}", flush=True)
    if err:
        print(f"smoke: {msg}", file=sys.stderr, flush=True)


def require_tpu(devices) -> None:
    """The `__main__` guard: anything but a TPU first device ends the run."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax.devices()[0].platform is "
            f"{platform!r} ({len(devices)} device(s)); nothing was run"
        )


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy", "pyarrow"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "absent"
    return out


def _device_memory() -> dict:
    """{device id: {in_use, peak, limit}} from the allocator where the
    backend has one, else from the library's own census (the CPU mesh)."""
    import jax

    from spark_rapids_ml_tpu.telemetry.memory import sample_devices

    census = sample_devices()
    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[d.id] = {
            "in_use": int(stats.get("bytes_in_use", census.get(d.id, 0))),
            "peak": int(stats.get("peak_bytes_in_use", 0)),
            "limit": int(stats.get("bytes_limit", 0)),
        }
    return out


def _find_events(nodes, prefix: str) -> list:
    found = []
    for n in nodes:
        if n["name"].startswith(prefix):
            found.append(n)
        found += _find_events(n.get("children", []), prefix)
    return found


def _head(n_cols: int, rows: int, slab_rows: int):
    """(X, y) of the first `rows` rows of the seeded stream, regenerated
    (never read back from the file the system under test wrote through)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from benchmark.gen_data import classification_slab

    X = np.empty((rows, n_cols), np.float32)
    y = np.empty((rows,), np.float64)

    def fill(i: int) -> None:
        at = i * slab_rows
        m = min(slab_rows, rows - at)
        X[at:at + m], y[at:at + m] = classification_slab(n_cols, SEED, i, m)

    with ThreadPoolExecutor(GEN_WORKERS) as pool:
        list(pool.map(fill, range(-(-rows // slab_rows))))
    return X, y


def _host_used_gb() -> float:
    """Host memory in use as the kernel counts it (tmpfs files included)."""
    with open("/proc/meminfo") as f:
        kb = {ln.split(":")[0]: int(ln.split()[1]) for ln in f}
    return (kb["MemTotal"] - kb["MemAvailable"]) / 1e6


class Smoke:
    def __init__(self) -> None:
        self.failures: list = []
        self.facts: dict = {}

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            say(f"FAILED {what}", err=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase; a phase that raises is a failure with its
        traceback shown, and the phases after it still run (each says
        what it could not do)."""
        t0 = time.perf_counter()
        peak, done = [_host_used_gb()], threading.Event()

        def watch() -> None:  # a sealed machine that runs out dies silently
            while not done.wait(0.5):
                peak[0] = max(peak[0], _host_used_gb())

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        try:
            yield
        except Exception as e:
            self.failures.append(
                f"phase {name} raised {type(e).__name__}: {str(e)[:300]}")
            say(f"FAILED phase {name} raised:\n{traceback.format_exc()}",
                err=True)
        finally:
            done.set()
            watcher.join()
            self.facts[f"{name}_s"] = round(time.perf_counter() - t0, 2)
            self.facts[f"{name}_host_peak_gb"] = round(peak[0], 1)
            say(f"phase {name}: {self.facts[f'{name}_s']} s; host memory in "
                f"use peaked at {peak[0]:.1f} GB", err=True)


def _fs_type(path: str) -> str:
    """File-system type of the mount that holds `path` ('' if unknown)."""
    path = os.path.realpath(path)
    best, kind = "", ""
    try:
        with open("/proc/mounts") as f:
            for ln in f:
                _, mnt, fstype = ln.split()[:3]
                under = path == mnt or path.startswith(mnt.rstrip("/") + "/")
                if under and len(mnt) >= len(best):
                    best, kind = mnt, fstype
    except OSError:
        pass
    return kind


def _data_homes(need_bytes: float) -> list:
    """Directories where the parquet could live for the length of one run,
    in the order to try them: inside the checkout, then the system's
    temporary directories.  Never a tmpfs: on the sealed one-chip machine
    the process already pays host memory for the TPU runtime (5.3 GB) and
    for what the staging readers hold, and 12 GB of file beside that is
    what ran it out of its 40 GiB — the machine then dies without a word.
    An empty list means: fit the same array from memory."""
    homes = []
    for d in (DATA_DIR, tempfile.gettempdir(), "/var/tmp"):
        try:
            os.makedirs(d, exist_ok=True)
            kind, free = _fs_type(d), shutil.disk_usage(d).free
        except OSError as e:
            say(f"data: cannot use {d}: {e!r}", err=True)
            continue
        if kind in ("tmpfs", "ramfs") or free < need_bytes:
            say(f"data: cannot use {d}: file system {kind or '?'}, "
                f"{free / 1e9:.1f} GB free of {need_bytes / 1e9:.1f} needed",
                err=True)
        elif d not in homes:
            homes.append(d)
    return homes


def run_smoke(
    n_rows: int, n_cols: int, out_dir: str, data_home: str = None,
    slab_rows: int = SLAB_ROWS, from_memory: bool = False,
) -> dict:
    """The whole smoke at `n_rows` x `n_cols` on whatever devices jax
    shows (a tier-1 test calls this at a toy size on the CPU mesh, with
    small slabs so the file still has several row groups to read in
    parallel).  Returns {"failures": [...], ...facts}; prints as it goes.
    The data is made from the seed every run and removed with it."""
    if from_memory:
        homes = []
    elif data_home:
        homes = [data_home]
    else:
        homes = _data_homes(n_rows * n_cols * 4 * 1.05 + (1 << 30))
    with contextlib.ExitStack() as cleanup:
        return _run_smoke(n_rows, n_cols, out_dir, homes, slab_rows, cleanup)


def _run_smoke(n_rows, n_cols, out_dir, homes, slab_rows, cleanup) -> dict:
    import jax
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from benchmark.gen_data import write_classification_slabs
    from spark_rapids_ml_tpu import native
    from spark_rapids_ml_tpu._jax_env import CACHE_ENV, compile_cache_dir
    from spark_rapids_ml_tpu.classification import (
        LogisticRegression,
        LogisticRegressionModel,
    )
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY, delta

    s = Smoke()
    facts = s.facts
    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform == "tpu"
    os.makedirs(out_dir, exist_ok=True)
    metrics0 = REGISTRY.snapshot()

    # -- environment ---------------------------------------------------------
    facts.update(
        rows=n_rows, cols=n_cols, reference_size=(n_rows, n_cols) == (REF_ROWS, REF_COLS),
        platform=devices[0].platform, device_kind=devices[0].device_kind,
        device_count=n_dev, versions=_versions(), host_cpus=os.cpu_count(),
        compile_cache=compile_cache_dir(),
        compile_cache_placed_by_env=CACHE_ENV in os.environ,
        native_staging=native.status(),
    )
    say(f"device {facts['platform']} {facts['device_kind']!r} x{n_dev}; "
        f"versions {facts['versions']}")
    say(f"rows {n_rows} x cols {n_cols} f32 = {n_rows * n_cols * 4 / 1e9:.2f} GB"
        + ("" if facts["reference_size"] else
           f" — ROWS CUT from the reference's {REF_ROWS}"))
    say(f"host cpus {facts['host_cpus']}; host staging: {facts['native_staging']}; "
        f"compile cache: {facts['compile_cache']} ("
        f"{'placed by' if facts['compile_cache_placed_by_env'] else 'no'} {CACHE_ENV})")
    mem0 = _device_memory()
    say("bytes_limit per device: "
        + ", ".join(f"{i}: {m['limit']}" for i, m in mem0.items()))

    # -- generate ------------------------------------------------------------
    dataset = None
    with s.phase("generate"):
        for home in homes:
            # a place that cannot keep the file (full, over a quota, not
            # writable) is the smoke's own trouble, not the system's: say
            # so and try the next; with none left, fit from memory
            path = None
            try:
                work = cleanup.enter_context(
                    tempfile.TemporaryDirectory(prefix="chip_smoke_", dir=home))
                path = os.path.join(
                    work, f"smoke_{n_rows}x{n_cols}_seed{SEED}.parquet")
                # uncompressed and undictionaried: the values are random
                # floats, and the 12 GB file is written inside the time limit
                write_classification_slabs(
                    path, n_rows, n_cols, seed=SEED, slab_rows=slab_rows,
                    workers=GEN_WORKERS, compression=None, use_dictionary=False,
                )
                kept = pq.read_metadata(path).num_rows
                if kept != n_rows:
                    raise OSError(f"{path} holds {kept} rows of {n_rows}")
            except (OSError, pa.ArrowInvalid) as e:  # no footer: ArrowInvalid
                say(f"data: {home} could not keep the parquet: {e!r}", err=True)
                with contextlib.suppress(OSError, TypeError):
                    os.remove(path)
                continue
            say(f"data: wrote {path} ({os.path.getsize(path) / 1e9:.2f} GB, "
                f"row groups of {slab_rows} rows, file system {_fs_type(path)})")
            dataset, facts["data"] = path, f"parquet under {home}"
            break
        else:
            say("data: no place for the parquet — FITTING THE SAME ARRAY FROM "
                "MEMORY (fit((X, y)) -> _stage_fit_input)", err=True)
            dataset, facts["data"] = _head(n_cols, n_rows, slab_rows), "memory"

    # -- stage + fit ---------------------------------------------------------
    model = report = None
    staged: dict = {}
    with s.phase("fit"):
        if dataset is None:
            raise RuntimeError("no dataset: generate failed")
        est = LogisticRegression(**FIT_PARAMS)  # num_workers=None: every device
        solve = est._fit_array

        def observe_then_solve(fit_input):
            # between staging and the solve: where the rows landed
            X = fit_input.X
            mem = _device_memory()
            staged.update(
                shape=tuple(X.shape),
                shard_shapes={tuple(sh.data.shape) for sh in X.addressable_shards},
                shard_devices=sorted(sh.device.id for sh in X.addressable_shards),
                grew={i: mem[i]["in_use"] - mem0[i]["in_use"] for i in mem},
            )
            return solve(fit_input)

        est._fit_array = observe_then_solve
        model = est.fit(dataset)
        report = model.fit_report()

    if model is not None:
        # the fit's own staging: the `stage` span where the rows came from
        # memory, the parquet staging's seconds where they were streamed in
        stage = report["staging"]
        facts["stage_s"] = round(sum(
            e["seconds"] for e in _find_events(report["spans"], "stage")
            if e["name"] == "stage"
        ), 2) or stage.get("seconds")
        facts["solve_s"] = round(facts["fit_s"] - float(facts["stage_s"] or 0), 2)
        facts["staging"] = {
            k: stage.get(k) for k in ("engine", "readers", "mb_per_s", "pieces", "label")
            if stage.get(k) is not None
        }
        say(f"phase stage: {facts['stage_s']} s, then solve {facts['solve_s']} s; "
            f"staging {facts['staging']}")

        # every device holds an equal share, and only its share
        expect = n_rows * n_cols * 4 / n_dev
        s.check(staged.get("shard_devices") == sorted(d.id for d in devices),
                f"staged features sharded over all {n_dev} devices "
                f"(got devices {staged.get('shard_devices')})")
        s.check(len(staged.get("shard_shapes", ())) == 1,
                f"equal addressable shards (got {staged.get('shard_shapes')})")
        for i, grew in staged.get("grew", {}).items():
            s.check(abs(grew - expect) <= PER_DEVICE_BYTES_TOL * expect,
                    f"device {i} holds {grew} staged bytes, expected "
                    f"{expect:.0f} +-{PER_DEVICE_BYTES_TOL:.0%}")
        say(f"staged {staged.get('shape')} as {n_dev} shard(s) of "
            f"{staged.get('shard_shapes')}; bytes grown per device "
            f"{staged.get('grew')} vs N*d*4/n_dev = {expect:.0f}")

        # the resident route ran, and nothing recovered behind our back
        res = report["resilience"]
        memsec = report.get("memory", {})
        route = [e["name"] for e in _find_events(report["spans"], "lbfgs_route[")]
        comp = report.get("compile", {})
        facts.update(
            resilience=res, memory_provider=memsec.get("provider"),
            lbfgs_route=route, fit_compile_s=comp.get("seconds", 0.0),
            fit_compile_events=comp.get("events", 0),
        )
        resident = bool(staged) and not model._get_model_attributes().get("streaming_epochs")
        s.check(resident and res["oom_streaming_refits"] == 0,
                f"resident fit, no OOM-to-streaming refit ({res})")
        s.check(res["retries"] == 0 and "recoveries" not in res,
                f"no retries and no recoveries ({res})")
        s.check(not mesh_mod.excluded_device_ids(),
                f"no elastic exclusion ({sorted(mesh_mod.excluded_device_ids())})")
        s.check(memsec.get("provider") == ("real" if on_tpu else "simulated"),
                f"memory provider {'real' if on_tpu else 'simulated'} "
                f"(got {memsec.get('provider')!r})")
        s.check(len(route) == 1, f"one L-BFGS route event (got {route})")
        say(f"route: {'resident' if resident else 'STREAMED'}, {route}, resilience {res}, "
            f"memory provider {memsec.get('provider')}")
        say(f"fit compile: {facts['fit_compile_s']} s over "
            f"{facts['fit_compile_events']} trace/lower/compile event(s) (fit report)")

        # results
        hist = [float(v) for v in model.summary.objectiveHistory]
        coef = np.asarray(model.coefficients, np.float64)
        b = float(model.intercept)
        facts["objective_history"] = hist
        s.check(abs(hist[0] - np.log(2.0)) <= LN2_TOL,
                f"objectiveHistory[0] = {hist[0]!r} is ln 2")
        s.check(len(hist) >= 2 and all(b_ < a for a, b_ in zip(hist, hist[1:])),
                f"objectiveHistory strictly decreases ({hist})")
        s.check(coef.shape == (n_cols,) and bool(np.isfinite(coef).all())
                and np.isfinite(b), "coefficients finite, one per column")
        say(f"objective {hist}")
        np.save(os.path.join(out_dir, f"coef_{n_dev}dev_{n_rows}x{n_cols}.npy"),
                np.append(coef, b))

    # -- transform -----------------------------------------------------------
    probs = Xt = None
    with s.phase("transform"):
        if model is None:
            raise RuntimeError("no model: fit failed")
        Xt, _ = _head(n_cols, min(TRANSFORM_ROWS, n_rows), slab_rows)
        t0 = time.perf_counter()
        out = model.transform(Xt)
        facts["transform_call_s"] = round(time.perf_counter() - t0, 2)
        probs = np.asarray(out["probability"])
        s.check(probs.shape == (len(Xt), 2) and bool(np.isfinite(probs).all()),
                f"probability is finite ({len(Xt)}, 2) (got {probs.shape})")
        idx = np.random.default_rng(SEED).choice(
            len(Xt), size=min(SAMPLE_ROWS, len(Xt)), replace=False)
        margin = Xt[idx].astype(np.float64) @ coef + b
        gap = float(np.abs(probs[idx, 1] - 1.0 / (1.0 + np.exp(-margin))).max())
        facts["prob_gap_vs_float64"] = gap
        s.check(gap <= PROB_TOL,
                f"on-device probability within {PROB_TOL} of float64 numpy "
                f"(measured max abs difference {gap:.3e})")
        say(f"transform {len(Xt)} rows in {facts['transform_call_s']} s (cold); "
            f"max |probability - float64 reference| over {len(idx)} sampled "
            f"rows = {gap:.3e} (tolerance {PROB_TOL}; no precision conf, "
            "so this is XLA's default for an f32 matvec)")

    # -- save / load / transform --------------------------------------------
    with s.phase("save_load"):
        if probs is None:
            raise RuntimeError("no transform output to compare with")
        mdir = os.path.join(cleanup.enter_context(
            tempfile.TemporaryDirectory(prefix="chip_smoke_model_")), "model")
        model.save(mdir)
        again = LogisticRegressionModel.load(mdir).transform(Xt)
        s.check(all(np.array_equal(np.asarray(out[c]), np.asarray(again[c]))
                    for c in out),
                "loaded model's transform is bit-equal to the fitted model's")

    # -- what the run cost ---------------------------------------------------
    mem1 = _device_memory()
    moved = delta(metrics0, REGISTRY.snapshot())
    compile_s = sum(v["sum"] for k, v in moved.get("compile_seconds", {}).items()
                    if "phase=backend_compile" in k)
    compiles = sum(moved.get("compiles_total", {}).values())
    data_bytes = n_rows * n_cols * 4
    peaks = {i: m["peak"] for i, m in mem1.items()}
    if not on_tpu and report:  # no allocator: the fit's sampled watermark
        peaks = {int(i): p for i, p in
                 report.get("memory", {}).get("per_device_peak_bytes", {}).items()}
    facts.update(
        process_compile_s=round(compile_s, 2), process_compiles=int(compiles),
        peak_bytes_per_device=peaks,
        peak_over_dataset_share={i: round(p * n_dev / data_bytes, 3)
                                 for i, p in peaks.items()},
        hang_doctor_stalls=int(sum(moved.get("hang_doctor_stalls_total", {}).values())),
    )
    say(f"process compile: {facts['process_compile_s']} s in "
        f"{facts['process_compiles']} backend compile(s)")
    say(f"peak_bytes_in_use per device {peaks}; ratio to the dataset's "
        f"per-device bytes {facts['peak_over_dataset_share']}")
    say(f"hang doctor stalls during the run: {facts['hang_doctor_stalls']}")

    result_path = os.path.join(out_dir, f"result_{n_dev}dev_{n_rows}x{n_cols}.json")
    if os.path.exists(result_path):
        with open(result_path) as f:
            prev = json.load(f)
        say(f"compile seconds, previous run -> this run: fit "
            f"{prev.get('fit_compile_s')} -> {facts['fit_compile_s']}, process "
            f"{prev.get('process_compile_s')} -> {facts['process_compile_s']}; "
            f"fit wall {prev.get('fit_s')} -> {facts['fit_s']} s")
    facts["failures"] = s.failures
    with open(result_path, "w") as f:
        json.dump(facts, f, indent=1, default=str)
    return facts


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="LogisticRegression smoke on the chip")
    ap.add_argument("--rows", type=int, default=REF_ROWS,
                    help="fewer rows than the reference's (the output says so)")
    ap.add_argument("--from-memory", action="store_true",
                    help="skip the parquet and fit the same array from memory "
                    "(rows are padded to the shape-bucket grid {1, 1.5} x 2^k: "
                    "4.9 %% at 1M, and past the staged-bytes check at 400k)")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    from spark_rapids_ml_tpu._jax_env import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = jax.devices()
    require_tpu(devices)
    facts = run_smoke(
        args.rows, REF_COLS, OUT_DIR, from_memory=args.from_memory
    )
    say(f"total {time.perf_counter() - t0:.1f} s")
    if facts["failures"]:
        say(f"{len(facts['failures'])} check(s) FAILED:", err=True)
        for f in facts["failures"]:
            say(f"  - {f}", err=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
