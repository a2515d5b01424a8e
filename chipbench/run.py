#!/usr/bin/env python3
#
# chipbench/run.py: one run of one cell of BENCHMARK.json.
#
#   python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
#
# A new process each time.  Set-up (counted as setup_s): imports, the rows
# made on the devices from --seed, the traffic's input (a DeviceDataset, or
# host arrays), warm-up fits that compile or load every program.  Window:
# Estimator.fit back to back; no new fit starts once --seconds have passed,
# the one in flight finishes.  After the window: the peak device memory is
# read, then the plain reference runs over the same rows and every fit's
# answer is held to the configuration's limits.
#
# The last line of stdout is one JSON object (correct, attempted, failed,
# metrics, device, ..., checks).  With --trace 0 the metrics are the cell's
# end-to-end ones, with --trace 1 its per-layer ones and a breakdown.
# No TPU, or fewer chips than the cell asks for: exit 1, no result line.
#
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest as mf  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def require_chips(devices, chips: int):
    """The devices the cell runs on: TPU chips, as many as it asks for."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"chipbench: needs {chips} TPU chip(s); jax shows {len(devices)} "
            f"device(s) of platform {platform!r}. Nothing was run.")
    return list(devices[:chips])


def _flatten(spans, out, parents, parent=-1):
    """The report's span tree as a list of (name, start, end), with each
    span's parent (its index in the list, -1 for none) in `parents`."""
    for s in spans:
        out.append((s["name"], float(s["t0"]), float(s["t0"]) + float(s["seconds"])))
        parents.append(parent)
        _flatten(s.get("children", []), out, parents, len(out) - 1)
    return out


def fit_fault(report: dict, on_tpu: bool) -> str:
    """Why a fit that returned does not count: a streamed refit, a retry or
    a recovery is a different result, not a slow one.  '' when sound."""
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    res = report.get("resilience", {})
    for key in ("oom_streaming_refits", "retries", "recoveries",
                "dispatch_timeouts", "checkpoint_resumes"):
        if res.get(key):
            return f"{key}={res[key]}"
    if mesh_mod.excluded_device_ids():
        return f"excluded devices {sorted(mesh_mod.excluded_device_ids())}"
    provider = report.get("memory", {}).get("provider")
    if on_tpu and provider != "real":
        return f"memory provider {provider!r}"
    return ""


def one_fit(adapter, est, fit_input, on_tpu: bool) -> dict:
    """One Estimator.fit, ended when its answer is on the host."""
    t0 = time.perf_counter()
    model = est.fit(fit_input)
    ans = adapter.answer(model)
    wall = time.perf_counter() - t0
    report = model.fit_report()
    parents: list = []
    spans = _flatten(report["spans"], [], parents)
    return {
        "wall_s": wall, "answer": ans, "fault": fit_fault(report, on_tpu),
        "stage_s": sum(t1 - t0 for n, t0, t1 in spans if n == "stage"),
        "spans": spans, "span_parents": parents,
        "routes": [n for n, _, _ in spans if n.startswith("lbfgs_route[")],
    }


def make_input(traffic: dict, mesh, cfg: dict, seed: int, data: dict):
    """(what the window's fits are given, as the traffic file says; a call
    that gives the reference those same rows on the devices).  `data` is the
    configuration's data block (`manifest.data_of`)."""
    from chipbench import datagen
    from spark_rapids_ml_tpu.data import DeviceDataset

    rows, cols = int(cfg["rows"]), int(cfg["cols"])
    kind = traffic["input"]
    if kind == "device_dataset":
        X, y, w = datagen.make_rows(mesh, rows, cols, seed, data)
        return DeviceDataset(mesh, X, rows, y=y, weight=w), lambda: (X, y)
    if kind == "host_arrays":
        Xh, yh = datagen.host_rows(rows, cols, seed, data)
        return (Xh, yh), lambda: datagen.put_rows(mesh, Xh, yh)
    raise ValueError(f"traffic input {kind!r}: 'device_dataset' or 'host_arrays'")


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def _compiles() -> float:
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    return float(sum(REGISTRY.snapshot().get("compiles_total", {}).values()))


def check(adapter, fits: list, ref: dict, limits: dict) -> dict:
    """{name: [worst value over the fits, limit]}, every fit compared."""
    worst: dict = {}
    for f in fits:
        for name, value in adapter.compare(f["answer"], ref).items():
            if name not in worst or not value <= worst[name]:
                worst[name] = value  # a NaN stays
    missing = sorted(set(limits) ^ set(worst))
    if missing:
        raise KeyError(f"limits and compared numbers differ: {missing}")
    return {name: [worst[name], float(limits[name])] for name in sorted(worst)}


def run_cell(manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices) -> dict:
    import jax

    from chipbench import roofline, trace_reduce
    from spark_rapids_ml_tpu.parallel import get_mesh

    cell = mf.cell(manifest, workload)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    adapter = mf.adapter(cfg["adapter"])
    chips = int(cell["chips"])
    on_tpu = devices[0].platform == "tpu"
    rows, cols, params = int(cfg["rows"]), int(cfg["cols"]), cfg["params"]

    # -- set-up --------------------------------------------------------------
    mesh = get_mesh(chips)
    fit_input, reference_rows = make_input(traffic, mesh, cfg, seed, mf.data_of(cfg, adapter))
    est = adapter.build(params, chips)
    warm = one_fit(adapter, est, fit_input, on_tpu)  # compiles or loads every program
    if warm["fault"]:
        say(f"warm-up fit at fault: {warm['fault']}")
    gc.collect()
    compiles0 = _compiles()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        sync_epoch = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            pass

    # -- window --------------------------------------------------------------
    fits = []
    t0 = time.perf_counter()
    t0_epoch = time.time()
    setup_s = t0 - T_START
    while True:
        fits.append(one_fit(adapter, est, fit_input, on_tpu))
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    window_s = t1 - t0
    if trace:
        jax.profiler.stop_trace()
    compiles = _compiles() - compiles0
    memory_peak = _memory_peak(devices)

    # -- the comparison ------------------------------------------------------
    t_ref = time.perf_counter()
    del fit_input
    gc.collect()
    X, y = reference_rows()
    ref = adapter.reference(X, y, params)
    reference_s = time.perf_counter() - t_ref
    checks = check(adapter, fits, ref, cfg["limits"])
    failed = sum(1 for f in fits if f["fault"])
    correct = all(v <= lim for v, lim in checks.values())

    # -- metrics -------------------------------------------------------------
    summary = breakdown = None
    if trace:
        events = trace_reduce.load_events(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # trace seconds of a host epoch time: the clock mark ties the two
        shift = trace_reduce.sync_start(events) - sync_epoch
        start = t0_epoch + shift
        summary = trace_reduce.reduce(events, (start, start + window_s))
        if summary is not None:
            host, parents = [], []
            for f in fits:  # one list over the window, the parents' indices moved along
                parents += [p + len(host) if p >= 0 else -1 for p in f["span_parents"]]
                host += [(n, a + shift, b + shift) for n, a, b in f["spans"]]
            breakdown = {
                "device_ops": trace_reduce.top_ops(summary),
                "idle_gaps": trace_reduce.attribute_gaps(summary["gaps"], host, parents),
            }
    kind = devices[0].device_kind
    ctx = {
        "adapter": adapter, "chips": chips,
        "rows": rows, "cols": cols, "fits": fits, "window_s": window_s,
        "memory_peak_bytes": memory_peak if on_tpu else None,
        "compiles_in_window": compiles, "reference": ref,
        "work": adapter.work(rows, cols, chips, params),
        "trace": summary, "traced_fits": len(fits) if summary else 0,
        "peaks": roofline.peaks_for(kind) if on_tpu else None,
    }
    measured = {"fit_s": window_s / len(fits), "setup_s": setup_s}
    metrics = {}
    # a number from a run off the chip never goes under a device metric's name
    prefix = "" if on_tpu else f"{devices[0].platform}_rehearsal."
    for m in mf.metrics_of(manifest, "per_layer" if trace else "end_to_end", workload):
        value = mf.reader(m["name"])(ctx) if trace else measured.get(m["name"])
        if value is not None and math.isfinite(value):
            metrics[prefix + m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    result = {
        "correct": bool(correct), "attempted": len(fits), "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result.update(
        workload=workload, seed=seed, seconds=seconds, window_s=window_s,
        reference_s=reference_s,
        routes=sorted({r for f in fits for r in f["routes"]}),
        faults=sorted({f["fault"] for f in fits if f["fault"]}),
        checks=checks,
    )
    return result


def report_checks(result: dict) -> None:
    """Each number compared beside its limit, as the last lines of stderr."""
    for name, (value, limit) in result["checks"].items():
        verdict = "ok" if value <= limit else "OVER"
        say(f"check {name} {value!r} limit {limit!r} {verdict}")
    say(f"correct {result['correct']} attempted {result['attempted']} "
        f"failed {result['failed']} {result['faults'] or ''}")


def main(argv) -> int:
    ap = argparse.ArgumentParser(description="one run of one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = mf.load_manifest()
    cell = mf.cell(manifest, args.workload)

    from spark_rapids_ml_tpu._jax_env import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = require_chips(jax.devices(), int(cell["chips"]))
    result = run_cell(manifest, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    report_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
