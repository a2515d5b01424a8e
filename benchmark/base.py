#
# Benchmark infrastructure — the analog of reference python/benchmark/
# base.py (BenchmarkBase: timing via with_benchmark, CSV report,
# base.py:43-295).
#
from __future__ import annotations

import csv
import json
import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# exit code of a measuring script that was not pinned to the CPU and
# found no TPU
NO_TPU_RC = 4


def require_tpu_unless_cpu_pinned(who: str) -> str:
    """The platform label of a process about to measure, e.g. "tpu x4".
    Initialises the jax backend, so the process that calls this owns the
    chip.  The only CPU measurement is one the caller asked for by
    pinning JAX_PLATFORMS=cpu (the CI smoke, the tests); without the pin
    a machine with no TPU ends the script here — jax falls back to the
    CPU silently, and a CPU number must never be printed under a device
    metric's name."""
    import jax

    devs = jax.devices()
    if os.environ.get("JAX_PLATFORMS", "") != "cpu" and devs[0].platform != "tpu":
        print(
            f"{who}: no TPU (jax.devices()[0].platform is "
            f"{devs[0].platform!r}) and JAX_PLATFORMS=cpu was not pinned; "
            "nothing measured",
            file=sys.stderr, flush=True,
        )
        raise SystemExit(NO_TPU_RC)
    return ",".join(sorted({d.platform for d in devs})) + f" x{len(devs)}"


def with_benchmark(name: str, fn: Callable[[], Any]) -> Tuple[Any, float]:
    """Run fn, return (result, elapsed_seconds); prints like the reference
    benchmark/utils.py with_benchmark."""
    t0 = time.perf_counter()
    result = fn()
    elapsed = time.perf_counter() - t0
    print(f"{name}: {elapsed:.3f}s")
    return result, elapsed


_git_revision_cache: Optional[str] = None


def git_revision() -> str:
    global _git_revision_cache
    if _git_revision_cache is None:
        try:
            proc = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            rev = proc.stdout.strip() if proc.returncode == 0 else ""
            _git_revision_cache = rev or "unknown"
        except Exception:
            _git_revision_cache = "unknown"
    return _git_revision_cache


class Report:
    """Accumulates benchmark rows and writes a CSV report (reference
    base.py:177-187, 259-282 report with git hash)."""

    FIELDS = ["benchmark", "mode", "num_rows", "num_cols", "fit_sec",
              "transform_sec", "score_name", "score", "git_rev", "extra"]

    def __init__(self, path: Optional[str] = None) -> None:
        self.path = path
        self.rows: List[Dict[str, Any]] = []

    def add(self, **row: Any) -> None:
        row.setdefault("git_rev", git_revision())
        if isinstance(row.get("extra"), dict):
            row["extra"] = json.dumps(row["extra"])
        self.rows.append(row)
        print(json.dumps(row))

    def write(self) -> None:
        if not self.path:
            return
        exists = os.path.exists(self.path)
        with open(self.path, "a", newline="") as f:
            w = csv.DictWriter(f, fieldnames=self.FIELDS, extrasaction="ignore")
            if not exists:
                w.writeheader()
            for row in self.rows:
                w.writerow(row)
