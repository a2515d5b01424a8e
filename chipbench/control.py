#!/usr/bin/env python3
#
# chipbench/control.py: the readings a cell's limits are set from.  Not part
# of a benchmark run; run it on the chip when a limit is set or questioned:
#
#   python3 chipbench/control.py --workload <cell> --seeds 1,2,3 [--program 1]
#
# For each seed it makes the cell's rows, runs the plain reference (the
# truth) and the control: the same reference computed in the precision below
# the one the configuration states (the estimator family's `lowered` path),
# put in the program's place and held to the same comparison.  The control
# has to come out over the limit.  With --program 1 it also fits once through
# the cell's own traffic and prints the program's reading for that seed, so a
# dozen seeds' lower readings cost one process.
#
# One JSON line per seed on stdout:
#   {"seed": .., "control": {name: value}, "program": {name: value}, "limits": {..}}
#
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest as mf  # noqa: E402
from chipbench import run  # noqa: E402


def readings(cell: dict, seed: int, devices, program: bool) -> dict:
    from spark_rapids_ml_tpu.parallel import get_mesh

    cfg, traffic = cell["config_file"], cell["traffic_file"]
    adapter = mf.adapter(cfg["adapter"])
    chips, params = int(cell["chips"]), cfg["params"]
    out = {"seed": seed, "limits": cfg["limits"]}
    fit_input, reference_rows = run.make_input(
        traffic, get_mesh(chips), cfg, seed, mf.data_of(cfg, adapter))
    if program:
        fit = run.one_fit(adapter, adapter.build(params, chips), fit_input,
                          devices[0].platform == "tpu")
    del fit_input
    gc.collect()
    X, y = reference_rows()
    ref = adapter.reference(X, y, params)
    if program:
        out["program"] = adapter.compare(fit["answer"], ref)
        out["fault"] = fit["fault"]
    low = adapter.reference(X, y, params, lowered=True)
    out["control"] = adapter.compare(low, ref)
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description="control and program readings of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = mf.cell(mf.load_manifest(), args.workload)

    from spark_rapids_ml_tpu._jax_env import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = run.require_chips(jax.devices(), int(cell["chips"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, devices, bool(args.program))), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
