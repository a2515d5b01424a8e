#!/usr/bin/env python
#
# TPU-pod benchmark launcher — the analog of the reference's cluster
# benchmark orchestration (python/run_benchmark.sh modes + the
# Databricks/Dataproc/EMR scripts with cluster specs, e.g. the
# reference python/benchmark/databricks/run_benchmark.sh + gpu_cluster_spec.sh).
#
# Two modes:
#
#   LOCAL EMULATION (default; works on any machine, used by CI smoke):
#     python benchmark/pod/launch.py --num_processes 2 --devices_per_process 2 \
#         -- kmeans --num_rows 20000 --num_cols 16 --mode tpu
#     Spawns N local processes, each a JAX "host" with
#     --xla_force_host_platform_device_count virtual CPU devices, wires
#     jax.distributed over localhost, and runs benchmark_runner.py's
#     workload in every process (rank 0 writes the report).
#
#   POD (one process per real TPU host, e.g. under GKE / queued
#   resources / gcloud ssh --worker=all):
#     python benchmark/pod/launch.py --pod --coordinator <host0>:8476 \
#         --process_id $WORKER_ID --num_processes $NUM_WORKERS \
#         -- logistic_regression --num_rows 100000000 ...
#     Runs THIS process's shard directly (no spawning): the launcher is
#     invoked once per host by the pod scheduler, exactly how the
#     reference's init scripts invoke spark-submit per node.
#
# The workload args after `--` are benchmark_runner.py's CLI verbatim, so
# every registered benchmark (pca, kmeans, dbscan, linear_regression,
# logistic_regression, random_forest_*, nearest_neighbors,
# approximate_nearest_neighbors, umap) runs unchanged across processes:
# the estimators' multi-process staging keeps each process's rows local
# (parallel/mesh.py RowStager) and XLA collectives do the rest.
#
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_shard(
    coordinator: str,
    process_id: int,
    num_processes: int,
    runner_args: list,
    platform: str,
    devices_per_process: int,
) -> int:
    """Configure distributed bootstrap in THIS process and exec the
    benchmark runner (each pod host runs exactly this)."""
    if platform == "cpu":
        # before jax is imported: it reads both at import
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices_per_process}"
        )
    sys.path.insert(0, REPO)
    import jax

    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config

    if num_processes > 1:
        set_config(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        if not init_distributed():
            print("jax.distributed bootstrap failed", file=sys.stderr)
            return 2
        assert jax.process_count() == num_processes
    if process_id != 0:
        # only rank 0 writes the CSV report; other ranks participate in
        # the collectives and discard their local copy
        kept = []
        skip = False
        for a in runner_args:
            if skip:
                skip = False
                continue
            if a == "--report":
                skip = True  # drop the following path token too
                continue
            if a.startswith("--report="):
                continue
            kept.append(a)
        runner_args = kept
    from benchmark import benchmark_runner

    return benchmark_runner.main(runner_args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--num_processes", type=int, default=2)
    ap.add_argument("--devices_per_process", type=int, default=2,
                    help="virtual CPU devices per process (local emulation)")
    ap.add_argument("--pod", action="store_true",
                    help="run THIS process's shard (invoked once per host)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (pod mode)")
    ap.add_argument("--process_id", type=int, default=0)
    ap.add_argument("--platform", default=None, choices=("cpu", "tpu"),
                    help="default: tpu in --pod mode (real chips), cpu for "
                    "local emulation")
    ap.add_argument("runner_args", nargs=argparse.REMAINDER,
                    help="-- then benchmark_runner.py args verbatim")
    args = ap.parse_args(argv)
    runner_args = args.runner_args
    if runner_args and runner_args[0] == "--":
        runner_args = runner_args[1:]
    if not runner_args:
        ap.error("pass the benchmark_runner.py CLI after `--`")

    if args.pod:
        if args.num_processes > 1 and not args.coordinator:
            ap.error("--pod with >1 process requires --coordinator")
        # a real pod invocation means real chips unless told otherwise
        return _run_shard(
            args.coordinator or "", args.process_id, args.num_processes,
            runner_args, args.platform or "tpu", args.devices_per_process,
        )

    # local emulation: spawn one subprocess per "host".  A CPU mode only:
    # every local process would open every chip of this host, and a chip
    # belongs to one process at a time
    if args.platform == "tpu":
        ap.error(
            "local emulation is a CPU mode (N local processes cannot "
            "share this host's chips); use --pod, one process per host"
        )
    port = _free_port()
    procs = []
    for pid in range(args.num_processes):
        cmd = [
            sys.executable, os.path.abspath(__file__), "--pod",
            "--coordinator", f"127.0.0.1:{port}",
            "--process_id", str(pid),
            "--num_processes", str(args.num_processes),
            "--devices_per_process", str(args.devices_per_process),
            "--platform", args.platform or "cpu",
            "--", *runner_args,
        ]
        procs.append(
            subprocess.Popen(
                cmd,
                cwd=REPO,
                stdout=None if pid == 0 else subprocess.DEVNULL,
                stderr=None,
            )
        )
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
