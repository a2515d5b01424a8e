#
# Benchmark smoke tests — the analog of reference tests/test_benchmark.py:
# every registered benchmark runs end to end at toy sizes in both modes.
#
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import gen_data
from benchmark.benchmark_runner import BENCHMARKS, main


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_benchmark_smoke_tpu(name, tmp_path):
    report = str(tmp_path / "report.csv")
    main([
        name, "--num_rows", "300", "--num_cols", "8", "--mode", "tpu",
        "--num_workers", "2", "--max_iter", "5", "--num_trees", "4",
        "--max_depth", "4", "--report", report,
    ])
    assert os.path.exists(report)


def test_benchmark_smoke_cpu(tmp_path):
    report = str(tmp_path / "report.csv")
    main([
        "kmeans", "--num_rows", "300", "--num_cols", "8", "--mode", "cpu",
        "--report", report,
    ])
    with open(report) as f:
        content = f.read()
    assert "kmeans" in content and "cpu" in content


def test_gen_data_parquet(tmp_path):
    X, y = gen_data.gen_classification(100, 6, n_classes=3, seed=1)
    assert X.shape == (100, 6) and set(np.unique(y)) == {0.0, 1.0, 2.0}
    path = str(tmp_path / "d.parquet")
    gen_data.write_parquet(X, y, path)
    import pandas as pd

    df = pd.read_parquet(path)
    assert len(df) == 100 and "label" in df.columns

    # scalar layout
    path2 = str(tmp_path / "d2.parquet")
    gen_data.write_parquet(X, None, path2, feature_layout="scalar")
    df2 = pd.read_parquet(path2)
    assert list(df2.columns) == [f"c{i}" for i in range(6)]


def test_gen_data_distributed_consistency(tmp_path):
    """Partition-decomposable generation: any partitioning of the same
    (kind, seed, shape) yields the same dataset, and the streaming fit
    recovers the shared structure."""
    import pyarrow.parquet as pq

    from benchmark.gen_data_distributed import generate_partitioned

    a = generate_partitioned(
        "regression", 2000, 8, str(tmp_path / "a"), parts=4, seed=7
    )
    t = pq.read_table(a)
    assert t.num_rows == 2000
    # two datagen workers writing interleaved parts == one worker
    b_dir = str(tmp_path / "b")
    generate_partitioned("regression", 2000, 8, b_dir, parts=4, seed=7,
                         part_offset=0, part_stride=2)
    generate_partitioned("regression", 2000, 8, b_dir, parts=4, seed=7,
                         part_offset=1, part_stride=2)
    tb = pq.read_table(b_dir)
    assert t.equals(tb)


def test_gen_data_distributed_streaming_fit(tmp_path):
    import numpy as np

    from benchmark.gen_data_distributed import RegressionGen, generate_partitioned
    from spark_rapids_ml_tpu.regression import LinearRegression

    out = generate_partitioned(
        "regression", 3000, 6, str(tmp_path / "reg"), parts=6, seed=3,
        noise=0.01,
    )
    model = LinearRegression().fit(out)  # parquet-path streaming ingest
    w = RegressionGen(6, noise=0.01).shared(3)
    np.testing.assert_allclose(model.coef_, w, rtol=0.05, atol=0.5)


def test_gen_data_distributed_kinds(tmp_path):
    import pyarrow.parquet as pq

    from benchmark.gen_data_distributed import GENERATORS, generate_partitioned

    for kind in GENERATORS:
        out = generate_partitioned(
            kind, 300, 5, str(tmp_path / kind), parts=3, seed=1
        )
        t = pq.read_table(out)
        assert t.num_rows == 300, kind


def test_pod_launcher_two_process(tmp_path):
    # the pod benchmark launcher (benchmark/pod/launch.py) must run a
    # registered workload across 2 jax.distributed processes and write
    # rank 0's CSV report
    import subprocess
    import sys

    report = tmp_path / "pod.csv"
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "benchmark", "pod", "launch.py"),
            "--num_processes", "2", "--devices_per_process", "2",
            "--", "kmeans", "--num_rows", "8000", "--num_cols", "8",
            "--mode", "tpu", "--max_iter", "5", "--report", str(report),
        ],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert report.exists()
    content = report.read_text()
    assert "kmeans" in content and "inertia" in content


def test_rehearsal_pod_phase_smoke(tmp_path):
    """benchmark/rehearsal_100m.py's 2-process pod phase at toy scale:
    2-process streaming fit must match the
    1-process run over the same device count, survive a whole-pod
    SIGKILL, and resume from rank 0's checkpoint to the same model."""
    import json
    import subprocess
    import sys

    env = dict(
        os.environ,
        REHEARSAL_ROWS="60000",
        REHEARSAL_COLS="8",
        REHEARSAL_MAX_ITER="4",
        REHEARSAL_POD_ROWS="60000",
        REHEARSAL_DIR=str(tmp_path),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "rehearsal_100m.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["pod_parity_ok"], out
    assert out["pod_resume_ok"], out
    # self-describing artifact metadata
    assert "host_loadavg_start" in out and "contended" in out


def test_ann_10m_script_smoke():
    """benchmark/ann_10m.py (BASELINE-scale ANN runner) at toy scale:
    both algorithms must report build/qps/recall
    with no *_error keys, and recall on clustered data must be high."""
    import json
    import subprocess
    import sys

    env = dict(
        os.environ,
        ANN_ROWS="20000",
        ANN_COLS="16",
        ANN_QUERIES="200",
        ANN_K="5",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "ann_10m.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    errors = {k: v for k, v in out.items() if k.endswith("_error")}
    assert not errors, errors
    assert out["ivfflat_recall_at_5"] > 0.8, out
    assert out["cagra_recall_at_5"] > 0.8, out
    assert out["ivfflat_search_qps"] > 0
