#
# Multi-process (multi-host analog) execution tests — the TPU answer to the
# reference's NCCL multi-rank path (common/cuml_context.py:35-206 bootstrap,
# core.py:742-1013 barrier fit).  Real pods run one JAX process per host;
# here N CPU processes with --xla_force_host_platform_device_count emulate
# the topology: each process loads only its LOCAL rows (per-partition data
# loading) and `RowStager` assembles the global sharded arrays via
# jax.make_array_from_process_local_data.  A 1-process run over the SAME
# total device count must produce the same models.
#
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent(
    """
    import json, os, sys
    pid, nproc, port, outfile = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    n_dev_local = 4 // nproc
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev_local}"
    )
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["SRMT_REPO"])
    import numpy as np
    from spark_rapids_ml_tpu import init_distributed
    from spark_rapids_ml_tpu.config import set_config

    if nproc > 1:
        # the config-tier bootstrap (analog of the NCCL-uid allGather,
        # reference cuml_context.py:96-102)
        set_config(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=nproc,
            process_id=pid,
        )
        assert init_distributed()
        assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()

    # identical global dataset on every process; each fits on its slice ONLY
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1003, 8)).astype(np.float64)
    beta = rng.normal(size=8)
    y = (X @ beta + 0.2 * rng.normal(size=1003) > 0).astype(np.float64)
    bounds = np.linspace(0, 1003, nproc + 1).astype(int)
    # deliberately uneven split so per-process padding differs
    if nproc == 2:
        bounds = np.array([0, 601, 1003])
    lo, hi = bounds[pid], bounds[pid + 1]
    Xl, yl = X[lo:hi], y[lo:hi]

    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA

    out = {}
    lr = LogisticRegression(maxIter=40, tol=1e-9, regParam=0.01).fit((Xl, yl))
    out["lr_coef"] = np.asarray(lr.coef_, np.float64).ravel().tolist()
    out["lr_intercept"] = float(np.asarray(lr.intercept_).ravel()[0])
    out["lr_objective"] = float(lr._model_attributes["objective"])

    # KMeans on well-separated blobs: the init draws depend on the padded
    # row layout (which differs between 1- and 2-process runs), but with
    # separated blobs every init converges to the same global optimum
    centers_true = np.array(
        [[8.0 * np.cos(2 * np.pi * j / 5), 8.0 * np.sin(2 * np.pi * j / 5)]
         for j in range(5)]
    )
    Xb = (
        centers_true[rng.integers(0, 5, size=1003)]
        + 0.3 * rng.normal(size=(1003, 2))
    ).astype(np.float64)
    km = KMeans(k=5, seed=7, maxIter=60).fit(Xb[lo:hi])
    centers = np.asarray(km.cluster_centers_, np.float64)
    out["km_centers"] = centers[np.lexsort(centers.T)].tolist()
    out["km_inertia"] = float(km.inertia_)

    import pandas as pd
    pca = PCA(k=3).setInputCol("f").setOutputCol("o").fit(
        pd.DataFrame({"f": list(Xl)})
    )
    out["pca_var"] = np.asarray(
        pca.explained_variance_, np.float64
    ).tolist()

    # exact kNN: fit gathers items to the replicated full set; query with a
    # replicated block -> indices must match the single-process run exactly
    from spark_rapids_ml_tpu.knn import NearestNeighbors
    nn = NearestNeighbors(k=3).fit(Xl)
    assert nn.item_features.shape[0] == 1003, nn.item_features.shape
    d_knn, idx_knn = nn._search(X[:32].astype(np.float32), 3)
    out["knn_idx"] = idx_knn.tolist()

    # distributed-item kNN: past knn_replicate_max_bytes the model keeps
    # feature rows PROCESS-LOCAL (no host/device ever holds the full
    # N x d matrix) and only the id vector replicates; results must still
    # match the replicated model exactly
    set_config(knn_replicate_max_bytes=1024)  # 1003x8 f32 >> 1 KiB
    nn_d = NearestNeighbors(k=3).fit(Xl)
    set_config(knn_replicate_max_bytes=1024 * 1024 * 1024)
    if nproc > 1:
        assert nn_d.distributed_items, "expected distributed-item layout"
        # the memory probe: this process holds ONLY its local rows
        assert nn_d.item_features.shape[0] == (hi - lo), (
            nn_d.item_features.shape, hi - lo
        )
        try:
            nn_d.save(os.path.join(os.path.dirname(outfile), "nn_d"))
            raise AssertionError("distributed model save must refuse")
        except NotImplementedError:
            pass
    _, idx_knn_d = nn_d._search(X[:32].astype(np.float32), 3)
    out["knn_idx_dist"] = idx_knn_d.tolist()

    # DBSCAN transform on a replicated input (deterministic labels)
    from spark_rapids_ml_tpu.clustering import DBSCAN
    db = DBSCAN(eps=0.5, min_samples=4).fit(Xb)
    lab = db._transform_array(Xb.astype(np.float32))
    out["db_labels"] = lab[db.getOrDefault("predictionCol")].tolist()

    # RandomForest: trees differ across layouts (per-device bootstrap), so
    # only the ensemble quality is comparable
    from spark_rapids_ml_tpu.classification import RandomForestClassifier
    rf = RandomForestClassifier(numTrees=8, maxDepth=5, seed=3).fit((Xl, yl))
    rf_pred = rf._transform_array(X.astype(np.float32))["prediction"]
    out["rf_acc"] = float((np.asarray(rf_pred) == y).mean())

    # UMAP: fit gathers the full sample -> identical model on every process
    from spark_rapids_ml_tpu.umap import UMAP
    um = UMAP(n_neighbors=8, n_epochs=5, random_state=0).fit(Xl)
    emb = um._transform_array(X[:20].astype(np.float32))
    out["umap_emb"] = np.asarray(
        emb[um.getOrDefault("outputCol")], np.float64
    ).tolist()

    # streaming ingest in multi-process mode: each process reads ONLY its
    # global row slice from parquet (streaming.py stage_parquet), and the
    # beyond-HBM streamed-stats fit sums partial statistics across
    # processes (linreg_streaming_stats + process_allgather)
    ppath = os.path.join(os.path.dirname(outfile), f"stream_{pid}_{nproc}.parquet")
    y_reg = (X @ beta).astype(np.float64)
    pd.DataFrame(
        {"features": list(X.astype(np.float32)), "label": y_reg}
    ).to_parquet(ppath)
    from spark_rapids_ml_tpu.streaming import stage_parquet
    ds = stage_parquet(ppath, label_col="label", dtype=np.float32)
    assert ds.n_valid == 1003, ds.n_valid
    del ds  # free the staged copy before the streamed-stats fit below
    from spark_rapids_ml_tpu.regression import LinearRegression
    set_config(force_streaming_stats=True)
    lrs = LinearRegression().fit(ppath)
    set_config(force_streaming_stats=False)
    out["stream_coef"] = np.asarray(lrs.coef_, np.float64).tolist()

    if pid == 0:
        with open(outfile, "w") as f:
            json.dump(out, f)
    """
)


def _run_workers(nproc: int, tmp_path, timeout: int = 900) -> dict:
    script = tmp_path / "mp_worker.py"
    script.write_text(WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    outfile = tmp_path / f"out_{nproc}.json"
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["SRMT_REPO"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nproc), str(port),
             str(outfile)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(nproc)
    ]
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        errs.append((p.returncode, err))
    for rc, err in errs:
        assert rc == 0, err[-4000:]
    with open(outfile) as f:
        return json.load(f)


def test_two_process_fit_matches_single_process(tmp_path):
    """2 processes x 2 devices vs 1 process x 4 devices: same 4-way mesh,
    same global data split per-process -> same LogReg/KMeans/PCA models."""
    single = _run_workers(1, tmp_path)
    multi = _run_workers(2, tmp_path)

    # tolerances are float32-scale: the per-process padding layout gives the
    # 2-process run different shard sizes, so f32 reduction order differs
    np.testing.assert_allclose(
        multi["lr_coef"], single["lr_coef"], rtol=2e-3, atol=5e-4
    )
    assert abs(multi["lr_intercept"] - single["lr_intercept"]) < 1e-3
    assert abs(multi["lr_objective"] - single["lr_objective"]) < 1e-5
    np.testing.assert_allclose(
        multi["km_centers"], single["km_centers"], rtol=2e-3, atol=1e-3
    )
    assert abs(multi["km_inertia"] - single["km_inertia"]) < 1e-2 * abs(
        single["km_inertia"]
    )
    np.testing.assert_allclose(
        multi["pca_var"], single["pca_var"], rtol=1e-4
    )
    assert multi["knn_idx"] == single["knn_idx"]
    # the distributed-item layout must search identically to replication
    assert multi["knn_idx_dist"] == single["knn_idx"]
    assert single["knn_idx_dist"] == single["knn_idx"]
    assert multi["db_labels"] == single["db_labels"]
    assert multi["rf_acc"] > 0.85 and single["rf_acc"] > 0.85, (
        multi["rf_acc"],
        single["rf_acc"],
    )
    np.testing.assert_allclose(
        multi["umap_emb"], single["umap_emb"], rtol=1e-3, atol=1e-3
    )
    # streamed-stats fit: per-process partial statistics summed across
    # processes must reproduce the single-process solve
    np.testing.assert_allclose(
        multi["stream_coef"], single["stream_coef"], rtol=1e-4, atol=1e-5
    )
