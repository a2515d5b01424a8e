#
# Framework-contract tests with a fake algorithm — the analog of the
# reference's `CumlDummy`/`SparkRapidsMLDummy` (tests/test_common_estimator.py:
# 46-200+): validates param mapping (direct / None / "" / value-mapped), the
# fit plumbing (FitInput contents, PartitionDescriptor, mesh sharding), and
# fitMultiple, independent of any real algorithm.
#
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core import FitInput, _TpuEstimator, _TpuModel
from spark_rapids_ml_tpu.params import (
    HasFeaturesCol,
    Param,
    TypeConverters,
    _TpuParams,
)


class DummyClass:
    @classmethod
    def _param_mapping(cls):
        return {
            "alpha": "a",          # direct mapping
            "beta": "",            # accepted, ignored
            "gamma": None,         # unsupported -> error / CPU fallback
        }

    @classmethod
    def _param_value_mapping(cls):
        return {"alpha": lambda v: v * 10.0}

    @classmethod
    def _get_tpu_params_default(cls):
        return {"a": 1.0, "extra_kw": "x"}


class _DummyParams(_TpuParams, HasFeaturesCol):
    alpha = Param("_", "alpha", "doc", TypeConverters.toFloat)
    beta = Param("_", "beta", "doc", TypeConverters.toString)
    gamma = Param("_", "gamma", "doc", TypeConverters.toString)


class DummyModel(DummyClass, _TpuModel, _DummyParams):
    def __init__(self, **attrs):
        super().__init__(**attrs)
        self.col_sums = np.asarray(attrs["col_sums"])
        self.n_rows = int(attrs["n_rows"])

    def _transform_array(self, X):
        return {"prediction": X.sum(axis=1)}


class DummyEstimator(DummyClass, _TpuEstimator, _DummyParams):
    def __init__(self, **kwargs):
        super().__init__()
        self._setDefault(alpha=0.1, beta="b", gamma="g")
        self._set_params(**kwargs)
        self.seen_fit_inputs = []

    def _fit_array(self, fit_input: FitInput):
        import jax

        self.seen_fit_inputs.append(fit_input)
        # plumbing assertions: X sharded over the mesh, weights mask padding
        assert fit_input.pdesc.m == fit_input.X.shape[0]
        assert fit_input.pdesc.n == fit_input.X.shape[1]
        assert len(fit_input.pdesc.parts_rank_size) == fit_input.mesh.devices.size
        col_sums = np.asarray(
            jax.jit(lambda X, w: (X * w[:, None]).sum(0))(fit_input.X, fit_input.w)
        )
        return {
            "col_sums": col_sums,
            "n_rows": fit_input.n_valid,
            "a_value": fit_input.params["a"],
        }

    def _create_model(self, attrs):
        m = DummyModel(**attrs)
        return m


def test_chunked_device_put_matches_oneshot(monkeypatch):
    """Staging above _MAX_PUT_BYTES uploads in bounded pieces.  Forcing
    a tiny limit: the assembled device array must be bit-identical to a direct
    put, sharded and unsharded, 1-D and 2-D, including uneven tails."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
    from spark_rapids_ml_tpu.parallel.mesh import RowStager, get_mesh

    monkeypatch.setattr(mesh_mod, "_MAX_PUT_BYTES", 1000)
    rng = np.random.default_rng(0)
    # sharded direct call: rows must divide the mesh (the RowStager pads
    # before calling; this mirrors that contract)
    X = rng.standard_normal((1004, 7)).astype(np.float32)
    y = rng.standard_normal((1003,))
    m = get_mesh(4)
    sh2 = NamedSharding(m, PartitionSpec("data"))
    out = mesh_mod._chunked_device_put(X, sh2)
    np.testing.assert_array_equal(np.asarray(out), X)
    assert out.sharding.is_equivalent_to(sh2, X.ndim)
    np.testing.assert_array_equal(
        np.asarray(mesh_mod._chunked_device_put(y)), y
    )
    # and through the stager end to end (pad + layout + chunked upload,
    # uneven row count padded by the stager itself)
    Xu = X[:1003]
    st = RowStager(1003, m, bucketing=False)
    staged = st.stage(Xu)
    np.testing.assert_array_equal(np.asarray(staged)[: st.n_valid], Xu)
    # the fetch mirror: bounded-slice device->host must equal one-shot
    np.testing.assert_array_equal(
        mesh_mod._chunked_device_get(staged), np.asarray(staged)
    )
    np.testing.assert_array_equal(
        mesh_mod._chunked_device_get(mesh_mod._chunked_device_put(y)), y
    )


def test_param_mapping_and_defaults():
    est = DummyEstimator()
    assert est._tpu_params == {"a": 1.0, "extra_kw": "x"}
    est = DummyEstimator(alpha=0.5)
    assert est._tpu_params["a"] == pytest.approx(5.0)  # value-mapped x10
    assert est.getOrDefault("alpha") == 0.5
    est._set_params(beta="ignored")
    assert "b" not in est._tpu_params  # "" mapping: accepted, ignored
    est._set_params(extra_kw="y")  # backend kwarg passthrough
    assert est._tpu_params["extra_kw"] == "y"


def test_unsupported_param_raises_without_fallback():
    with pytest.raises(ValueError, match="not supported on TPU"):
        DummyEstimator(gamma="nope")


def test_unsupported_param_arms_fallback():
    config.set_config(cpu_fallback_enabled=True)
    try:
        est = DummyEstimator(gamma="nope")
        assert est._use_cpu_fallback()
        # Dummy has no CPU implementation -> NotImplementedError surfaces
        with pytest.raises(NotImplementedError):
            est.fit(np.ones((4, 2), dtype=np.float32))
    finally:
        config.reset_config()


def test_fit_plumbing(num_workers):
    X = np.arange(20, dtype=np.float32).reshape(10, 2)
    est = DummyEstimator(num_workers=num_workers)
    model = est.fit(X)
    fi = est.seen_fit_inputs[0]
    assert fi.mesh.devices.size == num_workers
    # padded total divides evenly across the mesh
    assert fi.X.shape[0] % num_workers == 0
    assert model.n_rows == 10
    np.testing.assert_allclose(model.col_sums, X.sum(axis=0))
    # params flow: spark name alpha=0.1 default is NOT in paramMap-set, but
    # the backend dict default a=1.0 reaches the kernel
    assert est.seen_fit_inputs[0].params["a"] == 1.0


def test_fit_with_pandas_and_weights(num_workers):
    df = pd.DataFrame(
        {
            "features": [[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]],
            "w": [1.0, 0.0, 2.0],
        }
    )
    est = DummyEstimator(num_workers=num_workers)
    est._set(featuresCol="features")
    # no weightCol param on dummy -> plain fit
    model = est.fit(df)
    np.testing.assert_allclose(model.col_sums, [9.0, 12.0])


def test_fit_multiple_single_pass():
    X = np.ones((8, 3), dtype=np.float32)
    est = DummyEstimator()
    maps = [{est.alpha: 1.0}, {est.alpha: 2.0}]
    it = est.fitMultiple(X, maps)
    results = {i: m for i, m in it}
    assert len(results) == 2
    assert results[0]._model_attributes["a_value"] == pytest.approx(10.0)
    assert results[1]._model_attributes["a_value"] == pytest.approx(20.0)


def test_model_transform_and_copy():
    X = np.arange(12, dtype=np.float32).reshape(4, 3)
    est = DummyEstimator()
    model = est.fit(X)
    preds = model.transform(X)
    np.testing.assert_allclose(preds, X.sum(axis=1))
    est2 = est.copy({est.alpha: 3.0})
    assert est2.getOrDefault("alpha") == 3.0
    assert est2._tpu_params["a"] == pytest.approx(30.0)
    # original untouched
    assert est.getOrDefault("alpha") == 0.1


def test_num_workers_inference():
    est = DummyEstimator()
    assert est.num_workers == 8  # all virtual devices
    est.num_workers = 2
    assert est.num_workers == 2


def test_sparse_input_densified(num_workers):
    import scipy.sparse as sp

    X = sp.random(10, 4, density=0.5, format="csr", random_state=0, dtype=np.float64)
    est = DummyEstimator(num_workers=num_workers)
    model = est.fit(X)
    np.testing.assert_allclose(model.col_sums, X.toarray().sum(axis=0), rtol=1e-5)
    preds = model.transform(X)
    np.testing.assert_allclose(preds, X.toarray().sum(axis=1), rtol=1e-5)


def test_num_workers_config_respected():
    config.set_config(num_workers=2)
    try:
        est = DummyEstimator()
        est.fit(np.ones((4, 2), dtype=np.float32))
        assert est.seen_fit_inputs[0].mesh.devices.size == 2
    finally:
        config.reset_config()


def test_copy_isolates_fallback_state():
    config.set_config(cpu_fallback_enabled=True)
    try:
        est = DummyEstimator()
        est2 = est.copy()
        est2._set_params(gamma="nope")
        assert est2._use_cpu_fallback()
        assert not est._use_cpu_fallback()
    finally:
        config.reset_config()


def test_fit_params_unsupported_raises():
    est = DummyEstimator()
    with pytest.raises(ValueError, match="not supported on TPU"):
        est.fit(np.ones((4, 2), dtype=np.float32), {est.gamma: "x"})


def test_transform_empty_dataframe():
    X = np.arange(12, dtype=np.float32).reshape(4, 3)
    est = DummyEstimator()
    est._set(featuresCol="features")
    model = est.fit(X)
    model._set(featuresCol="features")
    empty = pd.DataFrame({"features": pd.Series([], dtype=object)})
    out = model.transform(empty)
    assert len(out) == 0
    assert "prediction" in out.columns


def test_shape_bucketing_shares_padded_shapes(rng):
    """Nearby dataset sizes stage to ONE padded shape (compile reuse);
    disabling bucketing restores exact padding."""
    import numpy as np

    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.parallel.mesh import RowStager, bucket_rows, get_mesh

    mesh = get_mesh(4)
    try:
        a = RowStager(900, mesh)
        b = RowStager(1000, mesh)
        assert a.n_padded == b.n_padded == 1024
        assert a.n_valid == 900 and b.n_valid == 1000
        Xs = a.stage(np.ones((900, 3), np.float32))
        assert Xs.shape[0] == 1024
        # bucket grid: {1, 1.5} x 2^k
        assert bucket_rows(1536) == 1536
        assert bucket_rows(1537) == 2048
        assert bucket_rows(10) == 256
        set_config(shape_bucketing=False)
        c = RowStager(1000, mesh)
        assert c.n_padded == 1000
    finally:
        reset_config()


def test_bucketed_fit_matches_exact(rng):
    import numpy as np

    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.regression import LinearRegression

    X = rng.normal(size=(900, 4))
    y = X @ np.array([1.0, -2.0, 0.5, 3.0]) + 0.5
    m_bucket = LinearRegression(float32_inputs=False).fit((X, y))
    try:
        set_config(shape_bucketing=False)
        m_exact = LinearRegression(float32_inputs=False).fit((X, y))
    finally:
        reset_config()
    np.testing.assert_allclose(m_bucket.coef_, m_exact.coef_, rtol=1e-10)
    np.testing.assert_allclose(m_bucket.intercept_, m_exact.intercept_, rtol=1e-10)
