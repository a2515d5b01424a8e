#
# chipbench/estimators/pca.py: PCA, the top-k eigenpairs of the rows'
# covariance, as the reference project's pca benchmark row computes them
# (cuML PCAMG: a distributed covariance and its exact eigendecomposition).
#
# What a fit returns: the column means; the k eigenvectors of the
# covariance C = sum_i (x_i - mean)(x_i - mean)^T / (n - 1) with the largest
# eigenvalues, in descending order, each with its largest-|.| coordinate
# positive; those eigenvalues (`explained_variance_`); and their shares of
# trace(C) (`explained_variance_ratio_`).
#
# The plain reference, independent of spark_rapids_ml_tpu/ops/: column sums
# and Grams per 50,000-row block through chipbench/blocks.py, added in
# float64 on the host, numpy's full eigh in float64.  The Grams are of the
# rows less the float32 mean (a second pass; what that mean misses of the
# float64 one is removed exactly on the host), so rows with a large mean
# lose nothing to cancellation here either.
#
# A block's Gram is NOT `jnp.matmul(..., precision=HIGHEST)`: on a v5e that
# product of a 50,000-row block of these rows reads 7.6e-6 low on every
# diagonal entry and 5.75e-6 low in the top eigenvalues against the host's
# float64 Gram of the same block (`high` 1.1e-5 and 7.9e-6 low; my chip
# run, PR 35), which is ten times the float32 the configuration states.
# It is the product written out (`exact_gram`): the float32 rows are the
# sum of three bfloat16 parts to the last bit, a product of two bfloat16
# numbers is exact in float32, so the Gram is the sum of all nine part
# products, each one MXU pass accumulated in float32 (trace 2e-8, top
# eigenvalues 4e-10 off the host's float64 on the same chip run).
#
from __future__ import annotations

import numpy as np

LABELS = "linear"  # never read: the configuration names its own data model
# XLA module names of the programs that hold the covariance's work: the
# Gram's row-block programs (ridge's own) and the pass that makes the shift
PROGRAMS = {"gram": ("linreg_sufficient_stats", "pca_covariance")}
# what the fault test may alter, in this order: its first number is the
# largest explained variance
_STATE = ("explained_variance_", "components_", "mean_", "explained_variance_ratio_")


def build(params: dict, chips: int):
    """The estimator.  Until the model is made, its fit kernel's attributes
    carry the fitted floats as ONE vector under `coef_`, the explained
    variances first: chipbench/tests' fault test alters `attrs["coef_"]` of
    whatever family it is given (PERF.md §7: an adapter should name its
    state, then this goes).  36 KB a fit, nothing in the timed kernels.

    The estimator's FIRST fit, run.py's warm-up in set-up, is also asked
    which solver answered, and then never again (`exact_or_nothing`)."""
    from spark_rapids_ml_tpu.feature import PCA

    est = PCA(num_workers=chips, **params)
    fit, fit_array, create_model = est.fit, est._fit_array, est._create_model

    def fit_array_as_coef(fit_input):
        attrs = fit_array(fit_input)
        parts = [np.asarray(attrs.pop(k)) for k in _STATE]
        attrs["coef_shapes"] = [p.shape for p in parts]
        attrs["coef_"] = np.concatenate([p.ravel() for p in parts])
        return attrs

    def create_model_from_coef(attrs):
        state, at = attrs.pop("coef_"), 0
        for key, shape in zip(_STATE, attrs.pop("coef_shapes")):
            n = int(np.prod(shape))
            attrs[key] = state[at:at + n].reshape(shape)
            at += n
        return create_model(attrs)

    def exact_or_nothing(fit_input):
        """The configuration guarantees the exact eigendecomposition of the
        full covariance.  A program whose `auto` answers these rows from a
        sketch (the parent of PR 35: 13 columns, two power iterations,
        `component_gap` 0.10 in 0.153 s a fit) cannot run the configuration:
        its time is no baseline for an exact fit's, so the run ends here, in
        set-up, before anything is measured, as `run.require_chips` ends one
        without a chip.  The window's fits go straight to `fit`, and
        `compare` holds each of them to the limits whatever it says of
        itself."""
        est.fit = fit
        model = fit(fit_input)
        solver = ((model.fit_report() or {}).get("solver_decision") or {}).get("solver")
        if solver != "full":
            raise SystemExit(
                f"chipbench: pca: the warm-up fit's solver was {solver!r}, not the exact "
                "'full' eigendecomposition the configuration guarantees. Nothing was "
                "measured.")
        return model

    est._fit_array, est._create_model = fit_array_as_coef, create_model_from_coef
    est.fit = exact_or_nothing
    return est


def answer(model) -> dict:
    """What a fit returned, as host arrays."""
    return {
        "mean": np.asarray(model.mean_, np.float64),
        "components": np.asarray(model.components_, np.float64),
        "variance": np.asarray(model.explained_variance_, np.float64),
        "ratio": np.asarray(model.explained_variance_ratio_, np.float64),
    }


def work(rows: int, cols: int, chips: int, params: dict) -> dict:
    """Least work per chip, from the shapes alone.  The covariance: 2 rows
    cols^2 FLOP over one read of the rows (what the products need; the mean
    could come from the same read).  The eigensolve: the tridiagonalisation
    any dense direct method pays, 4/3 cols^3 FLOP over one read of the
    matrix, whatever k is and wherever it runs."""
    share = rows / chips
    gram = {"flops": 2.0 * share * cols * cols, "bytes": share * cols * 4.0}
    eigh = {"flops": 4.0 / 3.0 * cols ** 3, "bytes": cols * cols * 4.0}
    return {
        "kernels": {"gram": gram},
        "fit": [dict(gram, count=1), dict(eigh, count=1)],
    }


def block_sums():
    return lambda Xb, yb: (Xb.sum(axis=0),)


def exact_gram(Z):
    """Z^T Z of float32 rows from all nine products of their three bfloat16
    parts (six computed, three mirrored), smallest terms added first."""
    import jax
    import jax.numpy as jnp

    parts, rest = [], Z
    for _ in range(3):
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    h, m, l = parts
    cross = dot(m, l) + dot(h, l) + dot(h, m)
    return dot(l, l) + dot(m, m) + (cross + cross.T) + dot(h, h)


def block_scatter(lowered: bool):
    """(X_block, y_block, shift (d,)) -> the Gram and the column sums of the
    block's rows less `shift`: the exact float32 product (`exact_gram`), or,
    when `lowered`, the shifted rows rounded to bfloat16 and both
    accumulated in f32 (one MXU pass)."""
    import jax
    import jax.numpy as jnp

    def stats(Xb, yb, shift):
        Z = Xb - shift
        if lowered:
            # reduce_precision, not a cast there and back: XLA elides that
            # pair on a TPU and the sums come out unrounded
            Z = jax.lax.reduce_precision(Z, exponent_bits=8, mantissa_bits=7)
            Zq = Z.astype(jnp.bfloat16)
            return jnp.matmul(Zq.T, Zq, preferred_element_type=jnp.float32), Z.sum(axis=0)
        return exact_gram(Z), Z.sum(axis=0)

    return stats


def flip_signs(components: np.ndarray) -> np.ndarray:
    """Each row with its largest-|.| coordinate positive."""
    at = np.argmax(np.abs(components), axis=1)
    signs = np.sign(components[np.arange(len(components)), at])
    return components * np.where(signs == 0, 1.0, signs)[:, None]


def eigen_answer(scatter: np.ndarray, mean: np.ndarray, n: int, k: int) -> dict:
    """The answer's keys, and the covariance itself, from the float64
    centred scatter matrix of n rows."""
    cov = scatter / (n - 1.0)
    evals, evecs = np.linalg.eigh(cov)
    return {
        "mean": mean,
        "components": flip_signs(evecs[:, ::-1][:, :k].T),
        "variance": evals[::-1][:k].copy(),
        "ratio": evals[::-1][:k] / np.trace(cov),
        "covariance": cov,
    }


def reference(X, y, params: dict, lowered: bool = False) -> dict:
    """The exact PCA of the benchmark's own device rows, in float64 but for
    the per-block Grams, which are float32-exact (`exact_gram`).  `lowered`: the rows rounded to
    bfloat16 before the products and the sums, the precision below the
    float32 the configuration states.  Returns the answer's keys and the
    covariance (`compare`'s residual is taken against the reference's)."""
    import jax.numpy as jnp

    from chipbench import blocks

    n, d = X.shape
    block_rows, mesh = blocks.block_rows_of(X), X.sharding.mesh
    (s1,) = blocks.sum_blocks(
        blocks.block_caller(block_sums(), mesh, block_rows, n_args=0), X, y, block_rows)
    shift = (s1 / n).astype(np.float32)
    scatter, rest = blocks.sum_blocks(
        blocks.block_caller(block_scatter(lowered), mesh, block_rows, n_args=1),
        X, y, block_rows, jnp.asarray(shift))
    delta = rest / n
    scatter -= n * np.outer(delta, delta)
    return eigen_answer(scatter, shift.astype(np.float64) + delta, n, int(params["k"]))


def compare(ans: dict, ref: dict) -> dict:
    """The numbers held to the configuration's `limits`.  The components
    are compared one by one in the same order under the same sign rule,
    which asks for gaps between the eigenvalues; `residual` does not: how
    far each returned direction is from being an eigenvector of the
    reference's covariance at all."""
    cov, v = ref["covariance"], ans["components"]
    spread = np.sqrt(np.trace(cov) / len(cov))  # rms of the columns' standard deviations
    Cv = v @ cov  # rows C v_i: cov is symmetric
    rayleigh = np.einsum("ij,ij->i", Cv, v)
    return {
        "mean_gap": float(np.linalg.norm(ans["mean"] - ref["mean"]) / spread),
        "variance_gap": float(np.max(np.abs(ans["variance"] / ref["variance"] - 1.0))),
        "ratio_gap": float(np.max(np.abs(ans["ratio"] / ref["ratio"] - 1.0))),
        "component_gap": float(np.max(np.linalg.norm(v - ref["components"], axis=1))),
        "residual": float(np.max(np.linalg.norm(Cv - rayleigh[:, None] * v, axis=1))
                          / ref["variance"][0]),
    }
