#
# jit-audit sanitizer — runtime jit hygiene, generalized from the PR-7
# captured-constant audit (tests/test_logistic_regression.py
# test_host_dispatched_lbfgs_no_constant_capture).  Three invariants,
# all of which failed silently at some point in this repo's history:
#
#   captured constants   a jit built AT CALL TIME over local data can
#                        close over the dataset: jax lowers the closed
#                        array as a program CONSTANT (at refconfig
#                        1M x 3000 scale that was a 12 GB host-side
#                        materialization during lowering — jax's "large
#                        amount of constants were captured" warning,
#                        observed live on chip).  Every audited jit is
#                        re-traced with `make_jaxpr` on first call and
#                        its captured-const bytes bounded (16 KB).
#   donations consumed   `donate_argnums` is a performance CONTRACT: a
#                        declared donation whose buffer is not actually
#                        consumed (dtype/sharding mismatch) silently
#                        degrades to a copy — double HBM for the
#                        donated staging/accumulator updates.  Checked
#                        via `Array.is_deleted()` after the first call.
#   steady-state         solver ITERATIONS must not compile: iteration
#   recompiles           k > 1 re-lowering every step is the compile
#                        storm the PR-7 listener exists to catch.
#                        Checked by differencing `compiles_total` /
#                        `recompiles_total` growth between a short and a
#                        long fit of the same shape (per-fit program
#                        builds cancel; per-iteration compiles do not).
#
# Module-level `@jax.jit` functions are data-as-argument by
# construction (bound at import, before any dataset exists), so the
# interesting surface is jits created AT CALL TIME.  `audit_jits`
# patches `jax.jit` itself for the duration of the block (the only hook
# that catches every creation style — module-global `jax.jit`,
# function-local `import jax`, `functools.partial(jax.jit, ...)` built
# inside the block) and records the jits whose defining module is in
# the audited set.  Shared by tests/test_analysis.py, the per-solver
# tests, and the `python -m spark_rapids_ml_tpu.analysis --jit-audit`
# CI job.
#
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field
from typing import Any, Iterator, List, Optional, Sequence, Tuple

# the 16 KB bound the L-BFGS test established: generous for scalar/shape
# constants, far below any dataset (the test-scale dataset alone is 128 KB)
MAX_CONST_BYTES = 16 * 1024

# modules that create jits at call time along the audited solver paths
# (records are attributed by the jitted function's __module__; the
# fused accumulator steps are defined in ops/stats.py)
AUDITED_MODULES = (
    "spark_rapids_ml_tpu.fused",
    "spark_rapids_ml_tpu.streaming",
    "spark_rapids_ml_tpu.parallel.mesh",
    "spark_rapids_ml_tpu.parallel.device_cache",
    "spark_rapids_ml_tpu.ops.logistic",
    "spark_rapids_ml_tpu.ops.kmeans",
    "spark_rapids_ml_tpu.ops.pca",
    "spark_rapids_ml_tpu.ops.linear",
    "spark_rapids_ml_tpu.ops.stats",
)


@dataclass
class JitRecord:
    """One audited jit: where it was created and what the first call's
    re-trace measured."""

    module: str
    fn_name: str
    const_bytes: int = 0
    donate_argnums: Tuple[int, ...] = ()
    # None = nothing checkable was donated (no declaration, or the
    # donated args were host arrays consumed by the implicit device_put)
    donated_consumed: Optional[bool] = None
    error: str = ""


@dataclass
class JitAuditReport:
    """Everything `audit_jits` observed, plus the violation rollup."""

    max_const_bytes: int = MAX_CONST_BYTES
    records: List[JitRecord] = field(default_factory=list)

    def violations(self) -> List[str]:
        out: List[str] = []
        for r in self.records:
            if r.error:
                out.append(
                    f"{r.module}.{r.fn_name}: audit re-trace failed: {r.error}"
                )
            if r.const_bytes > self.max_const_bytes:
                out.append(
                    f"{r.module}.{r.fn_name}: captured {r.const_bytes} bytes "
                    f"of constants (bound {self.max_const_bytes}) — data "
                    "must ride the jit as an argument, not a closure"
                )
            if r.donated_consumed is False:
                out.append(
                    f"{r.module}.{r.fn_name}: declared donation "
                    f"{r.donate_argnums} was NOT consumed — the donated "
                    "buffer silently degraded to a copy"
                )
        return out


class JitAuditError(AssertionError):
    """Raised by `assert_clean` when an audited solver violates the
    jit-hygiene contract."""


def _const_bytes(consts: Sequence[Any]) -> int:
    import numpy as np

    return int(sum(np.asarray(c).nbytes for c in consts))


def _retrace(real_jax: Any, fn: Any, kw: dict, args: tuple, kwargs: dict):
    """Re-trace `fn` the way its jit saw the first call: statics passed
    as KWARGS bind into a partial and statics passed POSITIONALLY map to
    static_argnums through the signature — either way they stay Python
    values while everything else traces."""
    import inspect

    static_names = kw.get("static_argnames") or ()
    if isinstance(static_names, str):
        static_names = (static_names,)
    static_nums = kw.get("static_argnums", ())
    if isinstance(static_nums, int):
        static_nums = (static_nums,)
    nums = set(static_nums)
    if static_names:
        try:
            params = list(inspect.signature(fn).parameters)
        except (ValueError, TypeError):
            params = []
        for name in static_names:
            if name in params and params.index(name) < len(args):
                nums.add(params.index(name))
    static_kw = {k: v for k, v in kwargs.items() if k in static_names}
    dyn_kw = {k: v for k, v in kwargs.items() if k not in static_names}
    target = functools.partial(fn, **static_kw) if static_kw else fn
    mj_kw = {"static_argnums": tuple(sorted(nums))} if nums else {}
    return real_jax.make_jaxpr(target, **mj_kw)(*args, **dyn_kw)


class _AuditedJit:
    """Callable standing in for a `PjitFunction` created inside an
    audit block: first call runs the audit, later calls pass straight
    through.  Unknown attributes DELEGATE to the real jitted function —
    a module first imported inside an audit block (the audited fit's
    own lazy imports) keeps this wrapper for the life of the process,
    so the PjitFunction surface (`_cache_size`, `clear_cache`,
    `lower`, …) must keep working on it."""

    def __init__(self, fn, jitted, on_first) -> None:
        self._fn = fn
        self._jitted = jitted
        self._on_first = on_first
        self._first = True
        functools.update_wrapper(self, fn)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._first:
            self._first = False
            return self._on_first(args, kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name: str) -> Any:
        if name in ("_fn", "_jitted", "_on_first", "_first"):
            raise AttributeError(name)  # never recurse mid-__init__
        return getattr(self._jitted, name)


def _make_auditing_jit(real_jax: Any, real_jit: Any,
                       prefixes: Optional[Tuple[str, ...]],
                       report: JitAuditReport) -> Any:
    def auditing_jit(fn: Any = None, **kw: Any) -> Any:
        if fn is None:  # @jax.jit(static_argnames=...) decorator form
            return lambda f: auditing_jit(f, **kw)
        jitted = real_jit(fn, **kw)
        modname = getattr(fn, "__module__", "") or ""
        if prefixes is not None and modname not in prefixes:
            return jitted  # outside the audited set: zero footprint
        donate = kw.get("donate_argnums", ())
        if isinstance(donate, int):
            donate = (donate,)
        rec = JitRecord(
            modname,
            getattr(fn, "__name__", repr(fn)),
            donate_argnums=tuple(donate),
        )

        def first_call(args: tuple, kwargs: dict) -> Any:
            report.records.append(rec)
            try:
                closed = _retrace(real_jax, fn, kw, args, kwargs)
                rec.const_bytes = _const_bytes(closed.consts)
            except Exception as e:  # surfaced via violations()
                rec.error = f"{type(e).__name__}: {e}"
            donated = [
                leaf
                for i in donate if i < len(args)
                # a donated arg may be a PYTREE (the fused
                # accumulator tuples); host arrays (no is_deleted)
                # are consumed by the implicit device_put and are
                # not checkable
                for leaf in real_jax.tree_util.tree_leaves(args[i])
                if hasattr(leaf, "is_deleted")
            ]
            out = jitted(*args, **kwargs)
            if donated:
                rec.donated_consumed = all(
                    a.is_deleted() for a in donated
                )
            return out

        return _AuditedJit(fn, jitted, first_call)

    return auditing_jit


@contextlib.contextmanager
def audit_jits(
    modules: Optional[Sequence[str]] = AUDITED_MODULES,
    max_const_bytes: int = MAX_CONST_BYTES,
) -> Iterator[JitAuditReport]:
    """Patch `jax.jit` for the duration of the block; every jit created
    inside it whose defining module is in `modules` (None = all) is
    audited on its first call and lands in the yielded report.  Jits
    created inside the block keep their (wrapper) identity afterwards —
    only `jax.jit` is restored — so long-lived program caches (mesh
    staging programs, the fused step cache) stay valid."""
    import jax as real_jax

    report = JitAuditReport(max_const_bytes=max_const_bytes)
    real_jit = real_jax.jit
    real_jax.jit = _make_auditing_jit(
        real_jax, real_jit,
        tuple(modules) if modules is not None else None, report,
    )
    try:
        yield report
    finally:
        real_jax.jit = real_jit


def assert_clean(report: JitAuditReport, expect_records: bool = True) -> None:
    """Raise `JitAuditError` on any violation (or, with
    `expect_records`, on a vacuous audit that saw no jits at all)."""
    problems = report.violations()
    if expect_records and not report.records:
        problems.append(
            "the audit saw no call-time jits — the proxy is not "
            "installed on the modules this path creates programs in"
        )
    if problems:
        raise JitAuditError("; ".join(problems))


# ---------------------------------------------------------------------------
# Steady-state recompile check (reuses the PR-7 compile listener)
# ---------------------------------------------------------------------------


def _compile_totals() -> Tuple[float, float]:
    from ..telemetry.compile import compiles_total, recompiles_total

    def total(metric: Any) -> float:
        return float(sum(
            v for v in metric.samples().values()
            if isinstance(v, (int, float))
        ))

    return total(compiles_total), total(recompiles_total)


@dataclass
class CompileDelta:
    compiles: float = 0.0
    recompiles: float = 0.0
    listener: bool = False


@contextlib.contextmanager
def count_compiles() -> Iterator[CompileDelta]:
    """Measure `compiles_total` / `recompiles_total` growth across the
    block (the jax.monitoring listener installs on entry)."""
    from ..telemetry.compile import install_jax_listener

    delta = CompileDelta(listener=install_jax_listener())
    c0, r0 = _compile_totals()
    try:
        yield delta
    finally:
        c1, r1 = _compile_totals()
        delta.compiles = c1 - c0
        delta.recompiles = r1 - r0


# ---------------------------------------------------------------------------
# The CI sanitizer: drive every audited solver on the CPU mesh
# ---------------------------------------------------------------------------


def _dataset(n: int = 2048, d: int = 16, seed: int = 0):
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y_bin = (X[:, 0] > 0).astype(np.float64)
    y_reg = X @ rng.normal(size=d) + rng.normal(scale=0.1, size=n)
    df_cls = pd.DataFrame({"features": list(X), "label": y_bin})
    df_reg = pd.DataFrame({"features": list(X), "label": y_reg})
    df_feat = pd.DataFrame({"features": list(X)})
    return df_cls, df_reg, df_feat


def run_sanitizer(max_const_bytes: int = MAX_CONST_BYTES) -> int:
    """`python -m spark_rapids_ml_tpu.analysis --jit-audit`: run each
    host-dispatched solver under the audit on the CPU mesh, enforce the
    three invariants plus metric-label cardinality, print the rollup,
    exit nonzero on any violation."""
    import tempfile

    from ..config import reset_config, set_config
    from ..telemetry.registry import check_cardinality

    problems: List[str] = []
    audited = 0

    def run(label: str, steady: bool, fit, expect: bool = True) -> None:
        nonlocal audited
        # short fit: per-fit program builds land here...
        with audit_jits(AUDITED_MODULES, max_const_bytes) as rep:
            with count_compiles() as short:
                fit(4)
            # ...long fit: only ITERATION-driven compiles can differ
            with count_compiles() as long_run:
                fit(12)
        audited += len(rep.records)
        probs = rep.violations()
        if expect and not rep.records:
            probs.append("audit saw no call-time jits (vacuous)")
        if steady and long_run.listener:
            extra = long_run.compiles - short.compiles
            if extra > 0:
                probs.append(
                    f"steady-state recompiles: the 12-iteration fit "
                    f"compiled {extra:g} more program(s) than the "
                    "4-iteration fit — iterations are re-lowering"
                )
        if long_run.recompiles or short.recompiles:
            probs.append(
                "recompiles_total grew during a steady-shape fit"
            )
        status = "FAIL" if probs else "ok"
        print(
            f"jit-audit {label:10s} {status}: {len(rep.records)} jit(s), "
            f"worst consts "
            f"{max([r.const_bytes for r in rep.records], default=0)} B, "
            f"donations "
            f"{sum(1 for r in rep.records if r.donated_consumed)} consumed"
            + (f", compiles short/long {short.compiles:g}/"
               f"{long_run.compiles:g}" if long_run.listener else "")
        )
        problems.extend(f"{label}: {p}" for p in probs)

    df_cls, df_reg, df_feat = _dataset()
    # the fused accumulator steps are lru-cached per shape: clear so
    # they are re-created (and so audited) inside this run regardless
    # of what already executed in the process
    from ..fused import _jitted_steps

    _jitted_steps.cache_clear()
    with tempfile.TemporaryDirectory() as ckpt:
        try:
            from ..classification import LogisticRegression
            from ..clustering import KMeans
            from ..feature import PCA
            from ..regression import LinearRegression

            # host-dispatched L-BFGS (the PR-7 bug's home)
            set_config(dispatch_flops_limit=1e6)
            run(
                "lbfgs", True,
                lambda iters: LogisticRegression(maxIter=iters).fit(df_cls),
            )
            reset_config()

            # stepwise KMeans Lloyd (checkpointing forces the host
            # loop).  Its solver jits are module-level (data-as-argument
            # by construction) and its staging programs were built — and
            # audited — by the first workload, so `expect` is off: the
            # value here is the steady-state compile check
            set_config(checkpoint_dir=ckpt)
            run(
                "kmeans", True,
                lambda iters: KMeans(k=3, seed=7, maxIter=iters, tol=0.0)
                .fit(df_feat),
                expect=False,
            )
            reset_config()

            # fused stage-and-solve PCA, randomized solver
            set_config(fused_stage_solve="on", pca_solver="randomized")
            run(
                "pca_rand", False,
                lambda iters: PCA(k=4).setInputCol("features")
                .setOutputCol("o").fit(df_feat),
            )
            reset_config()

            # fused PCA, full eigensolver
            set_config(fused_stage_solve="on", pca_solver="full")
            run(
                "pca_full", False,
                lambda iters: PCA(k=4).setInputCol("features")
                .setOutputCol("o").fit(df_feat),
            )
            reset_config()

            # FISTA elastic-net LinearRegression over fused accumulators
            set_config(fused_stage_solve="on")
            run(
                "fista", True,
                lambda iters: LinearRegression(
                    regParam=0.1, elasticNetParam=0.5, maxIter=iters
                ).fit(df_reg),
            )
        finally:
            reset_config()

    problems.extend(check_cardinality())
    for p in problems:
        print(f"jit-audit: VIOLATION: {p}")
    print(
        f"jit-audit: {audited} jit(s) audited across 5 solvers, "
        f"{len(problems)} violation(s)"
    )
    return 1 if problems else 0
