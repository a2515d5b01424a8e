#
# The spans inside `fit_kernel` and `stage` (docs/observability.md, "Span
# vocabulary"): each route of a fit records the spans it is documented to
# record, they tile their parent, their counts are the program's counts,
# the jitted programs carry their named scopes under the module names the
# benchmark matches, and a span costs microseconds and opens a profiler
# annotation only where jax is loaded.
#
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.lbfgs as lbfgs_mod
import spark_rapids_ml_tpu.ops.logistic as logistic_mod
from spark_rapids_ml_tpu import tracing
from spark_rapids_ml_tpu.classification import LogisticRegression, RandomForestClassifier
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.config import reset_config, set_config
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.regression import LinearRegression, RandomForestRegressor
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.report import span_tree


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


def _rows(n, d, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    return X, y


def _logistic(**kw):
    return LogisticRegression(
        maxIter=5, regParam=1e-5, standardization=False, tol=1e-30, **kw
    )


# route -> (estimator, rows, the span that holds the route's work, the spans
# the route must record under it)
ROUTES = {
    "logistic_host_dispatch": (
        lambda: _logistic(num_workers=1), (65_536, 64), "fit_kernel",
        {"label_range", "lbfgs_layout", "lbfgs_eval", "lbfgs_eval_dispatch",
         "lbfgs_eval_wait", "lbfgs_host_step", "lbfgs_unpack", "lbfgs_release", "solve_fetch",
         "compile[trace]", "compile[lower]", "compile[backend_compile]"},
    ),
    "logistic_fused": (
        lambda: _logistic(num_workers=2), (65_536, 64), "fit_kernel",
        {"label_range", "lbfgs_fused_dispatch", "solve_fetch"},
    ),
    "ridge": (
        lambda: LinearRegression(regParam=1e-5, num_workers=1),
        (131_072, 64), "fit_kernel",
        {"linreg_gram", "linreg_fetch", "linreg_host_solve", "linreg_solve_assemble",
         "linreg_solve_factor", "linreg_solve_release", "linreg_solve_summary",
         "linreg_residual", "linreg_release", "linreg_solver[cholesky]"},
    ),
    # the resident exact PCA: the covariance's passes, the fetch, the host's
    # eigensolve, and which Gram and which eigensolver ran
    "pca": (
        lambda: PCA(k=3, num_workers=1), (131_072, 64), "fit_kernel",
        {"pca_covariance", "pca_fetch", "pca_eigensolve", "pca_lapack", "pca_release",
         "linreg_gram_kernel[xla]", "pca_eigensolver[host_lapack]"},
    ),
    # ... and at 640 columns with gaps between the eigenvalues, where the block
    # iteration and its float64 polish answer in LAPACK's place
    "pca_subspace": (
        lambda: PCA(k=3, num_workers=1), (8_192, 640), "fit_kernel",
        {"pca_covariance", "pca_fetch", "pca_eigensolve", "pca_subspace_device",
         "pca_polish_sweep", "pca_release", "linreg_gram_kernel[xla]",
         "pca_eigensolver[subspace_polished]"},
    ),
    # the host-dispatched Lloyd, the route of rows a device cannot hold twice
    "kmeans_stepwise": (
        lambda: KMeans(k=16, maxIter=4, tol=1e-20, initMode="random", seed=2,
                       num_workers=1),
        (65_536, 64), "fit_kernel",
        {"kmeans_route[stepwise]", "kmeans_init", "kmeans_lloyd_iter",
         "kmeans_cost", "kmeans_fetch"},
    ),
    # the bins, every dispatched chunk of trees (here one), the fetch
    "forest": (
        lambda: RandomForestClassifier(numTrees=4, maxDepth=8, seed=1, num_workers=1),
        (32_768, 16), "fit_kernel",
        {"label_range", "forest_bin", "forest_grow", "forest_fetch", "forest_assemble"},
    ),
    # ... and a regression forest's: no label maximum to read, the label shift
    # rides `forest_bin`
    "forest_regressor": (
        lambda: RandomForestRegressor(numTrees=4, maxDepth=5, seed=1, num_workers=1),
        (32_768, 16), "fit_kernel",
        {"forest_bin", "forest_grow", "forest_fetch", "forest_assemble"},
    ),
    # 256 MB of rows, far over `_PIPELINED_MIN_BYTES`: the staging engine
    "pipelined_stage": (
        lambda: _logistic(num_workers=1), (1_048_576, 64), "stage",
        {"stage_alloc", "stage_prep", "stage_put", "stage_put_wait", "stage_put_call",
         "stage_put_update", "stage_finish"},
    ),
}


def _wait_or_work():
    """{span: (`wait` | `work`, the spans it may lie beneath)}: the rows of
    docs/observability.md's table of the spans under the leaf spans."""
    docs = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "docs", "observability.md")
    with open(docs) as f:
        rows = re.findall(r"^\| `(\w+)`[^|]*\| (WAIT|WORK) \| ([^|]+) \|", f.read(), re.M)
    return {name: (host.lower(), set(re.findall(r"`(\w+)`", beneath)))
            for name, host, beneath in rows}


WAIT_OR_WORK = _wait_or_work()


def _walk(nodes, parent=None):
    for n in nodes:
        yield n, parent
        yield from _walk(n.get("children", []), n)


def _find(report, name):
    found = [n for n, _ in _walk(report["spans"]) if n["name"] == name]
    assert len(found) == 1, f"{len(found)} spans called {name}"
    return found[0]


def _covered(parent):
    """Share of `parent` that the union of its child spans covers."""
    covered, end = 0.0, parent["t0"]
    for c in sorted(parent.get("children", []), key=lambda c: c["t0"]):
        t1 = c["t0"] + c["seconds"]
        if t1 > end:
            covered += t1 - max(c["t0"], end)
            end = t1
    return covered / parent["seconds"]


def _backend_compiles():
    return sum(REGISTRY.snapshot().get("compiles_total", {}).values())


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_route_records_its_spans(route, monkeypatch):
    build, (n, d), holder, names = ROUTES[route]
    if route == "logistic_host_dispatch":
        # the per-program budget sends a toy autodiff fit down the route that
        # checkpointed fits and the fits the kernel declines take
        set_config(dispatch_flops_limit=1.0)
    if route == "kmeans_stepwise":
        # 16.8 MB of rows, and a device that cannot hold them twice
        set_config(hbm_bytes=30_000_000)
    if route == "pipelined_stage":
        # eight pieces of 32 MB, so that puts wait for older pieces
        set_config(staging_chunk_bytes=32 * 1024 * 1024)
    X, y = _rows(n, d)
    if route == "pca_subspace":
        X *= 0.9 ** np.arange(d, dtype=np.float32)  # eigenvalues 1, 0.81, 0.66, ...

    evaluations = []
    real = lbfgs_mod.lbfgs_minimize_host

    def counting(value_and_grad, *a, **kw):
        def counted(w):
            evaluations[-1] += 1
            return value_and_grad(w)

        evaluations.append(0)
        return real(counted, *a, **kw)

    monkeypatch.setattr(lbfgs_mod, "lbfgs_minimize_host", counting)

    est = build()
    first = est.fit((X, y)).fit_report()
    under = {n["name"] for n, _ in _walk(_find(first, holder).get("children", []))}
    assert names - {n for n in names if n.startswith("compile[")} <= under, under
    if route == "logistic_host_dispatch":
        # this route jits its evaluation anew in every fit
        assert names <= under, under
    if route == "ridge":
        # the host solve builds its system once and factors it once; which
        # factorisation solved it is a fact of the factoring
        solve = _find(first, "linreg_host_solve")
        assert [c["name"] for c in solve["children"]] == [
            "linreg_solve_assemble", "linreg_solve_factor", "linreg_solve_release",
            "linreg_solve_summary"]
        assert [c["name"] for c in solve["children"][1]["children"]] == [
            "linreg_solver[cholesky]"]
        # ... into the kept buffer, the instant inside the assembly saying
        # how it was had (`new` where no earlier solve of this d kept one)
        (how,) = solve["children"][0]["children"]
        assert how["name"] in ("linreg_solve_workspace[new]",
                               "linreg_solve_workspace[reused]"), how
    if route in ("pca", "pca_subspace"):
        # each instant under the span whose work it names; the eigensolve
        # holds the device loop and one span a float64 sweep, as many as the
        # instant's `polish_steps` says, or LAPACK where the shape rules the
        # block iteration out
        inside = [c["name"] for c in _find(first, "pca_covariance")["children"]]
        assert [n for n in inside if not n.startswith("compile[")] == [
            "linreg_gram_kernel[xla]"]
        solve = _find(first, "pca_eigensolve")["children"]
        if route == "pca":
            assert [c["name"] for c in solve] == [
                "pca_eigensolver[host_lapack]", "pca_lapack"]
        else:
            sweeps = int(re.search(r"polish_steps=(\d+)", solve[-1]["detail"]).group(1))
            assert sweeps >= 1 and [c["name"] for c in solve] == [
                "pca_subspace_device", *["pca_polish_sweep"] * sweeps,
                "pca_eigensolver[subspace_polished]"]
    if route == "pipelined_stage":
        # a put is the wait for an older piece (from the third piece on: two
        # may be in flight), the runtime's call and the update's dispatch
        # ... and the drain under `stage_finish` waits for those still in
        # flight.  A writer an array: X's eight pieces, then y's and w's one
        puts, most = 0, 0
        for node in _find(first, "stage")["children"]:
            inside = [c["name"] for c in node.get("children", [])
                      if not c["name"].startswith("compile[")]
            if node["name"] == "stage_alloc":
                puts = 0
            elif node["name"] == "stage_put":
                wait = ["stage_put_wait"] if puts >= 2 else []
                assert inside == [*wait, "stage_put_call", "stage_put_update"], (puts, inside)
                puts += 1
                most = max(most, puts)
            elif node["name"] == "stage_finish":
                assert inside.count("stage_put_wait") == min(puts, 2), (puts, inside)
        assert most == 8

    # the report times its own assembly: a root after the fit's
    assert [n["name"] for n in first["spans"]] == [
        f"fit[{type(est).__name__}]", "fit_report"]

    # a span beneath a leaf span says whether the host waits or works, as
    # the vocabulary's table has it, under the parent the table names
    documented = names & set(WAIT_OR_WORK)
    for node, parent in _walk(first["spans"]):
        if node["name"] in WAIT_OR_WORK:
            host, beneath = WAIT_OR_WORK[node["name"]]
            assert node.get("detail") == host, node
            assert (parent["name"] in beneath) if parent else not beneath, (node, parent)
            documented.discard(node["name"])
        else:
            assert node.get("detail") not in ("wait", "work"), node
    assert not documented, documented

    # a child lies inside its parent in time
    for node, parent in _walk(first["spans"]):
        if parent is not None:
            assert node["t0"] >= parent["t0"] - 2e-3, (node, parent)
            assert (node["t0"] + node["seconds"]
                    <= parent["t0"] + parent["seconds"] + 2e-3), (node, parent)

    # the spans tile their parent: a load spike on a shared CPU only ever
    # lowers the share, so the best of a few fits is the one that counts
    best, reports = 0.0, [first]
    for _ in range(4):
        if best >= 0.9:
            break
        before = _backend_compiles()
        report = est.fit((X, y)).fit_report()
        compiled = _backend_compiles() - before
        reports.append(report)
        best = max(best, _covered(_find(report, holder)))
        # a later fit of the same estimator shows backend compiles exactly
        # where the counter moved
        spans = [n for n, _ in _walk(report["spans"])
                 if n["name"] == "compile[backend_compile]"]
        assert len(spans) == compiled
    assert best >= 0.9, f"{holder} covered to {best:.2f}"

    # the children tile every put (of a millisecond or more: under a put of
    # 4 MB the spans' own microseconds show), on the same reading of a
    # loaded host
    if route == "pipelined_stage":
        tiled = max(
            min(_covered(n) for n, _ in _walk(r["spans"])
                if n["name"] == "stage_put" and n["seconds"] >= 1e-3)
            for r in reports)
        assert tiled >= 0.9, f"a stage_put covered to {tiled:.2f}"

    # a later fit of the same estimator builds its system in the buffer the
    # fit before kept; no PCA route builds in it
    for report in reports:
        workspace = [n for n, _ in _walk(report["spans"])
                     if n["name"].startswith("linreg_solve_workspace[")]
        if route != "ridge":
            assert not workspace, workspace
        elif report is not first:
            solve = _find(report, "linreg_host_solve")
            assert [c["name"] for c in solve["children"]] == [
                "linreg_solve_assemble", "linreg_solve_factor", "linreg_solve_release",
                "linreg_solve_summary"]
            assert [c["name"] for c in solve["children"][0]["children"]] == [
                "linreg_solve_workspace[reused]"]
            assert workspace[0]["detail"] == f"d={d}"

    # one iteration span an iteration, the rest of the vocabulary once a fit
    if route == "kmeans_stepwise":
        for report in reports:
            names = [n["name"] for n, _ in _walk(report["spans"])]
            assert names.count("kmeans_lloyd_iter") == 4
            for once in ("kmeans_init", "kmeans_cost", "kmeans_fetch",
                         "kmeans_route[stepwise]"):
                assert names.count(once) == 1, once

    # the evaluation spans ARE the evaluations
    if route == "logistic_host_dispatch":
        assert len(evaluations) == len(reports)
        for report, made in zip(reports, evaluations):
            evals = [n for n, _ in _walk(report["spans"]) if n["name"] == "lbfgs_eval"]
            steps = [n for n, _ in _walk(report["spans"])
                     if n["name"] == "lbfgs_host_step"]
            assert len(evals) == made >= 6
            assert len(steps) == made + 1  # before, between and after
            # each evaluation is one dispatch and one wait, and nothing else
            # but the first's compiles
            for e in evals:
                assert [c["name"] for c in e["children"]] == [
                    "lbfgs_eval_dispatch", "lbfgs_eval_wait"]
    else:
        assert not any(n["name"] == "lbfgs_eval"
                       for r in reports for n, _ in _walk(r["spans"]))


def test_a_regression_forest_records_one_grow_span_a_chunk_and_its_fact(monkeypatch):
    """`forest_bin` (the label shift inside it), one `forest_grow` a
    dispatched chunk of trees, `forest_fetch`; `fact[forest]` says what the
    shapes decided: the criterion, the panel of features and how many a
    level, the trees to a dispatch."""
    from spark_rapids_ml_tpu.ops import forest as forest_ops

    real = forest_ops.forest_fit
    monkeypatch.setattr(forest_ops, "forest_fit",
                        lambda *a, **kw: real(*a, **dict(kw, chunk_trees=2)))
    # 32 bins: 256 of a panel's 8,192 one-hot columns hold 12 features
    monkeypatch.setattr(forest_ops, "_PANEL_COLUMNS", 256)
    X, y = _rows(8_192, 24)
    y = (X[:, :3].sum(axis=1) + 0.1 * y).astype(np.float32)
    model = RandomForestRegressor(numTrees=6, maxDepth=4, maxBins=32, seed=2,
                                  featureSubsetStrategy="all", num_workers=1).fit((X, y))
    report = model.fit_report()
    names = [n["name"] for n, _ in _walk(_find(report, "fit_kernel").get("children", []))
             if not n["name"].startswith("compile[")]
    assert names == ["forest_bin", "forest_grow", "forest_grow", "forest_grow",
                     "forest_fetch", "forest_assemble", "fact[forest]"], names
    fact = report["forest"]
    assert fact["criterion"] == "variance" and fact["chunk_trees"] == 2 and fact["trees"] == 6
    assert (fact["features_per_node"], fact["feature_panel"], fact["panels_per_level"]) == (24, 8, 3)
    room = forest_ops.rows_room(8_192, True, 1.0)
    assert fact["tree_bytes"] == forest_ops.tree_bytes(room, 24, 4, 32, 3, 24, 8_192)
    # tiles of 256 rows or more at every level: each product keeps features
    # and bins apart, in every panel
    assert fact["split_hist_levels"] == 4


@pytest.mark.parametrize("depth, split", [(4, 4), (8, 5)])
def test_a_one_panel_classifier_counts_the_levels_whose_product_keeps_features_and_bins_apart(
        depth, split):
    """`fact[forest]`'s `split_hist_levels`: the levels of a tree whose
    tiles hold 128 rows or more (`forest.split_hist`).  At 8,192 rows a
    frontier of 8 nodes has tiles of 256; one of 128 nodes tiles of 16,
    and the levels from 32 nodes on merge features and bins."""
    X, y = _rows(8_192, 24)
    model = RandomForestClassifier(numTrees=2, maxDepth=depth, seed=2,
                                   num_workers=1).fit((X, y))
    fact = model.fit_report()["forest"]
    assert (fact["criterion"], fact["panels_per_level"]) == ("gini", 1)
    assert fact["split_hist_levels"] == split


def test_span_tree_keeps_concurrent_threads_apart():
    """A worker that adopted the caller's trace context records at the
    caller's depth while the caller records too: a span hangs under the
    span that holds it in time, on its own thread first, never under a
    neighbour that merely started earlier."""
    E = tracing.TraceEvent

    def ev(name, t0, t1, depth, thread):
        return E(name, t1 - t0, depth, t0=t0, t1=t1, thread_id=thread)

    events = [
        ev("stage", 0.0, 10.0, 0, 1),
        ev("stage_put", 1.0, 4.0, 1, 1),
        ev("stage_prep", 2.0, 6.0, 1, 2),       # the prefetch thread
        ev("compile[lower]", 3.0, 3.5, 2, 1),   # inside the put, not the prep
        ev("stage_put", 6.5, 7.0, 1, 1),
        ev("late", 9.0, 11.0, 1, 1),            # outlives `stage`: no child of it
    ]
    tree = span_tree(events)
    assert [n["name"] for n in tree] == ["stage", "late"]
    stage = tree[0]
    assert [c["name"] for c in stage["children"]] == [
        "stage_put", "stage_prep", "stage_put"]
    assert [c["name"] for c in stage["children"][0]["children"]] == ["compile[lower]"]
    assert "children" not in stage["children"][1]


def _lowered_logistic_vg(monkeypatch):
    """The evaluation program of the host-dispatched route, as that route
    jits it: the function is local to a call, so catch it at `jax.jit`."""
    caught = []

    class Jax:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def jit(fn, *a, **kw):
            jitted = jax.jit(fn, *a, **kw)
            caught.append(jitted)
            return jitted

    monkeypatch.setattr(logistic_mod, "jax", Jax())
    X, y = _rows(256, 8)
    Xd, yd, w = jnp.asarray(X), jnp.asarray(y), jnp.ones(256, jnp.float32)
    logistic_mod.logreg_fit_host_dispatch(
        Xd, w, yd, n_classes=2, l2=1e-5, l1=0.0, max_iter=1, binomial=True)
    (vg_fn,) = caught
    return vg_fn.lower(jnp.zeros(9, jnp.float32), Xd, w, yd)


def _gram_acc(d, lead=()):
    """The split Gram's accumulators (ops/linear.py `linreg_stats_split`)."""
    return tuple(jnp.zeros(lead + shape, jnp.float32)
                 for shape in ((d, d), (d,), (d,), (), (), ()))


def test_programs_keep_their_scopes_and_module_names(monkeypatch):
    from chipbench import manifest as mf

    from spark_rapids_ml_tpu.ops import linear
    from spark_rapids_ml_tpu.ops import pca as pca_ops
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    X, y = _rows(256, 8)
    Xd, yd, w = jnp.asarray(X), jnp.asarray(y), jnp.ones(256, jnp.float32)
    at0 = jnp.asarray(0, jnp.int32)
    lowered = {
        "jit_vg_fn": (_lowered_logistic_vg(monkeypatch), {"lbfgs_eval"}),
        "jit_logreg_fit_binary": (
            logistic_mod.logreg_fit_binary.lower(Xd, w, yd, 1e-5, 0.0, max_iter=2),
            {"lbfgs_eval", "lbfgs_two_loop"}),
        "jit_logreg_fit": (
            logistic_mod.logreg_fit.lower(
                Xd, w, yd.astype(jnp.int32), n_classes=3, l2=1e-5, l1=0.0,
                max_iter=2),
            {"lbfgs_eval", "lbfgs_two_loop"}),
        # every program that holds Gram work: the single matmul's, and the
        # split's row-block program (alone and under a mesh) and its finish
        "jit__linreg_sufficient_stats_xla": (
            linear._linreg_sufficient_stats_xla.lower(Xd, w, yd), {"linreg_gram"}),
        "jit__linreg_sufficient_stats_block": (
            linear._split_block_program(None, 64, 4).lower(
                _gram_acc(8), Xd, w, yd, at0, at0),
            {"linreg_gram"}),
        "jit__linreg_sufficient_stats_finish": (
            linear._linreg_sufficient_stats_finish.lower(_gram_acc(8)), {"linreg_gram"}),
        # PCA's covariance: the pass that makes the shift; its products are
        # the Gram's programs above, without labels and with the shift
        "jit__pca_covariance_shift": (
            pca_ops._pca_covariance_shift.lower(Xd, w), {"pca_covariance"}),
        # ... and the eigensolve's device half, which is no Gram work
        "jit__pca_subspace_iterate": (
            pca_ops._pca_subspace_iterate.lower(jnp.zeros((8, 8)), block=4, steps=2),
            {"pca_subspace"}),
        "jit_linreg_residual_sse": (
            linear.linreg_residual_sse.lower(Xd, w, yd, jnp.zeros(8), 0.0),
            {"linreg_residual"}),
        "jit__dus_rows_done": (
            jax.jit(mesh_mod._dus_rows_done).lower(
                Xd, Xd[:16], jnp.asarray(0, jnp.int32)),
            {"stage_chunk"}),
    }
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    on_a_mesh = linear._split_block_program(mesh, 64, 4).lower(
        _gram_acc(8, lead=(2,)), Xd, w, yd, at0, at0)
    unlabelled = tuple(_gram_acc(8, lead=(2,))[i] for i in (0, 2, 3))
    shifted_on_a_mesh = linear._split_block_program(mesh, 64, 4, False, True).lower(
        unlabelled, Xd, w, None, at0, at0, jnp.zeros(8))
    for module, (low, scopes) in [
        *lowered.items(),
        ("jit__linreg_sufficient_stats_block", (on_a_mesh, {"linreg_gram"})),
        ("jit__linreg_sufficient_stats_block", (shifted_on_a_mesh, {"linreg_gram"})),
    ]:
        # the name XLA gives the program, which the profiler's "XLA Modules"
        # line and chipbench/estimators/*.PROGRAMS go by
        assert re.search(r"module @(\S+)", low.as_text()).group(1) == module
        located = set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True)))
        for scope in scopes:
            # (a program under `shard_map` starts its paths at the scope)
            assert any(re.search(rf"(^|[/(]){scope}[/)]", loc) for loc in located), (
                module, scope)
    # the Gram's roofline divides by the device time of what its pattern
    # matches: Gram work under another name would flatter it
    (gram_pattern,) = mf.adapter("ridge").PROGRAMS["gram"]
    assert sum(gram_pattern in module for module in lowered) == 3
    assert not any(pattern in "jit__pca_subspace_iterate"
                   for pattern in mf.adapter("pca").PROGRAMS["gram"])
    # every pattern the benchmark's adapters match finds its program
    for adapter in ("logreg", "ridge", "pca"):
        for patterns in mf.adapter(adapter).PROGRAMS.values():
            for pattern in patterns:
                assert any(pattern in module for module in lowered), pattern


def test_trace_opens_an_annotation_only_where_jax_is_loaded(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with tracing.trace("with_jax"):
        pass
    assert opened == ["with_jax"]

    monkeypatch.delitem(sys.modules, "jax")
    with tracing.trace("without_jax"):
        pass
    assert opened == ["with_jax"] and "jax" not in sys.modules
    assert tracing.get_trace_events()[-1].name == "without_jax"


def test_a_span_costs_microseconds_without_a_profiler_session():
    """A loose ceiling, not a speed claim: a fit records 50-150 of these.
    The machine is shared (five other test workers load it), so the span is
    read against the same loop with a bare clock read in the span's place,
    taken in turn with it: a host that runs everything five times slower
    moves both.  The fastest of many short batches on each side."""
    def per_pass(body, k=500):
        t0 = time.perf_counter()
        for _ in range(k):
            body()
        return (time.perf_counter() - t0) / k

    def span():
        with tracing.trace("cost"):
            pass

    per_pass(span, 200)
    spans, bare = [], []
    for _ in range(20):
        spans.append(per_pass(span))
        bare.append(per_pass(time.perf_counter))
    # 6.6 us a span on an idle host (PERF.md §6, PR 26) against ~0.05 us a
    # clock read: 20 us, or 400 bare reads where those are slower than that
    assert min(spans) < max(20e-6, 400 * min(bare)), (min(spans), min(bare))
    assert tracing.get_trace_events()[-1].thread_id == threading.get_ident()
