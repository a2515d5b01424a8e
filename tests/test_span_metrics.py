#
# The per-layer metrics that read the spans inside `fit_kernel` and `stage`
# (chipbench/layer_metrics/): each reader on a hand-made window of two fits
# with known spans and a table of device programs, its value by hand, and
# None where the program records no such span (the parent commit does not).
#
import types

import pytest

from chipbench import manifest as mf

MANIFEST = mf.load_manifest()
READERS = ["stage_prep_s", "stage_put_s", "lbfgs_eval_gap_ms", "lbfgs_host_step_s",
           "lbfgs_evals_per_fit", "compile_s", "linreg_fetch_s", "linreg_host_solve_s"]


def parents_of(spans):
    """Each span's parent as `run.py` flattens the report's tree: the
    innermost earlier span that holds it in time (the hand-made fits below
    list a parent before its children), -1 for none."""
    out = []
    for i, (_, t0, t1) in enumerate(spans):
        holders = [j for j in range(i) if spans[j][1] <= t0 and t1 <= spans[j][2]
                   and spans[j][2] > spans[j][1]]
        out.append(max(holders, key=lambda j: (spans[j][1], -spans[j][2]), default=-1))
    return out


def ctx_of(fits, modules=None, compiles=0.0, programs=None):
    adapter = types.SimpleNamespace(
        PROGRAMS={"lbfgs_eval": ("vg_fn", "logreg_fit")} if programs is None else programs)
    trace = None if modules is None else {"modules": modules}
    return {"adapter": adapter,
            "fits": [{"spans": s, "span_parents": parents_of(s)} for s in fits],
            "trace": trace, "compiles_in_window": compiles}


# two fits of the host-dispatched logistic route from host rows.  Fit 1 re-jits
# (its first evaluation holds the compile), fit 2 compiles nothing.
FIT_1 = [
    ("fit[LogisticRegression]", 0.0, 10.0),
    ("stage", 0.0, 4.0),
    ("stage_prep", 0.1, 1.1), ("stage_put", 0.6, 2.1),      # 1.0 and 1.5
    ("stage_put_call", 0.6, 1.9), ("stage_put_update", 1.9, 2.0),   # none to wait for yet
    ("stage_prep", 1.2, 2.2), ("stage_put", 2.2, 3.7),      # 1.0 and 1.5
    ("stage_put_wait", 2.2, 2.9), ("stage_put_call", 2.9, 3.5), ("stage_put_update", 3.5, 3.6),
    ("stage_finish", 3.7, 3.9), ("stage_put_wait", 3.7, 3.8),       # the drain
    ("fit_kernel", 4.0, 10.0),
    ("lbfgs_host_step", 4.0, 4.1),
    ("lbfgs_eval", 4.1, 6.1),                                # the re-jit: 2.0
    ("lbfgs_eval_dispatch", 4.1, 5.7),
    ("compile[trace]", 4.2, 4.5), ("compile[lower]", 4.5, 4.6),
    ("compile[backend_compile]", 4.6, 5.6), ("compile[cache_read]", 4.7, 5.5),
    ("lbfgs_eval_wait", 5.7, 6.1),
    ("lbfgs_host_step", 6.1, 6.3),
    ("lbfgs_eval", 6.3, 6.8),                                # 0.5
    ("lbfgs_eval_dispatch", 6.3, 6.4), ("lbfgs_eval_wait", 6.4, 6.8),
    ("lbfgs_host_step", 6.8, 7.1),
    ("lbfgs_eval", 7.1, 7.8),                                # 0.7
    ("lbfgs_eval_dispatch", 7.1, 7.3), ("lbfgs_eval_wait", 7.3, 7.8),
    ("lbfgs_host_step", 7.8, 7.9),
    ("solve_fetch", 7.9, 8.0),
]
FIT_2 = [
    ("fit[LogisticRegression]", 20.0, 26.0),
    ("stage", 20.0, 23.0),
    ("stage_prep", 20.0, 20.5), ("stage_put", 20.5, 22.5),  # 0.5 and 2.0
    ("stage_put_wait", 20.5, 20.9), ("stage_put_call", 20.9, 22.3),
    ("stage_put_update", 22.3, 22.4),
    ("fit_kernel", 23.0, 26.0),
    ("lbfgs_host_step", 23.0, 23.2),
    ("lbfgs_eval", 23.2, 24.2),                              # first: left out
    ("lbfgs_eval_dispatch", 23.2, 23.9), ("lbfgs_eval_wait", 23.9, 24.2),
    ("lbfgs_host_step", 24.2, 24.3),
    ("lbfgs_eval", 24.3, 24.9),                              # 0.6
    ("lbfgs_eval_dispatch", 24.3, 24.4), ("lbfgs_eval_wait", 24.4, 24.9),
    ("lbfgs_host_step", 24.9, 25.0),
    ("lbfgs_eval", 25.0, 25.6),                              # 0.6
    ("lbfgs_eval_dispatch", 25.0, 25.3), ("lbfgs_eval_wait", 25.3, 25.6),
]
RIDGE = [
    ("fit_kernel", 0.0, 2.0), ("linreg_gram", 0.0, 0.6), ("linreg_fetch", 0.6, 0.7),
    ("linreg_host_solve", 0.7, 1.9),
    ("linreg_solve_assemble", 0.75, 1.55), ("linreg_solve_factor", 1.55, 1.85),
    ("linreg_solver[cholesky]", 1.7, 1.7),
    ("linreg_residual", 1.9, 2.0),
]
# device seconds and runs by program, as trace_reduce.reduce gives them
MODULES = {"jit_vg_fn": (2.4, 6), "jit__dus_rows_done": (0.3, 3)}


def read(name, ctx):
    return mf.reader(name)(ctx)


# PR 29's, appended after them: the readers of the KMeans Lloyd spans
KMEANS_READERS = ["lloyd_step_roofline", "lloyd_iter_gap_ms", "lloyd_iters_per_fit",
                  "kmeans_init_s"]


# PR 33's, appended after them: the readers of the forest's spans, fact and programs
FOREST_READERS = ["forest_bin_s", "forest_grow_s", "forest_fetch_s", "forest_nodes_per_fit",
                  "forest_level_roofline", "forest_bin_roofline"]


# PR 35's, appended after them: the readers of the resident PCA's spans
PCA_READERS = ["pca_covariance_s", "pca_eigensolve_s", "pca_fetch_s"]


# PR 37's, appended after them: the readers of the wait-or-work spans beneath
# four leaf spans, then the two that read spans every program has (a span's
# self time, one fit among the window's)
CHILD_READERS = ["stage_put_wait_s", "stage_put_call_s", "stage_put_longest_ms",
                 "linreg_solve_assemble_s", "linreg_solve_factor_s", "pca_subspace_device_s",
                 "pca_polish_sweeps_per_fit", "lbfgs_eval_dispatch_ms"]
WHOLE_FIT_READERS = ["fit_kernel_self_ms", "fit_longest_x"]


# PR 39's, appended after them: the reader of the forest fact's trees to a dispatch
DISPATCH_READERS = ["forest_trees_per_dispatch"]


def test_the_manifest_lists_the_readers_last_and_finds_them():
    # each PR appends: PR 26's eight readers in their order, PR 29's four,
    # PR 33's six, PR 35's three, PR 37's ten, PR 39's one
    names = [m["name"] for m in MANIFEST["per_layer"]]
    appended = (READERS + KMEANS_READERS + FOREST_READERS + PCA_READERS
                + CHILD_READERS + WHOLE_FIT_READERS + DISPATCH_READERS)
    assert names[-len(appended):] == appended
    assert mf.problems(MANIFEST) == []
    for m in MANIFEST["per_layer"][-len(appended):]:
        assert m["moves"] == "fit_s" and m["workloads"]
        # a share of a roofline and the trees a dispatch holds are the better the higher
        rises = m["name"].endswith("_roofline") or m["name"] in DISPATCH_READERS
        assert m["better"] == ("higher" if rises else "lower")
    every_cell = [w["name"] for w in MANIFEST["workloads"]]
    whole_fit = [m for m in MANIFEST["per_layer"] if m["name"] in WHOLE_FIT_READERS]
    assert [m["name"] for m in whole_fit] == WHOLE_FIT_READERS
    for m in whole_fit:
        assert m["workloads"] == every_cell and m["source"] == "program_span"
    forest_cells = ["rfc_fit_cached", "rfr_fit_cached"]
    for m in MANIFEST["per_layer"]:
        if m["name"] in FOREST_READERS + DISPATCH_READERS:
            assert m["workloads"] == forest_cells, m["name"]
    assert MANIFEST["per_layer"][-1]["source"] == "program_counter"


@pytest.mark.parametrize("name, by_hand", [
    ("stage_prep_s", (2.0 + 0.5) / 2),
    ("stage_put_s", (3.0 + 2.0) / 2),
    ("lbfgs_host_step_s", (0.7 + 0.4) / 2),
    ("lbfgs_evals_per_fit", 3.0),
    # the later evaluations: 0.5, 0.7, 0.6, 0.6 -> 0.6 s; the program: 2.4 s / 6
    ("lbfgs_eval_gap_ms", 1e3 * (0.6 - 0.4)),
    # fit 1: trace, lower and backend_compile end to end, the cache read
    # inside the last counted once; fit 2 compiled nothing
    ("compile_s", (1.4 + 0.0) / 2),
    # fit 1 waits 0.7 under a put and 0.1 in the drain, fit 2 0.4
    ("stage_put_wait_s", (0.8 + 0.4) / 2),
    ("stage_put_call_s", (1.3 + 0.6 + 1.4) / 2),
    ("stage_put_longest_ms", 2000.0),
    # the later dispatches: 0.1, 0.2, 0.1, 0.3 s; each fit's first holds the re-jit
    ("lbfgs_eval_dispatch_ms", 1e3 * 0.7 / 4),
    # the kernels' 6.0 and 3.0 s less what their children cover, 4.0 and 2.6
    ("fit_kernel_self_ms", 1e3 * (2.0 + 0.4) / 2),
    # fits of 10 and 6 s: the longest over their median
    ("fit_longest_x", 10.0 / 8.0),
])
def test_logistic_readers_by_hand(name, by_hand):
    ctx = ctx_of([FIT_1, FIT_2], MODULES, compiles=1.0)
    assert read(name, ctx) == pytest.approx(by_hand, abs=1e-12)


@pytest.mark.parametrize("name, by_hand", [
    ("linreg_fetch_s", 0.1), ("linreg_host_solve_s", 1.2), ("compile_s", 0.0),
    ("linreg_solve_assemble_s", 0.8), ("linreg_solve_factor_s", 0.3),
    # 2.0 s less gram, fetch, solve and residual end to end: nothing left
    ("fit_kernel_self_ms", 0.0)])
def test_ridge_readers_by_hand(name, by_hand):
    assert read(name, ctx_of([RIDGE, RIDGE], {})) == pytest.approx(by_hand, abs=1e-12)


@pytest.mark.parametrize("name", READERS + CHILD_READERS)
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent commit: the spans the benchmark already read, none of the
    new ones, and a counter that says the window compiled."""
    old = [("fit[LogisticRegression]", 0.0, 5.0), ("stage", 0.0, 4.0),
           ("fit_kernel", 4.0, 5.0), ("lbfgs_route[host_dispatch]", 4.0, 4.0)]
    assert read(name, ctx_of([old, old], MODULES, compiles=2.0)) is None


# two fits of the host-dispatched Lloyd: an init, three iterations, the cost
# pass and the fetch each; the second fit's iterations run a little longer
KMEANS_1 = [
    ("fit_kernel", 0.0, 5.0), ("kmeans_route[stepwise]", 0.0, 0.0),
    ("kmeans_init", 0.0, 0.2),
    ("kmeans_lloyd_iter", 0.2, 1.2), ("kmeans_lloyd_iter", 1.2, 2.2),
    ("kmeans_lloyd_iter", 2.2, 3.2),
    ("kmeans_cost", 3.2, 3.8), ("kmeans_fetch", 3.8, 3.9),
]
KMEANS_2 = [
    ("fit_kernel", 10.0, 15.5),
    ("kmeans_init", 10.0, 10.4),
    ("kmeans_lloyd_iter", 10.4, 11.6), ("kmeans_lloyd_iter", 11.6, 12.8),
    ("kmeans_lloyd_iter", 12.8, 14.0),
    ("kmeans_cost", 14.0, 14.6), ("kmeans_fetch", 14.6, 14.7),
]
KMEANS_PROGRAMS = mf.adapter("kmeans").PROGRAMS
# 6 iterations of 2 blocks and an update, 2 cost passes of 2 blocks; the init's
# programs are no part of a step
KMEANS_MODULES = {"jit__lloyd_block_step": (5.4, 12), "jit__lloyd_center_update": (0.06, 6),
                  "jit__lloyd_block_cost": (1.0, 4), "jit__slice_rows": (0.2, 2),
                  "jit_random_init_rows": (0.1, 2)}


def kmeans_ctx(fits, modules=KMEANS_MODULES, programs=KMEANS_PROGRAMS):
    from chipbench import roofline

    ctx = ctx_of(fits, modules, programs=programs)
    ctx.update(
        work=mf.adapter("kmeans").work(1_000_000, 3_000, 1, {"k": 1000, "maxIter": 3}),
        reference={"n_iter": 3}, traced_fits=len(fits) if modules is not None else 0,
        peaks=roofline.peaks_for("TPU v5 lite"))
    return ctx


@pytest.mark.parametrize("name, by_hand", [
    ("kmeans_init_s", (0.2 + 0.4) / 2),
    ("lloyd_iters_per_fit", 3.0),
    # six spans of 6.6 s in all, less 5.46 s of the device in them, over six
    ("lloyd_iter_gap_ms", 1e3 * (6.6 - 5.46) / 6),
    # three steps of 6.003e12 FLOP and an assignment of 6e12 at 197e12 a second,
    # over (5.4 + 0.06 + 1.0) / 2 device seconds a fit
    ("lloyd_step_roofline", 100 * ((3 * 6.003e12 + 6e12) / 197e12) / 3.23),
])
def test_kmeans_readers_by_hand(name, by_hand):
    assert read(name, kmeans_ctx([KMEANS_1, KMEANS_2])) == pytest.approx(by_hand, rel=1e-12)


@pytest.mark.parametrize("name", KMEANS_READERS)
def test_kmeans_readers_read_nothing_where_there_is_nothing(name):
    """Another family's fits, a program without the spans or the programs (the
    parent), an untraced run: None, never a raise."""
    assert read(name, kmeans_ctx([FIT_1, FIT_2], MODULES, programs={"lbfgs_eval": ("vg_fn",)})) is None
    bare = [("fit_kernel", 0.0, 5.0)]
    assert read(name, kmeans_ctx([bare, bare], {"jit_kmeans_init": (1.0, 2)})) is None
    if name in ("lloyd_step_roofline", "lloyd_iter_gap_ms"):
        assert read(name, kmeans_ctx([KMEANS_1, KMEANS_2], None)) is None  # untraced


def test_compile_s_is_zero_where_nothing_compiled_and_none_without_fits():
    assert read("compile_s", ctx_of([RIDGE])) == 0.0
    assert read("compile_s", ctx_of([])) is None


def test_eval_gap_needs_the_device_trace_and_a_later_evaluation():
    assert read("lbfgs_eval_gap_ms", ctx_of([FIT_1, FIT_2])) is None          # untraced
    assert read("lbfgs_eval_gap_ms", ctx_of([FIT_1], {"jit_other": (1.0, 2)})) is None
    only_first = [s for s in FIT_2 if s[0] != "lbfgs_eval"] + [("lbfgs_eval", 23.2, 24.2)]
    assert read("lbfgs_eval_gap_ms", ctx_of([only_first], MODULES)) is None
    assert read("lbfgs_eval_gap_ms", ctx_of([FIT_1], MODULES, programs={})) is None


def test_evaluations_that_differ_between_fits_show():
    uneven = FIT_2 + [("lbfgs_eval", 25.7, 25.9)]
    assert read("lbfgs_evals_per_fit", ctx_of([FIT_1, uneven])) == 3.5


def test_compile_s_counts_an_overlap_once():
    nested = [("compile[trace]", 0.0, 2.0), ("compile[lower]", 1.0, 3.0),
              ("compile[backend_compile]", 5.0, 6.0), ("compile[cache_read]", 5.2, 5.3),
              ("lbfgs_eval", 0.0, 9.0)]
    assert read("compile_s", ctx_of([nested], compiles=1.0)) == pytest.approx(4.0)


# two fits of a forest of two dispatched chunks of trees; the second fit's
# chunks run a little longer
FOREST_1 = [
    ("fit_kernel", 0.0, 5.0), ("forest_bin", 0.0, 0.4),
    ("forest_grow", 0.4, 2.4), ("forest_grow", 2.4, 4.4), ("forest_fetch", 4.4, 4.5),
]
FOREST_2 = [
    ("fit_kernel", 10.0, 15.6), ("forest_bin", 10.0, 10.6),
    ("forest_grow", 10.6, 12.8), ("forest_grow", 12.8, 15.0), ("forest_fetch", 15.0, 15.3),
]
FOREST_PARAMS = {"numTrees": 4, "maxDepth": 13, "featureSubsetStrategy": "auto"}
# four chunk programs and the bin phase's 2 x 16 block programs and one sort
FOREST_MODULES = {"jit__forest_fit_chunk": (8.0, 4), "jit__forest_sample_block": (0.1, 32),
                  "jit__forest_bin_block": (0.5, 32), "jit__forest_edges": (0.2, 2),
                  "jit__label_check_kernel": (0.3, 2)}


def forest_ctx(fits, modules=FOREST_MODULES, facts=(1000, 1200)):
    from chipbench import roofline

    rfc = mf.adapter("rfc")
    ctx = ctx_of(fits, modules, programs=rfc.PROGRAMS)
    for f, n in zip(ctx["fits"], facts):
        f["answer"] = {"fact": None if n is None else {"internal_nodes": n}}
    ctx.update(work=rfc.work(500_000, 3_000, 1, FOREST_PARAMS), reference={},
               traced_fits=len(fits) if modules is not None else 0,
               peaks=roofline.peaks_for("TPU v5 lite"))
    return ctx


@pytest.mark.parametrize("name, by_hand", [
    ("forest_bin_s", (0.4 + 0.6) / 2),
    ("forest_grow_s", (4.0 + 4.4) / 2),
    ("forest_fetch_s", (0.1 + 0.3) / 2),
    ("forest_nodes_per_fit", 1100.0),
    # 4 trees x 13 levels of 500,000 x 66 bytes at 819e9 a second, over 4 device seconds a fit
    ("forest_level_roofline", 100 * (52 * 33e6 / 819e9) / 4.0),
    # 7.5e9 bytes at 819e9 a second over (0.1 + 0.5 + 0.2) / 2 device seconds a fit
    ("forest_bin_roofline", 100 * (7.5e9 / 819e9) / 0.4),
])
def test_forest_readers_by_hand(name, by_hand):
    assert read(name, forest_ctx([FOREST_1, FOREST_2])) == pytest.approx(by_hand, rel=1e-12)


def test_forest_trees_per_dispatch_reads_the_fact():
    ctx = forest_ctx([FOREST_1, FOREST_2])
    for f, size in zip(ctx["fits"], (5, 3)):
        f["answer"]["fact"]["chunk_trees"] = size
    assert read("forest_trees_per_dispatch", ctx) == 4.0
    # a program whose fact has no such field (the parent's has it; one before
    # PR 33 has no fact), another family's fits: None, never a raise
    assert read("forest_trees_per_dispatch", forest_ctx([FOREST_1, FOREST_2])) is None
    assert read("forest_trees_per_dispatch", forest_ctx([FOREST_1], facts=(None,))) is None
    assert read("forest_trees_per_dispatch", ctx_of([FIT_1, FIT_2], MODULES)) is None


def test_the_regressors_adapter_feeds_the_forest_readers():
    """The six forest readers take an adapter's `work` and `PROGRAMS` as
    they are: with rfr's, a level is 500,000 x 1,012 bytes, a fit 15 x 6 of
    them, and the label shift's program counts with the bins'."""
    from chipbench import roofline

    rfr = mf.adapter("rfr")
    modules = dict(FOREST_MODULES, jit__forest_label_shift=(0.2, 2))
    ctx = ctx_of([FOREST_1, FOREST_2], modules, programs=rfr.PROGRAMS)
    for f in ctx["fits"]:
        f["answer"] = {"fact": {"internal_nodes": 945, "chunk_trees": 5}}
    ctx.update(work=rfr.work(500_000, 3_000, 1, {"numTrees": 15, "maxDepth": 6}),
               reference={}, traced_fits=2, peaks=roofline.peaks_for("TPU v5 lite"))
    assert read("forest_level_roofline", ctx) == pytest.approx(
        100 * (90 * 506e6 / 819e9) / 4.0, rel=1e-12)
    assert read("forest_bin_roofline", ctx) == pytest.approx(
        100 * (7.5e9 / 819e9) / 0.5, rel=1e-12)  # (0.1 + 0.5 + 0.2 + 0.2) / 2 device seconds
    assert read("forest_nodes_per_fit", ctx) == 945.0
    assert read("forest_trees_per_dispatch", ctx) == 5.0


@pytest.mark.parametrize("name", FOREST_READERS)
def test_forest_readers_read_nothing_where_there_is_nothing(name):
    """The parent (no span, no fact, no such program), another family's fits,
    an untraced run: None, never a raise."""
    bare = [("fit_kernel", 0.0, 5.0)]
    assert read(name, forest_ctx([bare, bare], {"jit__forest_prep": (1.0, 2)}, (None, None))) is None
    if name.endswith("_roofline"):
        assert read(name, forest_ctx([FOREST_1, FOREST_2], None)) is None  # untraced
    else:
        assert read(name, ctx_of([FIT_1, FIT_2], MODULES)) is None


# two fits of the resident exact PCA: the covariance's passes, the fetch, the
# host's eigensolve; the second fit's eigensolve runs a little longer
PCA_1 = [
    ("fit_kernel", 0.0, 2.0), ("pca_covariance", 0.0, 0.4),
    ("linreg_gram_kernel[symmetric_split]", 0.0, 0.0), ("pca_fetch", 0.4, 0.5),
    ("pca_eigensolve", 0.5, 1.9), ("pca_eigensolver[host_lapack]", 0.5, 0.5),
]
PCA_2 = [
    ("fit_kernel", 10.0, 12.2), ("pca_covariance", 10.0, 10.4), ("pca_fetch", 10.4, 10.6),
    ("pca_eigensolve", 10.6, 12.2),
]
# two fits whose eigensolve the block iteration answers, in three sweeps and
# in four; a third where it gives up after two and LAPACK answers
PCA_SUBSPACE = [
    [("fit_kernel", t, t + 2.0), ("pca_eigensolve", t + 0.5, t + 1.9),
     ("pca_subspace_device", t + 0.5, t + 0.5 + device),
     *[("pca_polish_sweep", t + 0.8 + 0.2 * i, t + 1.0 + 0.2 * i) for i in range(sweeps)],
     *([("pca_lapack", t + 1.3, t + 1.9)] if lapack else [])]
    for t, device, sweeps, lapack in ((0.0, 0.1, 3, False), (10.0, 0.2, 4, False),
                                      (20.0, 0.3, 2, True))]
# the shift pass, 16 row-block programs and a finish, a fit
PCA_MODULES = {"jit__pca_covariance_shift": (0.04, 2), "jit__label_check_kernel": (0.3, 2),
               "jit__linreg_sufficient_stats_block": (0.7, 32),
               "jit__linreg_sufficient_stats_finish": (0.01, 2)}


def pca_ctx(fits, modules=PCA_MODULES):
    from chipbench import roofline

    pca = mf.adapter("pca")
    ctx = ctx_of(fits, modules, programs=pca.PROGRAMS)
    ctx.update(work=pca.work(1_000_000, 3_000, 1, {"k": 3}), reference={},
               traced_fits=len(fits) if modules is not None else 0,
               peaks=roofline.peaks_for("TPU v5 lite"))
    return ctx


@pytest.mark.parametrize("name, by_hand", [
    ("pca_covariance_s", 0.4),
    ("pca_fetch_s", (0.1 + 0.2) / 2),
    ("pca_eigensolve_s", (1.4 + 1.6) / 2),
    # 1.8e13 FLOP at 197e12 a second over (0.04 + 0.7 + 0.01) / 2 device seconds
    # a fit: the shift pass counts against the covariance, the label check does not
    ("gram_roofline", 100 * (1.8e13 / 197e12) / 0.375),
])
def test_pca_readers_by_hand(name, by_hand):
    assert read(name, pca_ctx([PCA_1, PCA_2])) == pytest.approx(by_hand, rel=1e-12)


@pytest.mark.parametrize("name, by_hand", [
    ("pca_subspace_device_s", (0.1 + 0.2 + 0.3) / 3),
    ("pca_polish_sweeps_per_fit", (3 + 4 + 2) / 3),
    # the kernel's 2.0 s less the eigensolve's 1.4, whatever lies inside that
    ("fit_kernel_self_ms", 600.0),
])
def test_pca_subspace_readers_by_hand(name, by_hand):
    assert read(name, pca_ctx(PCA_SUBSPACE)) == pytest.approx(by_hand, rel=1e-12)
    if name != "fit_kernel_self_ms":
        assert read(name, pca_ctx([PCA_1, PCA_2])) is None  # LAPACK by shape: no try


@pytest.mark.parametrize("name", PCA_READERS)
def test_pca_readers_read_nothing_where_there_is_nothing(name):
    """The parent (no such span): None, never a raise."""
    bare = [("fit_kernel", 0.0, 5.0)]
    assert read(name, pca_ctx([bare, bare])) is None
    assert read(name, ctx_of([RIDGE, RIDGE], {})) is None


def test_self_time_counts_an_overlap_of_children_once():
    """Children on two threads overlap under one parent (a prefetch thread's
    spans beside the caller's); a grandchild is its parent's to count."""
    fit = [
        ("fit_kernel", 0.0, 10.0),
        ("a", 1.0, 4.0), ("inside_a", 1.5, 2.5),
        ("b", 2.0, 6.0),                      # another thread: overlaps `a`
        ("instant", 6.5, 6.5),
        ("c", 7.0, 8.0),
    ]
    ctx = ctx_of([fit])
    assert ctx["fits"][0]["span_parents"] == [-1, 0, 1, 0, 0, 0]
    # 10 s less the union (1, 6) and (7, 8)
    assert read("fit_kernel_self_ms", ctx) == pytest.approx(4000.0)
    # a kernel with no child is all self time; one the children fill has none
    assert read("fit_kernel_self_ms", ctx_of([[("fit_kernel", 0.0, 1.5)]])) == pytest.approx(1500.0)
    full = [("fit_kernel", 0.0, 1.0), ("a", 0.0, 0.5), ("b", 0.5, 1.0)]
    assert read("fit_kernel_self_ms", ctx_of([full])) == pytest.approx(0.0)


def test_one_long_fit_among_many_shows():
    fits = [[("fit[LogisticRegression]", 10.0 * i, 10.0 * i + wall), ("stage", 10.0 * i, 10.0 * i + 1)]
            for i, wall in enumerate((2.1, 2.2, 2.1, 5.58, 2.2, 2.15, 2.1))]
    assert read("fit_longest_x", ctx_of(fits)) == pytest.approx(5.58 / 2.15)
    even = [[("fit[PCA]", float(i), i + 0.5)] for i in range(5)]
    assert read("fit_longest_x", ctx_of(even)) == pytest.approx(1.0)


@pytest.mark.parametrize("name", WHOLE_FIT_READERS)
def test_whole_fit_readers_read_nothing_where_there_is_nothing(name):
    """No window, fits that recorded no such span, a harness that kept no
    record of parents: None, never a raise."""
    assert read(name, ctx_of([])) is None
    assert read(name, ctx_of([[("stage", 0.0, 1.0)], [("stage", 2.0, 3.0)]])) is None
    if name == "fit_kernel_self_ms":
        assert read(name, {"fits": [{"spans": [("fit_kernel", 0.0, 1.0)]}]}) is None
