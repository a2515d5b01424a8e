#
# graft-lint self-tests: every shipped rule has a seeded-violation
# fixture proving it FIRES (and the CLI exits nonzero on it), the real
# tree stays at zero findings (the merge-gate acceptance), and the
# jit-audit sanitizer's three invariants each trip on a seeded
# violation.  Fixture trees mirror the registry anchor paths
# (spark_rapids_ml_tpu/config.py etc.) under tmp_path so the rules
# cross-check exactly the way they do on the repo.
#
from __future__ import annotations

import json
import warnings

import pytest

from spark_rapids_ml_tpu.analysis import Project, run_analysis
from spark_rapids_ml_tpu.analysis.__main__ import main as cli_main
from spark_rapids_ml_tpu.analysis.rules_builtin import RULES as BUILTIN_RULES
from spark_rapids_ml_tpu.analysis.rules_concurrency import (
    NamedLockRule,
    SpanPairingRule,
    ThreadLockRule,
)
from spark_rapids_ml_tpu.analysis.rules_docs import ModuleRefRule
from spark_rapids_ml_tpu.analysis.rules_registry import (
    ConfKeyRule,
    FaultSiteRule,
    MetricNameRule,
)

# ---------------------------------------------------------------------------
# fixture scaffolding: a mini-repo with the registry anchors in place
# ---------------------------------------------------------------------------

CONFIG_PY = """
_DEFAULTS = {
    "alpha": True,
    "beta_bytes": 4 * 1024 * 1024,
    "gamma": "on",
}
"""

FAULTS_PY = """
KNOWN_SITES = frozenset({"site_a"})
FAULT_KINDS = ("oom", "timeout")
"""

REGISTRY_PY = """
METRIC_CATALOG = {
    "hits_total": {"kind": "counter", "labels": ("site",), "cardinality": 4},
    "depth": {"kind": "gauge", "labels": (), "cardinality": 1},
    "legacy": {"kind": "view", "labels": ("key",), "cardinality": 8},
}
def counter(name, help=""):
    pass
def gauge(name, help=""):
    pass
def histogram(name, help="", buckets=None):
    pass
def dict_view(name, help="", initial=None):
    pass
"""

CONF_DOC = """# conf
| Key | Default | Meaning |
|---|---|---|
| `alpha` | `True` | a |
| `beta_bytes` | `4 MiB` | b |
| `gamma` | `"on"` | c |
"""

RESIL_DOC = "sites: `site_a`\n"

# keeps the base fixture tree CLEAN under every rule: the registered
# site is instrumented, every cataloged metric is registered
BASE_OK_PY = """
from .resilience.faults import maybe_inject
from .telemetry.registry import counter, dict_view, gauge

HITS = counter("hits_total", "help")
DEPTH = gauge("depth")
LEGACY = dict_view("legacy")


def dispatch():
    maybe_inject("site_a")
"""


def make_tree(tmp_path, files):
    base = {
        "spark_rapids_ml_tpu/config.py": CONFIG_PY,
        "spark_rapids_ml_tpu/resilience/faults.py": FAULTS_PY,
        "spark_rapids_ml_tpu/telemetry/registry.py": REGISTRY_PY,
        "spark_rapids_ml_tpu/tracing.py": "def trace(n):\n    pass\n",
        "spark_rapids_ml_tpu/base_ok.py": BASE_OK_PY,
        "docs/configuration.md": CONF_DOC,
        "docs/resilience.md": RESIL_DOC,
    }
    base.update(files)
    for rel, text in base.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return Project(root=tmp_path)


def messages(findings, rule=None):
    return [f.message for f in findings if rule is None or f.rule == rule]


# ---------------------------------------------------------------------------
# the acceptance bar: HEAD is clean, and stays clean
# ---------------------------------------------------------------------------


def test_repo_tree_is_clean():
    findings = run_analysis()
    assert not findings, "\n".join(f.render() for f in findings)


def test_cli_clean_tree_exits_zero(capsys):
    assert cli_main([]) == 0
    assert "0 problem(s)" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# builtin rules (the ci/lint.py originals)
# ---------------------------------------------------------------------------


def test_builtin_rules_fire(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/bad.py": (
            "import os\n"
            "def f(x=[]):\n"
            "    try:\n"
            "        return f'no placeholder'\n"
            "    except:\n"
            "        pass\n"
        ),
    })
    findings = run_analysis(project, rules=BUILTIN_RULES)
    rules = {f.rule for f in findings}
    assert rules == {
        "unused-import", "mutable-default", "fstring-placeholder",
        "bare-except",
    }


# ---------------------------------------------------------------------------
# conf-key
# ---------------------------------------------------------------------------


def test_conf_key_unknown_literals(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .config import get_config, set_config\n"
            "a = get_config('alpha')\n"
            "b = get_config('vanished')\n"
            "c = get_config('vanished', 3)\n"  # explicit default: allowed
            "set_config(gamma='off', vanished=2)\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[ConfKeyRule()]))
    assert len(msgs) == 2 and all("vanished" in m for m in msgs)


def test_conf_key_env_var_reference(tmp_path):
    # the env prefix is split so the analyzer never matches these
    # fixture literals in THIS file's own source (zero suppressions)
    prefix = "SPARK_RAPIDS" + "_ML_TPU_"
    project = make_tree(tmp_path, {
        "tests/test_x.py": (
            "import os\n"
            f"os.environ['{prefix}ALPHA'] = '1'\n"
            f"os.environ['{prefix}RETIRED_KNOB'] = '1'\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[ConfKeyRule()]))
    assert len(msgs) == 1 and "RETIRED_KNOB" in msgs[0]


def test_conf_key_docs_drift(tmp_path):
    bad_doc = CONF_DOC.replace("| `gamma` | `\"on\"` | c |\n", "")
    bad_doc = bad_doc.replace("`4 MiB`", "`8 MiB`")
    project = make_tree(tmp_path, {"docs/configuration.md": bad_doc})
    msgs = messages(run_analysis(project, rules=[ConfKeyRule()]))
    assert any("gamma" in m and "no docs" in m for m in msgs)
    assert any("beta_bytes" in m and "!=" in m for m in msgs)


def test_confdocs_generate_repairs(tmp_path):
    from spark_rapids_ml_tpu.analysis import confdocs

    bad_doc = CONF_DOC.replace("| `gamma` | `\"on\"` | c |\n", "")
    bad_doc = bad_doc.replace("`4 MiB`", "`8 MiB`")
    project = make_tree(tmp_path, {"docs/configuration.md": bad_doc})
    text = confdocs.generate(project)
    (tmp_path / "docs/configuration.md").write_text(text)
    assert "| `gamma` |" in text and "`4 MiB`" in text
    assert not confdocs.verify(Project(root=tmp_path))


def test_confdocs_generate_appends_after_stale_last_row(tmp_path):
    # the LAST table row names a removed key: the repair must still
    # drop it AND append the missing-key template rows
    from spark_rapids_ml_tpu.analysis import confdocs

    bad_doc = CONF_DOC.replace(
        "| `gamma` | `\"on\"` | c |\n",
        "| `removed_key` | `1` | gone |\n",
    )
    project = make_tree(tmp_path, {"docs/configuration.md": bad_doc})
    text = confdocs.generate(project)
    (tmp_path / "docs/configuration.md").write_text(text)
    assert "removed_key" not in text and "| `gamma` |" in text
    assert not confdocs.verify(Project(root=tmp_path))


# ---------------------------------------------------------------------------
# fault-site
# ---------------------------------------------------------------------------


def test_fault_site_violations(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/resilience/faults.py": (
            'KNOWN_SITES = frozenset({"site_a", "ghost_site"})\n'
            'FAULT_KINDS = ("oom", "timeout")\n'
        ),
        "spark_rapids_ml_tpu/mod.py": (
            "from .resilience.faults import maybe_inject\n"
            "def f():\n"
            "    maybe_inject('site_a')\n"
            "    maybe_inject('rogue_site')\n"
        ),
        "tests/test_y.py": (
            "from spark_rapids_ml_tpu.resilience import fault_inject\n"
            "def test_a():\n"
            "    with fault_inject('nowhere', 'oom'):\n"
            "        pass\n"
            "    with fault_inject('site_a', 'meteor'):\n"
            "        pass\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[FaultSiteRule()]))
    assert any("rogue_site" in m and "not registered" in m for m in msgs)
    assert any("ghost_site" in m and "dead registration" in m for m in msgs)
    assert any("ghost_site" in m and "not listed" in m for m in msgs)
    assert any("nowhere" in m and "never fires" in m for m in msgs)
    assert any("meteor" in m and "unknown fault kind" in m for m in msgs)


def test_fault_site_pytest_raises_exempt(tmp_path):
    # a fault_inject that exists to BE rejected (arm-validation tests)
    # is exempt under `with pytest.raises(...)` — no suppression needed
    project = make_tree(tmp_path, {
        "tests/test_y.py": (
            "import pytest\n"
            "from spark_rapids_ml_tpu.resilience import fault_inject\n"
            "def test_a():\n"
            "    with pytest.raises(ValueError):\n"
            "        with fault_inject('nowhere', 'meteor'):\n"
            "            pass\n"
        ),
    })
    assert not run_analysis(project, rules=[FaultSiteRule()])


def test_fault_site_test_local_sites_allowed(tmp_path):
    # a test that instruments its own ad-hoc site with maybe_inject may
    # arm it with fault_inject — the machinery tests do exactly this
    project = make_tree(tmp_path, {
        "tests/test_y.py": (
            "from spark_rapids_ml_tpu.resilience import fault_inject\n"
            "from spark_rapids_ml_tpu.resilience.faults import maybe_inject\n"
            "def test_a():\n"
            "    with fault_inject('local_site', 'oom'):\n"
            "        maybe_inject('local_site')\n"
        ),
    })
    assert not run_analysis(project, rules=[FaultSiteRule()])


# ---------------------------------------------------------------------------
# metric-name
# ---------------------------------------------------------------------------


def test_metric_name_violations(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/telemetry/registry.py": REGISTRY_PY.replace(
            "METRIC_CATALOG = {",
            "METRIC_CATALOG = {\n"
            '    "never_used": {"kind": "counter", "labels": (), '
            '"cardinality": 1},',
        ),
        "spark_rapids_ml_tpu/mod.py": (
            "from .telemetry.registry import counter, gauge\n"
            "HITS = counter('hits_total', 'help')\n"
            "ROGUE = counter('rogue_total', 'minted ad hoc')\n"
            "KINDED = gauge('hits_total')\n"
            "def f():\n"
            "    HITS.inc(site='a')\n"
            "    HITS.inc(zone='b')\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[MetricNameRule()]))
    assert any("rogue_total" in m and "not declared" in m for m in msgs)
    assert any("registered as gauge" in m for m in msgs)
    # exactly ONE label-set finding: the zone inc; the site inc is clean
    label_msgs = [m for m in msgs if "!=" in m]
    assert len(label_msgs) == 1 and "zone" in label_msgs[0]
    # `never_used` is cataloged but never registered
    assert any("never_used" in m and "stale catalog" in m for m in msgs)


def test_metric_name_kwargs_expansion_unverifiable(tmp_path):
    # a `**labels` expansion is not statically checkable: no finding
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .telemetry.registry import counter\n"
            "HITS = counter('hits_total', 'help')\n"
            "def f(labels):\n"
            "    HITS.inc(**labels)\n"
        ),
    })
    assert not run_analysis(project, rules=[MetricNameRule()])


def test_metric_name_cross_module_import(tmp_path):
    # a metric var imported from its defining module still label-checks
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/a.py": (
            "from .telemetry.registry import counter\n"
            "HITS = counter('hits_total', 'help')\n"
        ),
        "spark_rapids_ml_tpu/b.py": (
            "from .a import HITS\n"
            "def f():\n"
            "    HITS.inc(wrong='x')\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[MetricNameRule()]))
    assert any("wrong" in m and "b.py" not in m for m in msgs)


def test_check_cardinality_bounds():
    from spark_rapids_ml_tpu.telemetry.registry import (
        MetricsRegistry,
        check_cardinality,
    )

    reg = MetricsRegistry()
    g = reg.gauge("solver_iteration")  # cataloged bound: 16
    for i in range(20):
        g.set(i, solver=f"s{i}")
    problems = check_cardinality(reg)
    assert len(problems) == 1 and "solver_iteration" in problems[0]


# ---------------------------------------------------------------------------
# stat-program
# ---------------------------------------------------------------------------


_STATS_STUB = (
    "def register_program(p):\n    pass\n"
    "class StatProgram:\n"
    "    def __init__(self, **kw):\n        pass\n"
)


def test_stat_program_violations(tmp_path):
    from spark_rapids_ml_tpu.analysis.rules_stats import StatProgramRule

    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/stats/programs.py": _STATS_STUB + (
            "register_program(StatProgram(name='good', kind='device',\n"
            "                             shapes=None))\n"
            "register_program(StatProgram(name='good', kind='device',\n"
            "                             shapes=None))\n"
            "register_program(StatProgram(kind='device', shapes=None))\n"
            "register_program(StatProgram(name='noshape', kind='device'))\n"
        ),
        "spark_rapids_ml_tpu/user.py": (
            "from .stats.engine import run_program\n"
            "def f(src):\n"
            "    return run_program('missing', src)\n"
        ),
        "docs/statistics.md": "programs: `good`\n",
    })
    msgs = messages(run_analysis(project, rules=[StatProgramRule()]))
    assert any("registered twice" in m for m in msgs)
    assert any("literal `name=`" in m for m in msgs)
    assert any("`shapes=`" in m for m in msgs)
    assert any(
        "names no registered statistic program" in m for m in msgs
    )
    assert any("`noshape` is not" in m for m in msgs)


def test_stat_program_clean_and_docs_gate(tmp_path):
    from spark_rapids_ml_tpu.analysis.rules_stats import StatProgramRule

    files = {
        "spark_rapids_ml_tpu/stats/programs.py": _STATS_STUB + (
            "register_program(StatProgram(name='good', kind='device',\n"
            "                             shapes=None))\n"
        ),
        "spark_rapids_ml_tpu/user.py": (
            "from .stats.engine import run_program\n"
            "def f(src):\n"
            "    return run_program('good', src)\n"
        ),
        "docs/statistics.md": "programs: `good`\n",
    }
    assert not run_analysis(
        make_tree(tmp_path / "clean", files), rules=[StatProgramRule()]
    )
    # a tree with no stats registry at all is clean too (the rule only
    # anchors once programs exist)
    assert not run_analysis(
        make_tree(tmp_path / "bare", {}), rules=[StatProgramRule()]
    )
    # dropping the docs page fires the registry-documented gate
    files_no_doc = dict(files)
    files_no_doc["docs/statistics.md"] = "nothing here\n"
    msgs = messages(run_analysis(
        make_tree(tmp_path / "nodoc", files_no_doc),
        rules=[StatProgramRule()],
    ))
    assert any("not listed in docs/statistics.md" in m for m in msgs)


# ---------------------------------------------------------------------------
# thread-lock
# ---------------------------------------------------------------------------


def test_thread_lock_unguarded_mutation(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "import threading\n"
            "_lock = threading.Lock()\n"
            "_cache = {}\n"
            "def good(k, v):\n"
            "    with _lock:\n"
            "        _cache[k] = v\n"
            "def also_good_locked(k):\n"
            "    _cache.pop(k, None)\n"
            "def bad(k, v):\n"
            "    _cache[k] = v\n"
            "def also_bad():\n"
            "    _cache.clear()\n"
        ),
    })
    findings = run_analysis(project, rules=[ThreadLockRule()])
    lines = sorted(f.line for f in findings)
    assert lines == [10, 12], findings


def test_thread_lock_trace_adoption(tmp_path):
    worker = (
        "import threading\n"
        "from .tracing import trace\n"
        "def _worker():\n"
        "    with trace('stage'):\n"
        "        pass\n"
        "def spawn():\n"
        "    t = threading.Thread(target=_worker)\n"
        "    t.start()\n"
    )
    project = make_tree(
        tmp_path, {"spark_rapids_ml_tpu/mod.py": worker}
    )
    findings = run_analysis(project, rules=[ThreadLockRule()])
    assert len(findings) == 1 and "adopt_trace_context" in findings[0].message
    # referencing adopt_trace_context in the creator silences it
    fixed = worker.replace(
        "def spawn():\n",
        "def spawn():\n"
        "    from .tracing import adopt_trace_context\n"
        "    adopt = adopt_trace_context()\n",
    )
    project = make_tree(tmp_path, {"spark_rapids_ml_tpu/mod.py": fixed})
    assert not run_analysis(project, rules=[ThreadLockRule()])


# ---------------------------------------------------------------------------
# named-lock
# ---------------------------------------------------------------------------

LOCKS_PY = """
LOCK_CATALOG = {
    "good": {"kind": "lock", "module": "spark_rapids_ml_tpu/mod.py"},
    "good_r": {"kind": "rlock", "module": "spark_rapids_ml_tpu/mod.py"},
}
def named_lock(name, kind="lock"):
    pass
"""


def test_named_lock_bare_lock_flagged(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/telemetry/locks.py": LOCKS_PY,
        "spark_rapids_ml_tpu/mod.py": (
            "import threading\n"
            "from .telemetry.locks import named_lock\n"
            "_lock = named_lock('good')\n"
            "_bare = threading.Lock()\n"
            "class C:\n"
            "    _cls_lock = threading.RLock()\n"
            "def f():\n"
            "    local = threading.Lock()\n"  # function-local: not flagged
            "    return local\n"
        ),
        "spark_rapids_ml_tpu/mod2.py": (
            "from .telemetry.locks import named_lock\n"
            "_r = named_lock('good_r', kind='rlock')\n"
        ),
    })
    findings = run_analysis(project, rules=[NamedLockRule()])
    msgs = messages(findings, "named-lock")
    assert len(msgs) == 2, findings
    assert any("threading.Lock()" in m for m in msgs)
    assert any("threading.RLock()" in m for m in msgs)


def test_named_lock_unknown_name_and_kind_mismatch(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/telemetry/locks.py": LOCKS_PY,
        "spark_rapids_ml_tpu/mod.py": (
            "from .telemetry.locks import named_lock\n"
            "_a = named_lock('good')\n"
            "_b = named_lock('rogue')\n"          # not cataloged
            "_c = named_lock('good_r')\n"         # cataloged rlock, minted lock
            "def f(n):\n"
            "    return named_lock(n)\n"          # non-literal name
        ),
    })
    msgs = messages(
        run_analysis(project, rules=[NamedLockRule()]), "named-lock"
    )
    assert any("`rogue` is not declared" in m for m in msgs)
    assert any(
        "minted as kind `lock` but cataloged as `rlock`" in m for m in msgs
    )
    assert any("non-literal lock name" in m for m in msgs)


def test_named_lock_stale_catalog_and_dead_module(tmp_path):
    stale = LOCKS_PY.replace(
        '"good_r": {"kind": "rlock", "module": "spark_rapids_ml_tpu/mod.py"},',
        '"good_r": {"kind": "rlock", "module": "spark_rapids_ml_tpu/mod.py"},\n'
        '    "ghost": {"kind": "lock", "module": "spark_rapids_ml_tpu/gone.py"},',
    )
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/telemetry/locks.py": stale,
        "spark_rapids_ml_tpu/mod.py": (
            "from .telemetry.locks import named_lock\n"
            "_a = named_lock('good')\n"
            "_b = named_lock('good_r', kind='rlock')\n"
        ),
    })
    msgs = messages(
        run_analysis(project, rules=[NamedLockRule()]), "named-lock"
    )
    assert any("`ghost` is never minted" in m for m in msgs)
    assert any("`spark_rapids_ml_tpu/gone.py` which does not exist" in m
               for m in msgs)


def test_named_lock_rule_stands_down_without_catalog(tmp_path):
    # a tree with no telemetry/locks.py (rule fixtures, partial
    # checkouts): the rule yields nothing rather than flagging every
    # bare lock against a catalog that does not exist
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "import threading\n_bare = threading.Lock()\n"
        ),
    })
    assert not run_analysis(project, rules=[NamedLockRule()])


def test_thread_lock_rule_treats_named_lock_as_lock(tmp_path):
    # converting `_lock = threading.Lock()` to `named_lock(...)` must
    # keep the module in the guarded-mutation rule's lock-declaring set
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .telemetry.locks import named_lock\n"
            "_mu = named_lock('good')\n"
            "_cache = {}\n"
            "def good(k, v):\n"
            "    with _mu:\n"
            "        _cache[k] = v\n"
            "def bad(k, v):\n"
            "    _cache[k] = v\n"
        ),
    })
    findings = run_analysis(project, rules=[ThreadLockRule()])
    assert [f.line for f in findings] == [8], findings


# ---------------------------------------------------------------------------
# span-pairing
# ---------------------------------------------------------------------------


def test_span_pairing_discarded_factory(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .tracing import trace\n"
            "def good():\n"
            "    with trace('a'):\n"
            "        pass\n"
            "def wrapper():\n"
            "    return trace('b')\n"  # factory passthrough: fine
            "def bad():\n"
            "    trace('c')\n"  # discarded: records nothing
        ),
    })
    findings = run_analysis(project, rules=[SpanPairingRule()])
    assert len(findings) == 1 and findings[0].line == 8


def test_span_pairing_assigned_then_entered(tmp_path):
    # `cm = trace(..)` later entered via `with cm:` is properly paired;
    # an assigned CM that is NEVER entered still fires
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .tracing import trace\n"
            "def ok():\n"
            "    cm = trace('a')\n"
            "    with cm:\n"
            "        pass\n"
            "def leaky():\n"
            "    dangling = trace('b')\n"
            "    return 1\n"
        ),
    })
    findings = run_analysis(project, rules=[SpanPairingRule()])
    assert len(findings) == 1 and findings[0].line == 7


def test_span_pairing_manual_enter(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "def leaky(cm):\n"
            "    cm.__enter__()\n"
            "    work = 1\n"
            "def paired(cm):\n"
            "    cm.__enter__()\n"
            "    try:\n"
            "        work = 1\n"
            "    finally:\n"
            "        cm.__exit__(None, None, None)\n"
        ),
    })
    findings = run_analysis(project, rules=[SpanPairingRule()])
    assert len(findings) == 1 and findings[0].line == 2


# ---------------------------------------------------------------------------
# module-ref
# ---------------------------------------------------------------------------


def test_module_ref_stale_path_and_conf(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "# staging lives in parallel/vanished.py now\n"
            "# the `retired_knob` conf gates it\n"
            "# the `alpha` conf is fine\n"
            "# reference utils/cuda_stuff.py is an external citation\n"
            "x = 1\n"
        ),
    })
    msgs = messages(run_analysis(project, rules=[ModuleRefRule()]))
    assert len(msgs) == 2
    assert any("parallel/vanished.py" in m for m in msgs)
    assert any("retired_knob" in m for m in msgs)


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI exit codes
# ---------------------------------------------------------------------------


def test_suppression_comment(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .config import get_config\n"
            "a = get_config('vanished')  # lint: disable=conf-key\n"
            "# lint: disable=conf-key\n"
            "b = get_config('vanished')\n"
            "c = get_config('vanished')\n"
        ),
    })
    findings = run_analysis(project, rules=[ConfKeyRule()])
    assert [f.line for f in findings] == [5]


def test_baseline_filters_known_findings(tmp_path):
    project = make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .config import get_config\n"
            "a = get_config('vanished')\n"
        ),
    })
    findings = run_analysis(project, rules=[ConfKeyRule()])
    assert len(findings) == 1
    baseline = [
        {"file": f.file, "rule": f.rule, "message": f.message}
        for f in findings
    ]
    assert not run_analysis(
        project, rules=[ConfKeyRule()], baseline=baseline
    )


def test_cli_seeded_tree_exits_nonzero(tmp_path, capsys):
    make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .config import get_config\n"
            "a = get_config('vanished')\n"
        ),
    })
    assert cli_main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "conf-key" in out and "vanished" in out
    # --disable turns the rule (and only it) off
    assert cli_main(["--root", str(tmp_path), "--disable", "conf-key"]) == 0


def test_cli_baseline_flag(tmp_path):
    make_tree(tmp_path, {
        "spark_rapids_ml_tpu/mod.py": (
            "from .config import get_config\n"
            "a = get_config('vanished')\n"
        ),
    })
    baseline = tmp_path / "known.json"
    baseline.write_text(json.dumps([{
        "file": "spark_rapids_ml_tpu/mod.py",
        "rule": "conf-key",
        "message": "unknown conf key `vanished` (not in config._DEFAULTS)",
    }]))
    assert cli_main(
        ["--root", str(tmp_path), "--baseline", str(baseline)]
    ) == 0


def test_lint_shim_is_jax_free():
    # the ci/lint.py shim loads the analysis subpackage under a stub
    # parent: a full static pass must complete without importing jax
    # (lint works in jax-less environments and never pays the
    # accelerator import)
    import pathlib
    import subprocess
    import sys as _sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    code = (
        "import runpy, sys\n"
        "sys.argv = ['ci/lint.py']\n"
        "try:\n"
        "    runpy.run_path('ci/lint.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert not e.code, f'lint found problems: {e.code}'\n"
        "assert 'jax' not in sys.modules, 'lint shim paid the jax import'\n"
        "print('shim jax-free')\n"
    )
    r = subprocess.run(
        [_sys.executable, "-c", code], cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0 and "shim jax-free" in r.stdout, (
        r.stdout + r.stderr
    )


# ---------------------------------------------------------------------------
# jit-audit sanitizer units (jax; CPU backend)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def jax_mod():
    return pytest.importorskip("jax")


def test_jit_audit_flags_closure_capture(jax_mod):
    import numpy as np

    from spark_rapids_ml_tpu.analysis.jit_audit import audit_jits

    jnp = jax_mod.numpy
    big = jnp.asarray(np.ones((256, 256), np.float32))  # 256 KB

    def build_and_run():
        captured = jax_mod.jit(lambda q: q @ big)  # closure capture: BAD
        as_arg = jax_mod.jit(lambda q, m: q @ m)   # data as argument: GOOD
        q = jnp.ones((4, 256), jnp.float32)
        captured(q)
        as_arg(q, big)

    with audit_jits(modules=(build_and_run.__module__,)) as rep:
        build_and_run()
    assert len(rep.records) == 2
    bad = [r for r in rep.records if r.const_bytes > 16 * 1024]
    assert len(bad) == 1
    assert any("captured" in v for v in rep.violations())


def test_jit_audit_donation_consumed(jax_mod):
    from spark_rapids_ml_tpu.analysis.jit_audit import audit_jits

    jnp = jax_mod.numpy

    def build_and_run():
        ok = jax_mod.jit(lambda a, x: a + x, donate_argnums=0)
        acc = jnp.zeros((1024,), jnp.float32)
        ok(acc, jnp.ones((1024,), jnp.float32))
        # dtype mismatch: the donation cannot be consumed
        bad = jax_mod.jit(
            lambda a, x: (a + x).astype(jnp.float64), donate_argnums=0
        )
        acc2 = jnp.zeros((1024,), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            bad(acc2, jnp.ones((1024,), jnp.float32))

    with jax_mod.enable_x64(True), audit_jits(
        modules=(build_and_run.__module__,)
    ) as rep:
        build_and_run()
    consumed = {r.donated_consumed for r in rep.records}
    assert consumed == {True, False}
    assert any("NOT consumed" in v for v in rep.violations())


def test_jit_audit_steady_state_compiles(jax_mod):
    from spark_rapids_ml_tpu.analysis.jit_audit import count_compiles

    jnp = jax_mod.numpy
    f = jax_mod.jit(lambda x: x * 2 + 1)
    with count_compiles() as warm:
        f(jnp.ones((8,)))
    assert warm.listener, "jax.monitoring listener must install here"
    assert warm.compiles >= 1
    with count_compiles() as steady:
        f(jnp.ones((8,)))   # same shape: cached
    assert steady.compiles == 0 and steady.recompiles == 0
    with count_compiles() as reshape:
        f(jnp.ones((16,)))  # new shape: recompiles
    assert reshape.compiles >= 1


def test_jit_audit_solver_kmeans_stepwise(jax_mod, tmp_path):
    # the generalized PR-7 audit applied to the stepwise KMeans solver:
    # every call-time jit on the path bounded at 16 KB of consts, the
    # donated Lloyd block accumulator actually consumed
    import numpy as np
    import pandas as pd

    from spark_rapids_ml_tpu.analysis.jit_audit import (
        assert_clean,
        audit_jits,
    )
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.config import reset_config, set_config

    rng = np.random.default_rng(0)
    df = pd.DataFrame(
        {"features": list(rng.normal(size=(512, 8)).astype(np.float32))}
    )
    set_config(checkpoint_dir=str(tmp_path))
    try:
        with audit_jits() as rep:
            KMeans(k=3, seed=1, maxIter=4).fit(df)
    finally:
        reset_config()
    assert_clean(rep, expect_records=False)
    assert all(r.const_bytes <= 16 * 1024 for r in rep.records)


def test_jit_audit_solver_fused_linreg(jax_mod):
    # fused stage-and-solve accumulator steps: audited, bounded, donated
    import numpy as np
    import pandas as pd

    from spark_rapids_ml_tpu.analysis.jit_audit import (
        assert_clean,
        audit_jits,
    )
    from spark_rapids_ml_tpu.config import reset_config, set_config
    from spark_rapids_ml_tpu.fused import _jitted_steps
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(1)
    X = rng.normal(size=(512, 8))
    y = X @ rng.normal(size=8)
    df = pd.DataFrame({"features": list(X.astype(np.float32)), "label": y})
    _jitted_steps.cache_clear()  # force re-creation under the audit
    set_config(fused_stage_solve="on")
    try:
        with audit_jits() as rep:
            LinearRegression(regParam=0.0).fit(df)
    finally:
        reset_config()
    assert_clean(rep, expect_records=True)
    donated = [r for r in rep.records if r.donate_argnums]
    assert donated and all(r.donated_consumed for r in donated)
