#
# spark_rapids_ml_tpu.stats — the declarative one-pass statistics
# subsystem (ROADMAP item 5): statistic programs registered in
# `STAT_PROGRAMS` (programs.py), a fused multi-program engine that runs
# any set of them in ONE pass over every existing chunk path
# (engine.py), mergeable sketch state (sketches.py), and the
# reference-compatible `Summarizer` / `describe()` surface
# (summarizer.py).  See docs/statistics.md for the program contract,
# the registered-program table and registration how-to.
#
from .engine import iter_chunk_accs, run_program, run_programs
from .programs import (
    STAT_PROGRAMS,
    Field,
    StatProgram,
    get_program,
    merge_accs,
    register_program,
)
from .summarizer import SUPPORTED_METRICS, Summarizer, describe, summarize

__all__ = [
    "Field",
    "STAT_PROGRAMS",
    "SUPPORTED_METRICS",
    "StatProgram",
    "Summarizer",
    "describe",
    "get_program",
    "iter_chunk_accs",
    "merge_accs",
    "register_program",
    "run_program",
    "run_programs",
    "summarize",
]
