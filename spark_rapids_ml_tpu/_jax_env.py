#
# Where the persistent XLA compilation cache lives — the one place in the
# repo that names it.  Entry scripts (chip_smoke.py, chipbench/run.py)
# call `configure_compile_cache()` before their first use
# of jax; the library itself sets nothing on import.
#
# jax reads JAX_COMPILATION_CACHE_DIR itself at import.  When the caller
# (a chip tool, a CI job) set it, that directory is the cache and no code
# here touches it.  When it is unset the cache goes to a FIXED path inside
# the checkout: the path is part of what a later process must agree on to
# hit, so it is never /tmp, a pid, a mkdtemp or a timestamp.
#
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_CONF = "jax_compilation_cache_dir"
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at the one agreed place
    and return it.  Touches only jax's config (no backend is
    initialised, no directory is created — jax creates it on first
    write)."""
    import jax

    if (
        _MIN_SECS_ENV not in os.environ
        and os.environ.get("JAX_PLATFORMS", "") != "cpu"
    ):
        # jax keeps only programs that took a second to compile.  On a
        # TPU host the flagship fit is thirty programs of a sixth of a
        # second each, so at the default nothing is ever kept.  (A
        # CPU-pinned run keeps the default: XLA:CPU logs an error per
        # cached program it loads back.)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update(CACHE_CONF, DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def compile_cache_dir():
    """The directory jax's config currently names (None = no persistent
    cache)."""
    import jax

    return getattr(jax.config, CACHE_CONF)
