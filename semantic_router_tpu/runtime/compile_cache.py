"""Where JAX's persistent compilation cache lives.

A cold default server compiles buckets × task kinds × variants at many
seconds each; the persistent cache turns every start after the first
into cache reads.  The directory is part of the cache key, so it must
never move between starts: either the operator places it
(``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable itself, and this
module then sets nothing), or it is ONE fixed path inside the checkout.

Called from the process entry points only (``python -m
semantic_router_tpu serve|serve-extproc``, ``bench.py``,
``chip_smoke.py``) — never at import, so tests and library users keep
whatever JAX configuration they chose.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns its directory.
    Call before the first compilation."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
