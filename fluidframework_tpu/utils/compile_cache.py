"""Where the entry points keep JAX's persistent compilation cache.

Called from ``main()`` of the entry points only (``chip_smoke.py``,
``bench.py``, ``tools/bench_configs.py``, ``tools/bench_kernels.py``,
``service/server.py``) — never at import, so tests compile as before.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: The fixed in-repo default (git-ignored).  A directory that moves
#: between runs never hits, so it is never built from a temporary name, a
#: pid or the time.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing is set here."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
