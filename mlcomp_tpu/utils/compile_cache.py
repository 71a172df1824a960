"""Where JAX's persistent compilation cache lives.

A 1.2B train step or serve dispatch compiles for tens of seconds, and
every process (``cli dag``, each ``serve`` daemon, task children) used to pay that from cold.  The cache directory is part of
the cache key, so it must never move: no mkdtemp, pid or timestamp.

``JAX_COMPILATION_CACHE_DIR`` set from outside wins and nothing else is
set in code (JAX reads the variable itself).  Unset, the cache sits at
one fixed directory inside the checkout, exported through the same
variable so child processes land in the same place.

Called from process entry points only (``cli.main``,
``scheduler.child``) — never at import of the package.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Resolve the cache directory for this process and its children;
    returns it."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = str(DEFAULT_DIR)
    os.environ[ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path
