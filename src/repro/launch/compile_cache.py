"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` once,
before their first compile, so that a second process on the same
checkout reuses what the first one compiled.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
no path is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``,
a fixed path resolved from the repository root: a directory that moves
between runs (a temporary name, a pid, the time) never finds what an
earlier run wrote.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.

    Sets ``jax_compilation_cache_dir`` only where the environment does
    not name a directory.
    """
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
