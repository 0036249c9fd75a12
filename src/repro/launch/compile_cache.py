"""JAX's persistent compilation cache for the repository's entry points.

Called by the entry points (`chip_smoke.py`, `examples/serve_dcnn.py`,
`benchmarks/run.py`) before they compile anything; the tests leave the
cache off.  A cold serving run compiles one Pallas kernel per layer per
bucket and precision, each in about a second, so a second run of the same
code on the same chip type skips most of its set-up."""
from __future__ import annotations

import os
import pathlib

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (git-ignored): the directory is where
# the next run looks, so it must not move between runs
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is `DEFAULT_DIR`.
    Every compile is kept, not only those over JAX's 1 s default."""
    path = os.environ.get(_ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
