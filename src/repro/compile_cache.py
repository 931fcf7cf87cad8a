"""JAX's persistent compilation cache, kept where the next run finds it.

A published-width prefill, decode step or train step takes minutes to
compile. JAX keys its cache by the cache directory among other things, so
the directory must not move between runs: where `JAX_COMPILATION_CACHE_DIR`
is set, that directory is used and no other; otherwise the fixed directory
`.jax_cache` at the root of this checkout (listed in `.gitignore`).

Entry points (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
`benchmarks.run`) call `enable()` before they compile anything.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent cache lives in."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()`; returns
    that directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
