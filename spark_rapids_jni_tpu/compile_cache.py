"""Where JAX keeps its persistent compilation cache for the repo's programs.

A chip run starts with no compiled code, and the distributed q97 plan alone
takes minutes to compile cold.  The cache key includes the cache path, so
the path must be the same from one run to the next: ``JAX_COMPILATION_CACHE_DIR``
when the caller sets it (JAX then reads it itself and nothing here touches
it), otherwise ``<repo>/.jax_cache``, which ``.gitignore`` lists.

Entry points call :func:`enable` before their first compile: ``chip_smoke.py``,
the benchmark (``python3 -m benchmark.run``) and the ``nds_harness`` CLI.  The
CPU test suite never does, so its cache stays off (``tests/conftest.py``).
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory the entry points use under ``environ``."""
    env = os.environ if environ is None else environ
    return env.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    return it.  Call before the process compiles anything."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
