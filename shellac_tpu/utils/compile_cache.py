"""Where XLA's persistent compilation cache lives.

One rule, shared by every entry point that compiles (the CLI,
chip_smoke.py, bench.py, scripts/): where JAX_COMPILATION_CACHE_DIR is
set, jax reads it and this module sets nothing; where it is not, the
cache goes to ONE fixed directory inside the checkout. The directory is
part of the cache key, so it is never built from tempfile, a pid or the
clock — a path that moves never hits.

tests/conftest.py turns the cache off (JAX_ENABLE_COMPILATION_CACHE=0,
inherited by the subprocesses tests spawn), so tier-1 writes nothing
into the checkout whatever this module points at.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns the directory.

    Call before the first compile. Idempotent.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
