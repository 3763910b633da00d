"""Shared utilities: the compile-cache root and the persistent XLA
compilation cache."""
from __future__ import annotations

import os

# src/repro/util.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """Root of both compile caches: ``$JAX_COMPILATION_CACHE_DIR`` when it
    is set, else one fixed directory inside the checkout (``.jax_cache``,
    listed in .gitignore).  The XLA cache lives at the root itself and
    the executable cache (`repro.core.execache`) under ``zkdl-exec/``.
    The path is fixed, never temporary: it is part of what the caches
    hit on."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compilation_cache() -> None:
    """Persist compiled executables across processes (tests, benchmarks)."""
    import jax

    d = cache_root()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    # 0.0: the proof pipeline is built from hundreds of SMALL programs
    # (per-round IPA/sumcheck shapes); at the default 0.5s threshold none
    # of them persist and every process pays ~35s of recompiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
