"""Placement of jax's persistent compilation cache.

Called by the process entry points (``chip_smoke.py``, ``perfbench/run.py``,
``dscli serve``, ``benchmarks/*.py``, the launcher for its workers) before
their first compile, never at ``import deepspeed_tpu``. The directory is part
of nothing but the lookup, so it must not move between runs: either the
environment places it (``JAX_COMPILATION_CACHE_DIR``, which jax reads itself
— the program then sets no directory in code), or it is the one fixed path
inside the checkout. Never a temp name, a pid or a time.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Where this process's compile cache lives (see module docstring)."""
    return os.environ.get(_ENV) or _IN_CHECKOUT


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that directory. A no-op on jax's configuration when the environment
    already placed the cache."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
    return compile_cache_dir()


def compile_cache_entries() -> int:
    """Number of files under the cache directory (0 when it does not exist
    yet) — what entry points report before and after a run."""
    d = compile_cache_dir()
    return sum(len(files) for _, _, files in os.walk(d)) if os.path.isdir(d) else 0
