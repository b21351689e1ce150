"""Where the chip's compiled programs persist between runs.

JAX keys its persistent cache by the directory, so the directory must not
move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads
it itself, and no other directory is set here), else ``.jax_cache`` at the
root of the checkout, which git ignores.  Entry points call
:func:`enable_compile_cache` from ``main()``; nothing calls it at import,
and the tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Optional[str]:
    """The directory to set, or ``None`` when ``$JAX_COMPILATION_CACHE_DIR``
    already places the cache."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> None:
    """Point JAX's persistent cache at :func:`compile_cache_dir` on a TPU
    backend.  Off TPU nothing is set: CPU compiles are cheap, and a cache
    there would also collect the AOT compiles for described TPUs, which
    cannot be read back without a chip."""
    path = compile_cache_dir()
    if path is not None and jax.default_backend() == "tpu":
        jax.config.update("jax_compilation_cache_dir", path)
