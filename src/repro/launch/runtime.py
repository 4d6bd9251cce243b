"""Process set-up shared by the entry points: the compile cache, and the
device every result names.

The askotch step, the residual matvec and every serving bucket compile from
scratch in a fresh process; the persistent cache lets the next process load
them instead.  The cache is keyed by its path as well as the program, so
the path must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), else ``.jax_cache/``
at the root of the checkout.  On the CPU backend no default is set: CPU
compiles are cheap, and JAX logs a machine-feature mismatch error for every
CPU executable it loads back from the cache.

Entry points call :func:`use_compile_cache` from their ``main`` — never at
import, so importing the library changes no JAX configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.kernels.ops import resolve_backend

#: ``.jax_cache/`` at the checkout root (git-ignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; return its directory, or
    None where it stays off (the CPU backend with no directory set)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def device_fields(backend: str) -> dict:
    """Where a result ran: JAX's platform, device kind and device count, and
    the kernel backend ``backend`` resolves to on it."""
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "backend": resolve_backend(backend),
    }
