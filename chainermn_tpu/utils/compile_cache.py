"""Persistent compilation cache, placed from outside.

Every chip call starts on a new machine, so each one pays the full
compile of every step unless JAX's persistent cache sits where the
caller can put it.  The directory is part of the cache key's
surroundings (a directory that moves never hits), so it is either what
``JAX_COMPILATION_CACHE_DIR`` says — JAX reads that variable itself and
this module then sets nothing — or one fixed path inside the checkout.
Never a temporary, pid- or time-stamped directory.

Entry points that jit call :func:`place_compile_cache` before their
first compile (``chip_smoke.py``, ``benchmarks/*.py``).
The library itself never calls it: importing ``chainermn_tpu`` writes
nothing to disk, and ``tests/`` do not write there.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the one in-checkout location (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one place and return
    that directory.  With ``JAX_COMPILATION_CACHE_DIR`` set, nothing is
    set in code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


__all__ = ["DEFAULT_CACHE_DIR", "place_compile_cache"]
