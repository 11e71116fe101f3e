"""Small shared utilities."""

from __future__ import annotations

import jax


def pvary(x, axes):
    """Mark ``x`` as varying over mesh ``axes`` inside shard_map.

    Idempotent: axes already in the value's vma are skipped
    (``jax.lax.pcast`` rejects varying→varying)."""
    want = (axes,) if isinstance(axes, str) else tuple(axes)
    have = jax.typeof(x).vma
    missing = tuple(a for a in want if a not in have)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


__all__ = ["pvary"]
