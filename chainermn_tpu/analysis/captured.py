"""Closure-captured constant audit — the ``captured-constant`` rule core.

Moved here from ``chainermn_tpu.utils.jaxpr_audit`` (which remains as a
deprecation re-export) when the one-off guard was promoted into the
static-analysis subsystem.

What this guards: every array a traced function closes over becomes a
*constant* of its jaxpr.  A constant is baked into the compiled program —
serialized into the executable and its persistent-cache entry, held in
HBM beside the live copy, and a new value means a recompile — where an
argument is just a buffer (found in round 5 on the long-context example,
whose step closed over its token batch).  The fix is always the same: pass the array as an explicit argument to
the jitted function.  ``assert_no_captured_constants(step,
*example_args)`` fails with the offending shapes/dtypes and that exact
fix in the message; the lint rule reports the same records as findings.

Scalar/config constants (loop bounds, eps values, small masks) are fine
and unavoidable; only constants above ``max_bytes`` are reported.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np

# One 32x32 f32 tile.  Big enough to pass the small lookup tables and
# iota-style constants tracing legitimately bakes in, small enough that
# any real operand (a batch, a parameter leaf) trips it.
DEFAULT_MAX_BYTES = 4096


class CapturedConstantError(ValueError):
    """A traced function closed over array constants above the size
    threshold (see module docstring for why that is a bug)."""


def _const_nbytes(c: Any):
    nb = getattr(c, "nbytes", None)
    if nb is not None:
        return int(nb)
    try:
        return int(np.asarray(c).nbytes)
    except Exception:  # noqa: BLE001 — non-array consts are not operands
        return None


def _iter_closed_jaxprs(closed):
    """The top-level ClosedJaxpr plus every ClosedJaxpr reachable through
    equation params (pjit/scan/cond bodies) — inner calls keep their own
    consts in some jax versions rather than hoisting them to the top."""
    from jax.extend.core import ClosedJaxpr

    stack, seen = [closed], set()
    while stack:
        cj = stack.pop()
        if id(cj) in seen:
            continue
        seen.add(id(cj))
        yield cj
        for eqn in cj.jaxpr.eqns:
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                stack.extend(x for x in vs if isinstance(x, ClosedJaxpr))


def constants_in_jaxpr(closed, max_bytes: int = DEFAULT_MAX_BYTES) \
        -> List[Dict[str, Any]]:
    """Captured-constant records of an already-traced ClosedJaxpr —
    the shared core of :func:`find_captured_constants` and the lint
    rule (which traces once and runs every rule on the same jaxpr)."""
    findings: List[Dict[str, Any]] = []
    seen_ids = set()
    for cj in _iter_closed_jaxprs(closed):
        for c in cj.consts:
            if id(c) in seen_ids:
                continue
            seen_ids.add(id(c))
            nb = _const_nbytes(c)
            if nb is not None and nb > max_bytes:
                findings.append({
                    "shape": tuple(getattr(c, "shape", ())),
                    "dtype": str(getattr(c, "dtype", type(c).__name__)),
                    "nbytes": nb,
                })
    findings.sort(key=lambda f: -f["nbytes"])
    return findings


def find_captured_constants(fn, *args,
                            max_bytes: int = DEFAULT_MAX_BYTES,
                            **kwargs) -> List[Dict[str, Any]]:
    """Trace ``fn(*args, **kwargs)`` and return one record per jaxpr
    constant larger than ``max_bytes``:
    ``{"shape", "dtype", "nbytes"}``, largest first.  Empty list means
    every big operand is an explicit argument."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return constants_in_jaxpr(closed, max_bytes=max_bytes)


def captured_constant_message(found: List[Dict[str, Any]], label: str,
                              max_bytes: int) -> str:
    lines = "\n".join(
        f"  - {f['dtype']}{list(f['shape'])} ({f['nbytes']} bytes)"
        for f in found)
    return (
        f"{label} closes over {len(found)} array constant(s) larger than "
        f"{max_bytes} bytes:\n{lines}\n"
        "Closure-captured arrays are baked into the compiled program "
        "(serialized with the executable, duplicated in HBM, a recompile "
        "per new value).  Pass them to the jitted function as explicit "
        "arguments instead of capturing them.")


def assert_no_captured_constants(fn, *args,
                                 max_bytes: int = DEFAULT_MAX_BYTES,
                                 name: str = None,
                                 **kwargs) -> None:
    """Raise :class:`CapturedConstantError` if tracing ``fn`` bakes in
    array constants above ``max_bytes`` (closure-captured operands)."""
    found = find_captured_constants(fn, *args, max_bytes=max_bytes,
                                    **kwargs)
    if not found:
        return
    label = name or getattr(fn, "__name__", repr(fn))
    raise CapturedConstantError(
        captured_constant_message(found, label, max_bytes))


__all__ = ["CapturedConstantError", "DEFAULT_MAX_BYTES",
           "assert_no_captured_constants", "captured_constant_message",
           "constants_in_jaxpr", "find_captured_constants"]
