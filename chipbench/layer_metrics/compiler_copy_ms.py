"""``compiler_copy_ms``: self time per step of the data movement the compiler
added: ``copy`` and ``copy-start`` / ``copy-done`` under any name, and the
instructions with no op_name of their own (named after their consumer:
``scopes.INHERITED``) that are themselves a relayout or a prefetch,
``transpose``, ``bitcast``, ``slice-start`` / ``slice-done`` (layer: device).
Any other inherited instruction is the program's work that lost its metadata
(``parts.is_copy``).  An overlay on the parts of ``chipbench/parts.py``, read
on every cell: 0 where the trace holds none.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import parts, reduce_trace, scopes


def read(events, host, context):
    if not scopes.readable(events):
        return None
    named = scopes.of(events)
    return reduce_trace.time_of(
        reduce_trace.first_device(events),
        lambda name: parts.is_copy(name, named.get(name, ""))
    ) / 1e6 / host["steps"]
