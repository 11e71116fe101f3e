"""``forward_recompute_ratio``: the forward pass's self time counting XLA's
rematerialised clones, over the same without them (layer: train step).  A
clone (``fusion.12.remat``) keeps its original's op_name, so ``forward_ms``
counts a forward instruction that the compiler runs again in the backward
pass as forward; this says by how much: 1.0 where nothing is rematerialised.
An overlay on the parts of ``chipbench/parts.py``, read on every cell.  Needs
the EVENTS document's ``"scopes"``."""

from chipbench import parts, reduce_trace, scopes


def read(events, host, context):
    if not scopes.readable(events):
        return None
    named = scopes.of(events)
    forward = clones = 0.0
    for name, ns in reduce_trace.self_times(
            reduce_trace.first_device(events)).items():
        path = named.get(name, "")
        if scopes.under(path, scopes.GRAD) and not scopes.is_backward(path):
            forward += ns
            if parts.is_clone(name):
                clones += ns
    return forward / (forward - clones) if forward > clones else None
