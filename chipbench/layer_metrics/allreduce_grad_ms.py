"""``allreduce_grad_ms``: the time per step in which an operation under
``chainermn.allreduce_grad`` is in flight on the first device: packing, wire
cast, every plan stage, cast back and scale (layer: communicator / plan).
On one chip it is what the wire round trip costs with nobody to talk to.
Needs the EVENTS document's ``"scopes"``."""

from chipbench import reduce_trace, scopes


def in_scope(path):
    return scopes.under(path, scopes.ALLREDUCE_GRAD)


def read(events, host, context):
    if not scopes.readable(events):
        return None
    spans = scopes.spans_where(events, in_scope)
    return reduce_trace.length(spans) / 1e6 / host["steps"]
