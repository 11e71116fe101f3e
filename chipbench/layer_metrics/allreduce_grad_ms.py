"""``allreduce_grad_ms``: the time per step in which an operation under
``chainermn.allreduce_grad`` is IN FLIGHT on the first device: wire cast,
every plan stage, cast back and scale (layer: communicator / plan).  An
asynchronous collective counts from its start's beginning to its done's
end, so since PR 29 (the dp4 step's all-reduces are asynchronous chains)
this is how long the exchange is open, not what it costs: the cost is
``allreduce_grad_exposed_ms``, and the waiting in it ``collective_wait_ms``.
Read where there is an exchange: on more than one chip (on one the scope
holds the wire cast's round trip, 0.004-0.043 ms, and measures nothing).
Needs the EVENTS document's ``"scopes"``."""

from chipbench import reduce_trace, scopes


def in_scope(path):
    return scopes.under(path, scopes.ALLREDUCE_GRAD)


def read(events, host, context):
    if context["chips"] < 2 or not scopes.readable(events):
        return None
    spans = scopes.spans_where(events, in_scope)
    return reduce_trace.length(spans) / 1e6 / host["steps"]
