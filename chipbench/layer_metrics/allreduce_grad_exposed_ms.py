"""``allreduce_grad_exposed_ms``: the part per step of ``allreduce_grad_ms``
that no operation outside ``chainermn.allreduce_grad`` covers on the first
device (layer: communicator / plan).  Read where the exchange has someone to
overlap with: on more than one chip."""

from chipbench import reduce_trace, scopes
from chipbench.layer_metrics import allreduce_grad_ms


def read(events, host, context):
    if context["chips"] < 2 or not scopes.readable(events):
        return None
    alone = scopes.exposed(events, allreduce_grad_ms.in_scope)
    return reduce_trace.length(alone) / 1e6 / host["steps"]
