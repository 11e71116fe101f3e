"""``collective_ms``: the time per step in which a collective operation
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all) is
in flight on the first device (layer: communicator / plan).  Nothing to read
on one chip."""

from chipbench import reduce_trace


def read(events, host, context):
    if context["chips"] < 2 or not events["devices"]:
        return None
    ops = reduce_trace.first_device(events)
    return reduce_trace.collective_ns(ops) / 1e6 / host["steps"]
