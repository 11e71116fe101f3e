"""``collective_exposed_ms``: the part per step of the collectives' time in
which no other operation runs on the first device (layer: communicator /
plan).  Nothing to read on one chip."""

from chipbench import reduce_trace


def read(events, host, context):
    if context["chips"] < 2 or not events["devices"]:
        return None
    ops = reduce_trace.first_device(events)
    return reduce_trace.collective_exposed_ns(ops) / 1e6 / host["steps"]
