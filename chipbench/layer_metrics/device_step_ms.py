"""``device_step_ms``: the union of the first device's operation intervals
over the traced steps (layer: train step)."""

from chipbench import reduce_trace


def read(events, host, context):
    if not events["devices"]:
        return None
    ops = reduce_trace.first_device(events)
    return reduce_trace.busy_ns(ops) / 1e6 / host["steps"]
