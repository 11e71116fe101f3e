"""``idle_share``: 1 - busy union / traced window on the first device, in
percent (layer: device)."""

from chipbench import reduce_trace


def read(events, host, context):
    if not events["devices"]:
        return None
    return 100.0 * reduce_trace.idle_share(reduce_trace.first_device(events))
