"""``dispatch_ms``: host clock around the step call, mean per step of the
traced window (layer: entry point / host loop)."""


def read(events, host, context):
    times = host["dispatch_s"]
    return 1e3 * sum(times) / len(times) if times else None
