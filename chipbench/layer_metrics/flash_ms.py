"""``flash_ms``: device time per step of the flash-attention forward and
backward kernels on the first device (layer: kernels).  The kernels are
matched by the names seen in a chip trace looked at by hand (PR 23): a
Pallas kernel is a ``custom-call`` whose target is ``tpu_custom_call``, and
the trace names it after the flax scope that called it, so the three flash
kernels of layer N are ``block_N.3`` (forward: output and logsumexp),
``block_N.4`` (backward, dk and dv) and ``block_N.5`` (backward, dq)."""

from chipbench import reduce_trace


def is_flash(name):
    """On a name as ``reduce_trace.short_name`` leaves it."""
    return name.startswith("block_") and name.endswith(" tpu_custom_call")


def flash_ns_per_step(events, host):
    ops = reduce_trace.first_device(events)
    return reduce_trace.time_of(ops, is_flash) / host["steps"]


def read(events, host, context):
    if not events["devices"]:
        return None
    if context["sizes"].get("attention_impl") != "flash":
        return None
    # no such kernel ran: another family's names, nothing to read
    return flash_ns_per_step(events, host) / 1e6 or None
