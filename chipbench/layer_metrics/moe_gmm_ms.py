"""``moe_gmm_ms``: device time per step of ALL the expert layers'
grouped-matmul kernels on the first device, forward, ``dlhs`` and ``drhs``
(layer: kernels).  The kernels are matched by the names seen in a chip
trace looked at by hand (PR 26, kept as
``fixtures/lfm2-8b-a1b-ep4share-t8192.two-steps.json.gz``): a Pallas kernel
is a ``custom-call`` whose target is ``tpu_custom_call``, and the trace
names it after the innermost flax module that called it, which for the
grouped products is the layer's ``moe`` (``moe.36`` ... ``moe.71``: nine a
layer, ``layer_<n>`` itself is not in the name).  The ``full_attention``
layers' flash kernels are ``attn.<n>`` (``gqa_flash_ms``)."""

from chipbench import reduce_trace


def is_gmm(name):
    """On a name as ``reduce_trace.short_name`` leaves it."""
    return name.startswith("moe.") and name.endswith(" tpu_custom_call")


def gmm_ns_per_step(events, host):
    ops = reduce_trace.first_device(events)
    return reduce_trace.time_of(ops, is_gmm) / host["steps"]


def read(events, host, context):
    if not events["devices"]:
        return None
    if context["sizes"].get("moe_matmul_impl") != "pallas":
        return None
    return gmm_ns_per_step(events, host) / 1e6
