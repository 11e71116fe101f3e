"""``gqa_flash_ms``: device time per step of the flash-attention forward and
backward kernels of the ``full_attention`` layers on the first device, at
this family's 32 query / 8 kv heads of 64 (layer: kernels).  Matched by
name as in a chip trace looked at by hand (PR 26; the fixture named in
``moe_gmm_ms``): the kernels are called from the layer's ``attn`` module,
so they are ``attn.3`` (forward: output and logsumexp), ``attn.4``
(backward, dk and dv) and ``attn.5`` (backward, dq).  ``flash_ms`` reads the
same kernel under ``TransformerLM``'s names (``block_<n>.<k>``)."""

from chipbench import reduce_trace


def is_flash(name):
    """On a name as ``reduce_trace.short_name`` leaves it."""
    return name.startswith("attn.") and name.endswith(" tpu_custom_call")


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
