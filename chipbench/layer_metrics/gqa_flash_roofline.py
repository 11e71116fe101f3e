"""``gqa_flash_roofline``: the least time the chip could take for the
attention of the step's ``full_attention`` layers (``chipbench/flops_lfm2.py``
through ``flops.flash_train_flop`` / ``flash_train_bytes`` at 32 query / 8 kv
heads of 64) over the kernels' measured time, in percent (layer: kernels).
At T=8192 the compute bound applies; at head_dim 64 each score tile feeds
the MXU half the contraction depth that ``flash_roofline``'s 128 does."""

from chipbench import flops, flops_lfm2
from chipbench.layer_metrics import gqa_flash_ms


def read(events, host, context):
    sizes = context["sizes"]
    if context["peaks"] is None or not events["devices"]:
        return None
    if sizes.get("attention_impl") != "flash":
        return None
    measured_s = gqa_flash_ms.flash_ns_per_step(events, host) / 1e9
    if measured_s <= 0:
        return None
    flop, nbytes = flops_lfm2.gqa_flash_train_flop_and_bytes(sizes)
    least_s, _ = flops.roofline_seconds(flop, nbytes, context["peaks"])
    return 100.0 * least_s / measured_s
