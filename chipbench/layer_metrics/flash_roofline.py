"""``flash_roofline``: the least time the chip could take for the step's
attention (the larger of needed operations over the bf16 peak and needed
bytes over the HBM peak, from ``chipbench/flops.py``) over the kernels'
measured time, in percent (layer: kernels).  At T=8192 and head_dim 128 the
compute bound applies."""

from chipbench import flops
from chipbench.layer_metrics import flash_ms


def read(events, host, context):
    sizes = context["sizes"]
    if context["peaks"] is None or not events["devices"]:
        return None
    if sizes.get("attention_impl") != "flash":
        return None
    measured_s = flash_ms.flash_ns_per_step(events, host) / 1e9
    if measured_s <= 0:
        return None
    heads = sizes["n_head"]
    kv_heads = 1 if sizes["multi_query"] else heads
    rows = sizes["batch_per_chip"]
    flop = sizes["n_layer"] * flops.flash_train_flop(
        rows, sizes["seq_len"], heads, sizes["n_embd"] // heads)
    nbytes = sizes["n_layer"] * flops.flash_train_bytes(
        rows, sizes["seq_len"], heads, kv_heads, sizes["n_embd"] // heads)
    least_s, _ = flops.roofline_seconds(flop, nbytes, context["peaks"])
    return 100.0 * least_s / measured_s
