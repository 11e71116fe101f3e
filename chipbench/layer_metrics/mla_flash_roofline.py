"""``mla_flash_roofline``: the least time the chip could take for the
attention of the step's latent-attention layers
(``chipbench/flops_deepseek_v3.py``: ``3 x 2 x T (T + 1) / 2 x (192 + 128)``
operations a head and row, the bytes of q and k at 192 and of v and the
output at 128: the same need whatever implements it) over the ``mla.<k>``
kernels' measured time, in percent (layer: kernels).  At T=8192 the compute
bound applies.  The need counts 192-wide keys: kernels that pad them to the
chip's 256 lanes do a third more score work than is counted, and read
lower for it."""

from chipbench import flops, flops_deepseek_v3
from chipbench.layer_metrics import mla_flash_ms


def read(events, host, context):
    measured_ms = mla_flash_ms.read(events, host, context)
    if measured_ms is None or context["peaks"] is None:
        return None
    flop, nbytes = flops_deepseek_v3.mla_flash_train_flop_and_bytes(
        context["sizes"])
    least_s, _ = flops.roofline_seconds(flop, nbytes, context["peaks"])
    return 100.0 * least_s / (measured_ms / 1e3)
