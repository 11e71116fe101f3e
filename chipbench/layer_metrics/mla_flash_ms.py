"""``mla_flash_ms``: device time per step of the flash-attention forward and
backward kernels of ALL the latent-attention layers of family
``deepseek_v3`` on the first device (layer: kernels): plain causal attention
at 16 heads whose keys are 192 wide (128 from the latent, 64 rotated) and
whose values are 128 wide, so q, k and their gradients move at 192 and v,
the output and theirs at 128.  The family calls the kernel from a module
named ``mla`` with no scope between the two, so the kernels are ``mla.<k>``
(three a layer: forward, dk and dv, dq), matched as ``swa_flash_ms`` matches
its own; ``MODULE`` makes ``layer_<n>/mla/*`` an attention module of
``chipbench/parts.py``."""

from chipbench.layer_metrics import swa_flash_ms

MODULE = "mla"


def is_flash(name):
    return swa_flash_ms.is_kernel(name, MODULE)


def read(events, host, context):
    return swa_flash_ms.read_ms(events, host, context, MODULE)
