"""``attn_proj_ms``: self time per step inside a layer's attention module
(``attn``, ``swa``, ``nope``, ``full``, ``sliding``: what the flash readers
declare or their kernels' runs show, ``parts.attention_modules``) that is no
Pallas kernel, no ``chainermn.rope`` and no norm module: the q, k, v, gate and
output products with their gradients, ``chainermn.attn_gate`` and the
transposes between them and the kernels (layer: models).  One of the parts of
``chipbench/parts.py``; read where a model names its layers ``layer_<n>``.
Needs the EVENTS document's ``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, "attn_proj_ms")
