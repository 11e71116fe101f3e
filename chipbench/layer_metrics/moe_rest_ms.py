"""``moe_rest_ms``: self time per step inside a layer's ``moe`` module that
is no grouped-matmul kernel and under none of the scopes the expert layer's
other readers sum (``chainermn.moe.route`` / ``.softmax_route``,
``.dispatch``, ``.combine``, ``.shared``): the router's product (``gate``),
``chainermn.moe.experts`` (the gate's elementwise product between the grouped
kernels), ``chainermn.moe.afmoe_route`` and, on a step whose remainder runs,
XLA's own ``ragged-dot`` kernels (layer: expert layer).  One of the parts of
``chipbench/parts.py``; read where a layer has a ``moe`` module.  Needs the
EVENTS document's ``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, "moe_rest_ms")
