"""``conv_proj_ms``: self time per step inside a layer's ``conv`` module and
outside ``chainermn.shortconv`` (``shortconv_ms``): ``conv/in_proj`` and
``conv/out_proj`` with their gradients; an ``in_proj`` that XLA
rematerialises counts here too (``forward_recompute_ratio`` says how much
that is) (layer: models).  One of the parts of ``chipbench/parts.py``; read
where a layer has a ``conv`` module.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, "conv_proj_ms")
