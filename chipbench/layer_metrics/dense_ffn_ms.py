"""``dense_ffn_ms``: self time per step inside a layer's ``ffn`` module, the
dense SwiGLU of the leading layers: ``w1``, ``w3``, ``w2`` and their
gradients (layer: models).  One of the parts of ``chipbench/parts.py``; read
where a layer has an ``ffn`` module (a model whose every feed-forward is
sparse has none).  Needs the EVENTS document's ``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, "dense_ffn_ms")
