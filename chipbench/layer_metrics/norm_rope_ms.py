"""``norm_rope_ms``: self time per step under ``chainermn.rope`` or a norm
module inside a layer (``*_norm``, ``*_layernorm``: ``q_norm``, ``k_norm``,
``q_layernorm``, ``k_layernorm``, ``operator_norm``, ``ffn_norm``,
``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
``post_mlp_layernorm``): the elementwise float32 passes over the hidden state
and over q and k, forward and backward (layer: models).  One of the parts of
``chipbench/parts.py``; read where a model names its layers ``layer_<n>``.
Needs the EVENTS document's ``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, "norm_rope_ms")
