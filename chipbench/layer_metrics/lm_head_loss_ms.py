"""``lm_head_loss_ms``: self time per step under ``chainermn.grad`` and
outside every ``layer_<n>``: ``lm_head`` (``embed_tokens.attend`` where the
head is tied), ``embed_tokens`` and its gradient's scatter-add, the final
norm, and the caller's loss with its float32 logits (layer: models).
``head_loss_ms`` reads the same under ``TransformerLM``'s names.  One of the
parts of ``chipbench/parts.py``; read where a model names its layers
``layer_<n>``.  Needs the EVENTS document's ``"scopes"``."""

from chipbench import parts


def read(events, host, context):
    return parts.ms_per_step(events, host, parts.OUTSIDE_LAYERS)
