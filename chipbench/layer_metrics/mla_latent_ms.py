"""``mla_latent_ms``: self time per step of what the LATENT costs inside a
latent-attention module (``layer_<n>/mla``): the product into the latent and
the shared rotated key (``kv_a_proj_with_mqa``), the RMSNorm of the latent
(``kv_a_layernorm``), the product from it to every head's ``k_nope`` and
``v`` (``kv_b_proj``) and ``chainermn.mla_key``, which builds the 192-wide q
and k (the concatenations, the one ``k_pe`` head repeated for every head),
forward and backward (layer: models).  An "of which" figure: every one of
these events lies in ``attn_proj_ms`` or, the norm, in ``norm_rope_ms``
already (``chipbench/parts.py`` gives an event one part); beside them it
says what the latent costs where plain attention has a k and a v product.
Read where a module named ``mla`` ran.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes

LATENT = ("kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
          "chainermn.mla_key")


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, "mla") and scopes.under(
            path, *LATENT)) or None
