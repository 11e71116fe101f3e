"""``moe_shared_experts_ms``: self time per step under
``chainermn.moe.shared_experts``, the shared experts of family
``deepseek_v3``'s MoE layers: one SwiGLU ``n_shared_experts *
moe_intermediate_size`` wide that every token visits, three plain matrix
products forward and their gradients (layer: expert layer).  An "of which"
figure inside ``moe_rest_ms``: ``chipbench/parts.py`` knows the scope
``chainermn.moe.shared`` alone (``moe_shared_ms``, which an accepted test
holds to AFMoE's cell), so this family's shared experts, inside the ``moe``
module under a scope of their own, fall to ``moe_rest_ms``, and this reader
says how much of it they are.  Beside ``moe_gmm_ms`` it says what every
token's visit to two experts costs as plain products against 0.75 expected
visits through the grouped kernels.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, "chainermn.moe.shared_experts")
    ) or None
