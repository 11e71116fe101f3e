"""``moe_dispatch_combine_ms``: self time per step under
``chainermn.moe.dispatch`` and ``chainermn.moe.combine``: the sort of the
(token, expert) pairs, the row gathers into the grouped products and the
weighted scatter-adds out of them, forward and backward (layer: expert
layer).  It is what droplessness costs beside the grouped products
themselves (``moe_gmm_ms``).  Read where the program opens those scopes.
Needs the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(
            path, "chainermn.moe.dispatch", "chainermn.moe.combine")) or None
