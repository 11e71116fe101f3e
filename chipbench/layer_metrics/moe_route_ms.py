"""``moe_route_ms``: self time per step under ``chainermn.moe.route``, the
expert layers' router: its product, the sigmoid scores with ``expert_bias``
and the top-k selection, forward and backward (layer: expert layer).  Read
where the program opens that scope.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, "chainermn.moe.route")) or None
