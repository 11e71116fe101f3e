"""``block_ms``: self time per step under a ``block_<n>`` module scope,
forward and backward, over the number of layers (layer: models).  Read on
the transformer cells.  Needs the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    layers = context["sizes"].get("n_layer")
    if not layers:
        return None
    total = scopes.ms_per_step(
        events, host, lambda path: scopes.under(path, "block_*"))
    return None if total is None else total / layers
