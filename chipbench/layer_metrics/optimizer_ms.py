"""``optimizer_ms``: self time per step under ``chainermn.update``, the inner
optimizer's pass over its state and the parameter write (layer: train step).
Needs the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host, lambda path: scopes.under(path, scopes.UPDATE))
