"""``backward_ms``: self time per step of the first device's operations
under ``chainermn.grad`` that the backward pass runs, a rematerialized
forward included (layer: train step).  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, scopes.GRAD)
        and scopes.is_backward(path))
