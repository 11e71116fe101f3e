"""``norm_ms``: self time per step under a BatchNorm module's scope
(``BatchNorm_<n>``, ``norm_proj``, ``bn_init``), forward and backward
(layer: models).  Read on the ResNet cell.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes

NORMS = ("BatchNorm_*", "norm_proj", "bn_init")


def read(events, host, context):
    if context["sizes"].get("family") != "resnet":
        return None
    return scopes.ms_per_step(
        events, host, lambda path: scopes.under(path, *NORMS))
