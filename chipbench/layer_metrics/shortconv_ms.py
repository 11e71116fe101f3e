"""``shortconv_ms``: self time per step under ``chainermn.shortconv``, the
gated short convolution of the ``conv`` layers between their ``in_proj`` and
``out_proj`` (elementwise gates and ``conv_L_cache`` taps, float32 inside),
forward and backward (layer: models).  Read where the program opens that
scope.  Needs the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, "chainermn.shortconv")) or None
