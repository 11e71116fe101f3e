"""``pack_unpack_ms``: self time per step under ``chainermn.pack`` and
``chainermn.unpack``, the copies around the collective with the wire cast
and the 1/size scale (layer: communicator / plan).  Needs the EVENTS
document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, "chainermn.pack", "chainermn.unpack"))
