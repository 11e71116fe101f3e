"""``scope_unnamed_share``: the share, in percent, of the first device's
summed self time in operations under no ``chainermn.*`` scope (layer:
device): what the program's names do not reach.  Needs the EVENTS document's
``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    if not scopes.readable(events):
        return None
    totals = scopes.by_top_level(events)
    return 100.0 * totals["none"] / sum(totals.values())
