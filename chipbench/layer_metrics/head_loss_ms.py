"""``head_loss_ms``: self time per step of the vocabulary head and the loss
(layer: models): under ``chainermn.grad`` and either under the ``head``
module or under none of the model's other modules (the loss is the caller's
code and carries no module scope).  Read on the transformer cells.  Needs
the EVENTS document's ``"scopes"``."""

from chipbench import scopes

BODY = ("block_*", "tok_emb", "pos_emb", "ln_f")


def read(events, host, context):
    if not context["sizes"].get("n_layer"):
        return None
    return scopes.ms_per_step(
        events, host,
        lambda path: scopes.under(path, scopes.GRAD)
        and (scopes.under(path, "head") or not scopes.under(path, *BODY)))
