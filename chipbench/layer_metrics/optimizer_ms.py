"""``optimizer_ms``: self time per step under ``chainermn.update``, the inner
optimizer's pass over its state and the parameter write (layer: train step).
A fusion carries ONE op_name, so this is the time of the fusions NAMED after
the update: on ``starcoder1b-dp4-t8192`` it reads 6.17 ms of an update of
about 17.3 (PR 30's chip runs, the same in two compiles): there the update's
loop fusions ride in the step fusions of PR 29's asynchronous collective
chains, named after the all-reduce (4.3 ms) or after nothing (11.1 ms,
``scope_unnamed_share``).  Compare it within a cell, never dp4's against one
chip's.  Needs the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host, lambda path: scopes.under(path, scopes.UPDATE))
