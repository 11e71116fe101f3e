"""``collective_wait_ms``: self time per step of the first device's events
in which the chip does nothing but wait for the wire (layer: communicator /
plan): the ``async-collective-done.N`` that ends an asynchronous collective
fusion (PR 29: ``async-collective-start.N`` -> ``fusion.K`` steps ->
``async-collective-done.N``), the ``...-done`` of a plain asynchronous pair,
and every blocking ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
``all-to-all`` / ``collective-permute``.  A ``...-start`` issues the
transfer and returns; the step fusions of a chain do the reduce's
arithmetic beside other work: neither is waiting.  Matched by instruction
name (on one chip no such event exists, and nothing is read): XLA's own
for a collective it made or combined, the JAX primitive's for one it left
as it came (the loss's ``psum_invariant.N``; every all-reduce of PR 24's
step).  ``allreduce_grad_exposed_ms`` holds this time and the exchange's
other exposed parts, by scope."""

from chipbench import reduce_trace, scopes

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "async-collective",
               # a collective the compiler left alone keeps JAX's name
               "psum", "pmax", "pmin", "all_gather", "all_to_all",
               "ppermute", "reduce_scatter")


def is_wait(name):
    """On a name as ``reduce_trace.short_name`` leaves it."""
    instruction = scopes.instruction_of(name).lower()
    return (instruction.startswith(COLLECTIVES)
            and "-start" not in instruction)


def read(events, host, context):
    if not events["devices"]:
        return None
    ops = reduce_trace.first_device(events)
    return reduce_trace.time_of(ops, is_wait) / 1e6 / host["steps"] or None
