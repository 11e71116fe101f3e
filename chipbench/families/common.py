"""What the families share: the communicator and the optimizer named in a
configuration file, built through the program's public entry points."""

from __future__ import annotations


def make_comm(sizes, devices):
    """``create_communicator`` as the configuration names it, on exactly the
    cell's devices."""
    import chainermn_tpu
    from chainermn_tpu.parallel.topology import init_topology

    spec = dict(sizes["communicator"])
    name = spec.pop("name")
    return chainermn_tpu.create_communicator(
        name, topology=init_topology(devices=list(devices)), **spec)


def make_optimizer(sizes, comm):
    import optax

    import chainermn_tpu

    spec = sizes["optimizer"]
    if spec["rule"] != "sgd":
        raise ValueError(f"unknown optimizer rule {spec['rule']!r}")
    return chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(spec["learning_rate"], momentum=spec["momentum"]), comm,
        double_buffering=bool(spec["double_buffering"]))


def init_opt_state(place, optimizer, params):
    """The program's ``init_opt_state``.  For the double buffer it builds the
    stacked per-device ``pending`` zeros ([chips, ...] of every parameter)
    on the DEFAULT device before sharding them: on four chips that is four
    times the parameters on chip 0, which a model sized to fill one chip
    cannot hold (my chip run, PR 23: RESOURCE_EXHAUSTED at 10 layers).  So
    on more than one chip the default device is the host while it runs; the
    arrays it returns are placed as the program places them."""
    import jax

    from chainermn_tpu.optimizers import init_opt_state as program_init

    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:        # JAX_PLATFORMS leaves the host backend out
        host = None
    if place.size == 1 or host is None:
        return program_init(place, optimizer, params)
    with jax.default_device(host):
        return program_init(place, optimizer, params)


def first_gradient_after(sizes):
    """The step after whose update the inner optimizer's momentum holds the
    first gradient as it got it (all-reduced, through the wire dtype): the
    first, or the second where the double buffer applies gradients one step
    late (its first update applies zeros)."""
    return 2 if sizes["optimizer"]["double_buffering"] else 1


def momentum_trace(opt_state):
    """optax.sgd(momentum)'s trace inside the multi-node optimizer's state."""
    inner = getattr(opt_state, "inner", opt_state)
    return inner[0].trace
