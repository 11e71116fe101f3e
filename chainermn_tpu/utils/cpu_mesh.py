"""Virtual CPU device-mesh bootstrap.

The test/dryrun analogue of the reference's ``mpiexec -n 8`` on one box
(SURVEY.md §4): an n-device CPU mesh in a single process, over which every
communicator runs real XLA collectives.

The installed JAX reads the CPU device count when the CPU client is first
created, from ``jax_num_cpu_devices`` (``JAX_NUM_CPU_DEVICES``) or from
``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` — both work on
a plain ``import jax``.  So the plain case here is to set the option before
any backend exists.  A process that has already created a too-small CPU
client is torn down and rebuilt (``clear_backends`` clears the
"initialized" latch); a live ACCELERATOR backend never is — a chip run
that silently became a CPU run is the fallback this module must not be.
Shared by ``tests/conftest.py``, ``__graft_entry__.dryrun_multichip`` and
the CPU-mesh tools.
"""

from __future__ import annotations


def _backend_uninitialized() -> bool:
    """True when no XLA client has been created yet in this process."""
    from jax._src import xla_bridge

    return not xla_bridge.backends_are_initialized()


def _want_cpu_devices(n: int) -> None:
    import jax

    if jax.config.jax_num_cpu_devices < n:
        jax.config.update("jax_num_cpu_devices", n)


def reset_to_cpu_mesh(n: int) -> None:
    """Tear down the current (CPU) JAX backend and bring up ``n`` CPU
    devices.  A live accelerator backend is never torn down: tearing
    down a chip to run on virtual CPU devices would turn a chip run into
    a CPU run without saying so."""
    import jax
    import jax.extend as jex

    if not _backend_uninitialized() and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{n} CPU devices wanted, but this process holds a live "
            f"{jax.default_backend()} backend with {jax.device_count()} "
            f"device(s); a virtual-mesh run must be started with "
            f"JAX_PLATFORMS=cpu")
    jex.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    _want_cpu_devices(n)
    devs = jax.devices()
    if jax.default_backend() != "cpu" or len(devs) < n:
        raise RuntimeError(
            f"CPU mesh bootstrap failed: backend={jax.default_backend()} "
            f"devices={len(devs)} (wanted >= {n})")


def ensure_cpu_mesh(n: int = 8) -> None:
    """Guarantee a CPU backend with at least ``n`` devices (tests, and
    tools that are CPU-mesh runs by definition)."""
    import jax

    if _backend_uninitialized():
        jax.config.update("jax_platforms", "cpu")
        _want_cpu_devices(n)
    if jax.default_backend() != "cpu" or len(jax.devices()) < n:
        reset_to_cpu_mesh(n)


def ensure_device_count(n: int):
    """Return >= ``n`` devices: the attached backend's if it has them
    (real chips win), else an ``n``-device CPU mesh.

    An accelerator backend with FEWER than ``n`` devices raises (see
    :func:`reset_to_cpu_mesh`).  Start a CPU-mesh dry run with
    ``JAX_PLATFORMS=cpu`` instead.
    """
    import jax

    if _backend_uninitialized():
        # only the CPU client reads this; harmless when a TPU backend wins
        _want_cpu_devices(n)
    devices = jax.devices()
    if len(devices) < n:
        reset_to_cpu_mesh(n)
        devices = jax.devices()
    return devices
