"""Multi-controller bootstrap — ``jax.distributed`` with no launcher.

Reference analogue (SURVEY.md §2.5, §3.1): the reference's world came from
``mpiexec -n N`` + ``MPI.COMM_WORLD``; the north star
(`BASELINE.json:north_star`) replaces that with "one controller process per
TPU host, topology from TPU slice metadata, no MPI launcher in the loop".

One env contract covers the whole stack (shared with
:mod:`chainermn_tpu.runtime.control_plane`):

    CHAINERMN_TPU_COORDINATOR=host:port   rank-0 host
    CHAINERMN_TPU_NUM_PROCESSES=N
    CHAINERMN_TPU_PROCESS_ID=r

``init_distributed()`` wires ``jax.distributed.initialize`` from it —
the JAX coordination service listens on ``port + 1`` (the control plane
owns ``port``).  On a multi-host TPU slice the arguments can be omitted
entirely: ``jax.distributed.initialize()`` discovers everything from
slice metadata, which IS the no-launcher path.  A slice of ONE host —
what the TPU runtime's own environment says with a single entry in
``TPU_WORKER_HOSTNAMES`` — is the plain case: one controller already
drives every chip of the host, and nothing is initialized or probed.  On
CPU the explicit path also selects gloo cross-process collectives so the
multi-controller tests/examples run on any machine.
"""

from __future__ import annotations

import os
import sys
from typing import Optional


def install_crash_dumps(out_dir: Optional[str] = None,
                        rank: Optional[int] = None,
                        recorder=None, watchdog=None,
                        signals=None, force: bool = False):
    """Wire the flight-recorder dump path to process-fatal events:

    * unhandled exceptions (``sys.excepthook`` — dump, then chain to the
      previous hook so the traceback still prints);
    * fatal signals (default: ``SIGTERM``, the preemption/kill signal —
      dump, restore the prior disposition, re-deliver);
    * native crashes (``faulthandler.enable`` into
      ``flight_<rank>.stacks.txt`` — when the interpreter cannot run the
      JSON dump, the C-level stack writer still can).

    Returns an ``uninstall()`` callable, or ``None`` (installing nothing)
    when observability is disabled and ``force`` is not set.  When a
    ``watchdog`` handle is passed, dumps go through its cross-rank state
    exchange; otherwise the dump is local-only.
    """
    import faulthandler
    import signal as _signal

    from chainermn_tpu.observability import flight_recorder as _flight

    rec = recorder if recorder is not None else _flight.get_flight_recorder()
    if rec is None:
        if not force:
            return None
        rec = _flight.install_flight_recorder()
    if out_dir is None:
        out_dir = os.environ.get("CHAINERMN_TPU_FLIGHT_DIR", ".")
    if rank is None:
        rank = int(os.environ.get("CHAINERMN_TPU_PROCESS_ID", "0") or 0)

    def _dump(reason: str) -> None:
        try:
            if watchdog is not None:
                watchdog.dump_now(reason)
            else:
                # crash-time evidence stamp: ring capacity + overwrite
                # count travel in the dump so a restart manifest can
                # flag a truncated evidence window (the dump itself
                # also records both; stamping here keeps the contract
                # explicit even for pre-ring readers of `extra`)
                rec.dump(out_dir=out_dir, rank=rank, reason=reason,
                         extra={"crash_dump": True,
                                "ring_capacity": int(rec.capacity),
                                "dropped_events": int(rec.dropped_events)})
        except Exception:
            pass  # the dump path must never mask the original failure

    prev_hook = sys.excepthook

    def _excepthook(tp, val, tb):
        _dump(f"unhandled_exception:{tp.__name__}: {val}")
        prev_hook(tp, val, tb)

    sys.excepthook = _excepthook

    fh_file = None
    try:
        os.makedirs(out_dir or ".", exist_ok=True)
        fh_file = open(os.path.join(out_dir or ".",
                                    f"flight_{rank}.stacks.txt"), "w")
        faulthandler.enable(file=fh_file)
    except OSError:
        fh_file = None

    prev_handlers = {}
    sigs = signals if signals is not None else (_signal.SIGTERM,)
    for sig in sigs:
        try:
            prev = _signal.getsignal(sig)

            def _handler(signum, frame, _prev=prev):
                _dump(f"signal:{_signal.Signals(signum).name}")
                restore = _prev if (callable(_prev) or _prev in (
                    _signal.SIG_IGN, _signal.SIG_DFL)) else _signal.SIG_DFL
                _signal.signal(signum, restore)
                os.kill(os.getpid(), signum)  # re-deliver to prior handler

            _signal.signal(sig, _handler)
            prev_handlers[sig] = prev
        except (ValueError, OSError):
            pass  # not the main thread, or unsupported signal

    def uninstall():
        sys.excepthook = prev_hook
        for sig, prev in prev_handlers.items():
            try:
                _signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        if fh_file is not None:
            try:
                faulthandler.disable()
                fh_file.close()
            except (OSError, ValueError):
                pass

    return uninstall


_SLICE_ENV = ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
              "TPU_ACCELERATOR_TYPE")


def _tpu_metadata_present() -> bool:
    """True when this host looks like part of a Cloud TPU slice.

    On standard Cloud TPU VMs ``JAX_PLATFORMS`` is typically unset (the
    TPU plugin is auto-discovered), so platform config alone cannot
    decide whether the no-arg ``jax.distributed.initialize()`` pod path
    should run.  Check the slice-metadata env the TPU runtime exports
    (any one suffices).  Deliberately NOT a libtpu-presence check: the
    wheel being installed says nothing about running on a slice, and a
    false positive here costs an off-GCP metadata-server probe.  Nor
    ``TPU_SKIP_MDS_QUERY``: ``import jax`` sets that itself on a host
    where it finds no chip.
    """
    return any(os.environ.get(var) for var in _SLICE_ENV)


def _declared_single_host() -> bool:
    """True when the TPU runtime's environment declares a slice of one
    host: ``TPU_WORKER_HOSTNAMES`` lists a single worker and no
    multislice coordinator is named.  An undeclared worker list is NOT
    single-host — on a GCE pod it lives on the metadata server, and only
    ``jax.distributed.initialize()`` can ask."""
    hosts = [h for h in os.environ.get(
        "TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    return (len(hosts) == 1
            and not os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def init_distributed(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_count: Optional[int] = None,
) -> None:
    """Initialize the JAX multi-controller runtime from args or env.

    No-op when neither args, env, nor TPU metadata indicate a
    multi-process world (single-controller remains the default).
    """
    import jax

    coordinator = coordinator or os.environ.get("CHAINERMN_TPU_COORDINATOR")
    if num_processes is None:
        n = os.environ.get("CHAINERMN_TPU_NUM_PROCESSES")
        num_processes = int(n) if n else None
    if process_id is None:
        r = os.environ.get("CHAINERMN_TPU_PROCESS_ID")
        process_id = int(r) if r else None

    # IMPORTANT: nothing in this function may query the backend
    # (jax.devices()/default_backend()) before initialize() — that would
    # initialize XLA and make jax.distributed.initialize() fail.
    if coordinator is None and num_processes is None and process_id is None:
        # TPU pod path: `jax.distributed.initialize()` with no args reads
        # slice metadata.  Attempt it when the configured platform looks
        # like TPU — or when Cloud TPU metadata is present even though
        # JAX_PLATFORMS is unset (the common case: the TPU plugin is
        # auto-discovered, nobody exports JAX_PLATFORMS) — unless the
        # runtime declares a one-host slice: single-controller is then
        # the whole world, and the call would only probe a metadata
        # server that a sealed host cannot reach.  Off-TPU stay
        # single-controller.
        platforms = (os.environ.get("JAX_PLATFORMS")
                     or getattr(jax.config, "jax_platforms", None) or "")
        on_slice = "tpu" in platforms or (
            "cpu" not in platforms and _tpu_metadata_present())
        if on_slice and not _declared_single_host():
            try:
                jax.distributed.initialize()
            except RuntimeError as e:
                # "already initialized" is fine.  Anything else is a
                # multi-host bootstrap that did not come up, and must
                # not be swallowed: each host silently proceeding as its
                # own single-controller world would train divergent
                # models.
                if "already" not in str(e).lower():
                    raise
        install_crash_dumps()   # no-op when observability is disabled
        return

    if coordinator is None or num_processes is None or process_id is None:
        raise ValueError(
            "multi-process bootstrap needs coordinator, num_processes and "
            "process_id (args or CHAINERMN_TPU_* env)")

    host, port = coordinator.rsplit(":", 1)
    jax_coord = f"{host}:{int(port) + 1}"   # control plane owns `port`

    platforms = (os.environ.get("JAX_PLATFORMS")
                 or getattr(jax.config, "jax_platforms", None) or "")
    if not platforms or platforms.startswith("cpu"):
        # cross-process CPU collectives (the tests' multi-host analogue)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if local_device_count is not None:
        jax.config.update("jax_num_cpu_devices", local_device_count)

    jax.distributed.initialize(
        coordinator_address=jax_coord,
        num_processes=num_processes,
        process_id=process_id,
    )
    install_crash_dumps(rank=process_id)  # no-op when observability is off


__all__ = ["init_distributed", "install_crash_dumps"]
