"""Spawn a real multi-controller world for validation.

The reference's multi-node tests ran under ``mpiexec -n N pytest``
〔SURVEY.md §4〕; this rebuild has no launcher, so validation harnesses
(tests, the driver's ``dryrun_multichip``) spawn N controller processes
directly: each child gets the ``CHAINERMN_TPU_*`` bootstrap env contract,
its own CPU device set, and reports results as a ``RESULT {json}`` stdout
line.  This module is the ONE copy of that choreography — port pairing,
env construction, harvest, and orphan cleanup (a surviving child blocked
in a collective against a dead coordinator would outlive the whole run).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, Optional


def free_port() -> int:
    """A free TCP port (single — for control planes with no sidecar)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def free_port_pair() -> int:
    """A free TCP port whose successor is also free: the control plane
    binds the given port and jax's coordination service binds port+1."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    t = socket.socket()
    try:
        t.bind(("127.0.0.1", port + 1))
    except OSError:
        t.close()
        return free_port_pair()
    t.close()
    return port


def spawn_world(worker_src: str, n_procs: int = 2, local_devices: int = 4,
                timeout: float = 600.0,
                repo: Optional[str] = None) -> Dict[int, dict]:
    """Run ``worker_src`` in ``n_procs`` controller processes and return
    ``{rank: parsed_result}`` from each worker's ``RESULT {json}`` line.

    Workers bootstrap with ``chainermn_tpu.init_distributed(
    local_device_count=...)`` using the ``CHAINERMN_TPU_*`` env contract
    set here; ``CHAINERMN_TPU_REPO`` points at the package checkout.  The
    children are CPU-only by construction (``JAX_PLATFORMS=cpu`` in their
    environment): a chip belongs to one process, and N controllers on one
    host can only share the CPU.  On any failure every still-running child is killed before
    the error propagates — no orphans; a crashed rank surfaces as soon as
    it exits, even while its siblings are still blocked on it.

    Workers must keep their stdout/stderr small (a RESULT line plus
    incidental warnings): pipes are only drained after exit, so a child
    streaming more than the ~64 KB pipe buffer would block itself.
    """
    if repo is None:
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    coord = f"127.0.0.1:{free_port_pair()}"
    procs = []
    for r in range(n_procs):
        env = dict(os.environ)
        env.update({
            "CHAINERMN_TPU_COORDINATOR": coord,
            "CHAINERMN_TPU_NUM_PROCESSES": str(n_procs),
            "CHAINERMN_TPU_PROCESS_ID": str(r),
            "CHAINERMN_TPU_REPO": repo,
            "PYTHONPATH": repo,
            "JAX_PLATFORMS": "cpu",
            "JAX_NUM_CPU_DEVICES": str(local_devices),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", worker_src], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results: Dict[int, dict] = {}
    try:
        # Poll ALL children: a crashed rank must surface immediately, not
        # after the full timeout spent blocking on a sibling that is itself
        # only hung waiting for the dead one.
        deadline = time.monotonic() + timeout
        while True:
            states = [p.poll() for p in procs]
            for r, (p, st) in enumerate(zip(procs, states)):
                if st is not None and st != 0:
                    stdout, stderr = p.communicate()
                    raise RuntimeError(
                        f"worker rank {r} failed (rc={st})\n"
                        f"stderr:\n{stderr[-3000:]}\n"
                        f"stdout:\n{stdout[-1000:]}")
            if all(st is not None for st in states):
                break
            if time.monotonic() > deadline:
                alive = [r for r, st in enumerate(states) if st is None]
                raise RuntimeError(
                    f"spawn_world timed out after {timeout}s; "
                    f"rank(s) {alive} still running")
            time.sleep(0.1)
        for r, p in enumerate(procs):
            stdout, _ = p.communicate()
            lines = [l for l in stdout.splitlines()
                     if l.startswith("RESULT ")]
            if not lines:
                raise RuntimeError(
                    f"worker rank {r} produced no RESULT line:\n{stdout}")
            results[r] = json.loads(lines[0][len("RESULT "):])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return results
