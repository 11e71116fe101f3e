"""Abstract communicator API.

Reference being rebuilt (path unverified, SURVEY.md provenance):
``CommunicatorBase`` in 〔chainermn/communicators/communicator_base.py〕 —
properties ``rank/size/intra_rank/inter_rank/...``, object and array
``send/recv/bcast/gather/alltoall``, and the two gradient entry points
``allreduce_grad(model)`` / ``bcast_data(model)``.

TPU-native re-interpretation (NOT a port — see README):

* The reference world is one MPI rank per GPU.  Here there are two levels:

  - **host level** — one controller process per host.  ``rank``/``size`` (and
    the whole object plane: ``send_obj``, ``bcast_obj``, ...) are host-level,
    carried by the DCN control plane.  This is what gates logging to rank 0
    and shards datasets, exactly where the reference used its MPI rank.
  - **device level** — the mesh.  Array collectives (``allreduce``, ``bcast``,
    ``allgather``, ``alltoall``, ...) are *traced* ops: they run inside an
    SPMD region (``jax.shard_map`` over the communicator's mesh) where each
    device plays the role of a reference rank; ``comm.axis_index()`` is the
    device-level rank.  ``comm.run_spmd(f, *args)`` launches such a region
    from eager code (the analogue of "everyone executes the script under
    mpiexec").

* ``allreduce_grad`` / ``bcast_data`` are functional: they take and return
  pytrees instead of mutating a Chainer link in place.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, List, Optional


class CommunicatorBase(abc.ABC):
    # ---- host-level topology (the reference's rank properties) -------------
    @property
    @abc.abstractmethod
    def rank(self) -> int:
        """Host-level rank (controller process index).  Use for rank-0 gating
        of logging/checkpointing, as the reference does with its MPI rank."""

    @property
    @abc.abstractmethod
    def size(self) -> int:
        """Total number of *devices* in the data-parallel world — the
        gradient-averaging denominator, as in the reference where one rank
        owned one GPU."""

    @property
    @abc.abstractmethod
    def host_size(self) -> int: ...

    @property
    @abc.abstractmethod
    def intra_rank(self) -> int: ...

    @property
    @abc.abstractmethod
    def intra_size(self) -> int: ...

    @property
    @abc.abstractmethod
    def inter_rank(self) -> int: ...

    @property
    @abc.abstractmethod
    def inter_size(self) -> int: ...

    # ---- object plane (control plane over DCN; reference: pickled MPI) -----
    @abc.abstractmethod
    def send_obj(self, obj: Any, dest: int, tag: int = 0) -> None: ...

    @abc.abstractmethod
    def recv_obj(self, source: int, tag: int = 0) -> Any: ...

    # Every collective object op carries ``tag=`` end to end — reserved
    # bands (telemetry, barrier, ...) ride these entry points, so a
    # communicator that narrowed the signature would strand them (see
    # runtime.control_plane.RESERVED_TAG_BANDS and the
    # wrapper-surface-drift protocol lint rule).
    @abc.abstractmethod
    def bcast_obj(self, obj: Any, root: int = 0, tag: int = 0) -> Any: ...

    @abc.abstractmethod
    def gather_obj(self, obj: Any, root: int = 0,
                   tag: int = 0) -> Optional[List[Any]]: ...

    @abc.abstractmethod
    def allgather_obj(self, obj: Any, tag: int = 0) -> List[Any]: ...

    @abc.abstractmethod
    def scatter_obj(self, objs: Optional[List[Any]], root: int = 0,
                    tag: int = 0) -> Any: ...

    @abc.abstractmethod
    def allreduce_obj(self, obj: Any,
                      op: "str | Callable[[Any, Any], Any]" = "sum",
                      tag: int = 0) -> Any:
        """Reduce picklable objects across hosts.  ``op``: "sum"/"prod"/
        "max"/"min" (applied structurally through dicts/lists, ndarray-aware)
        or any binary callable for custom reducibles."""

    @abc.abstractmethod
    def barrier(self, tag: int = 900) -> None: ...

    # ---- device plane (traced SPMD collectives) ----------------------------
    @abc.abstractmethod
    def axis_index(self): ...

    @abc.abstractmethod
    def allreduce(self, x, op: str = "sum"): ...

    @abc.abstractmethod
    def bcast(self, x, root: int = 0): ...

    @abc.abstractmethod
    def allgather(self, x): ...

    @abc.abstractmethod
    def alltoall(self, xs): ...

    @abc.abstractmethod
    def gather(self, x, root: int = 0): ...

    @abc.abstractmethod
    def scatter(self, x, root: int = 0): ...

    @abc.abstractmethod
    def run_spmd(self, f: Callable, *stacked_args): ...

    # ---- gradient entry points (the hot path) ------------------------------
    @abc.abstractmethod
    def allreduce_grad(self, grads, *, compressor=None, state=None,
                       like=None): ...

    @abc.abstractmethod
    def bcast_data(self, params): ...

    # ---- sub-communicators -------------------------------------------------
    @abc.abstractmethod
    def split(self, color: int, key: int) -> "CommunicatorBase": ...
