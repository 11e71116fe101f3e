"""``CollectiveSchedule`` — the canonical ordered collective list.

A communicator flavor *is* its collective decomposition (SURVEY.md §2.1,
HiCCL's thesis in PAPERS.md), so the unit the static analyzer reasons
about is the ordered list of collectives a program will issue.  Two
extractors produce the same schedule type:

* :func:`extract_schedule` walks a traced ``ClosedJaxpr`` — through
  pjit / shard_map / scan / cond / while / custom_vjp bodies — and
  records every collective primitive (psum, all_gather, psum_scatter,
  ppermute, all_to_all, pmax, pmin) with its axes, dtype, payload, and
  nesting path.  This is the *trace-time* view: it exists before any
  backend is involved, so it runs on CPU with no TPU attached.
* :func:`schedule_from_hlo` reads the *compiled* view out of optimized
  HLO text via :mod:`chainermn_tpu.analysis.hlo` (one parser shared with
  the census gate and artifact).

Schedules canonicalize (:meth:`CollectiveSchedule.canonical`) so that
per-rank / per-config schedules can be compared for the static version
of the flight recorder's ``identify_desync``: two ranks whose canonical
schedules differ WILL wedge the mesh at the first divergence — the lint
rule names that op before anything runs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from chainermn_tpu.analysis.hlo import HloParse, parse_hlo_collectives

#: jaxpr primitives that lower to cross-device communication
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle",
    "all_gather", "all_to_all", "psum_scatter", "pgather",
    "reduce_scatter",
})


@dataclass(frozen=True)
class CollectiveOp:
    """One collective, from either extractor.  ``axes`` is the jaxpr
    axis-name tuple (None for HLO ops); ``groups`` the HLO
    replica_groups text (None for jaxpr ops)."""
    kind: str
    dtype: str
    shape: Tuple[int, ...]
    nbytes: int
    axes: Optional[Tuple[str, ...]] = None
    groups: Optional[str] = None
    path: Tuple[str, ...] = ()
    source: Optional[str] = None

    @property
    def key(self) -> tuple:
        """Order-sensitive identity used for schedule comparison: kind,
        where it communicates (axes or groups), and what it moves."""
        return (self.kind, self.axes or self.groups, self.dtype,
                self.nbytes)

    def describe(self) -> str:
        where = ("axes=" + ",".join(self.axes) if self.axes
                 else f"groups={self.groups}" if self.groups else "?")
        return (f"{self.kind}[{self.dtype}, {self.nbytes}B, {where}]"
                + (f" @ {'/'.join(self.path)}" if self.path else ""))


@dataclass
class CollectiveSchedule:
    """Ordered collectives of one traced/compiled program."""
    ops: List[CollectiveOp] = field(default_factory=list)
    origin: str = "jaxpr"            # "jaxpr" | "hlo"
    label: str = ""                  # e.g. "rank0", "flavor=xla"
    problems: List[dict] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.ops)

    def __iter__(self) -> Iterator[CollectiveOp]:
        return iter(self.ops)

    def canonical(self) -> Tuple[tuple, ...]:
        return tuple(op.key for op in self.ops)

    def kinds(self) -> Tuple[str, ...]:
        return tuple(op.kind for op in self.ops)

    def count_by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.ops:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def counts_by_axes(self, kind: str) -> Dict[tuple, int]:
        out: Dict[tuple, int] = {}
        for op in self.ops:
            if op.kind == kind:
                k = op.axes or (op.groups,)
                out[k] = out.get(k, 0) + 1
        return out

    def diff(self, other: "CollectiveSchedule") -> Optional[dict]:
        """First structural divergence against ``other`` (None when the
        canonical schedules agree) — op index, and each side's op (or
        None past the shorter schedule's end)."""
        a, b = self.canonical(), other.canonical()
        if a == b:
            return None
        for i in range(max(len(a), len(b))):
            if i >= len(a) or i >= len(b) or a[i] != b[i]:
                return {
                    "index": i,
                    "left": self.ops[i].describe() if i < len(a) else None,
                    "right": other.ops[i].describe() if i < len(b) else None,
                    "left_label": self.label,
                    "right_label": other.label,
                }
        return None  # pragma: no cover — unreachable given a != b


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(tag, jaxpr-like) children reachable through this equation's
    params — ClosedJaxprs (pjit/scan/cond bodies) and raw Jaxprs
    (shard_map)."""
    from jax.extend.core import ClosedJaxpr

    out: List[Tuple[str, Any]] = []
    for pname, v in eqn.params.items():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for j, x in enumerate(vals):
            if isinstance(x, ClosedJaxpr) or hasattr(x, "eqns"):
                tag = eqn.primitive.name
                if isinstance(v, (list, tuple)) and len(vals) > 1:
                    tag = f"{tag}[{pname}{j}]"
                out.append((tag, x))
    return out


def _aval_payload(eqn) -> Tuple[str, Tuple[int, ...], int]:
    """(dtype, shape, nbytes) across an equation's array inputs."""
    dtype, shape, nbytes = "?", (), 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        if dtype == "?":
            dtype = str(np.dtype(aval.dtype).name)
            shape = tuple(int(d) for d in getattr(aval, "shape", ()))
        try:
            nbytes += int(np.prod(aval.shape or (1,))
                          * np.dtype(aval.dtype).itemsize)
        except Exception:  # noqa: BLE001 — abstract dims etc. stay 0
            pass
    return dtype, shape, nbytes


def _eqn_axes(eqn) -> Optional[Tuple[str, ...]]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name"))
    if ax is None:
        return None
    if isinstance(ax, (list, tuple)):
        return tuple(str(a) for a in ax)
    return (str(ax),)


def _eqn_source(eqn) -> Optional[str]:
    try:
        frame = jax.api_util.summarize_source_info(eqn.source_info)  # 0.5+
    except Exception:  # noqa: BLE001
        try:
            from jax._src import source_info_util
            frame = source_info_util.summarize(eqn.source_info)
        except Exception:  # noqa: BLE001
            frame = None
    return frame


def extract_schedule(fn_or_jaxpr, *args, label: str = "",
                     **kwargs) -> CollectiveSchedule:
    """Trace-time schedule of a function (traced via ``jax.make_jaxpr``)
    or of an already-traced ``ClosedJaxpr``.

    The walk descends through every jaxpr reachable from equation params
    — pjit, shard_map, scan, while, cond branches, custom_vjp/jvp bodies
    — so collectives hidden inside control flow or custom-derivative
    wrappers are all visible.  Both branches of a ``cond`` appear in the
    schedule (tagged in ``path``): a collective in only one branch is
    exactly the divergence hazard the desync rule exists to catch.
    """
    from jax.extend.core import ClosedJaxpr

    closed = fn_or_jaxpr
    if not (isinstance(closed, ClosedJaxpr) or hasattr(closed, "eqns")):
        closed = jax.make_jaxpr(fn_or_jaxpr)(*args, **kwargs)
    sched = CollectiveSchedule(origin="jaxpr", label=label)
    seen: set = set()

    def walk(jaxpr_like, path: Tuple[str, ...]):
        if id(jaxpr_like) in seen:
            return
        seen.add(id(jaxpr_like))
        jaxpr = getattr(jaxpr_like, "jaxpr", jaxpr_like)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMITIVES:
                dtype, shape, nbytes = _aval_payload(eqn)
                sched.ops.append(CollectiveOp(
                    kind=name, dtype=dtype, shape=shape, nbytes=nbytes,
                    axes=_eqn_axes(eqn), path=path,
                    source=_eqn_source(eqn)))
            for tag, sub in _sub_jaxprs(eqn):
                walk(sub, path + (tag,))

    walk(closed, ())
    return sched


def schedule_from_hlo(hlo_text_or_parse, label: str = "") \
        -> CollectiveSchedule:
    """Compiled-view schedule from optimized HLO text (or a pre-built
    :class:`~chainermn_tpu.analysis.hlo.HloParse`).  Parse problems
    (unmatched async halves) ride along in ``problems`` for the
    ``async-pair`` rule."""
    parse = hlo_text_or_parse
    if not isinstance(parse, HloParse):
        parse = parse_hlo_collectives(parse)
    sched = CollectiveSchedule(origin="hlo", label=label,
                               problems=list(parse.problems))
    for o in parse.ops:
        sched.ops.append(CollectiveOp(
            kind=o.op, dtype=o.dtype or "?", shape=(), nbytes=o.nbytes,
            groups=o.groups, source=o.name or None))
    return sched


__all__ = ["COLLECTIVE_PRIMITIVES", "CollectiveOp", "CollectiveSchedule",
           "extract_schedule", "schedule_from_hlo"]
