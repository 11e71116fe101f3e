"""Collective parser over compiled HLO text.

The ONE parser behind every HLO-census consumer: the ``cmn-lint`` rules
(``census-drift``, ``wire-dtype-mismatch``, ``async-pair``), the
``tests/test_census.py`` gate, and the committed ``CENSUS_r*.json``
artifact (``bench_allreduce.py --census``) all read collectives through
:func:`parse_hlo_collectives` — before this module, benchmarks/ and the
test gate each carried their own regex and could drift apart.

Two HLO renderings the naive one-regex-per-line approach missed:

* **multi-line ops** — an instruction whose operand list or replica
  groups wrap across physical lines.  The parser first joins physical
  lines into logical instructions (a line that does not open a new
  ``name = shape op(...)`` binding continues the previous one).
* **async pairs** — on real TPU schedules collectives lower to
  ``all-reduce-start`` / ``all-reduce-done`` (likewise all-gather and
  collective-permute).  A start/done pair is ONE collective: it is
  counted once, at the start's position (issue order), with the payload
  read from the *done*'s result shape (the start's tuple shape would
  double-count) and the groups from the start (done ops carry none).  An
  unmatched start or done is recorded as a parse problem — the
  ``async-pair`` lint rule turns those into error findings, because an
  unmatched start in a schedule is exactly the shape of program the
  runtime hang watchdog ends up diagnosing on-mesh.
* **asynchronous-collective fusions** — the TPU compiler's own
  asynchronous form (``xla_tpu_enable_async_collective_fusion``) prints
  ONE collective several times: in the computation that an
  ``async-collective-start.N`` instruction calls, in that of every
  ``async_collective_fusion.N`` step that carries it beside other work,
  and in that of its ``async-collective-done.N``.  It is counted once, at
  the start, and marked asynchronous.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

HLO_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s8": 1, "u8": 1, "pred": 1,
}

#: base collective op kinds recognized (async suffixes handled separately)
COLLECTIVE_KINDS = (
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute", "ragged-all-to-all", "collective-broadcast",
)

_ASYNC_START = tuple(k + "-start" for k in COLLECTIVE_KINDS)
_ASYNC_DONE = tuple(k + "-done" for k in COLLECTIVE_KINDS)

# name = shape op(...) — the shape is either a tuple (...) or one token; a
# TPU layout holds parentheses of its own (``{1,0:T(8,128)(2,1)}``), so a
# tuple ends where the op begins.  The one instruction pattern of this
# package: ``compiled.py`` reads with it too
_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?P<name>%?[\w.\-]+)\s+=\s+"
    r"(?P<shape>\(.*?\)|\S+)\s+(?P<op>[a-z][\w\-]*)\(")
_COLLECTIVE_OPS = frozenset(
    kind + edge for kind in COLLECTIVE_KINDS for edge in ("", "-start", "-done"))
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# the scope the gradient exchange opens around its cast to the wire dtype
# (``_packing.pack`` and the leaf-wise lowering of ``planner.compiler``)
_PACK_SCOPE = "chainermn.pack"

# a new instruction binding starts a logical line; the HLO printer
# renders bindings with a SPACED " = " while instruction attributes
# (replica_groups=..., to_apply=...) use an unspaced "=" — that spacing
# is what separates a wrapped attribute line from a fresh binding
_BINDING_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
# computation headers / module lines never continue an instruction
_HEADER_RE = re.compile(
    r"^\s*(?:HloModule\b|ENTRY\b|%?[\w.\-]+\s*(?:\([^)]*\))?\s*->|\}|\{)")

_GROUPS_RE = re.compile(
    r"replica_groups=(\{(?:[^{}]|\{[^{}]*\})*\}"
    r"|\[[^\]]*\](?:<=\[[^\]]*\])?)")

_SHAPE_TOKEN_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


@dataclass
class HloCollective:
    """One collective in a compiled HLO module (an async start/done pair
    folds into a single record)."""
    op: str                       # base kind, e.g. "all-reduce"
    nbytes: int                   # payload from the result shape
    groups: Optional[str]         # replica_groups text (or None)
    dtype: Optional[str]          # primary result dtype token, e.g. "f32"
    name: str = ""                # HLO instruction name
    is_async: bool = False        # came from a start/done pair
    line: int = 0                 # logical-line index (schedule order)

    def as_census_dict(self) -> dict:
        """The ``bench_allreduce.py --census`` artifact record shape —
        committed CENSUS_r*.json files compare on op/bytes/groups."""
        return {"op": self.op, "bytes": self.nbytes, "groups": self.groups,
                "dtype": self.dtype}


@dataclass
class HloParse:
    """Collectives plus any structural parse problems (unmatched async
    halves); ``ops`` is in schedule order (start position for pairs)."""
    ops: List[HloCollective] = field(default_factory=list)
    problems: List[dict] = field(default_factory=list)

    def kinds(self) -> Tuple[str, ...]:
        return tuple(o.op for o in self.ops)

    def count_by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for o in self.ops:
            out[o.op] = out.get(o.op, 0) + 1
        return out


def _logical_lines(text: str) -> List[str]:
    """Join wrapped instruction renderings: a physical line that neither
    opens a new binding nor is a computation header continues the
    previous logical line."""
    out: List[str] = []
    for raw in text.splitlines():
        if not raw.strip():
            continue
        if out and not _BINDING_RE.match(raw) and not _HEADER_RE.match(raw):
            out[-1] += " " + raw.strip()
        else:
            out.append(raw)
    return out


def _shape_payload(shape_txt: str) -> Tuple[int, Optional[str]]:
    """(total bytes, primary dtype token) of a shape rendering."""
    size = 0
    dtype = None
    for dt, dims in _SHAPE_TOKEN_RE.findall(shape_txt):
        if dt not in HLO_DTYPE_BYTES:
            continue
        if dtype is None:
            dtype = dt
        count = 1
        for d in dims.split(","):
            if d:
                count *= int(d)
        size += count * HLO_DTYPE_BYTES[dt]
    return size, dtype


def _first_operand(line: str) -> Optional[str]:
    """Instruction name of the first operand inside the op's parens."""
    m = re.search(r"\(\s*(?:\([^)]*\)|\S+?)\s+(%[\w.\-]+|[\w.\-]+)", line)
    return m.group(1).lstrip("%") if m else None


_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")


def _callers(lines: List[str]) -> Dict[str, str]:
    """``{computation: the instruction that calls it}`` over a program's
    fusions (``calls=``); a fused computation has one caller."""
    callers: Dict[str, str] = {}
    for line in lines:
        if "calls=" not in line:
            continue
        caller = _BINDING_RE.match(line)
        for computation in _CALLS_RE.findall(line):
            callers[computation] = caller.group(1) if caller else ""
    return callers


def parse_hlo_collectives(hlo_text: str) -> HloParse:
    """Parse every collective out of optimized HLO text.

    Returns an :class:`HloParse`: records in schedule order with op kind,
    payload bytes, primary dtype, and replica groups; async
    start/done pairs folded into one record; unmatched halves reported in
    ``problems`` (``{"kind": "unmatched-async-start"|"unmatched-async-done",
    "op", "name", "line"}``).
    """
    parse = HloParse()
    pending_starts: Dict[str, HloCollective] = {}
    pending_order: List[str] = []
    lines = _logical_lines(hlo_text)
    callers = _callers(lines)
    owner = ""
    for i, line in enumerate(lines):
        header = _COMPUTATION_RE.match(line)
        if header:
            owner = header.group(1)
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m or m.group("op") not in _COLLECTIVE_OPS:
            continue
        opname = m.group("op")
        # a collective inside an asynchronous-collective fusion chain is
        # printed in every link: count it at the start's
        caller = callers.get(owner, "")
        if (owner.startswith("async_collective_fusion")
                or caller.startswith("async-collective-done")):
            continue
        in_chain = caller.startswith("async-collective-start")
        name = m.group("name").lstrip("%")
        nbytes, dtype = _shape_payload(m.group("shape"))
        gm = _GROUPS_RE.search(line)
        groups = gm.group(1) if gm else None
        if opname.endswith("-start"):
            rec = HloCollective(op=opname[:-len("-start")], nbytes=nbytes,
                                dtype=dtype, groups=groups, name=name,
                                is_async=True, line=i)
            pending_starts[name] = rec
            pending_order.append(name)
            continue
        if opname.endswith("-done"):
            base = opname[:-len("-done")]
            src = _first_operand(line)
            start = pending_starts.pop(src, None) if src else None
            if start is None:
                # a done op whose start we never saw: count the
                # collective (payload is real) but flag the pairing
                parse.problems.append({"kind": "unmatched-async-done",
                                       "op": base, "name": name, "line": i})
                parse.ops.append(HloCollective(
                    op=base, nbytes=nbytes, dtype=dtype, groups=groups,
                    name=name, is_async=True, line=i))
                continue
            pending_order.remove(start.name)
            # ONE collective: start's position/groups, done's payload
            # (the start renders a tuple shape that double-counts)
            start.nbytes = nbytes or start.nbytes
            start.dtype = dtype or start.dtype
            parse.ops.append(start)
            continue
        parse.ops.append(HloCollective(
            op=opname, nbytes=nbytes, dtype=dtype, groups=groups,
            name=name, is_async=in_chain, line=i))
    for name in pending_order:
        rec = pending_starts[name]
        parse.problems.append({"kind": "unmatched-async-start",
                               "op": rec.op, "name": name, "line": rec.line})
        parse.ops.append(rec)  # it is still issued — keep schedule order
    parse.ops.sort(key=lambda o: o.line)
    return parse


def collective_census(hlo_text: str) -> List[dict]:
    """Census-artifact view: op/bytes/groups/dtype dicts in schedule
    order — the exact rows ``bench_allreduce.py --census`` commits."""
    return [o.as_census_dict() for o in parse_hlo_collectives(hlo_text).ops]


def _is_wire_cast(line: str) -> bool:
    found = _INSTRUCTION_RE.match(line)
    op_name = _OP_NAME_RE.search(line)
    return bool(found and op_name and found.group("op") == "convert"
                and _PACK_SCOPE in op_name.group(1).split("/"))


def all_reduce_overlap_census(hlo_text: str) -> dict:
    """How many of a compiled program's all-reduces block the device and
    how many run beside other work, with the bytes each kind reduces — the
    engagement counter of ``MeshCommunicator.exchange_compiler_options``
    (0 % asynchronous under TPU XLA's defaults).  Asynchronous is a
    start/done pair or an asynchronous-collective fusion chain; any other
    all-reduce, a variadic one included, blocks.  Bytes are the result
    shape's, which for an all-reduce is what crosses the wire once.

    ``wire_casts`` counts the ``convert`` instructions under the scope
    ``chainermn.pack``, fused or not: the casts of gradients to the wire
    dtype that stand before the exchange's collectives.  One a leaf (or
    fewer, where the compiler merged them) on an exchange handed
    gradients wider than its wire, which then waits for them; 0 where the
    gradients arrive in the wire dtype (the double buffer's ``pending``)
    or there is no wire dtype.  It reads the text of ``compiled.as_text()``
    and of ``lowered.as_text(dialect="hlo", debug_info=True)`` alike (the
    scope is in the instructions' ``op_name``)."""
    census = {"synchronous": 0, "asynchronous": 0,
              "synchronous_bytes": 0, "asynchronous_bytes": 0,
              "wire_casts": sum(map(_is_wire_cast,
                                    _logical_lines(hlo_text)))}
    for op in parse_hlo_collectives(hlo_text).ops:
        if op.op == "all-reduce":
            kind = "asynchronous" if op.is_async else "synchronous"
            census[kind] += 1
            census[kind + "_bytes"] += op.nbytes
    total = census["synchronous_bytes"] + census["asynchronous_bytes"]
    census["asynchronous_byte_share"] = (
        census["asynchronous_bytes"] / total if total else 0.0)
    return census


__all__ = ["HLO_DTYPE_BYTES", "COLLECTIVE_KINDS", "HloCollective",
           "HloParse", "parse_hlo_collectives", "collective_census",
           "all_reduce_overlap_census"]
