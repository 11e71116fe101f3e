"""What the compiler added to a compiled step, counted from its text.

``compiled_step_census(compiled.as_text())`` is for an operator who has the
compiled program and no trace: how many instructions run a second time
(XLA's rematerialised clones and what ``jax.checkpoint`` recomputes, by the
module they belong to), how many copies the compiler put in (by kind, with
the bytes they move), how many values it placed in the chip's fast
memory (``S(1)`` in a result's layout), and how many Pallas kernels the
step holds under each name the chip's trace will show them by.  It is a ``program_counter`` beside
``all_reduce_overlap_census`` and counts INSTRUCTIONS, not time: what the
clones and copies cost on the device is read from a trace
(``forward_recompute_ratio``, ``compiler_copy_ms``: ``chipbench/parts.py``).

Only instructions that leave a value in memory are counted: those of the
entry computation and of the loops, branches and calls it reaches, not the
ones inside a fusion or an ``async-start``'s wrapped computation (a fused
computation's parameters repeat the layouts of the fusion's operands,
``S(1)`` included).
"""

from __future__ import annotations

import re

from chainermn_tpu.analysis.hlo import (
    HLO_DTYPE_BYTES,
    _COMPUTATION_RE,
    _INSTRUCTION_RE,
    _OP_NAME_RE,
    _logical_lines,
)

# a computation that describes ONE instruction of its caller: a fusion's, or
# the wrapper the chip's own text puts around an asynchronous operation
_FUSED_RE = re.compile(r"\b(?:fusion|async-start)\(.*\bcalls=%?([\w.\-]+)")
# one array of a result shape with its layout: f32[8,128]{1,0:T(8,128)S(1)}
_ARRAY_RE = re.compile(r"(\w+)\[([0-9,]*)\](\{[^{}]*\})?")
_NUMBERED = re.compile(r"_\d+\b")
_SERIAL = re.compile(r"\.\d+$")
_KERNEL = 'custom_call_target="tpu_custom_call"'
_PALLAS = "pallas_call"
_CLONE = ".remat"
_RECOMPUTED = "rematted_computation"
COPY_KINDS = ("copy", "copy-start", "copy-done")
FAST_MEMORY = "S(1)"


def _module(op_name):
    """The module path of an op_name: the scopes between the transforms'
    wrappers (``jit(inner)``, ``transpose(jvp(Model))``) and the primitive,
    numbered layers folded (``layer_*/conv/in_proj``); "" without one."""
    pieces = op_name.split("/")[:-1]
    if _RECOMPUTED in pieces:
        pieces = pieces[pieces.index(_RECOMPUTED) + 1:]
    wrapped = [i for i, piece in enumerate(pieces) if "(" in piece]
    if wrapped:
        pieces = pieces[wrapped[-1] + 1:]
    return _NUMBERED.sub("_*", "/".join(pieces))


def _arrays(shape):
    """``(bytes, in fast memory)`` of every array of a result shape."""
    for dtype, dims, layout in _ARRAY_RE.findall(shape):
        if dtype not in HLO_DTYPE_BYTES:
            continue
        count = 1
        for dim in dims.split(","):
            count *= int(dim) if dim else 1
        yield count * HLO_DTYPE_BYTES[dtype], FAST_MEMORY in layout


def _count(table, key):
    table[key] = table.get(key, 0) + 1


def compiled_step_census(hlo_text: str) -> dict:
    """Counts over the instructions of a compiled program's text that leave
    a value in memory (see the module's text):

    ``instructions``; ``remat_clones`` and ``remat_clones_by_module`` (XLA's
    own rematerialisation: an instruction named ``<original>.remat...``
    keeps its original's op_name, so a reader by scope takes it for forward
    work); ``checkpoint_recomputed`` and ``..._by_module`` (what
    ``jax.checkpoint`` runs again in the backward pass, under
    ``rematted_computation``); ``copies`` (``{kind: count}`` over ``copy``,
    ``copy-start``, ``copy-done``), ``copies_without_op_name`` (the ones the
    compiler made, against a transpose the program asked for) and
    ``copy_bytes`` (what ``copy`` and ``copy-done`` results hold: each moved
    buffer once); ``fast_memory_values`` (result arrays whose layout says
    ``S(1)``: a count of placements over the whole program, not a size that
    is live at once); ``pallas_kernels_by_module`` (the ``tpu_custom_call``
    instructions that a ``pallas_call`` lowered to, by the name the compiler
    gave them less its serial number, which is the innermost module or scope
    around the call and what a reader of the chip's trace finds them by:
    ``{"swa": 12, "moe": 36, "chainermn.rope": 20}``; XLA's own kernels,
    ``ragged-dot-*``, are not counted)."""
    lines = _logical_lines(hlo_text)
    fused = {found.group(1) for found in map(_FUSED_RE.search, lines)
             if found}
    census = {"instructions": 0,
              "remat_clones": 0, "remat_clones_by_module": {},
              "checkpoint_recomputed": 0,
              "checkpoint_recomputed_by_module": {},
              "copies": dict.fromkeys(COPY_KINDS, 0),
              "copies_without_op_name": 0, "copy_bytes": 0,
              "fast_memory_values": 0, "pallas_kernels_by_module": {}}
    inside_fusion = False
    for line in lines:
        header = _COMPUTATION_RE.match(line)
        if header:
            inside_fusion = header.group(1) in fused
            continue
        found = None if inside_fusion else _INSTRUCTION_RE.match(line)
        if not found:
            continue
        census["instructions"] += 1
        op_name = _OP_NAME_RE.search(line)
        op_name = op_name.group(1) if op_name else ""
        if _CLONE in found.group("name"):
            census["remat_clones"] += 1
            _count(census["remat_clones_by_module"], _module(op_name))
        if _RECOMPUTED in op_name.split("/"):
            census["checkpoint_recomputed"] += 1
            _count(census["checkpoint_recomputed_by_module"],
                   _module(op_name))
        if _KERNEL in line and op_name.rsplit("/", 1)[-1] == _PALLAS:
            _count(census["pallas_kernels_by_module"],
                   _SERIAL.sub("", found.group("name").lstrip("%")))
        arrays = list(_arrays(found.group("shape")))
        if found.group("op") in COPY_KINDS:
            census["copies"][found.group("op")] += 1
            census["copies_without_op_name"] += not op_name
            if found.group("op") != "copy-start":
                census["copy_bytes"] += sum(size for size, _ in arrays)
        census["fast_memory_values"] += sum(fast for _, fast in arrays)
    return census


__all__ = ["compiled_step_census"]
