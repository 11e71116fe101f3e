"""Which part of the program a device event belongs to, read from the
named scopes the program opens (``jax.named_scope``: ``chainermn.grad``,
``chainermn.allreduce_grad``, ``chainermn.pack`` / ``chainermn.unpack``,
``chainermn.plan.<i>.<op>``, ``chainermn.update``, ``chainermn.report``;
docs/observability.md) and the ones flax opens around every module method
(``block_3``, ``qkv``, ``head``, ``BatchNorm_7``, ...).

A scope is HLO metadata: every instruction of the compiled step carries the
``op_name`` of the JAX operation it came from, such as

    jit(inner)/shard_map/chainermn.grad/transpose(jvp(TransformerLM))/block_1/qkv/dot_general

The chip's trace names a device event by its instruction's text WITHOUT
that metadata, and gives it no stat that holds it (looked at on the chip, PR
24: an "XLA Ops" event has ``device_offset_ps``, ``device_duration_ps`` and
a time scale, nothing else).  So the scope of an event comes from the
compiled program's own text: ``parse`` reads ``compiled.as_text()``,
``instruction_scopes`` makes ``{instruction name: op_name}`` of it and
``event_scopes`` keys it by the trace's event names.  The EVENTS document of
``reduce_trace`` gains ONE key from it,

    "scopes": {event name as in "devices": op_name, ...}

and everything below reads such a document: ``python3 -m chipbench.run
--trace 1`` adds the key itself (``harness.Run.compile(read_scopes=True)``
keeps the table, ``run.main`` keys it by the trace's names).  Without the
key (the fixtures of PR 23) ``of`` returns None and every reader built on it
returns None.

**An instruction the compiler made has no op_name** (a prefetch
``copy-start`` / ``copy-done``, a relayout ``copy``, the
``dynamic-update-slice`` chain a big ``concatenate`` becomes).  It exists to
feed something: ``instruction_scopes`` names it after the nearest named
instruction that consumes its result, and marks the name as inherited with
a leading ``~``, so that a reader can count what was named outright and what
by its consumer.

**A fusion carries one op_name, its root's.**  XLA fuses across scope
boundaries (the cast back from the wire dtype into the optimizer's pass,
the last backward matmul into the parameter update), and the whole fusion
then counts under the scope of the instruction it was named after.
``mixed_fusions`` counts the fusions whose fused instructions come from
more than one of the top-level program scopes, so that the blur has a
number beside ``scope_unnamed_share``.

Time under a scope is SELF time (``reduce_trace.self_times``), or a union of
intervals where the question is "how long was this in flight": one interval
library, ``reduce_trace``'s.
"""

from __future__ import annotations

import fnmatch
import re

from chipbench import reduce_trace

PROGRAM = "chainermn.*"
GRAD = "chainermn.grad"
ALLREDUCE_GRAD = "chainermn.allreduce_grad"
UPDATE = "chainermn.update"
REPORT = "chainermn.report"
# the scopes make_train_step's body is cut into; with "other program scope"
# and "no program scope" they partition a device's time
TOP_LEVEL = (GRAD, ALLREDUCE_GRAD, UPDATE, REPORT)
# leads an op_name taken from the instruction's nearest named consumer
INHERITED = "~"

_SEPARATORS = re.compile(r"[/()]+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_REFERENCE = re.compile(r"%([\w.\-]+)")
_ASYNC_EDGE = re.compile(r"^(?P<kind>[\w.\-]+?)-(?P<edge>start|done)(?:\.\d+)?$")


# ---- one op_name -----------------------------------------------------------

def segments(op_name):
    """The names along an ``op_name``, transforms unwrapped:
    ``a/transpose(jvp(M))/b/mul`` gives ``[a, transpose, jvp, M, b, mul]``.
    A scope's name holds no ``/`` and no parenthesis (the naming rule), so
    nothing is lost.  The mark of an inherited name is not part of it (the
    chains of PR 29 carry op_names that START with the scope, no ``jit(``
    before it)."""
    return [part for part
            in _SEPARATORS.split(op_name.removeprefix(INHERITED)) if part]


def under(op_name, *patterns):
    """Whether the path goes through a scope matching one of ``patterns``
    (``chainermn.update``, ``block_*``)."""
    return any(fnmatch.fnmatchcase(part, pattern)
               for part in segments(op_name) for pattern in patterns)


def is_backward(op_name):
    """JAX wraps what the backward pass runs in ``transpose(...)``; a
    rematerialized forward is inside it too, and counts as backward: it is
    time the backward pass costs."""
    return "transpose(" in op_name


def top_level(op_name):
    """The outermost program scope of the path: one of ``TOP_LEVEL``,
    ``"other"`` for another ``chainermn.*`` scope met first, or None."""
    for part in segments(op_name):
        if part in TOP_LEVEL:
            return part
        if fnmatch.fnmatchcase(part, PROGRAM):
            return "other"
    return None


# ---- from the compiled program's text --------------------------------------

def parse(hlo_text):
    """A compiled program's text (``compiled.as_text()``) as ``{instruction: [op_name or None, [called computations], [users]]}``
    and ``{computation: [root instruction, [instructions]]}``, instructions
    and users in the text's order."""
    instructions, computations, current = {}, {}, None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found and current is not None:
            name = found.group(2)
            op_name = _OP_NAME.search(line)
            instructions[name] = [
                op_name.group(1).replace("\\'", "'") if op_name else None,
                _CALLS.findall(line), []]
            current[1].append(name)
            if found.group(1):
                current[0] = name
            head = line.split(", metadata={", 1)[0]
            for operand in set(_REFERENCE.findall(head[found.end():])):
                if operand in instructions and operand != name:
                    instructions[operand][2].append(name)
            continue
        header = _COMPUTATION.match(line)
        if header:
            current = computations.setdefault(header.group(1), [None, []])
    return instructions, computations


def _nearest_named_user(name, instructions, named):
    """Breadth first along the users of ``name``: the op_name of the
    nearest instruction downstream that has one."""
    seen, level = {name}, [name]
    while level:
        following = []
        for at in level:
            for user in instructions[at][2]:
                if user in named:
                    return named[user]
                if user not in seen:
                    seen.add(user)
                    following.append(user)
        level = following
    return None


def _inside(name, instructions, computations, seen=()):
    """The op_names of the instructions that a fusion, a loop or a call
    holds."""
    names = []
    for called in instructions[name][1]:
        if called not in computations or called in seen:
            continue
        for inner in computations[called][1]:
            if instructions[inner][0]:
                names.append(instructions[inner][0])
            names.extend(_inside(inner, instructions, computations,
                                 seen + (called,)))
    return names


def instruction_scopes(program):
    """``{instruction name: op_name}`` for every instruction of a parsed
    program that can be given one.  An instruction without metadata
    that calls a computation (a fusion the compiler made late) takes the
    op_name of that computation's root, or else the commonest one inside;
    any other takes its nearest named consumer's, led by ``INHERITED``."""
    instructions, computations = program
    table = {}
    for name, (op_name, called, _) in instructions.items():
        if not op_name and called and called[0] in computations:
            root = computations[called[0]][0]
            op_name = instructions[root][0] if root else None
            if not op_name:
                inside = _inside(name, instructions, computations)
                op_name = max(set(inside), key=inside.count) if inside else None
        if op_name:
            table[name] = op_name
    inherited = {}
    for name in instructions:
        if name not in table:
            op_name = _nearest_named_user(name, instructions, table)
            if op_name:
                inherited[name] = INHERITED + op_name
    table.update(inherited)
    return table


def mixed_fusions(program):
    """The instructions whose fused (or called) instructions come from more
    than one top-level program scope: where "a fusion carries its root's
    op_name" puts time under the wrong name."""
    instructions, computations = program
    mixed = []
    for name in instructions:
        tops = {top_level(n) for n in _inside(name, instructions, computations)}
        if len(tops - {None}) > 1:
            mixed.append(name)
    return mixed


def instruction_of(event_name):
    """The instruction behind an event name as ``reduce_trace.short_name``
    leaves it (``fusion.9 f32[1,8191,49152]``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def event_scopes(events, table):
    """The value of the EVENTS document's ``"scopes"`` key: the op_name of
    every distinct event name of every device ("" where the program's text
    gives none)."""
    return {name: table.get(instruction_of(name), "")
            for ops in events["devices"].values() for name, _, _ in ops}


# ---- from an EVENTS document -----------------------------------------------

def of(events):
    """The document's ``{event name: op_name}``, or None before PR 24."""
    return events.get("scopes")


def readable(events):
    """Whether a reader by scope finds anything to read."""
    return of(events) is not None and bool(events["devices"])


def time_where(events, matches):
    """Summed self time, in nanoseconds, of the first device's events whose
    op_name ``matches``."""
    scopes = of(events)
    return reduce_trace.time_of(
        reduce_trace.first_device(events),
        lambda name: matches(scopes.get(name, "")))


def spans_where(events, matches):
    """Merged ``[start, end]`` intervals in which an event whose op_name
    ``matches`` is in flight on the first device.  An asynchronous pair
    (``...-start`` / ``...-done``) spans from its start's beginning to its
    done's end (the k-th done of a kind answers the k-th start) — where
    the program asked for it.  A pair named by its consumer is the compiler's
    own prefetch (``copy-start``, ``slice-start``): issued early on purpose,
    it costs the core its two ends and no more."""
    scopes = of(events)
    out, open_starts = [], {}
    for name, start, duration in sorted(
            reduce_trace.first_device(events), key=lambda e: e[1]):
        path = scopes.get(name, "")
        if not matches(path):
            continue
        edge = (None if path.startswith(INHERITED)
                else _ASYNC_EDGE.match(instruction_of(name)))
        began = start
        if edge and edge.group("edge") == "start":
            open_starts.setdefault(edge.group("kind"), []).append(start)
        elif edge and open_starts.get(edge.group("kind")):
            began = open_starts[edge.group("kind")].pop(0)
        out.append([began, start + duration])
    return reduce_trace.union(out)


def exposed(events, matches):
    """The parts of ``spans_where(matches)`` that no leaf operation outside
    the scope covers: time the device spends on the scope and on nothing
    else."""
    scopes = of(events)
    others = reduce_trace.union(
        [[start, start + duration] for name, start, duration
         in reduce_trace.leaf_ops(reduce_trace.first_device(events))
         if not matches(scopes.get(name, ""))])
    return reduce_trace.subtract(spans_where(events, matches), others)


def by_top_level(events):
    """``{scope: self nanoseconds}`` over ``TOP_LEVEL``, ``"other"`` and
    ``"none"``: a partition of the first device's summed self time."""
    scopes = of(events)
    totals = dict.fromkeys(TOP_LEVEL + ("other", "none"), 0.0)
    for name, ns in reduce_trace.self_times(
            reduce_trace.first_device(events)).items():
        totals[top_level(scopes.get(name, "")) or "none"] += ns
    return totals


def ms_per_step(events, host, matches):
    """What most readers report: self time under a scope, in milliseconds a
    step; None where the document has no scopes or no device."""
    if not readable(events):
        return None
    return time_where(events, matches) / 1e6 / host["steps"]


def describe(events, table, mixed):
    """How far the names reach, for a run's ``"phase": "scopes"`` line:
    instructions with an op_name (``table``), the first device's self time
    by top-level scope (milliseconds over the traced window), and how much
    of it lies in fusions that mix top-level scopes (``mixed``; a fusion
    carries one op_name, its root's) or in compiler-made instructions named
    after their consumer."""
    named = of(events)
    line = {"instructions_with_op_name": len(table),
            "event_names": len(named),
            "event_names_without_scope": sum(not v for v in named.values()),
            "mixed_fusions": len(mixed)}
    if events["devices"]:
        mixed = set(mixed)
        own = reduce_trace.self_times(reduce_trace.first_device(events))
        line["window_ms_by_top_level"] = {
            scope: ns / 1e6 for scope, ns in by_top_level(events).items()}
        line["window_ms_in_mixed_fusions"] = sum(
            ns for name, ns in own.items()
            if instruction_of(name) in mixed) / 1e6
        line["window_ms_named_by_consumer"] = sum(
            ns for name, ns in own.items()
            if named[name].startswith(INHERITED)) / 1e6
    return line
