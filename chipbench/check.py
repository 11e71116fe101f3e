"""How ``correct`` is decided: the timed path's first three steps against the
plain reference's, number by number, each with a limit of its own from
``limits/<cell>.json`` (PERF.md gives the readings each limit was set from).

Numbers compared:

``loss_step1..3``  |program's loss - reference's| / |reference's|, the loss
                   each step reports for its batch (rows that all differ).
                   It hardly moves with precision; its limit is there to
                   catch rows left out of a batch, or a wrong batch.
``grad_norm``      the first gradient as the optimizer gets it (read from
                   the momentum buffer after the step that applies it, so
                   all-reduced and through the wire dtype), by the worst
                   leaf: |program's norm - reference's| over the larger of
                   the reference's norm of that leaf and of the median leaf.
                   This is the number a lower precision fails.
``delta_norm``     the parameters' change after the three steps, by the
                   worst leaf in the same way.  It catches a step that
                   returns its state unchanged, or applies a gradient that
                   was not exchanged between the chips.
"""

from __future__ import annotations

import numpy as np


def leaf_gaps(program, reference):
    """Per leaf, |program - reference| over max(reference, median reference)
    (some gradients are all but zero); infinite where not finite."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    floor = np.maximum(reference, np.median(reference))
    gaps = np.abs(program - reference) / np.where(floor > 0, floor, 1.0)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def worst_leaf_gap(program, reference):
    """``(gap, leaf index)`` of the leaf with the largest gap."""
    if np.shape(program) != np.shape(reference):
        return float("inf"), -1
    gaps = leaf_gaps(program, reference)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst


def worst_leaves(program, reference, names, count=4):
    """For a look by hand: the leaves with the largest gradient-norm gap."""
    want = np.asarray(reference["grad_norms"], np.float64)
    gaps = leaf_gaps(program["grad_norms"], want)
    order = np.argsort(-gaps)[:count]
    return {"median_gap": float(np.median(gaps)),
            "median_norm": float(np.median(want)),
            "leaves": [[names[i], float(gaps[i]), float(want[i])]
                       for i in order]}


def numbers(program, reference):
    """The numbers compared, from two ``{"losses", "grad_norms",
    "delta_norms"}`` readings."""
    out = {}
    for index, (got, want) in enumerate(zip(program["losses"],
                                            reference["losses"])):
        gap = abs(got - want) / abs(want) if np.isfinite(got) else float("inf")
        out[f"loss_step{index + 1}"] = {
            "value": gap, "program": got, "reference": want}
    for name in ("grad_norm", "delta_norm"):
        gap, leaf = worst_leaf_gap(program[name + "s"], reference[name + "s"])
        out[name] = {"value": gap, "worst_leaf": leaf}
    return out


def limit_of(name, limits):
    return limits["loss"] if name.startswith("loss_step") else limits[name]


def judge(compared, limits):
    """Every number beside its limit, and whether all are within."""
    rows, ok = [], True
    for name, entry in compared.items():
        limit = limit_of(name, limits)
        within = bool(entry["value"] <= limit)
        ok = ok and within
        rows.append(dict(entry, check=name, limit=limit, within=within))
    return rows, ok
