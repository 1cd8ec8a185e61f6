"""The comparison that decides ``correct``: the program's first chunk of
optimizer steps against the plain reference's, number by number, each
under a limit of its own (the cell's traffic file holds the limits;
PERF.md the readings they were set from).

Numbers, all "smaller is closer":

    loss<k>      |program - reference| / |reference| of the loss at
                 optimizer step k (the cell lists which k)
    moment_gap   worst leaf of the updater's first moment after the
                 chunk: the gradients as the optimizer got them
    delta_gap    worst leaf of the parameters' change over the chunk
    moment_med,  the median leaf's gap of the same two: steady from
    delta_med    seed to seed where the worst leaf is one small leaf's
                 noise (PERF.md says which of these a cell is held to)
    mdiff_gap,   worst and median leaf of the norm of the *difference*
    mdiff_med    between the program's first moment after the chunk and
                 the reference's, over the reference's norm of that
                 leaf's moment or the median leaf's: a gap of norms sees
                 a bias and is blind to unbiased rounding noise, this
                 sees the noise

A leaf's gap is the distance between the program's norm and the
reference's (not the norm of their difference), over the reference's
norm of that leaf or of the median leaf, whichever is larger. Leaves
whose first gradient in the reference is under a thousandth of the
median leaf's are left out: they move by round-off alone (a bias in
front of a batch norm).
"""

import math
import statistics

import numpy as np

DEAD_LEAF_SHARE = 1e-3


def counted_leaves(reference):
    """Leaves that count: a parameter array whose first gradient in the
    reference is not nought to rounding."""
    grad1 = reference["grad1"]
    floor = DEAD_LEAF_SHARE * statistics.median(grad1.values())
    return sorted(k for k, v in grad1.items() if v >= floor)


def leaf_gaps(program, reference, leaves):
    """{leaf: gap} between the program's norm and the reference's."""
    median = statistics.median(reference[k] for k in leaves)
    return {
        k: abs(program.get(k, float("nan")) - reference[k])
        / max(reference[k], median) for k in leaves}


def worst_and_median(gaps):
    """(worst gap, its leaf, median gap); a NaN is the worst."""
    bad = [k for k, g in gaps.items() if math.isnan(g)]
    if bad:
        return float("nan"), bad[0], float("nan")
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def numbers(program, reference, loss_steps, per_leaf=None):
    """Every number compared, with the leaf a gap was read on. A dict
    given as ``per_leaf`` is filled with every leaf's reading, for
    setting limits by hand."""
    out, where = {}, {}
    per_leaf = {} if per_leaf is None else per_leaf
    for k in loss_steps:
        ref = reference["losses"][k - 1]
        out[f"loss{k}"] = abs(program["losses"][k - 1] - ref) / abs(ref)
    leaves = counted_leaves(reference)
    for name in ("moment", "delta"):
        per_leaf[name] = leaf_gaps(program[name], reference[name], leaves)
        worst, leaf, median = worst_and_median(per_leaf[name])
        out[f"{name}_gap"], where[f"{name}_gap"] = worst, leaf
        out[f"{name}_med"] = median
    if "moment_arrays" in program and "moment_arrays" in reference:
        median = statistics.median(reference["moment"][k] for k in leaves)
        per_leaf["mdiff"] = {
            k: float(np.linalg.norm(
                (np.asarray(program["moment_arrays"][k], np.float32)
                 - np.asarray(reference["moment_arrays"][k],
                              np.float32)).ravel()))
            / max(reference["moment"][k], median) for k in leaves}
        out["mdiff_gap"], where["mdiff_gap"], out["mdiff_med"] = \
            worst_and_median(per_leaf["mdiff"])
    return out, where


def decide(values, limits):
    """``(correct, checks)``: every limit's number has to be there and
    at or under its limit; ``checks`` shows each beside its limit."""
    checks, correct = {}, True
    for name, limit in limits.items():
        value = values.get(name, float("nan"))
        ok = value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    return correct, checks
