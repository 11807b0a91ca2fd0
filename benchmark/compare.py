"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same readings of the first three steps:

    {"loss":  [l1, l2, l3],                 each step's loss
     "scalars": {name: value},              further scalars, compared alike
     "grad":  {leaf: norm of the first gradient},
     "delta": {leaf: norm of the parameters' change after three steps},
     "aux":   {leaf: the same for auxiliary state (BatchNorm's running
               statistics), where the configuration has any}}

Each number compared is a relative gap, and has a limit of its own in the
configuration's ``limits``.  A number without a limit there is printed with
``null`` and decides nothing.  Leaves are taken by the worst one: the gap
between the two norms (not the norm of a difference) against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both leaf numbers: they move by rounding alone.
"""
import math

import numpy as np

NEGLIGIBLE = 1e-3


def rel_gap(got, want):
    return abs(got - want) / max(abs(want), 1e-30)


def leaf_gaps(got, want, keep):
    """-> {leaf: gap} over the leaves in ``keep``."""
    norms = np.asarray([want[k] for k in keep], np.float64)
    floor = float(np.median(norms)) if len(norms) else 0.0
    return {k: abs(float(got[k]) - float(want[k]))
            / max(float(want[k]), floor, 1e-30) for k in keep}


def numbers(program, reference):
    """-> {name: gap}, every number this comparison knows, in print order."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out["loss_step%d" % i] = rel_gap(a, b)
    for name, want in reference.get("scalars", {}).items():
        out[name] = rel_gap(program["scalars"][name], want)
    g = reference["grad"]
    med = float(np.median([g[k] for k in g]))
    keep = [k for k in g if g[k] >= NEGLIGIBLE * med]
    for name in ("grad", "delta", "aux"):
        if name not in reference:
            continue
        gaps = leaf_gaps(program[name], reference[name],
                         list(reference[name]) if name == "aux" else keep)
        worst = max(gaps, key=gaps.get)
        out["%s_worst_leaf" % name] = gaps[worst]
        out["%s_median_leaf" % name] = float(np.median(list(gaps.values())))
        out["_%s_worst_leaf_name" % name] = worst
    return out


def check(program, reference, limits):
    """-> (correct, {name: [value, limit]}, facts to print beside them)."""
    nums = numbers(program, reference)
    correct, compared = decide(nums, limits)
    facts = {k[1:]: v for k, v in nums.items() if k.startswith("_")}
    facts["reference_loss"] = reference["loss"]
    facts["program_loss"] = program["loss"]
    return correct, compared, facts


def decide(nums, limits):
    """-> (correct, {name: [value, limit]}).  A value that is not finite
    fails its limit."""
    compared = {}
    correct = True
    for name, value in nums.items():
        if name.startswith("_"):
            continue
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is not None and not (math.isfinite(value) and value <= limit):
            correct = False
    if not any(l is not None for _, l in compared.values()):
        correct = False          # nothing was compared: not a proof
    return correct, compared
