"""The comparison that decides ``correct``: what the timed path
produced in its first dispatches against the plain float32 reference
following the same steps from the same seed.

Numbers compared (each only where ``limits/<workload>.json`` gives it a
limit; the others are printed as readings):

  loss_gap        widest |program - reference| / reference over the
                  compared dispatches' last-step losses
  m1_worst/median per leaf, the gap between the program's and the
                  reference's norm of Adam's first moment after the
                  first compared dispatch (after one step it is
                  (1-beta1) x the first gradient as the optimizer got
                  it), over the larger of the reference's norm of that
                  leaf and of the median leaf; the worst and the median
                  leaf
  delta_worst/median  the same for the parameters' change after the
                  last compared dispatch, leaving out leaves whose
                  reference gradient is under a thousandth of the
                  median leaf's (they move by round-off alone)
  moved_worst     per leaf, the gap between how many elements of it the
                  program changed and how many the reference changed,
                  over the latter; the worst leaf (the same leaves left
                  out). Adam moves every element whose gradient is not
                  nought, whatever its size, so this is exact under any
                  masks: rows of an embedding that the batch does not
                  touch stay, and a cotangent flushed to nought stays
  m1_all, delta_all   the same two gaps with every leaf taken together
                  (one norm over all parameters): the steadiest of the
                  three, since the large leaves carry it

The gap is between norms, not the norm of a difference: the program's
dropout masks are its own (hardware generator), so element-wise
agreement is not defined; a norm is steady over masks.
"""

import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))


def leaf_gaps(program, reference, skip=()):
    """{leaf: |program - reference| / max(reference leaf, median
    reference leaf)} over the leaves not in ``skip``."""
    names = [n for n in reference if n not in skip]
    floor = statistics.median(reference[n] for n in names)
    return {n: abs(program[n] - reference[n])
            / max(reference[n], floor, 1e-30) for n in names}


def all_gap(program, reference, skip=()):
    """The gap between the two norms taken over every leaf together
    (the root of the summed squares of the leaves' norms)."""
    names = [n for n in reference if n not in skip]
    p = sum(program[n] ** 2 for n in names) ** 0.5
    r = sum(reference[n] ** 2 for n in names) ** 0.5
    return abs(p - r) / max(r, 1e-30)


def still_leaves(grad_norms):
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's."""
    med = statistics.median(grad_norms.values())
    return {n for n, v in grad_norms.items() if v < 1e-3 * med}


def readings(program, reference):
    """``program``: {"loss": [one per compared dispatch], "first":
    {"m1": {...}}, "last": {"delta": {...}, "moved": {...}}}.
    ``reference``: what ``reference.common.train`` returned with
    matching snapshots: {"loss": [...], "grad1": {...}, "first":
    {"m1"}, "last": {"delta", "moved"}}."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["loss"], reference["loss"]))
    m1 = leaf_gaps(program["first"]["m1"], reference["first"]["m1"])
    still = still_leaves(reference["grad1"])
    delta = leaf_gaps(program["last"]["delta"],
                      reference["last"]["delta"], skip=still)
    moved = {n: abs(program["last"]["moved"][n] - r) / max(r, 1.0)
             for n, r in reference["last"]["moved"].items()
             if n not in still}
    worst_moved = max(moved, key=moved.get)
    worst_m1 = max(m1, key=m1.get)
    worst_delta = max(delta, key=delta.get)
    return {
        "loss_gap": loss_gap,
        "m1_worst": m1[worst_m1],
        "m1_median": statistics.median(m1.values()),
        "delta_worst": delta[worst_delta],
        "delta_median": statistics.median(delta.values()),
        "m1_all": all_gap(program["first"]["m1"],
                          reference["first"]["m1"]),
        "delta_all": all_gap(program["last"]["delta"],
                             reference["last"]["delta"], skip=still),
        "moved_worst": moved[worst_moved],
    }, {"m1_worst_leaf": worst_m1, "delta_worst_leaf": worst_delta,
        "moved_worst_leaf": worst_moved,
        "still_leaves": sorted(still)}


def load_limits(workload):
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def decide(values, limits):
    """(correct, compared): ``compared`` is {name: {"value", "limit"}}
    for every number that has a limit; correct iff each is at or under
    its own."""
    compared = {n: {"value": values[n], "limit": lim}
                for n, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] and c["value"] == c["value"]
             for c in compared.values())
    return ok, compared
