"""The comparison that decides ``correct`` for a training cell.

Three numbers, each with its limit from the cell's configuration file:

``loss_gap``   worst of the followed steps: |loss - reference| / reference
``grad_gap``   worst leaf: the gap between the program's and the reference's
               norm of the first step's gradient
``delta_gap``  worst leaf: the same for the change of the parameters over
               the followed steps

A leaf's gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger (some gradients are all but zero). A
leaf whose reference gradient is under a thousandth of the median leaf's
moves by round-off alone and is left out of ``delta_gap``.
"""

import statistics
import sys

NEGLIGIBLE_GRADIENT = 1e-3


def _worst_leaf(prog, ref, leaves):
    floor = statistics.median(ref[k] for k in ref)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def numbers(prog, ref):
    """{name: value} of the numbers compared, and {name: leaf or step} of
    where each was read."""
    steps = range(len(ref["loss"]))
    loss = {t: abs(prog["loss"][t] - ref["loss"][t]) / abs(ref["loss"][t])
            for t in steps}
    t_worst = max(loss, key=loss.get)
    grad, grad_at = _worst_leaf(prog["grad"], ref["grad"], list(ref["grad"]))
    median_grad = statistics.median(ref["grad"].values())
    moved = [k for k in ref["delta"]
             if ref["grad"][k] >= NEGLIGIBLE_GRADIENT * median_grad]
    delta, delta_at = _worst_leaf(prog["delta"], ref["delta"], moved)
    return ({"loss_gap": loss[t_worst], "grad_gap": grad, "delta_gap": delta},
            {"loss_gap": f"step {t_worst + 1}", "grad_gap": grad_at,
             "delta_gap": delta_at})


def decide(values, limits, extra=()):
    """(correct, {name: {"value", "limit"}}); prints each number beside its
    limit on standard error. ``extra`` are (name, value, limit) triples
    that must be at most their limit too (counts that have to be nought)."""
    compared = {k: {"value": values[k], "limit": limits[k]} for k in values}
    for name, value, limit in extra:
        compared[name] = {"value": value, "limit": limit}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict}", file=sys.stderr)
    return correct, compared
