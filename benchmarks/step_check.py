"""The comparison that decides `correct` for a training cell: the timed
`train_step`'s first steps against the plain reference's
(`reference/train_steps.py`), number by number, each beside its limit.

`system` is what the loop kept of the timed object: `records`, the metrics of
its first steps as the step program returned them (`loss`, the cross-entropy,
`grad_norm`); `moment_sumsq`, every leaf's sum of squares of the optimizer's
first moment after ONE step, which is (1 - b1) x the gradient as Adam got it
(clipped), so the gradient the optimizer was handed has the leaf norm
sqrt(sumsq) / (1 - b1) x max(1, grad_norm / clip); `change_sumsq`, every
leaf's sum of squares of the parameters after the last followed step less the
seeded ones. `reference` is `train_steps.follow`'s result on the same
batches. Leaves are the reference's (the glue's layout), one set a layer.

A leaf's two norms are compared by their gap, | ||system|| - ||reference|| |,
over the reference's norm of that leaf or of the median leaf, whichever is
larger (some gradients are all but zero); the worst leaf is held to the
limit. A leaf whose reference gradient is under a thousandth of the median
leaf's moves under Adam by round-off alone (m / sqrt(v) of rounding noise is
of order one) and is left out of the change, by that rule and not by name.

The limits are the configuration's `reference` group: `step_loss_atol` (each
followed step's loss and cross-entropy), `grad_norm_rtol` (the first
gradient's whole norm), `grad_leaf_rtol`, `change_leaf_rtol` (the worst
leaf's gap); each with its readings in `reference.why`.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Mapping, Optional, Tuple

LEFT_OUT_UNDER = 1e-3       # of the median leaf's gradient norm


def leaf_norms(sumsq: Mapping[str, float], scale: float = 1.0
               ) -> Dict[str, float]:
    return {k: math.sqrt(max(v, 0.0)) * scale for k, v in sumsq.items()}


def worst_gap(system: Mapping[str, float], reference: Mapping[str, float],
              leave_out=()) -> Tuple[Optional[float], Optional[str]]:
    """(the largest gap of norms over the leaves, that leaf)."""
    kept = [k for k in reference if k not in leave_out]
    if not kept or set(system) != set(reference):
        return None, None
    median = statistics.median(reference[k] for k in kept)
    worst, where = -1.0, None
    for k in kept:
        gap = abs(system[k] - reference[k]) / max(reference[k], median, 1e-30)
        if math.isnan(gap):         # a NaN is the worst there is
            return gap, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(system: Mapping[str, Any], reference: Mapping[str, Any],
            group: Mapping[str, Any]) -> Tuple[List[List[Any]], List[str]]:
    """([name, what, value, limit] for every number compared, the
    problems)."""
    rows: List[List[Any]] = []
    problems: List[str] = []

    def hold(name: str, what: str, value: Optional[float],
             limit: Optional[float]):
        rows.append([name, what, value, limit])
        if limit is None:
            problems.append(f"the configuration's reference group has no "
                            f"limit for: {what}")
        elif value is None or not abs(value) <= limit:
            problems.append(f"{what}: {value!r} is not within {limit!r}")

    records, steps = system["records"], reference["steps"]
    if len(records) < len(steps):
        problems.append(f"the timed step reported {len(records)} of the "
                        f"{len(steps)} steps the reference followed")
    for i, (ours, theirs) in enumerate(zip(records, steps), start=1):
        hold(f"step{i}_loss_gap", f"step {i}: loss less the reference's",
             ours["loss"] - theirs["loss"], group.get("step_loss_atol"))
        hold(f"step{i}_ce_gap",
             f"step {i}: cross-entropy less the reference's",
             ours.get("ce_loss", ours["ppl_log"]) - theirs["ce"],
             group.get("step_loss_atol"))

    adamw = group["adamw"]
    norm = records[0]["grad_norm"] if records else float("nan")
    hold("grad_norm_gap",
         "first gradient: its norm over the reference's, less 1",
         norm / steps[0]["grad_norm"] - 1.0, group.get("grad_norm_rtol"))
    # what the optimizer was handed, from its first moment after one step
    unclip = max(1.0, norm / float(adamw["clip"]))
    ours = leaf_norms(system["moment_sumsq"],
                      unclip / (1.0 - float(adamw["b1"])))
    theirs = leaf_norms(reference["grad_sumsq"])
    gap, where = worst_gap(ours, theirs)
    hold("grad_leaf_gap",
         f"first gradient: the worst leaf's gap of norms ({where})", gap,
         group.get("grad_leaf_rtol"))
    if "change_sumsq" in reference:
        median = statistics.median(theirs.values())
        still = [k for k, v in theirs.items() if v < LEFT_OUT_UNDER * median]
        gap, where = worst_gap(leaf_norms(system["change_sumsq"]),
                               leaf_norms(reference["change_sumsq"]), still)
        hold("change_leaf_gap",
             f"parameters' change over {len(steps)} steps: the worst "
             f"leaf's gap of norms ({where}; {len(still)} leaves left out)",
             gap, group.get("change_leaf_rtol"))
    return rows, problems


def as_system(followed: Mapping[str, Any], adamw: Mapping[str, Any]
              ) -> Dict[str, Any]:
    """A `train_steps.follow` result put in the program's place (the
    controls: the reference at a lower precision, or with a fault planted):
    its steps as a step program would have reported them, and the first
    moment Adam would hold after one step of its clipped gradient."""
    norm = followed["steps"][0]["grad_norm"]
    held = (1.0 - float(adamw["b1"])) * min(1.0, float(adamw["clip"]) / norm)
    return {
        "records": [{"loss": s["loss"], "ppl_log": s["ce"],
                     "grad_norm": s["grad_norm"]} for s in followed["steps"]],
        "moment_sumsq": {k: v * held * held
                         for k, v in followed["grad_sumsq"].items()},
        "change_sumsq": dict(followed["change_sumsq"])}
