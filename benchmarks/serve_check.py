"""The comparison that decides `correct` for a scoring cell: answers the
timed window returned against the plain reference's scores of the same
documents, each number beside its limit.

Two numbers over every token of the sample: the widest |gap| between an
answer's log-probability and the reference's (`score_gap_max`, which swings
from sample to sample by its nature: it is one token of tens of thousands),
and the root mean square of the gaps (`score_gap_rms`, steady from seed to
seed). The limits are the served configuration's (`configs/<config>.serve.
json`, `reference.score_gap_max`, `reference.score_gap_rms`, each between a
dozen seeds' sound readings on the chip and what the float8-operand
reference reads: `reference.why`). An answer that does not hold L - 1 finite
values for a document of L cannot be compared and is a problem of its own.
"""

from __future__ import annotations

from typing import Any, List, Mapping, Sequence, Tuple

import numpy as np


def gaps(answers: Sequence[Any], reference: Sequence[Any]) -> np.ndarray:
    """Every sampled token's answer less the reference's, one flat array."""
    return np.concatenate([
        np.asarray(a, np.float64) - np.asarray(r, np.float64)
        for a, r in zip(answers, reference)]) if answers else np.zeros(0)


def compare(docs: Sequence[Any], answers: Sequence[Any],
            reference: Sequence[Any], limits: Mapping[str, Any]
            ) -> Tuple[List[List[Any]], List[str]]:
    """([name, what, value, limit] a number compared, the problems)."""
    problems: List[str] = []
    kept_a, kept_r = [], []
    for i, (doc, a, r) in enumerate(zip(docs, answers, reference)):
        a = np.asarray(a)
        if a.shape != (len(doc) - 1,) or not np.isfinite(a).all():
            problems.append(
                f"sampled answer {i}: {a.shape} values for a document of "
                f"{len(doc)}, or not all finite")
            continue
        kept_a.append(a)
        kept_r.append(r)
    if not kept_a:
        return [], problems + ["no sampled answer could be compared"]
    diff = gaps(kept_a, kept_r)
    worst = float(np.abs(diff).max())
    rms = float(np.sqrt(np.mean(diff ** 2)))
    rows = [
        ["score_gap_max", f"widest |answer - reference| of a token's "
         f"log-probability over {diff.size} tokens of {len(kept_a)} "
         f"answers", worst, limits["score_gap_max"]],
        ["score_gap_rms", "root mean square of the same gaps", rms,
         limits["score_gap_rms"]]]
    for name, _, value, limit in rows:
        if not value <= limit:
            problems.append(f"{name} {value:.3e} over its limit {limit}")
    return rows, problems
