"""The one traffic generator: packed token rows from a traffic file and a seed.

A traffic file (`traffic/<mix>.json`) holds only parameters. For kind
`train` this module reads:

    seq_len                      tokens per packed row
    documents.length             {"distribution": "lognormal", "median",
                                  "sigma", "min", "max"} — clipped
    tokens                       {"distribution": "zipf", "exponent",
                                  "support"} — ids 0..support-1 by rank
    eot_id                       written after every document
    blocks                       global batches made (one object-store block
                                 each); a run that wants more has outgrown
                                 its traffic file and fails

The result is a pure function of (parameters, rows wanted, seed). Documents
are laid end to end, each closed by `eot_id`, and cut into rows of `seq_len`
— so a long document spans rows and a row holds several short ones, which is
how a pretraining job packs (no padding, attention across boundaries is the
program's business).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping

import numpy as np


def zipf_probabilities(exponent: float, support: int) -> np.ndarray:
    ranks = np.arange(1, support + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    return p / p.sum()


def unigram_entropy(tokens: Mapping[str, Any]) -> float:
    """Entropy (nats) of the token distribution: where the loss of a model
    that learns only the unigram statistics ends up."""
    p = zipf_probabilities(tokens["exponent"], tokens["support"])
    return float(-(p * np.log(p)).sum())


def _document_lengths(spec: Mapping[str, Any], rng: np.random.Generator,
                      n: int) -> np.ndarray:
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution "
                         f"{spec['distribution']!r}")
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def packed_rows(traffic: Mapping[str, Any], rows: int, seed: int
                ) -> Dict[str, np.ndarray]:
    """`rows` packed rows of `seq_len` tokens (int32) and the document
    lengths they were cut from."""
    seq = int(traffic["seq_len"])
    need = rows * seq
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    tok = traffic["tokens"]
    if tok["distribution"] != "zipf":
        raise ValueError(f"unknown token distribution "
                         f"{tok['distribution']!r}")
    cdf = np.cumsum(zipf_probabilities(tok["exponent"], tok["support"]))
    cdf[-1] = 1.0

    lengths = np.empty(0, np.int64)
    while lengths.sum() + len(lengths) < need:      # + one eot each
        guess = max(16, int(1.2 * need / traffic["documents"]["length"]
                            ["median"]) // 4)
        lengths = np.concatenate([lengths, _document_lengths(
            traffic["documents"]["length"], rng, guess)])
    ends = np.cumsum(lengths + 1)                   # position after each eot
    n_docs = int(np.searchsorted(ends, need, side="left")) + 1
    lengths, ends = lengths[:n_docs], ends[:n_docs]

    stream = np.searchsorted(cdf, rng.random(int(ends[-1])),
                             side="right").astype(np.int32)
    np.minimum(stream, tok["support"] - 1, out=stream)
    stream[ends - 1] = int(traffic["eot_id"])
    return {"tokens": stream[:need].reshape(rows, seq), "doc_lengths": lengths}
