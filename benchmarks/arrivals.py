"""Traffic kind `serve`: an open-loop arrival schedule and its documents, from
a traffic file and a seed. Only parameters live in `traffic/<mix>.json`:

    documents.length   {"distribution": "lognormal", "median", "sigma",
                        "min", "max"} — `traffic_gen.py`'s, clipped
    tokens             {"distribution": "zipf", "exponent", "support"}
    arrivals           {"process": "poisson", "rate_per_s",
                        "burst": {"every_s", "for_s", "factor"} (optional),
                        "pool_seed", "shuffle_block"}

The rate is piecewise constant: `rate_per_s` outside a burst, `factor` times
it inside; a period of `every_s` seconds ends with its burst of `for_s`, so a
run starts quiet. Arrivals are a Poisson process of that rate, made by
time-warping unit-rate exponential gaps through the cumulative rate.

What the seed does, and what it does not. A run's tail follows the work that
arrives inside each burst, so two seeds that drew their own lengths and gaps
would be two different workloads, not two readings of one. The gaps and the
lengths are therefore a pool drawn from the traffic file's own `pool_seed`:
every seed sends the same multiset of documents' lengths on the same
multiset of gaps. The seed permutes both inside consecutive blocks of
`shuffle_block` arrivals (so any stretch of the run carries the same work to
within a block, in another order) and draws every document's token ids. The
weights come from the seed in the loop.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping

import numpy as np

from benchmarks import traffic_gen


def rate_at(arrivals: Mapping[str, Any], t: np.ndarray) -> np.ndarray:
    """Requests a second offered at time(s) `t` of the run."""
    base = float(arrivals["rate_per_s"])
    burst = arrivals.get("burst")
    t = np.asarray(t, np.float64)
    if not burst:
        return np.full(t.shape, base)
    inside = (t % burst["every_s"]) >= burst["every_s"] - burst["for_s"]
    return np.where(inside, base * burst["factor"], base)


def mean_rate(arrivals: Mapping[str, Any], seconds: float) -> float:
    """The offered rate averaged over a run of `seconds`."""
    grid = (np.arange(int(seconds * 1000)) + 0.5) / 1000.0
    return float(rate_at(arrivals, grid).mean())


def _warp(cumulative_units: np.ndarray, arrivals: Mapping[str, Any],
          seconds: float) -> np.ndarray:
    """Times at which the cumulative rate reaches each value: the inverse of
    the integral of `rate_at`, on a millisecond grid, interpolated."""
    grid = np.arange(int(seconds * 1000) + 1) / 1000.0
    big = np.concatenate([[0.0], np.cumsum(
        rate_at(arrivals, grid[:-1] + 0.0005) * 0.001)])
    inside = cumulative_units[cumulative_units < big[-1]]
    return np.interp(inside, big, grid)


def _permute_blocks(n: int, block: int, rng: np.random.Generator
                    ) -> np.ndarray:
    order = np.arange(n)
    for lo in range(0, n, block):
        rng.shuffle(order[lo:lo + block])
    return order


def schedule(traffic: Mapping[str, Any], seconds: float, seed: int
             ) -> Dict[str, np.ndarray]:
    """`send_s` (seconds after the window opens, ascending) and `lengths`
    (tokens) of every request due inside a window of `seconds`."""
    arrivals = traffic["arrivals"]
    block = int(arrivals["shuffle_block"])
    peak = float(rate_at(arrivals, np.arange(int(seconds * 1000)) / 1000.0
                         ).max())
    n = int(peak * seconds * 1.2) + 4 * block
    n = (n + block - 1) // block * block
    pool = np.random.default_rng([int(arrivals["pool_seed"]), 0xA881])
    gaps = pool.exponential(1.0, n)
    lengths = traffic_gen._document_lengths(
        traffic["documents"]["length"], pool, n)
    rng = np.random.default_rng([int(seed), 0x5E8F])
    gaps = gaps[_permute_blocks(n, block, rng)]
    lengths = lengths[_permute_blocks(n, block, rng)]
    send = _warp(np.cumsum(gaps), arrivals, seconds)
    return {"send_s": send, "lengths": lengths[:len(send)]}


def documents(traffic: Mapping[str, Any], lengths: np.ndarray, seed: int
              ) -> List[np.ndarray]:
    """One int32 array of token ids a document, Zipf by rank, from the
    seed."""
    tok = traffic["tokens"]
    if tok["distribution"] != "zipf":
        raise ValueError(f"unknown token distribution "
                         f"{tok['distribution']!r}")
    cdf = np.cumsum(traffic_gen.zipf_probabilities(tok["exponent"],
                                                   tok["support"]))
    cdf[-1] = 1.0
    rng = np.random.default_rng([int(seed), 0xD0C5])
    stream = np.searchsorted(cdf, rng.random(int(lengths.sum())),
                             side="right").astype(np.int32)
    np.minimum(stream, tok["support"] - 1, out=stream)
    ends = np.cumsum(lengths)
    return np.split(stream, ends[:-1])


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest rank: an observed value,
    never an interpolation between two."""
    ordered = np.sort(np.asarray(values, np.float64))
    rank = int(np.ceil(q / 100.0 * len(ordered))) - 1
    return float(ordered[min(max(rank, 0), len(ordered) - 1)])
