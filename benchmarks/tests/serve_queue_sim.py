"""The builder's CPU script for a served over-cell's spread between seeds
(PERF.md, PR 49): not a test and not part of a run. One replica above its
knee is a single server that is never idle, so the rate of a seed's run is
what the schedule and the buckets' times give: the requests of
`arrivals.schedule`, collected as `@serve.batch` collects them (at most
`max_batch_size`, waiting `batch_wait_timeout_s` for a second one), cut
into device calls by `loops/serve.py::plan_groups`, each call taking its
bucket's time, and a request counted if its batch returned inside the
window. With one traced run's bucket times for every seed this gave seven
of `keye2-score-16k-over`'s ten rates to the token and the other three one
batch off, so what it spreads over many seeds is what the traffic file
alone spreads, with a device that follows no seed:

    python3 benchmarks/tests/serve_queue_sim.py <workload> \\
        --ms 1x4096=49.4,2x4096=94.8,... [--seeds N] [--set key=value ...]

`--set` overrides a key of the traffic file (`arrivals.shuffle_block=16`,
`batching.max_batch_size=1`) to ask what another table would spread.
A line of JSON: the rate's mean and standard deviation over the seeds, and
of their sets of six the mean spread (first to third quartile over the
median, the run farthest from the median left out, as the driver reads a
new cell) and the share of sets under `--gate`.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import statistics
import sys
from typing import Any, Dict, Mapping, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                        != os.path.dirname(os.path.abspath(__file__))]

from benchmarks import arrivals, cells                  # noqa: E402
from benchmarks.loops.serve import plan_groups          # noqa: E402

# what a batch costs the replica beside its device calls (assemble, enqueue,
# split and the reply: `spans` of a window line)
HOST_MS = 3.0


def window_rate(traffic: Mapping[str, Any], bucket_ms: Mapping[
        Tuple[int, int], float], seed: int, seconds: float = 45.0) -> float:
    """Real tokens a second of the requests answered inside the window."""
    plan = arrivals.schedule(traffic, seconds, seed)
    send, lengths = plan["send_s"], plan["lengths"]
    batching = traffic["batching"]
    rows, widths = sorted(batching["rows"]), sorted(batching["lengths"])
    size = int(batching["max_batch_size"])
    wait = float(batching["batch_wait_timeout_s"])
    free, first, tokens = 0.0, 0, 0
    while first < len(send):
        start = max(free, send[first])
        last = first + 1
        while (last < len(send) and last - first < size
               and send[last] <= start + wait):
            last += 1
        if last - first == size:
            start = max(start, send[last - 1])
        else:
            start += wait
        docs = [int(n) for n in lengths[first:last]]
        calls = plan_groups(docs, rows, widths)
        free = start + (sum(bucket_ms[r, w] for r, w, _ in calls)
                        + HOST_MS) / 1000.0
        if free > seconds:
            break
        tokens += sum(docs)
        first = last
    return tokens / seconds


def spread(rates, drop_farthest: bool = True) -> float:
    """First to third quartile over the median, as a share."""
    rates = list(rates)
    if drop_farthest:
        median = statistics.median(rates)
        rates.remove(max(rates, key=lambda r: abs(r - median)))
    q = statistics.quantiles(rates, n=4)
    return (q[2] - q[0]) / statistics.median(rates)


def _override(traffic: Dict[str, Any], assignment: str) -> None:
    path, _, value = assignment.partition("=")
    *groups, key = path.split(".")
    for group in groups:
        traffic = traffic[group]
    traffic[key] = json.loads(value)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--ms", required=True,
                    help="rows x length = milliseconds, a bucket each")
    ap.add_argument("--seeds", type=int, default=240)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--gate", type=float, default=0.015)
    ap.add_argument("--benchmark-file", default=None)
    args = ap.parse_args()
    traffic = copy.deepcopy(dict(cells.resolve(
        args.workload, args.benchmark_file).traffic))
    for assignment in args.set:
        _override(traffic, assignment)
    bucket_ms = {}
    for item in args.ms.split(","):
        shape, _, ms = item.partition("=")
        rows, _, width = shape.partition("x")
        bucket_ms[int(rows), int(width)] = float(ms)
    seeds = np.random.default_rng(1).integers(1, 2 ** 31, args.seeds)
    rates = [window_rate(traffic, bucket_ms, int(s)) for s in seeds]
    sets = [spread(rates[lo:lo + 6]) for lo in range(0, len(rates) - 5, 6)]
    print(json.dumps({
        "workload": args.workload, "set": args.set, "seeds": len(rates),
        "rate_mean": float(np.mean(rates)),
        "rate_sd_share": float(np.std(rates) / np.mean(rates)),
        "sets_of_six": len(sets), "spread_mean": float(np.mean(sets)),
        "spread_median": float(np.median(sets)),
        "sets_under_gate": float(np.mean([s < args.gate for s in sets]))}))


if __name__ == "__main__":
    main()
