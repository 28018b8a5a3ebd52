"""The builder's chip script for the served cell of an early-routed,
ReLU-gated model of full and window layers (PERF.md, PR 57): not a test and
not part of a run. Each mode is one process that holds the chip and appends a
line a reading to `chiprun_out/smallthinker_readings.jsonl`:

    python3 benchmarks/tests/smallthinker_readings.py limits <workload> <seed> ...
        `trinity_readings.py limits`: through the largest bucket's one
        program, the sound program, the reference with bfloat16 operands and
        the control with float8_e4m3fn operands against the plain reference,
        a line a seed.

    python3 benchmarks/tests/smallthinker_readings.py faults <workload> <seed> ...
        [--faults a,b] for one seed a time the program with each fault of
        `smallthinker_faults.py` planted underneath the deployment class, at
        the cell's largest bucket, against the plain reference of the same
        documents: the widest gap and the root mean square, and `correct` as
        `serve_check.compare` decides it under the limits the served
        configuration ships.

    python3 benchmarks/tests/smallthinker_readings.py routing <workload> <seed> ...
        `trinity_readings.py routing`: the tokens each expert of each layer
        was given in the largest bucket's program, a line a seed.

    python3 benchmarks/tests/smallthinker_readings.py sweep <workload> <rate> ...
        `serve_readings.py sweep`: the knee (30 s a rate, steady Poisson).

`--embed-std X` reads any of them at another seeded start of the token
embedding than the configuration's (how `embed_std` was chosen).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                        != os.path.dirname(os.path.abspath(__file__))]

from benchmarks import arrivals, cells, serve_check     # noqa: E402
from benchmarks.loops import serve as loop              # noqa: E402
from benchmarks.tests import (serve_readings,           # noqa: E402
                              smallthinker_faults, trinity_readings)


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "smallthinker_readings.jsonl"), "a") as f:
        f.write(json.dumps(fields, default=str) + "\n")


def _start_at(embed_std) -> None:
    """Every cell resolved from here on starts its embedding at `embed_std`
    (None: the configuration's)."""
    if embed_std is None:
        return
    resolve = cells.resolve

    def resolved(*a, **kw):
        cell = resolve(*a, **kw)
        cell.config["model"]["embed_std"] = embed_std
        return cell
    cells.resolve = resolved


def faults(args) -> None:
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    platform = "cpu" if args.cpu else "tpu"
    traffic = trinity_readings._largest_bucket(cell)
    limits = served["reference"]
    size = int(traffic["batching"]["max_batch_size"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        lengths = arrivals.schedule(cell.traffic, 45.0, seed)["lengths"][
            :args.sample]
        lengths[0] = int(cell.traffic["documents"]["length"]["max"])
        docs = arrivals.documents(cell.traffic, lengths, seed)
        row = {"kind": "faults", "seed": seed, "docs": len(docs),
               "embed_std": cell.config["model"].get("embed_std"),
               "tokens": int(sum(len(d) - 1 for d in docs))}
        reference = None
        for fault in [None] + [f for f in args.faults.split(",") if f]:
            if fault:
                getattr(smallthinker_faults, fault)()
            try:
                scorer = loop.Scorer(loop.loop_config(
                    cell, served, traffic, seed, platform))
                answers = []
                for lo in range(0, len(docs), size):
                    answers += [a["logprobs"] for a in
                                scorer._score_batch(docs[lo:lo + size])]
                if reference is None:
                    # the sound program's start hands the reference its
                    # weights; a fault changes no weight
                    reference = scorer._control_reference({"docs": docs})
                    row["reference_s"] = reference["reference_check_s"]
                else:
                    scorer.params = None
                    scorer.programs.clear()
                    scorer.jax.clear_caches()
            finally:
                smallthinker_faults.restore()
            rows, problems = serve_check.compare(
                docs, answers, reference["scores"], limits)
            row[fault or "program"] = {**{r[0]: r[2] for r in rows},
                                       "correct": not problems}
            del scorer
            gc.collect()
        row["seconds"] = time.perf_counter() - t0
        say(**row)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("faults", "limits", "routing", "sweep"))
    ap.add_argument("workload")
    ap.add_argument("values", nargs="*")
    ap.add_argument("--sample", type=int, default=3)
    ap.add_argument("--faults",
                    default=",".join(smallthinker_faults.FAULTS))
    ap.add_argument("--embed-std", type=float, default=None)
    ap.add_argument("--seed", type=int, default=5700000001)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--batching", default=None)
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    _start_at(args.embed_std)
    trinity_readings.say = serve_readings.say = say
    if args.mode == "sweep":
        return serve_readings.sweep(args)
    args.seeds = [int(v) for v in args.values]
    {"faults": faults, "limits": trinity_readings.limits,
     "routing": trinity_readings.routing}[args.mode](args)


if __name__ == "__main__":
    main()
