"""The builder's chip script for the planted faults of the served
sparse-attention cell (PERF.md, PR 49): not a test and not part of a run.
`serve_readings.py limits` reads the sound program, the reference and its
controls of lower precision on many seeds; this reads, in one process that
holds the chip and for one seed a time, the program with each fault of
`keye_faults.py` planted underneath the deployment class — at the cell's own
size, through its compiled buckets — against the plain reference of the same
documents:

    python3 benchmarks/tests/keye_readings.py <workload> <seed> ...
        [--sample N] [--faults a,b] [--cpu] [--benchmark-file F]

A line a seed: for the sound program and for each fault the widest gap and
the root mean square, and `correct` as `serve_check.compare` decides it under
the limits the served configuration ships (`loops/serve.py` hands it the same
group): the sound program true, every fault false.
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
from benchmarks.tests import keye_faults                # noqa: E402

FAULTS = ("selection_ignored", "topk_halved", "relu_left_out",
          "key_after_the_query")


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "keye_readings.jsonl"),
              "a") as f:
        f.write(json.dumps(fields, default=str) + "\n")


def _answers(cell, served, seed, docs, platform, fault=None):
    """The deployment class's answers to `docs` in collected batches of the
    traffic's size, with `fault` planted underneath it."""
    from ray_tpu.models import gpt
    sound = gpt.sparse_index, gpt.dot_product_attention
    if fault:
        getattr(keye_faults, fault)()
    try:
        scorer = loop.Scorer(loop.loop_config(cell, served, cell.traffic,
                                              seed, platform))
        size = int(cell.traffic["batching"]["max_batch_size"])
        answers = []
        for lo in range(0, len(docs), size):
            answers += [a["logprobs"] for a in
                        scorer._score_batch(docs[lo:lo + size])]
        return scorer, answers
    finally:
        gpt.sparse_index, gpt.dot_product_attention = sound


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", nargs="+", type=int)
    ap.add_argument("--sample", type=int, default=6)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    platform = "cpu" if args.cpu else "tpu"
    limits = served["reference"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        lengths = arrivals.schedule(cell.traffic, 45.0, seed)["lengths"][
            :args.sample]
        lengths[0] = int(cell.traffic["documents"]["length"]["max"])
        docs = arrivals.documents(cell.traffic, lengths, seed)
        scorer, answers = _answers(cell, served, seed, docs, platform)
        reference = scorer._control_reference({"docs": docs})
        row = {"kind": "seed", "seed": seed, "docs": len(docs),
               "tokens": int(sum(len(d) - 1 for d in docs)),
               "reference_s": reference["reference_check_s"]}

        def read(subject):
            rows, problems = serve_check.compare(docs, subject,
                                                 reference["scores"], limits)
            return {**{r[0]: r[2] for r in rows}, "correct": not problems,
                    "problems": problems}

        row["program"] = read(answers)
        for fault in [f for f in args.faults.split(",") if f]:
            del scorer
            gc.collect()
            scorer, answers = _answers(cell, served, seed, docs, platform,
                                       fault)
            row[fault] = read(answers)
        row["seconds"] = time.perf_counter() - t0
        say(**row)
        del scorer
        gc.collect()


if __name__ == "__main__":
    main()
