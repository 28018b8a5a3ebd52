"""The builder's chip script for the served cells (PERF.md, PR 44): not a
test and not part of a run. Two modes, each one command on the chip:

    python3 benchmarks/tests/serve_readings.py sweep <workload> <rate> ...
        the knee: one deployment of the cell as `loops/serve.py` makes it,
        then 30 s of steady Poisson arrivals at each rate in turn (the
        traffic file's own lengths, no bursts), each drained before the
        next; a line a rate with what was offered, answered, the tails, the
        share inside the deadline and the batches.
        `--batching max_batch_size:wait_ms` tries another collection.

    python3 benchmarks/tests/serve_readings.py limits <workload> <seed> ...
        the limits of `serve_check.compare`: in one process that holds the
        chip, for each seed the deployment class itself (`Scorer`, its
        compiled bucket programs, its padding and splitting) scores a
        sample of the seed's documents in collected batches, and the plain
        reference scores each alone — as it is, and with every matmul's
        operands rounded to bfloat16 and to float8_e4m3fn (the control).
        A line a seed with each one's widest gap and root mean square.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                        != os.path.dirname(os.path.abspath(__file__))]

import numpy as np     # noqa: E402

from benchmarks import arrivals, cells, serve_check     # noqa: E402
from benchmarks.loops import serve as loop              # noqa: E402


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "serve_readings.jsonl"),
              "a") as f:
        f.write(json.dumps(fields, default=str) + "\n")


def sweep(args) -> None:
    import ray_tpu
    from ray_tpu import serve
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    work = cells.module(cell.root, cell.paths, served["work"]["module"])
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["arrivals"].pop("burst", None)
    if args.batching:
        size, wait_ms = args.batching.split(":")
        traffic["batching"].update(max_batch_size=int(size),
                                   batch_wait_timeout_s=float(wait_ms) / 1e3)
    if args.cpu:
        ray_tpu.init(num_cpus=4, num_tpus=1)
    else:
        ray_tpu.init()
    try:
        deployment = serve.deployment(
            loop.Scorer, name=loop.DEPLOYMENT,
            ray_actor_options={"num_tpus": cell.chips},
            max_concurrent_queries=int(traffic["max_concurrent_queries"]))
        handle = serve.run(deployment.bind(loop.loop_config(
            cell, served, traffic, args.seed, "cpu" if args.cpu else "tpu")))
        worker = handle.remote({"control": "facts"}).result(timeout=900)
        say(kind="worker", batching=traffic["batching"], **worker)
        deadline_s = float(traffic["deadline_ms"]) / 1e3
        for rate in args.values:
            traffic["arrivals"]["rate_per_s"] = float(rate)
            plan = arrivals.schedule(traffic, args.seconds, args.seed)
            docs = arrivals.documents(traffic, plan["lengths"], args.seed)
            client = loop._Client(handle, docs)
            for k in range(32):     # the path, warm
                client.send(k, time.time())
            client.wait_for(32, time.time() + 60)
            client.records.clear()
            before = handle.remote({"control": "window_log"}).result(
                timeout=60)
            t0 = time.time()
            client.offer(t0, plan["send_s"], 0)
            closed = time.time()
            client.wait_for(len(plan["send_s"]), closed + deadline_s + 1.0)
            drained = time.time()
            log = handle.remote({"control": "window_log",
                                 "since_ns": before["now_ns"]}).result(
                timeout=60)
            rows = loop.request_rows(client.records, plan["lengths"], 0,
                                     len(plan["send_s"]), deadline_s)
            window = loop.window_numbers(rows, log["batches"], t0,
                                         args.seconds, cell.chips, work,
                                         cell.config["model"])
            # does the queue grow? the latency of the last fifth against
            # the second fifth of the window's requests
            fifth = max(1, len(rows) // 5)
            early = np.median([r["latency_s"] for r in rows[fifth:2 * fifth]])
            late = np.median([r["latency_s"] for r in rows[-fifth:]])
            busy = sum(b["done"] - b["fired"] for b in log["batches"]
                       if t0 <= b["fired"] < t0 + args.seconds)
            say(kind="rate", rate=rate,
                offered_per_s=len(rows) / args.seconds,
                answered_per_s=window["answered_in_window"] / args.seconds,
                inside_deadline=1.0 - window["failed"] / len(rows),
                p50=window["latency_p50_ms"], p95=window["latency_p95_ms"],
                p99=window["latency_p99_ms"], max=window["latency_max_ms"],
                early_median_ms=1e3 * early, late_median_ms=1e3 * late,
                tokens_per_s=window["serve_tokens_per_s_per_chip"],
                batch_requests_mean=window["batch_requests_mean"],
                pad_share=1.0 - window["real_tokens_fired"]
                / max(1, window["padded_tokens_fired"]),
                batch_busy_share=busy / args.seconds,
                queue_ms=window["queue_ms"], ingress_ms=window["ingress_ms"],
                reply_ms=window["reply_ms"], batch_ms=window["batch_ms"],
                send_lag_p99_ms=window["send_lag_p99_ms"],
                drain_s=drained - closed, spans=log["spans"],
                peak_bytes_in_use=log["peak_bytes_in_use"])
    finally:
        try:
            serve.shutdown()
        except Exception:   # noqa: BLE001
            pass
        ray_tpu.shutdown()


def limits(args) -> None:
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    traffic = cell.traffic
    size = int(traffic["batching"]["max_batch_size"])
    for seed in (int(v) for v in args.values):
        t0 = time.perf_counter()
        plan = arrivals.schedule(traffic, 45.0, seed)
        lengths = plan["lengths"][:args.sample]
        lengths[0] = int(traffic["documents"]["length"]["max"])
        docs = arrivals.documents(traffic, lengths, seed)
        scorer = loop.Scorer(loop.loop_config(cell, served, traffic, seed,
                                          "cpu" if args.cpu else "tpu"))
        # collected batches as the window fires them: full ones, and a few
        # small ones so that the small row buckets run too
        groups = [docs[lo:lo + size] for lo in range(0, len(docs), size)]
        groups += [docs[lo:lo + n] for lo, n in ((0, 1), (1, 3), (4, 6),
                                                 (10, 11))]
        docs, answers, buckets = [], [], set()
        for group in groups:
            for doc, a in zip(group, scorer._score_batch(group)):
                docs.append(doc)
                answers.append(a["logprobs"])
                buckets.add(a["bucket"])
        t_program = time.perf_counter() - t0
        row = {"kind": "seed", "seed": seed, "docs": len(docs),
               "tokens": int(sum(len(d) - 1 for d in docs)),
               "buckets": sorted(buckets), "program_s": t_program}
        for name, operands in (("reference", None), ("bfloat16", "bfloat16"),
                               ("float8_e4m3fn", "float8_e4m3fn")):
            out = scorer._control_reference({"docs": docs,
                                             "operands": operands})
            if operands is None:
                reference = out["scores"]
                row["reference_s"] = out["reference_check_s"]
                subject = answers
                name = "program"
            else:
                subject = out["scores"]
            compared, _ = serve_check.compare(
                docs, subject, reference,
                {"score_gap_max": 1e9, "score_gap_rms": 1e9})
            row[name] = {r[0]: r[2] for r in compared}
        say(**row)
        del scorer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("sweep", "limits"))
    ap.add_argument("workload")
    ap.add_argument("values", nargs="+")
    ap.add_argument("--seed", type=int, default=4400000001)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--sample", type=int, default=64)
    ap.add_argument("--batching", default=None)
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    {"sweep": sweep, "limits": limits}[args.mode](args)


if __name__ == "__main__":
    main()
