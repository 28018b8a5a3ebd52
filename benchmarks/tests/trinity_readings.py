"""The builder's chip script for the served cell of window and full layers
(PERF.md, PR 53): not a test and not part of a run. `serve_readings.py
limits` reads the sound program, the reference and its controls of lower
precision on many seeds; this reads, in one process that holds the chip:

    python3 benchmarks/tests/trinity_readings.py faults <workload> <seed> ...
        [--sample N] [--faults a,b] [--cpu] [--benchmark-file F]
        for one seed a time the program with each fault of
        `trinity_faults.py` planted underneath the deployment class — at the
        cell's largest bucket, the one program a fault compiles — against
        the plain reference of the same documents. A line a seed: for the
        sound program (with the selection bias at zero and at its seeded
        value) and for each fault the widest gap and the root mean square,
        and `correct` as `serve_check.compare` decides it under the limits
        the served configuration ships: the sound program true, every fault
        false.

    python3 benchmarks/tests/trinity_readings.py limits <workload> <seed> ...
        `serve_readings.py limits` through the largest bucket's one program:
        the sound program, the reference with bfloat16 operands and the
        control with float8_e4m3fn operands against the plain reference, a
        line a seed and a line a reference pass with its seconds.

    python3 benchmarks/tests/trinity_readings.py routing <workload> <seed> ...
        the tokens each expert of each routed layer was given in the largest
        bucket's program (`moe_expert_tokens`), a line a seed of the
        weights: the largest and the smallest count over the mean, a layer.

    python3 benchmarks/tests/trinity_readings.py kernels <workload>
        the windowed forward kernel beside the full one on one row of the
        largest bucket's length at the configuration's heads, 24 calls in a
        scan each: milliseconds a call and their ratio.
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
from benchmarks.tests import trinity_faults             # noqa: E402


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "trinity_readings.jsonl"),
              "a") as f:
        f.write(json.dumps(fields, default=str) + "\n")


def _largest_bucket(cell):
    """The cell's traffic with its largest bucket alone."""
    traffic = json.loads(json.dumps(cell.traffic))
    batching = traffic["batching"]
    batching["rows"] = [max(batching["rows"])]
    batching["lengths"] = [max(batching["lengths"])]
    return traffic


def _answers(cell, served, traffic, seed, docs, platform, patches=()):
    """The deployment class's answers to `docs` in collected batches of the
    traffic's size, with `patches` planted underneath it."""
    for patch in patches:
        getattr(trinity_faults, patch)()
    try:
        scorer = loop.Scorer(loop.loop_config(cell, served, traffic, seed,
                                              platform))
        size = int(traffic["batching"]["max_batch_size"])
        answers = []
        for lo in range(0, len(docs), size):
            answers += [a["logprobs"] for a in
                        scorer._score_batch(docs[lo:lo + size])]
        # the reference is handed the weights this start made
        reference = scorer._control_reference({"docs": docs})
        return answers, reference
    finally:
        trinity_faults.restore()


def faults(args) -> None:
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    platform = "cpu" if args.cpu else "tpu"
    traffic = _largest_bucket(cell)
    limits = served["reference"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        lengths = arrivals.schedule(cell.traffic, 45.0, seed)["lengths"][
            :args.sample]
        lengths[0] = int(cell.traffic["documents"]["length"]["max"])
        docs = arrivals.documents(cell.traffic, lengths, seed)
        row = {"kind": "seed", "seed": seed, "docs": len(docs),
               "tokens": int(sum(len(d) - 1 for d in docs))}

        def read(patches):
            answers, reference = _answers(cell, served, traffic, seed, docs,
                                          platform, patches)
            rows, problems = serve_check.compare(docs, answers,
                                                 reference["scores"], limits)
            gc.collect()
            return {**{r[0]: r[2] for r in rows}, "correct": not problems,
                    "reference_s": reference["reference_check_s"]}

        row["program"] = read(())
        row["program_seeded_bias"] = read(("seeded_bias",))
        for fault in [f for f in args.faults.split(",") if f]:
            row[fault] = read((fault,))
        row["seconds"] = time.perf_counter() - t0
        say(**row)


def limits(args) -> None:
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    traffic = _largest_bucket(cell)
    size = int(traffic["batching"]["max_batch_size"])
    loose = {"score_gap_max": 1e9, "score_gap_rms": 1e9}
    for seed in args.seeds:
        lengths = arrivals.schedule(cell.traffic, 45.0, seed)["lengths"][
            :args.sample]
        lengths[0] = int(cell.traffic["documents"]["length"]["max"])
        docs = arrivals.documents(cell.traffic, lengths, seed)
        scorer = loop.Scorer(loop.loop_config(
            cell, served, traffic, seed, "cpu" if args.cpu else "tpu"))
        answers = []
        for lo in range(0, len(docs), size):
            answers += [a["logprobs"] for a in
                        scorer._score_batch(docs[lo:lo + size])]
        row = {"kind": "seed", "seed": seed, "docs": len(docs),
               "tokens": int(sum(len(d) - 1 for d in docs))}
        reference = None
        for name in (None, "bfloat16", "float8_e4m3fn"):
            out = scorer._control_reference({"docs": docs, "operands": name})
            say(kind="reference_pass", seed=seed, operands=name,
                seconds=out["reference_check_s"])
            if name is None:
                reference = out["scores"]
            rows, _ = serve_check.compare(
                docs, answers if name is None else out["scores"], reference,
                loose)
            row[name or "program"] = {r[0]: r[2] for r in rows}
        say(**row)


def routing(args) -> None:
    import jax
    import numpy as np
    cell = cells.resolve(args.workload, args.benchmark_file)
    served = loop.served_group(cell.root, cell.paths, cell.config_name)
    traffic = _largest_bucket(cell)
    rows, width = traffic["batching"]["rows"][0], traffic["batching"][
        "lengths"][0]
    for seed in args.seeds:
        scorer = loop.Scorer(loop.loop_config(
            cell, served, traffic, seed, "cpu" if args.cpu else "tpu"))
        lengths = np.full(rows, width)
        docs = arrivals.documents(cell.traffic, lengths, seed)
        _, aux = jax.jit(scorer.model.forward_with_aux)(
            scorer.params, np.stack(docs))
        counts = np.asarray(aux["moe_expert_tokens"], np.float64)
        say(kind="routing", seed=seed, rows=rows, width=width,
            max_over_mean=(counts.max(-1) / counts.mean(-1)).tolist(),
            min_over_mean=(counts.min(-1) / counts.mean(-1)).tolist(),
            experts_without_a_token=(counts == 0).sum(-1).tolist())
        # (the class's compile listener keeps a deployment alive: its
        # weights and programs go as `_control_reference` lets them go)
        scorer.params = None
        scorer.programs.clear()
        del aux
        jax.clear_caches()
        gc.collect()


def kernels(args) -> None:
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import dot_product_attention
    cell = cells.resolve(args.workload, args.benchmark_file)
    model = cell.config["model"]
    length = max(cell.traffic["batching"]["lengths"])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, model["n_heads"], length,
                                    model["d_head"]), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, model["n_kv_heads"], length,
                                    model["d_head"]), jnp.bfloat16)
            for key in keys[1:])
    row = {"kind": "kernels", "length": length}
    for name, window in (("flash_fwd", None),
                         ("flash_fwd_window", model["attn_window"])):
        def many(q, k, v, window=window):
            def step(q, _):
                out = dot_product_attention(q, k, v, impl="pallas",
                                            window=window)
                return q + out * jnp.bfloat16(1e-3), None
            return jax.lax.scan(step, q, None, length=24)[0]
        run = jax.jit(many)
        run(q, k, v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            run(q, k, v).block_until_ready()
        row[name + "_ms"] = (time.perf_counter() - t0) / 3 / 24 * 1e3
    row["ratio"] = row["flash_fwd_window_ms"] / row["flash_fwd_ms"]
    say(**row)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("faults", "limits", "routing",
                                     "kernels"))
    ap.add_argument("workload")
    ap.add_argument("seeds", nargs="*", type=int)
    ap.add_argument("--sample", type=int, default=3)
    ap.add_argument("--faults", default=",".join(trinity_faults.FAULTS))
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    {"faults": faults, "limits": limits, "routing": routing,
     "kernels": kernels}[args.mode](args)


if __name__ == "__main__":
    main()
