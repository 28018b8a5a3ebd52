#!/usr/bin/env python3
"""The builder's readings for the limits of `step_check.compare`, on the chip,
many seeds in one process (a run of the harness reads one seed and pays a
whole set-up for it):

    chiprun [--chips 4] -- python3 benchmarks/tests/first_steps_readings.py \\
        --cell <name> --seeds 11,12,... [--controls 3] [--out <file>]

For each seed: the cell's own compiled `train_step` (built as `loops/train.py`
builds it: the configuration's model, optimizer, mesh and rows) from the
seeded state through the first followed steps on the traffic's blocks 1..n
(block 0 is what the step is lowered on, as in the loop), what the loop keeps
of them, and the plain reference on the same batches. On the first
`--controls` seeds also the controls, each a `train_steps.follow` put in the
program's place (`step_check.as_system`): every matmul's operands rounded to
bfloat16 and to float8_e4m3fn, and the planted faults `half_batch`,
`no_bias_correction` and, on several chips, `no_exchange`. One JSON line a
reading: every compared number (`step_check.compare`'s rows) and the seconds
each part took. Nothing here decides `correct`; the limits set from these
lines are in the configurations' `reference.why`.
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
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--only", default=None,
                    help="of the controls, these alone (comma-separated)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK file (the fixtures', on the CPU)")
    ap.add_argument("--budget-s", type=float, default=1e9,
                    help="start no further seed after this many seconds")
    ap.add_argument("--patch", default=None,
                    help="module:function that breaks the program first "
                         "(benchmarks.tests.faults:<fault>)")
    args = ap.parse_args()

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import cells, step_check, traffic_gen
    from benchmarks.loops import train as loop
    from benchmarks.reference import train_steps
    from ray_tpu.models.training import batch_shardings, state_shardings
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    if args.patch:
        module, _, name = args.patch.partition(":")
        getattr(__import__("importlib").import_module(module), name)()
    from ray_tpu.models import (GPT, init_train_state, make_optimizer,
                                make_train_step)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.resolve(args.cell, args.benchmark)
    config, traffic = cell.config, cell.traffic
    group = config["reference"]
    cfg = {"config": config, "root": cell.root, "paths": cell.paths}
    reference, glue, _ = loop._reference_modules(cfg)
    devices = jax.devices()
    assert len(devices) == cell.chips, (len(devices), cell.chips)
    out = open(args.out, "a") if args.out else None

    def say(**fields):
        line = json.dumps(fields, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    mesh = build_mesh(MeshSpec(**config["mesh"])) if config["mesh"] else None
    model = GPT(loop._model_config(config), **({"mesh": mesh} if mesh else {}))
    optimizer = make_optimizer(**config["optimizer"])
    placement = (state_shardings(model, optimizer, mesh) if mesh is not None
                 else jax.sharding.SingleDeviceSharding(devices[0]))
    init_state = jax.jit(lambda k: init_train_state(model, optimizer, k),
                         out_shardings=placement)
    init_params = jax.jit(model.init, out_shardings=(
        placement.params if mesh is not None else placement))
    sharding = batch_shardings(mesh) if mesh is not None else None
    batch_rows = config["batch_per_chip"] * cell.chips
    n_steps = int(group.get("steps", 3))
    moment_sumsq = loop._kept_sumsq(cfg, mesh, devices)
    change_sumsq = loop._kept_sumsq(cfg, mesh, devices, model.init)

    began = time.perf_counter()
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if t0 - began > args.budget_s:
            break
        rows = traffic_gen.packed_rows(traffic, (n_steps + 1) * batch_rows,
                                       seed)["tokens"]
        blocks = [rows[i * batch_rows:(i + 1) * batch_rows]
                  for i in range(n_steps + 1)]

        def put(block):
            tokens = jnp.asarray(block, jnp.int32)
            return {"tokens": jax.device_put(tokens, sharding)
                    if sharding is not None else tokens}

        key = jax.random.PRNGKey(seed)
        jax.clear_caches()
        gc.collect()
        state = init_state(key)
        # built for every seed and dropped before the reference: on the chip
        # a loaded executable keeps its temporaries' room
        compiled = make_train_step(model, optimizer, mesh=mesh).lower(
            state, put(blocks[0])).compile()
        system = {"records": []}
        for i in range(1, n_steps + 1):
            state, metrics = compiled(state, put(blocks[i]))
            if i == 1:
                system["moment_sumsq"] = moment_sumsq(
                    loop._first_moment(state.opt_state), key)
            metrics = jax.device_get(metrics)
            system["records"].append({
                k: float(v) if v.ndim == 0 else v.tolist()
                for k, v in metrics.items()})
        system["change_sumsq"] = change_sumsq(state.params, key)
        del state, compiled
        t_program = time.perf_counter() - t0

        def start():
            return glue.reference_weights(init_params(key), mesh, devices)

        jax.clear_caches()
        gc.collect()
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        t0 = time.perf_counter()
        sound = train_steps.follow(reference, config, start, blocks[1:],
                                   devices)
        t_reference = time.perf_counter() - t0
        compared, problems = step_check.compare(system, sound, group)
        reading = {"cell": cell.name, "seed": seed,
                   "who": "program" + (" with " + args.patch.rpartition(":")[2]
                                       if args.patch else ""),
                   "program_s": t_program, "reference_s": t_reference,
                   "compared": {r[0]: r[2] for r in compared},
                   "where": {r[0]: r[1] for r in compared if "leaf" in r[0]},
                   "problems": problems,
                   "first": system["records"][0].get("loss"),
                   "reference_steps": sound["steps"],
                   "in_use_before_reference": in_use,
                   "in_use_after_reference": [
                       (d.memory_stats() or {}).get("bytes_in_use")
                       for d in devices],
                   "peak_bytes": [(d.memory_stats() or {}).get(
                       "peak_bytes_in_use") for d in devices]}
        if "chosen" in sound:
            t0 = time.perf_counter()
            reading["routing"] = loop._routing_against(
                model, init_params(key), put(blocks[1])["tokens"],
                system["records"][0], sound)
            reading["routing_s"] = time.perf_counter() - t0
        say(**reading)

        if n >= args.controls:
            continue
        controls = [("bfloat16", {"operands": jnp.bfloat16}),
                    ("float8_e4m3fn", {"operands": jnp.float8_e4m3fn}),
                    ("half_batch", {"fault": "half_batch"}),
                    ("no_bias_correction", {"fault": "no_bias_correction"})]
        if cell.chips > 1:
            controls.append(("no_exchange", {"fault": "no_exchange"}))
        for who, how in controls:
            if args.only and who not in args.only.split(","):
                continue
            # the executables of the follow before go too: on the chip what
            # they hold is in no `bytes_in_use`, and olmoe-steady's next
            # follow found 246 MB free of 15.75 GiB without this
            jax.clear_caches()
            gc.collect()
            t0 = time.perf_counter()
            try:
                other = train_steps.follow(reference, config, start,
                                           blocks[1:], devices, **how)
            except Exception as e:      # noqa: BLE001 — a control that
                say(cell=cell.name, seed=seed, who=who,     # crashes failed
                    crashed=repr(e)[:500])
                continue
            compared, problems = step_check.compare(
                step_check.as_system(other, group["adamw"]), sound, group)
            line = {"cell": cell.name, "seed": seed, "who": who,
                    "control_s": time.perf_counter() - t0,
                    "compared": {r[0]: r[2] for r in compared},
                    "where": {r[0]: r[1] for r in compared
                              if "leaf" in r[0]},
                    "problems": problems}
            if "chosen" in other and "operands" in how:
                line["routing"] = {
                    "choice_agreement": loop._choice_agreement(
                        other["chosen"], sound["chosen"],
                        sound["counts"].shape[-1]),
                    "counts_differ": int(np.abs(
                        loop._counts_as_reported(
                            other["counts"] - sound["counts"],
                            np.ndim(system["records"][0][
                                "moe_expert_tokens"]), model.config)).sum()),
                    "counts_differ_all_outputs": int(np.abs(
                        other["counts"] - sound["counts"]).sum())}
            say(**line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
