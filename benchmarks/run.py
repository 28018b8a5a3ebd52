#!/usr/bin/env python3
"""Run one cell of `BENCHMARK.json` and print its result as the last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process (the driver) never opens a JAX backend: the cell's loop module
(`loops/<kind>.py`) starts the system under test, and all device work happens
in the worker process the system grants the chips to. The last line of
standard output is one JSON object with `correct`, `attempted`, `failed`,
`metrics`, `device` and, with `--trace 1`, `breakdown`; earlier lines are
progress, one JSON object each. The exit code is 0 only if a result was
printed; a machine without the cell's chips, or a checkout without the
program, prints no result.
"""

from __future__ import annotations

import time

_PROCESS_START_WALL = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def main(argv=None, *, benchmark_file=None, rehearsal=None) -> int:
    """`rehearsal` is for the CPU rehearsals in `tests/`: a dict
    {"num_tpus": n} that declares chips JAX will not find, so the whole path
    runs on the CPU at a tiny size. Such a run can never print
    `correct: true`. There is no command-line way to ask for it."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # this checkout's packages and nothing else: the script's own directory
    # would shadow top-level modules in the workers, which inherit the path
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") not in (here, ROOT)]
    if not os.path.isfile(os.path.join(ROOT, "ray_tpu", "__init__.py")):
        print(f"{ROOT} holds the benchmark but not the program (ray_tpu/): "
              f"nothing to measure", file=sys.stderr)
        return 2

    from benchmarks import cells
    from benchmarks.cells import NoResult
    cell = cells.resolve(args.workload, benchmark_file)
    loop = cells.loop_module(cell)
    try:
        line = loop.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace),
                        process_start_wall=_PROCESS_START_WALL,
                        rehearsal=rehearsal, say=say)
    except NoResult as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    except Exception:   # noqa: BLE001 — a crash prints no result either
        traceback.print_exc()
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
