"""Runtime layer: seconds from the driver's first `_TrainWorker.remote()` to
the worker process entering its `main()` — scheduling onto the placement
group, the process's start, the interpreter and the runtime's imports:
`rtpu_train_gang_start_seconds{phase=spawn}`."""

from benchmarks import program_counters


def read(run):
    return program_counters.gang_phase_seconds(("spawn",))
