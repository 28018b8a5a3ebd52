"""Runtime layer: seconds from the driver's first `_TrainWorker.remote()` to
`run()` being entered in the worker, the mean over the gang's workers: the
three phases of `rtpu_train_gang_start_seconds` (`spawn`, `load`,
`run_wait`), which each worker observes from its own marks
(`ray_tpu/train/trainer.py`). Over before the loop's first line, where
`gang_start_s` ends. See `gang_worker_spawn_s`, `gang_worker_load_s`."""

from benchmarks import program_counters


def read(run):
    return program_counters.gang_phase_seconds()
