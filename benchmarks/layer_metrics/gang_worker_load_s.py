"""Runtime layer: seconds from the worker process entering its `main()` to
`_TrainWorker.__init__` being entered — registration with the node, the
creation task, and unpickling the actor class, which imports
`ray_tpu.train` and with it jax:
`rtpu_train_gang_start_seconds{phase=load}`. What is left of
`gang_worker_start_s` is `run_wait`: the driver splitting its datasets and
the loop shipped and unpickled."""

from benchmarks import program_counters


def read(run):
    return program_counters.gang_phase_seconds(("load",))
