"""Runtime layer: seconds a train worker spent unpickling `_TrainWorker`,
which imports `ray_tpu.train` and with it jax and orbax — span
`worker::load_code`, series `rtpu_worker_load_code_seconds{kind=actor_class,
name=_TrainWorker}`, the mean over the gang's workers. The second part of
`gang_worker_load_s`, after `gang_worker_register_s`."""

from benchmarks import program_compile


def read(run):
    return program_compile.class_load_seconds("_TrainWorker")
