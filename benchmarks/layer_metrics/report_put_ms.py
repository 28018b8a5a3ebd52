"""Trainer layer, from inside: mean milliseconds a worker spent in
`train.report` — checkpoint metadata and the actor call into the results
queue — over the whole job (`rtpu_train_report_seconds`, recorded by the
span `train::report` in `ray_tpu/train/session.py`)."""

from benchmarks import program_counters


def read(run):
    seconds = program_counters.mean("rtpu_train_report_seconds")
    return None if seconds is None else 1e3 * seconds
