"""Trainer layer: mean milliseconds from a worker's `train.report` to the
driver taking the report into the run's history
(`rtpu_train_report_lag_seconds`, observed in `trainer._drain`; the driver
polls every 50 ms, and a report behind a checkpoint's copy waits for it)."""

from benchmarks import program_counters


def read(run):
    seconds = program_counters.mean("rtpu_train_report_lag_seconds")
    return None if seconds is None else 1e3 * seconds
