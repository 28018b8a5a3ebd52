"""Checkpoint layer, driver side: mean seconds `trainer._drain` spent
copying one reported checkpoint into the experiment directory
(`rtpu_train_checkpoint_persist_seconds`, span
`train::persist_checkpoint`), warm-up saves included."""

from benchmarks import program_counters


def read(run):
    return program_counters.mean("rtpu_train_checkpoint_persist_seconds")
