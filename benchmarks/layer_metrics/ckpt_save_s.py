"""Checkpoint layer, worker side: mean seconds inside
`Checkpoint.from_pytree` per save (`rtpu_checkpoint_save_seconds`, span
`checkpoint::save`), warm-up saves included: the inside successor of
`ckpt_write_s`, which times the same call from the loop."""

from benchmarks import program_counters


def read(run):
    return program_counters.mean("rtpu_checkpoint_save_seconds")
