"""Checkpoint layer: mean seconds inside `Checkpoint.from_pytree` per save
(device-to-host copy and orbax's write),
over the window's saves."""


def read(run):
    values = [s["write_s"] for s in run["saves"] if "write_s" in s]
    return sum(values) / len(values) if values else None
