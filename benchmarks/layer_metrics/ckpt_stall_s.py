"""Checkpoint layer: seconds the training loop is blocked per save — from
the call of `Checkpoint.from_pytree`, made once the steps in flight are done,
to the call that enqueues the next step — mean over the window's saves."""


def read(run):
    values = [s["stall_s"] for s in run["saves"] if "stall_s" in s]
    return sum(values) / len(values) if values else None
