"""Checkpoint layer: seconds from a save's start until that checkpoint is
complete in the experiment directory (what `Result.checkpoint` would hand a
restart), seen from the benchmark's side by the watcher thread in the driver;
mean over the window's saves. Keeps an asynchronous save honest: a stall of
zero with the state at risk for a minute is not a gain."""


def read(run):
    values = [s["durable_s"] for s in run["saves"]
              if s.get("durable_s") is not None]
    return sum(values) / len(values) if values else None
