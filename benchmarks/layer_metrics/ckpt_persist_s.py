"""Checkpoint layer: mean seconds from a save's return to its being complete
in the experiment directory — the driver's copy (`trainer._drain`)."""


def read(run):
    values = [s["durable_s"] - s["write_s"] for s in run["saves"]
              if s.get("durable_s") is not None]
    return sum(values) / len(values) if values else None
