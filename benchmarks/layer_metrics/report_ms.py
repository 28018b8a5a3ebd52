"""Trainer layer: median host span around `train.report` in the window."""


def read(run):
    span = run["spans"].get("report")
    return span["median_ms"] if span else None
