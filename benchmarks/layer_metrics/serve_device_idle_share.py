"""Serve: 1 - union of device-operation intervals over the traced stretch,
in percent (`device_idle_share`'s reading, of the stretch of the arrival
schedule that follows the window: first to last start of a bucket's
program)."""

from benchmarks.layer_metrics.device_idle_share import read  # noqa: F401
