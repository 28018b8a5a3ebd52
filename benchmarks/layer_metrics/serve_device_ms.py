"""Serve: median device-plane duration, in milliseconds, of a bucket's
program (`jit_score_bucket`, whatever its rows x length) in the traced
stretch (`step_device_ms`'s reading)."""

from benchmarks.layer_metrics.step_device_ms import read  # noqa: F401
