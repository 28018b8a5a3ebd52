"""Model step: median device-plane duration of the jitted step's program in
the traced segment."""

import statistics


def read(run):
    trace = run["trace"]
    return statistics.median(trace["step_device_ms"]) if trace else None
