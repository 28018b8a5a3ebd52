"""Device: 1 - union of device-operation intervals over the traced window,
in percent, averaged over the chips."""


def read(run):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
