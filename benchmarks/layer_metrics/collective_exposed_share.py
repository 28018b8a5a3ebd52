"""Collectives: device time of collective operations during which no compute
ran on that chip (their own time on the core's operation line: for an
asynchronous pair the issue and the wait), over the traced window, in
percent."""


def read(run):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
