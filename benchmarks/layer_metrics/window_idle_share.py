"""Device: the share of the window, in percent, in which the device had no
step to run because the host was late — 1 - steps x median seconds between
step completions / the window (less the seconds blocked in saves). It is the
window's mean rate against `tokens_per_s_per_chip`, which is a median and so
does not carry sporadic stalls; this metric does. Host clock, whole window
(`device_idle_share` reads the trace, over the few traced steps)."""


def read(run):
    window = run["window"]
    mean = window.get("window_tokens_per_s_per_chip")
    median = window.get("tokens_per_s_per_chip")
    if not mean or not median or not window.get("median_step_s"):
        return None
    return 100.0 * (1.0 - mean / median)
